"""The one traffic generator: binary Automerge changes from a seed.

A cell names a configuration (the deployment: documents, their shape, their
preload) and a traffic mix (arrivals, popularity, change shape, sync lag),
both JSON files read here. Everything is made from ``--seed``:

- ``schedule``: when each change is due and which document and actor send
  it. The multisets of sizes (inter-arrival gaps, popularity ranks, burst
  lengths and kinds) are drawn from a fixed constant and only their ORDER
  (and the rank -> document scramble) comes from the seed, so every seed
  offers the same work in another arrangement.
- ``DocGen``: per document, the authors' causal views (vector clocks over
  the document's window actors), so every op's ``pred`` names exactly the
  ops its author could see and every change's ``deps`` name its actor's
  previous change and the document's latest change due ``sync_lag_ms``
  earlier.

A configuration's preload is either a few shared template histories
(``map_templates``, ``text_templates``: documents share them, ``d %
templates``) or, with ``"preload": {"kind": "trace", ...}``, an editing
history of each document's own (``trace_history``), made in the worker that
generates the document's traffic.

Each generated change carries its wire bytes (encoded with the program's
``encode_change``, the wire format's encoder) and the same ops as plain
tuples, which is all the reference (``reference.py``) reads.
"""
from __future__ import annotations

import bisect
import hashlib
import random
import zlib

import numpy as np

FIXED_SEED = 0x5EED_B0A7  # the multisets of sizes; never the run's seed
HEAD = None  # text insertion reference: the start of the sequence


def change_hash(buf: bytes) -> str:
    """SHA-256 change hash of an encoded change chunk (chunk type 1) or its
    deflated form (chunk type 2), as the binary format defines it."""
    if buf[8] == 1:
        return hashlib.sha256(buf[8:]).hexdigest()
    pos, length, shift = 9, 0, 0
    while True:  # LEB128 length of the deflated body
        b = buf[pos]
        length |= (b & 0x7F) << shift
        pos += 1
        shift += 7
        if not b & 0x80:
            break
    body = zlib.decompress(buf[pos:pos + length], -15)
    n, head = len(body), bytearray([1])
    while True:
        b = n & 0x7F
        n >>= 7
        head.append(b | (0x80 if n else 0))
        if not n:
            break
    return hashlib.sha256(bytes(head) + body).hexdigest()


def opid(ctr: int, actor: str) -> str:
    return f"{ctr}@{actor}"


def actor_id(seed: int, doc: int, who: str) -> str:
    """A 16-byte actor id of its own for (seed, doc, who)."""
    return hashlib.sha256(f"{seed}:{doc}:{who}".encode()).hexdigest()[:32]


def pool_actor(seed: int, j: int) -> str:
    """Member j of the deployment's pool of device actor ids."""
    return actor_id(seed, -1, f"pool{j}")


def doc_actors(config: dict, seed: int, doc: int) -> list:
    """The pool members that edit `doc`: `actors_per_doc` distinct ones."""
    rng = random.Random(f"{seed}:{doc}:actors")
    return rng.sample(range(config["actor_pool"]), config["actors_per_doc"])


# ---------------------------------------------------------------------- #
# schedule


def _fixed_gaps(count: int, span: float, rng) -> np.ndarray:
    """Arrival offsets of `count` changes over `span` seconds: a fixed
    multiset of exponential gaps scaled to the span, ordered by `rng`."""
    if count == 0:
        return np.zeros(0)
    gaps = np.random.default_rng(FIXED_SEED + count).exponential(size=count + 1)
    gaps *= span / gaps.sum()
    rng.shuffle(gaps)
    return np.cumsum(gaps[:count])


def _fixed_ranks(count: int, docs: int, popularity: dict, rng) -> np.ndarray:
    """Popularity ranks (0 = hottest): a fixed multiset ordered by `rng`."""
    fixed = np.random.default_rng(FIXED_SEED + 1 + count)
    if popularity["kind"] == "zipf":
        w = 1.0 / np.arange(1, docs + 1) ** popularity["s"]
        cdf = np.cumsum(w) / w.sum()
        ranks = np.minimum(np.searchsorted(cdf, fixed.random(count)), docs - 1)
    elif popularity["kind"] == "uniform":
        ranks = fixed.integers(0, docs, count)
    else:
        raise ValueError(f"unknown popularity {popularity['kind']!r}")
    rng.shuffle(ranks)
    return ranks


def _fixed_sizes(count: int, shape: dict, rng) -> list:
    """Per change (kind, length): a fixed multiset ordered by `rng`."""
    fixed = random.Random(FIXED_SEED + 2 + count)
    if shape["kind"] == "map_ops":
        sizes = [("map", shape["counter_incs"] + shape["key_sets"])] * count
    elif shape["kind"] == "typing_burst":
        p = 1.0 / shape["mean_keystrokes"]
        sizes = []
        for _ in range(count):
            n = 1
            while fixed.random() > p and n < shape["max_keystrokes"]:
                n += 1
            kind = "del" if fixed.random() < shape["delete_share"] else "ins"
            sizes.append((kind, n))
    else:
        raise ValueError(f"unknown change shape {shape['kind']!r}")
    order = list(range(count))
    rng.shuffle(order)
    return [sizes[i] for i in order]


def schedule(config: dict, traffic: dict, rate: float, warmup_s: float,
             seconds: float, seed: int, batch_docs: int = 0):
    """The run's changes in due order: (due_s, doc, actor_index, kind,
    length). Warm-up changes are due in [-warmup_s, 0), the window's in
    [0, seconds), and the cool-down's (offered while the window's last
    changes are served, never measured) in [seconds, seconds +
    cooldown_s)."""
    rng = np.random.default_rng(seed)
    docs = config["docs"]
    shapes = _shape_batches(config, traffic, warmup_s, batch_docs, rng)
    cool = traffic.get("cooldown_s", 0)
    counts = [round(rate * warmup_s), round(rate * seconds),
              round(rate * cool)]
    due = np.concatenate([
        _fixed_gaps(counts[0], warmup_s, rng) - warmup_s,
        _fixed_gaps(counts[1], seconds, rng),
        _fixed_gaps(counts[2], cool, rng) + seconds])
    n = sum(counts)
    ranks = _fixed_ranks(n, docs, traffic["doc_popularity"], rng)
    scramble = rng.permutation(docs) if traffic["doc_popularity"].get(
        "scramble") else np.arange(docs)
    actors = rng.integers(0, config["actors_per_doc"], n)
    sizes = _fixed_sizes(n, traffic["change"], rng)
    return shapes + [(float(due[i]), int(scramble[ranks[i]]), int(actors[i]),
                      sizes[i][0], sizes[i][1]) for i in range(n)], scramble


def _shape_batches(config, traffic, warmup_s, width, rng) -> list:
    """Set-up deliveries that compile every change width the run can reach:
    for each size in the mix's ``shape_warmup`` range, one delivery of
    documents as many as the least that shares the pow2 bucket of `width`
    (the cell's batch), one change each, the first of that size and the
    rest of the smallest. Due a second apart, long before the warm-up, so
    no author misses another's change."""
    spec = traffic.get("shape_warmup")
    if not spec or not width:
        return []
    lo, hi = spec["sizes"]
    kind = spec["kind"]
    count = (1 << max(0, width - 1).bit_length()) // 2 + 1
    out = []
    for k, size in enumerate(range(lo, hi + 1)):
        docs = rng.choice(config["docs"], count, replace=False)
        actors = rng.integers(0, config["actors_per_doc"], count)
        due = -warmup_s - 10.0 - (hi - lo + 1) + k
        for j in range(count):
            out.append((due, int(docs[j]), int(actors[j]), kind,
                        size if j == 0 else lo))
    return out


# ---------------------------------------------------------------------- #
# preload templates


def map_templates(config: dict):
    """`templates` shared change chains (chip_smoke.make_streams' shape):
    round 0 creates the counters, later rounds increment them; the other
    ops set root keys, each naming the key's previous op as its pred.
    Returns per template (buffers, ref ops per change, state) where state
    holds the visible op per key, the counter ops, max op and head hash."""
    from automerge_tpu.columnar import encode_change

    shape, pre = config["doc"], config["preload"]
    out = []
    for t in range(pre["templates"]):
        rng = random.Random(FIXED_SEED * 1000 + t)
        actor = f"{t:02x}" * 8
        last, deps, bufs, refs = {}, [], [], []
        start = 1
        for r in range(pre["template_changes"]):
            ctr, ops, ref = start, [], []
            for c in range(shape["counters"]):
                key = f"c{c}"
                if r == 0:
                    v = rng.randrange(100)
                    ops.append({"action": "set", "obj": "_root", "key": key,
                                "datatype": "counter", "value": v,
                                "pred": []})
                    ref.append(("set", "_root", key, (ctr, actor), v,
                                "counter", ()))
                    last[key] = (ctr, actor)
                else:
                    v = rng.randrange(1, 10)
                    ops.append({"action": "inc", "obj": "_root", "key": key,
                                "value": v, "pred": [opid(*last[key])]})
                    ref.append(("inc", "_root", key, (ctr, actor), v,
                                (last[key],)))
                ctr += 1
            for _ in range(pre["ops_per_change"] - shape["counters"]):
                key = f"k{rng.randrange(shape['keys'])}"
                v = rng.randrange(10**6)
                pred = (last[key],) if key in last else ()
                ops.append({"action": "set", "obj": "_root", "key": key,
                            "datatype": "uint", "value": v,
                            "pred": [opid(*p) for p in pred]})
                ref.append(("set", "_root", key, (ctr, actor), v, "uint",
                            pred))
                last[key] = (ctr, actor)
                ctr += 1
            buf = encode_change({"actor": actor, "seq": r + 1,
                                 "startOp": start, "time": 0, "deps": deps,
                                 "ops": ops})
            deps = [change_hash(buf)]
            bufs.append(buf)
            refs.append(ref)
            start = ctr
        out.append((bufs, refs, {"last": last, "max_op": start - 1,
                                 "head": deps[0], "actor": actor}))
    return out


def text_templates(config: dict):
    """`templates` shared histories: one change that makes the `text`
    object and types `chars` characters into it."""
    from automerge_tpu.columnar import encode_change

    pre = config["preload"]
    out = []
    for t in range(pre["templates"]):
        rng = random.Random(FIXED_SEED * 1000 + t)
        actor = f"{t:02x}" * 8
        text = (1, actor)
        ops = [{"action": "makeText", "obj": "_root", "key": "text",
                "pred": []}]
        ref = [("makeText", "_root", "text", text, ())]
        prev = HEAD
        for i in range(pre["chars"]):
            ch = rng.choice("abcdefghijklmnopqrstuvwxyz     ")
            op = (i + 2, actor)
            ops.append({"action": "set", "obj": opid(*text),
                        "elemId": "_head" if prev is HEAD else opid(*prev),
                        "insert": True, "value": ch, "pred": []})
            ref.append(("ins", opid(*text), prev, op, ch))
            prev = op
        buf = encode_change({"actor": actor, "seq": 1, "startOp": 1,
                             "time": 0, "deps": [], "ops": ops})
        out.append(([buf], [ref], {"max_op": pre["chars"] + 1,
                                   "head": change_hash(buf),
                                   "actor": actor, "text": text,
                                   "elems": [(i + 2, actor)
                                             for i in range(pre["chars"])]}))
    return out


def _run_lengths(total: int, mean: float, rng) -> list:
    """Geometric run lengths (at least 1, mean `mean`) summing to `total`
    exactly: the last run is cut to fit."""
    p = 1.0 / mean
    out, left = [], total
    while left:
        n = 1
        while rng.random() > p:
            n += 1
        n = min(n, left)
        out.append(n)
        left -= n
    return out


def trace_history(config: dict, seed: int, doc: int):
    """Document `doc`'s own editing history, shaped after automerge-perf's
    editing trace: one author types runs of characters at a cursor that
    jumps now and then, and deletes runs of characters with backspace.

    The counts are exact: ``keystrokes`` keystrokes, of which ``deletions``
    delete a visible character, leave ``final_chars`` (``keystrokes - 2 *
    deletions``) visible of ``keystrokes - deletions`` elements. Typing and
    backspace runs are geometric (``typing_run_mean``,
    ``backspace_run_mean``); each backspace run follows a typing run drawn
    at random, and is put off while the text is shorter than it; before
    each run the cursor jumps to a random position with probability
    ``jump_p`` (a backspace run too near the start jumps as well). The
    history is cut into changes of ``keystrokes_per_change`` keystrokes,
    the first of which also makes the text object. The author's actor id
    is drawn from (seed, doc), so no two documents share an element id.

    Returns (buffers, ref ops per change, state) as a template does; the
    state holds every element in RGA order and the positions of those the
    history deleted."""
    from automerge_tpu.columnar import encode_change

    pre = config["preload"]
    keys, dels = pre["keystrokes"], pre["deletions"]
    if keys - 2 * dels != pre["final_chars"]:
        raise ValueError("trace preload: keystrokes - 2 * deletions must be "
                         "final_chars")
    per_change, jump_p = pre["keystrokes_per_change"], pre["jump_p"]
    rng = random.Random(f"{seed}:{doc}:trace")
    actor = actor_id(seed, doc, "trace")
    text = (1, actor)
    text_id = opid(*text)
    typing = _run_lengths(keys - dels, pre["typing_run_mean"], rng)
    after: dict[int, list] = {}  # typing run -> backspace runs after it
    for n in _run_lengths(dels, pre["backspace_run_mean"], rng):
        after.setdefault(rng.randrange(len(typing)), []).append(n)

    # element k + 1 is the k-th insert; 0 stands for the head. A single
    # author's new element has the greatest opId, so RGA puts it right
    # after its reference: `nxt` is the whole sequence as a linked list.
    e_ctr = [0]
    nxt = [-1]
    dead = [False]
    vis: list = []  # visible elements in order
    p = 0  # the cursor: a position in vis
    ctr = 2  # the next op's counter; 1 made the text object
    changes = []  # (ops, ref ops, start op)
    start = 1
    ops = [{"action": "makeText", "obj": "_root", "key": "text", "pred": []}]
    ref = [("makeText", "_root", "text", text, ())]

    def cut():
        nonlocal ops, ref, start
        changes.append((ops, ref, start))
        ops, ref, start = [], [], ctr

    def keystroke(op, ref_op):
        nonlocal ctr
        ops.append(op)
        ref.append(ref_op)
        ctr += 1
        if (ctr - 2) % per_change == 0:
            cut()

    def elem_id(e):
        return (e_ctr[e], actor)

    pending: list = []
    for i, length in enumerate(typing):
        if rng.random() < jump_p:
            p = rng.randrange(len(vis) + 1)
        ref_e = vis[p - 1] if p else 0
        new = []
        for _ in range(length):
            e = len(e_ctr)
            e_ctr.append(ctr)
            nxt.append(nxt[ref_e])
            dead.append(False)
            nxt[ref_e] = e
            ch = rng.choice("abcdefghijklmnopqrstuvwxyz     ")
            keystroke({"action": "set", "obj": text_id,
                       "elemId": opid(*elem_id(ref_e)) if ref_e else "_head",
                       "insert": True, "value": ch, "pred": []},
                      ("ins", text_id, elem_id(ref_e) if ref_e else HEAD,
                       (ctr, actor), ch))
            new.append(e)
            ref_e = e
        vis[p:p] = new
        p += length
        pending.extend(after.get(i, ()))
        while pending and pending[0] <= len(vis):
            length = pending.pop(0)
            if rng.random() < jump_p or p < length:
                p = rng.randrange(length, len(vis) + 1)
            for e in vis[p - length:p][::-1]:
                eid = opid(*elem_id(e))
                dead[e] = True
                keystroke({"action": "del", "obj": text_id, "elemId": eid,
                           "pred": [eid]},
                          ("del", text_id, elem_id(e), (ctr, actor)))
            del vis[p - length:p]
            p -= length
    if ops:
        cut()

    bufs, refs, deps = [], [], []
    for seq, (ops, ref, start) in enumerate(changes, 1):
        buf = encode_change({"actor": actor, "seq": seq, "startOp": start,
                             "time": 0, "deps": deps, "ops": ops})
        deps = [change_hash(buf)]
        bufs.append(buf)
        refs.append(ref)
    elems, deleted = [], []
    e = nxt[0]
    while e >= 0:
        if dead[e]:
            deleted.append(len(elems))
        elems.append(elem_id(e))
        e = nxt[e]
    return bufs, refs, {"max_op": keys + 1, "head": deps[0], "actor": actor,
                        "text": text, "elems": elems, "deleted": deleted}


# ---------------------------------------------------------------------- #
# per-document generation


class DocGen:
    """One document's authors. Views are vector clocks over the window
    actors (preload ops are in every view). Call `join` for each actor in
    set-up, then `change` in due order."""

    def __init__(self, config, traffic, seed, doc, template):
        from automerge_tpu.columnar import encode_change

        self._encode = encode_change
        self.kind = config["doc"]["kind"]
        self.n = config["actors_per_doc"]
        self.rng = random.Random(f"{seed}:{doc}")
        self.actors = [pool_actor(seed, j)
                       for j in doc_actors(config, seed, doc)]
        self.lag = traffic.get("sync_lag_ms", 0) / 1000.0
        self.shape = traffic["change"] if traffic else {}
        self.keys = config["doc"].get("keys", 0)
        self.counters = config["doc"].get("counters", 0)
        self.preload_max = template["max_op"]
        self.preload_head = template["head"]
        self.seq = [0] * self.n
        self.clock = [(0,) * self.n for _ in range(self.n)]  # latest view
        self.hashes = [[] for _ in range(self.n)]
        self.end_ctr = [[] for _ in range(self.n)]
        self.history = []  # (due, actor, seq, clock, hash) in due order
        self.dues = []
        self.rows = 0  # rows the changes add on the device (estimate)
        if self.kind == "map":
            # key -> [opid, actor, seq, killers]; preload ops: actor -1
            self.entries = {k: [[p, -1, 0, []]]
                            for k, p in template["last"].items()}
            self.counter_ops = {k: p for k, p in template["last"].items()
                                if k.startswith("c")}
        else:
            self.text = template["text"]
            self.text_id = opid(*self.text)
            elems = template["elems"]
            self.e_id = list(elems)
            self.e_actor = np.full(len(elems), -1, np.int32)
            self.e_seq = np.zeros(len(elems), np.int32)
            self.e_del = np.full((len(elems), self.n), 1 << 30, np.int32)
            # deleted in the preload: hidden from every view
            self.e_del[template.get("deleted", [])] = 0
            self.order = np.arange(len(elems), dtype=np.int64)  # RGA order
            self.cursor = [-1] * self.n  # element index, -1 = head

    # -- views ---------------------------------------------------------- #

    def _in(self, a, s, view):
        return a < 0 or view[a] >= s

    def _max_op(self, view):
        m = self.preload_max
        for a, s in enumerate(view):
            if s:
                m = max(m, self.end_ctr[a][s - 1])
        return m

    def _dep(self, due):
        """Index into history of the doc's latest change due at least
        sync_lag earlier, or None."""
        i = bisect.bisect_right(self.dues, due - self.lag)
        return i - 1 if i else None

    # -- map ops -------------------------------------------------------- #

    def _visible(self, key, view):
        out = []
        for e in self.entries.get(key, ()):
            if self._in(e[1], e[2], view) and not any(
                    view[ka] >= ks for ka, ks in e[3]):
                out.append(e)
        return out

    def _map_ops(self, a, s, ctr, view, incs, sets):
        rng, ops, ref = self.rng, [], []
        for c in range(incs):
            key = f"c{c % self.counters}"
            v = rng.randrange(1, 10)
            p = self.counter_ops[key]
            ops.append({"action": "inc", "obj": "_root", "key": key,
                        "value": v, "pred": [opid(*p)]})
            ref.append(("inc", "_root", key, (ctr, self.actors[a]), v, (p,)))
            ctr += 1
            self.rows += 1
        for _ in range(sets):
            key = f"k{rng.randrange(self.keys)}"
            v = rng.randrange(10**6)
            vis = self._visible(key, view)
            pred = tuple(e[0] for e in vis)
            for e in vis:
                e[3].append((a, s))
            me = (ctr, self.actors[a])
            self.entries.setdefault(key, []).append([me, a, s, []])
            ops.append({"action": "set", "obj": "_root", "key": key,
                        "datatype": "uint", "value": v,
                        "pred": [opid(*p) for p in pred]})
            ref.append(("set", "_root", key, me, v, "uint", pred))
            ctr += 1
            self.rows += max(1, len(pred))
        return ops, ref

    def _prune(self):
        """Drops map entries that no current or future view can see."""
        low = tuple(min(c[a] for c in self.clock) for a in range(self.n))
        for key, es in self.entries.items():
            if len(es) > 4:
                self.entries[key] = [
                    e for e in es
                    if not any(low[ka] >= ks for ka, ks in e[3])]

    # -- text ops ------------------------------------------------------- #

    def _text_visible(self, view):
        """The elements `view` sees, in order, and a mask over element
        indices of the same (array work only, no step per element)."""
        n = len(self.e_id)
        v = np.asarray(view, np.int32)
        act, seq = self.e_actor[:n], self.e_seq[:n]
        ok = (act < 0) | (v[np.maximum(act, 0)] >= seq)
        ok &= ~(self.e_del[:n] <= v[None, :]).any(axis=1)
        return self.order[ok[self.order]], ok

    def _insert_after(self, ref_idx, new_idx, new_id):
        """RGA placement: after the reference, past every following
        element with a greater opId (those and their descendants sort
        first)."""
        pos = (0 if ref_idx < 0
               else int(np.flatnonzero(self.order == ref_idx)[0]) + 1)
        key = (new_id[0], new_id[1])
        while pos < len(self.order):
            other = self.e_id[self.order[pos]]
            if (other[0], other[1]) < key:
                break
            pos += 1
        self.order = np.insert(self.order, pos, new_idx)

    def _add_elem(self, a, s, eid):
        idx = len(self.e_id)
        if idx == self.e_actor.shape[0]:  # grow by doubling
            self.e_actor = np.concatenate([self.e_actor,
                                           np.full(idx, -1, np.int32)])
            self.e_seq = np.concatenate([self.e_seq, np.zeros(idx, np.int32)])
            self.e_del = np.concatenate(
                [self.e_del, np.full((idx, self.n), 1 << 30, np.int32)])
        self.e_id.append(eid)
        self.e_actor[idx] = a
        self.e_seq[idx] = s
        return idx

    def _text_ops(self, a, s, ctr, view, kind, length, jump_p):
        rng, ops, ref = self.rng, [], []
        vis, seen = self._text_visible(view)
        cur = self.cursor[a]
        if cur >= 0 and not seen[cur]:
            cur = None  # deleted under the cursor: jump
        if cur is None or rng.random() < jump_p:
            cur = -1
            pos = rng.randrange(len(vis) + 1)
            if pos:
                cur = int(vis[pos - 1])
        if kind == "del":
            p = int(np.flatnonzero(vis == cur)[0]) + 1 if cur >= 0 else 0
            if p < length:  # too near the start to backspace: jump
                p = rng.randrange(length, len(vis) + 1)
            for e in vis[p - length:p][::-1]:
                e = int(e)
                eid = self.e_id[e]
                ops.append({"action": "del", "obj": self.text_id,
                            "elemId": opid(*eid), "pred": [opid(*eid)]})
                ref.append(("del", self.text_id, eid, (ctr, self.actors[a])))
                self.e_del[e, a] = min(self.e_del[e, a], s)
                ctr += 1
                self.rows += 1
            start = p - length
            self.cursor[a] = int(vis[start - 1]) if start > 0 else -1
        else:
            prev = cur
            for _ in range(length):
                ch = rng.choice("abcdefghijklmnopqrstuvwxyz     ")
                me = (ctr, self.actors[a])
                ref_id = HEAD if prev < 0 else self.e_id[prev]
                ops.append({"action": "set", "obj": self.text_id,
                            "elemId": "_head" if ref_id is HEAD
                            else opid(*ref_id),
                            "insert": True, "value": ch, "pred": []})
                ref.append(("ins", self.text_id, ref_id, me, ch))
                idx = self._add_elem(a, s, me)
                self._insert_after(prev, idx, me)
                prev = idx
                ctr += 1
                self.rows += 1
            self.cursor[a] = prev
        return ops, ref

    # -- changes -------------------------------------------------------- #

    def _emit(self, a, due, dep_idx, make_ops):
        """Builds actor a's next change whose view also holds history
        entry dep_idx; returns (buffer, hash, ref ops, n_ops)."""
        s = self.seq[a] + 1
        own = self.clock[a]
        deps = []
        view = list(own)
        covered = False  # the dep's view holds this actor's previous change
        if dep_idx is not None:
            dclock, dhash = self.history[dep_idx][3], self.history[dep_idx][4]
            if any(dclock[i] > own[i] for i in range(self.n)):
                view = [max(x, y) for x, y in zip(own, dclock)]
                deps.append(dhash)
                covered = dclock[a] >= s - 1
        if s > 1 and not covered:
            deps.append(self.hashes[a][-1])
        if not deps:
            deps.append(self.preload_head)
        start = self._max_op(tuple(view)) + 1
        view[a] = s
        view = tuple(view)
        ops, ref = make_ops(a, s, start, view)
        buf = self._encode({"actor": self.actors[a], "seq": s,
                            "startOp": start, "time": 0,
                            "deps": sorted(set(deps)), "ops": ops})
        h = change_hash(buf)
        self.seq[a] = s
        self.clock[a] = view
        self.hashes[a].append(h)
        self.end_ctr[a].append(start + len(ops) - 1)
        self.history.append((due, a, s, view, h))
        self.dues.append(due)
        return buf, h, ref, len(ops)

    def join(self, a):
        """Actor a's first change, made in set-up: one op on the preload
        state (a counter increment, or one typed character)."""
        if self.kind == "map":
            def make(a, s, ctr, view):
                return self._map_ops(a, s, ctr, view, 1, 0)
        else:
            def make(a, s, ctr, view):
                return self._text_ops(a, s, ctr, view, "ins", 1, 1.0)
        return self._emit(a, float("-inf"), None, make)

    def change(self, due, a, kind, length):
        dep = self._dep(due)
        if self.kind == "map":
            incs = self.shape["counter_incs"]

            def make(a, s, ctr, view):
                return self._map_ops(a, s, ctr, view, incs, length - incs)
        else:
            jump = 1.0 - self.shape["continue_p"]

            def make(a, s, ctr, view):
                return self._text_ops(a, s, ctr, view, kind, length, jump)
        out = self._emit(a, due, dep, make)
        if self.kind == "map" and len(self.history) % 16 == 0:
            self._prune()
        return out


def generate_doc(task):
    """One document's set-up joins and run changes (a worker's unit).
    `task` is (config, traffic, seed, doc, template state, entries, local
    actors to join) with entries [(plan index, due, actor, kind, length)];
    a state of None makes the document's own `trace_history` first.
    Returns (doc, joins [(buffer, ref ops)], changes [(plan index, buffer,
    ref ops, n_ops, actor id, seq)], device rows the doc grows by, preload
    (buffers, ref ops per change) or None where the template's serves)."""
    config, traffic, seed, doc, state, entries, join = task
    preload = None
    if state is None:
        bufs, refs, state = trace_history(config, seed, doc)
        preload = (bufs, refs)
    gen = DocGen(config, traffic, seed, doc, state)
    joins = []
    for a in join:
        buf, _h, ref, _n = gen.join(a)
        joins.append((buf, ref))
    out = []
    for i, due, a, kind, length in entries:
        buf, _h, ref, nops = gen.change(due, a, kind, length)
        out.append((i, buf, ref, nops, gen.actors[a], gen.seq[a]))
    return doc, joins, out, gen.rows, preload
