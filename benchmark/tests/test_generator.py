"""The generator: deterministic by seed, with the stated shape."""
import hashlib

import numpy as np
import pytest

from benchmark import generator, harness, reference


def cfg(cell, docs):
    c = harness.load_cell(cell, unlisted=True)
    c["config"] = dict(c["config"], docs=docs)
    return c


def test_schedule_is_deterministic_and_fixed_in_size():
    c = cfg("map-8k.uniform-steady", 8192)
    a, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 2**33 + 7)
    b, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 2**33 + 7)
    other, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 99)
    assert a == b
    assert a != other
    assert len(a) == len(other) == 8000  # warm-up, window, cool-down
    # the multiset of sizes does not depend on the seed
    assert sorted(p[4] for p in a) == sorted(p[4] for p in other)
    dues = [p[0] for p in a]
    assert dues == sorted(dues) and -2 <= dues[0] and dues[-1] < 14


def test_zipf_rank_mass():
    c = cfg("map-8k.uniform-steady", 8192)
    traffic = dict(c["traffic"], doc_popularity={"kind": "zipf", "s": 0.99,
                                                 "scramble": True})
    plan, scramble = generator.schedule(c["config"], traffic, 2000, 0, 20, 3)
    inverse = np.argsort(scramble)
    ranks = np.array([inverse[p[1]] for p in plan])
    w = 1.0 / np.arange(1, 8193) ** 0.99
    want = w[0] / w.sum()  # about 10%
    assert abs((ranks == 0).mean() - want) < 0.01
    assert abs((ranks < 10).mean() - w[:10].sum() / w.sum()) < 0.02


def test_typing_bursts_keep_the_delete_ratio():
    c = cfg("text-512.typing-steady", 512)
    plan, _ = generator.schedule(c["config"], c["traffic"], 2000, 0, 10, 5)
    keys = sum(p[4] for p in plan)
    deleted = sum(p[4] for p in plan if p[3] == "del")
    assert abs(deleted / keys - 0.3) < 0.02
    lengths = [p[4] for p in plan]
    assert abs(np.mean(lengths) - 4) < 0.3 and max(lengths) <= 32


def _traffic(cell, docs, rate, seed):
    c = cfg(cell, docs)
    c["cell"] = dict(c["cell"], batching={"policy": "docs", "docs": docs // 2,
                                             "max_wait_s": 0.25})
    c["config"]["preload"] = dict(c["config"]["preload"], templates=4)
    return harness.Traffic(c, rate, 1, 4, seed, workers=1)


def test_changes_are_deterministic_by_seed():
    a = _traffic("map-8k.uniform-steady", 16, 30, 2**33 + 11)
    b = _traffic("map-8k.uniform-steady", 16, 30, 2**33 + 11)
    assert a.buf == b.buf and a.ref == b.ref


def test_deps_name_the_actor_and_the_lagged_change():
    from automerge_tpu.columnar import decode_change

    t = _traffic("map-8k.uniform-steady", 8, 40, 17)
    lag = 0.25
    by_hash, seen = {}, {}
    for d in range(8):
        for buf in t.extra[d][0]:
            by_hash[generator.change_hash(buf)] = (float("-inf"), None)
    for i in range(len(t.due)):
        ch = decode_change(t.buf[i])
        for dep in ch["deps"]:
            due, doc = by_hash[dep]
            assert doc in (None, int(t.doc[i]))
            assert due <= t.due[i]
        prev = seen.get((int(t.doc[i]), ch["actor"]))
        if prev is not None:  # own previous change, or a dep that holds it
            assert prev in ch["deps"] or any(
                by_hash[h][0] <= t.due[i] - lag for h in ch["deps"])
        by_hash[ch["hash"]] = (t.due[i], int(t.doc[i]))
        seen[(int(t.doc[i]), ch["actor"])] = ch["hash"]


def test_concurrent_actors_conflict_and_the_reference_applies_them():
    t = _traffic("map-8k.uniform-steady", 4, 60, 21)
    multi = sum(1 for r in t.ref if r for op in r
                if op[0] == "set" and len(op[6]) > 1)
    assert multi > 0  # some sets name two concurrent predecessors
    for d in t.touched:
        ref = reference.RefDoc()
        for ops in t.preload_refs(d):
            ref.apply(ops)
        for i in np.flatnonzero(t.doc == d):
            ref.apply(t.ref[i])
        assert ref.state()["_root"]


def test_text_reference_matches_the_typists_model():
    t = _traffic("text-512.typing-steady", 4, 30, 23)
    for d in t.touched:
        ref = reference.RefDoc()
        for ops in t.preload_refs(d):
            ref.apply(ops)
        for i in np.flatnonzero(t.doc == d):
            ref.apply(t.ref[i])
        (text,) = ref.texts
        assert len(ref.state()[text]) > 200  # 256 preloaded, 30% deletes


TRACE = {"kind": "trace", "keystrokes": 259778, "deletions": 77463,
         "final_chars": 104852, "keystrokes_per_change": 1000,
         "typing_run_mean": 8, "backspace_run_mean": 4, "jump_p": 0.1,
         "rows": 259779}


def trace_preload(keystrokes, deletions):
    return dict(TRACE, keystrokes=keystrokes, deletions=deletions,
                final_chars=keystrokes - 2 * deletions)


def trace_ops(refs):
    return [op for ops in refs for op in ops]


def test_trace_preload_has_the_traces_counts_at_full_size():
    from automerge_tpu.columnar import decode_change

    bufs, refs, state = generator.trace_history({"preload": TRACE},
                                                2**33 + 31, 0)
    ops = trace_ops(refs)
    kinds = [op[0] for op in ops]
    assert kinds.count("makeText") == 1
    assert kinds.count("ins") + kinds.count("del") == 259778
    assert kinds.count("del") == 77463
    assert len(state["elems"]) == 182315
    assert len(state["deleted"]) == 77463
    assert len(bufs) == len(refs) == 260
    assert [len(r) for r in refs] == [1001] + [1000] * 258 + [778]
    ref = reference.RefDoc()
    for r in refs:
        ref.apply(r)
    (text,) = ref.texts
    visible = ref.texts[text].visible()
    assert len(visible) == 104852
    # the state's RGA order and deleted set are the reference's
    dead = set(state["deleted"])
    assert [f"{c}@{a}" for i, (c, a) in enumerate(state["elems"])
            if i not in dead] == [op for op, _v in visible]
    last = decode_change(bufs[-1])
    assert last["seq"] == 260 and last["startOp"] == 259002
    assert state["max_op"] == 259779 and state["head"] == last["hash"]


def test_trace_preload_is_deterministic_by_seed_and_distinct_by_doc():
    cfg = {"preload": trace_preload(6000, 1800)}
    a = generator.trace_history(cfg, 2**33 + 41, 0)
    assert a == generator.trace_history(cfg, 2**33 + 41, 0)
    other_seed = generator.trace_history(cfg, 2**33 + 43, 0)
    other_doc = generator.trace_history(cfg, 2**33 + 41, 1)
    assert a[0] != other_seed[0]

    def ids(history):
        return {op[3] for op in trace_ops(history[1])
                if op[0] in ("ins", "makeText")}

    assert not ids(a) & ids(other_doc)
    assert len(ids(a)) == 6000 - 1800 + 1


def test_trace_preload_refuses_counts_that_disagree():
    with pytest.raises(ValueError):
        generator.trace_history(
            {"preload": dict(trace_preload(6000, 1800), final_chars=2401)},
            1, 0)


def test_window_starts_from_the_trace_preloads_state():
    """A document's own preload is made in the worker; its deleted
    elements are in no view, and the window's typists act on visible
    elements only."""
    c = cfg("text-512.typing-steady", 3)
    c["config"]["preload"] = trace_preload(6000, 1800)
    c["config"]["capacity"] = 8192
    c["cell"] = dict(c["cell"], batching={"policy": "docs", "docs": 2,
                                             "max_wait_s": 0.25})
    t = harness.Traffic(c, 40, 1, 4, 2**33 + 47, workers=2)
    assert t.tpl_of == [0, 1, 2]
    for d in range(3):
        pre = trace_ops(t.templates[d][1])
        dead = {op[2] for op in pre if op[0] == "del"}
        assert dead
        window = trace_ops(t.extra[d][1]) + trace_ops(
            t.ref[i] for i in np.flatnonzero(t.doc == d))
        assert window
        for op in window:
            target = op[2]
            assert target not in dead, op
        ref = reference.RefDoc()
        for ops in t.preload_refs(d):
            ref.apply(ops)
        for i in np.flatnonzero(t.doc == d):
            ref.apply(t.ref[i])
        (text,) = ref.texts
        assert len(ref.texts[text].visible()) > 1800


def traffic_hash(t):
    h = hashlib.sha256()
    for bufs in t.preload():
        for b in bufs:
            h.update(b)
    for d in range(t.docs):
        h.update(repr(t.preload_refs(d)).encode())
    for b in t.buf:
        h.update(b)
    h.update(repr(t.ref).encode())
    h.update(t.due.tobytes())
    h.update(t.doc.tobytes())
    return h.hexdigest()


# the text-512 traffic's hash at a reduced size, as the harness made it
# before trace preloads were added; the second case goes through the pool
TEXT_512_HASHES = [
    (16, 4, 256, 60, 1,
     "54a58a2152511a1fc653242c397fa26fbe22ea03724468bae12750e204c32231"),
    (96, 8, 128, 200, 2,
     "f54c3dc368842132df33e411e1cff81423a460c9dd778a5e11924e8e55d54a20"),
]


@pytest.mark.parametrize("docs,templates,chars,rate,workers,want",
                         TEXT_512_HASHES)
def test_text_512_traffic_is_unchanged(docs, templates, chars, rate, workers,
                                       want):
    c = cfg("text-512.typing-steady", docs)
    c["config"]["preload"] = dict(c["config"]["preload"], templates=templates,
                                  chars=chars, rows=chars + 1)
    c["cell"] = dict(c["cell"], batching={"policy": "docs", "docs": 8,
                                             "max_wait_s": 0.25})
    t = harness.Traffic(c, rate, 1, 4, 2**33 + 29, workers=workers)
    assert traffic_hash(t) == want
