"""The plain reference, and the patch reader it is compared with.

Imports nothing of the program. ``RefDoc`` applies the generator's op
tuples with Automerge's semantics, written out directly:

- a map key holds the set of visible ops: a ``set`` or ``makeText`` adds
  itself and hides every op named in its ``pred``; a counter's value is
  its initial value plus every ``inc`` that names it;
- a text object is an RGA: an insert goes after its reference element,
  past every following element with a greater opId (Lamport order:
  counter, then actor id); a ``del`` hides the element it names. The
  sequence is kept in blocks with a map from element id to its entry
  (``_Text``), so an op finds its place without a scan from the start.

``PatchDoc`` applies the program's reference-format patches (``props``
per key and opId, text ``edits``: insert, multi-insert, update, remove)
to a plain state. Both render to the same form, ``state()``;
``patch_agrees`` holds one served patch against the reference after the
same delivery, and a whole-document patch must rebuild the reference's
state exactly.
"""
from __future__ import annotations

from itertools import islice
from operator import attrgetter

BLOCK = 128  # a text block splits in two past this many elements
_VISIBLE, _DELETED = attrgetter("visible"), attrgetter("deleted")


def _oid(op):
    return f"{op[0]}@{op[1]}"


class _Elem:
    __slots__ = ("id", "value", "deleted", "block")

    def __init__(self, id_, value, deleted, block):
        self.id, self.value, self.deleted, self.block = (id_, value, deleted,
                                                         block)


class _Block:
    """A run of the sequence: its entries, how many are visible, and the
    block after it."""
    __slots__ = ("elems", "visible", "next")

    def __init__(self, elems, visible, next_):
        self.elems, self.visible, self.next = elems, visible, next_


class _Text:
    """One text object's RGA. The whole sequence, deleted elements
    included, is `blocks` in order; `by_id` finds an element's entry, and
    the entry its block. Once `visible()` has been asked for, the visible
    sequence is kept up to date op by op."""

    def __init__(self):
        self.blocks = [_Block([], 0, None)]
        self.by_id = {}
        self.seq = None  # [(opid str, value)] of the visible elements

    def insert(self, after, me, value) -> None:
        key = (me[0], me[1])
        if after is None:
            blk, i = self.blocks[0], 0
        else:
            rec = self.by_id[after]
            blk = rec.block
            i = blk.elems.index(rec) + 1
        # past every following element with a greater opId
        while True:
            elems = blk.elems
            while i < len(elems) and elems[i].id > key:
                i += 1
            if i < len(elems) or blk.next is None or not (
                    blk.next.elems[0].id > key):
                break
            blk, i = blk.next, 0
        if self.seq is not None:
            self.seq.insert(self._rank(blk, i), (_oid(key), value))
        rec = _Elem(key, value, False, blk)
        self.by_id[key] = rec
        blk.elems.insert(i, rec)
        blk.visible += 1
        if len(blk.elems) > BLOCK:
            self._split(blk)

    def delete(self, elem) -> None:
        rec = self.by_id.get(elem)
        if rec is None:
            raise KeyError(f"del of unknown element {elem}")
        if rec.deleted:
            return
        if self.seq is not None:
            del self.seq[self._rank(rec.block, rec.block.elems.index(rec))]
        rec.deleted = True
        rec.block.visible -= 1

    def _rank(self, blk, i) -> int:
        """Visible elements before position i of `blk` (C-level sums, no
        Python step per block or element)."""
        before = islice(self.blocks, self.blocks.index(blk))
        return (sum(map(_VISIBLE, before)) + i
                - sum(map(_DELETED, islice(blk.elems, i))))

    def _split(self, blk) -> None:
        half = len(blk.elems) // 2
        new = _Block(blk.elems[half:], 0, blk.next)
        del blk.elems[half:]
        for e in new.elems:
            e.block = new
        new.visible = sum(not e.deleted for e in new.elems)
        blk.visible -= new.visible
        blk.next = new
        self.blocks.insert(self.blocks.index(blk) + 1, new)

    def visible(self) -> list:
        if self.seq is None:
            self.seq = [(_oid(e.id), e.value) for b in self.blocks
                        for e in b.elems if not e.deleted]
        return self.seq

    def copy(self) -> "_Text":
        out = _Text()
        out.blocks = []
        for b in self.blocks:
            nb = _Block([], b.visible, None)
            for e in b.elems:
                rec = _Elem(e.id, e.value, e.deleted, nb)
                nb.elems.append(rec)
                out.by_id[e.id] = rec
            if out.blocks:
                out.blocks[-1].next = nb
            out.blocks.append(nb)
        out.seq = None if self.seq is None else list(self.seq)
        return out


class RefDoc:
    """One document's reference state."""

    def __init__(self):
        self.maps = {"_root": {}}  # obj -> key -> {opid str: entry}
        self.counters = {}  # counter opid str -> value
        self.texts = {}  # obj -> _Text

    def apply(self, ops) -> None:
        for op in ops:
            kind = op[0]
            if kind == "set":
                _, obj, key, me, value, datatype, pred = op
                self._put(obj, key, me, pred, ("v", value, datatype))
                if datatype == "counter":
                    self.counters[_oid(me)] = value
            elif kind == "inc":
                _, obj, key, _me, value, pred = op
                for p in pred:
                    if _oid(p) in self.counters:
                        self.counters[_oid(p)] += value
            elif kind == "makeText":
                _, obj, key, me, pred = op
                self._put(obj, key, me, pred, ("o", _oid(me), "text"))
                self.texts[_oid(me)] = _Text()
            elif kind == "ins":
                _, obj, after, me, value = op
                self.texts[obj].insert(after, me, value)
            elif kind == "del":
                _, obj, elem, _me = op
                self.texts[obj].delete(elem)
            else:
                raise ValueError(f"unknown op {kind!r}")

    def _put(self, obj, key, me, pred, entry):
        slot = self.maps[obj].setdefault(key, {})
        for p in pred:
            slot.pop(_oid(p), None)
        slot[_oid(me)] = entry

    def copy(self) -> "RefDoc":
        out = RefDoc()
        out.maps = {o: {k: dict(v) for k, v in m.items()}
                    for o, m in self.maps.items()}
        out.counters = dict(self.counters)
        out.texts = {o: t.copy() for o, t in self.texts.items()}
        return out

    def values(self, obj, key) -> dict:
        """The visible ops under `key` of map `obj`, each with its value (a
        counter: its current total); {} where none is."""
        return {op: (("v", self.counters[op], "counter")
                     if e[0] == "v" and e[2] == "counter" else e)
                for op, e in self.maps[obj].get(key, {}).items()}

    def state(self) -> dict:
        out = {}
        for obj, m in self.maps.items():
            out[obj] = {key: self.values(obj, key) for key, slot in m.items()
                        if slot}
        for obj, text in self.texts.items():
            out[obj] = list(text.visible())
        return out


class PatchDoc:
    """A document rebuilt from nothing but the patches it was served."""

    def __init__(self):
        self.maps = {"_root": {}}
        self.texts = {}

    def apply(self, diffs) -> None:
        self._object(diffs)

    def _object(self, diff):
        obj, kind = diff["objectId"], diff["type"]
        if kind == "map":
            m = self.maps.setdefault(obj, {})
            for key, values in diff.get("props", {}).items():
                if not values:
                    m.pop(key, None)
                    continue
                m[key] = {op: self._value(v) for op, v in values.items()}
        elif kind == "text":
            self._edits(self.texts.setdefault(obj, []), diff.get("edits", ()))
        else:
            raise ValueError(f"unexpected object type {kind!r}")

    def _value(self, v):
        if "objectId" in v:
            self._object(v)
            return ("o", v["objectId"], v["type"])
        return ("v", v["value"], v.get("datatype"))

    @staticmethod
    def _edits(elems, edits):
        for e in edits:
            action = e["action"]
            if action == "insert":
                elems.insert(e["index"], (e["elemId"], e["value"]["value"]))
            elif action == "multi-insert":
                ctr, actor = e["elemId"].split("@", 1)
                elems[e["index"]:e["index"]] = [
                    (f"{int(ctr) + i}@{actor}", v)
                    for i, v in enumerate(e["values"])]
            elif action == "update":
                elem = elems[e["index"]][0]
                elems[e["index"]] = (elem, e["value"]["value"])
            elif action == "remove":
                del elems[e["index"]:e["index"] + e["count"]]
            else:
                raise ValueError(f"unexpected edit {action!r}")

    def state(self) -> dict:
        out = {obj: {k: dict(v) for k, v in m.items() if v}
               for obj, m in self.maps.items()}
        for obj, elems in self.texts.items():
            out[obj] = list(elems)
        return out


def root_keys(ops) -> set:
    """Root-map keys a change's ops touch (a text op touches the key that
    holds its text object)."""
    out = set()
    for op in ops:
        if op[0] in ("set", "inc", "makeText") and op[1] == "_root":
            out.add(op[2])
        elif op[0] in ("ins", "del"):
            out.add("text")
    return out


def _leaf(v):
    if "objectId" in v:
        return ("o", v["objectId"], v["type"])
    return ("v", v["value"], v.get("datatype"))


def patch_agrees(diffs, got: PatchDoc, ref: RefDoc, touched: set) -> bool:
    """One served patch against the reference after the same delivery.

    - Every root key the delivery's changes touched is in the patch: each
      acknowledged change is in the patch returned for it.
    - Every key the patch lists carries the key's whole conflict map:
      exactly the reference's visible ops under that key, each with the
      reference's value (a counter: its current total). An empty map says
      the key was deleted, so it is right only where the reference holds
      nothing there.
    - Every text object, rebuilt from the patches so far (`got`), equals
      the reference's visible sequence exactly. Both sides keep that
      sequence up to date as they go, so this is one list comparison.
    """
    props = diffs.get("props", {})
    if not touched <= set(props):
        return False
    for key, values in props.items():
        mine = {op: _leaf(v) for op, v in values.items()}
        if mine != ref.values("_root", key):
            return False
    got.apply(diffs)
    return all(got.texts.get(obj) == text.visible()
               for obj, text in ref.texts.items())
