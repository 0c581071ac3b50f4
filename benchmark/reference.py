"""The plain reference, and the patch reader it is compared with.

Imports nothing of the program. ``RefDoc`` applies the generator's op
tuples with Automerge's semantics, written out directly:

- a map key holds the set of visible ops: a ``set`` or ``makeText`` adds
  itself and hides every op named in its ``pred``; a counter's value is
  its initial value plus every ``inc`` that names it;
- a text object is an RGA: an insert goes after its reference element,
  past every following element with a greater opId (Lamport order:
  counter, then actor id); a ``del`` hides the element it names.

``PatchDoc`` applies the program's reference-format patches (``props``
per key and opId, text ``edits``: insert, multi-insert, update, remove)
to a plain state. Both render to the same form, ``state()``;
``patch_agrees`` holds one served patch against the reference after the
same delivery, and a whole-document patch must rebuild the reference's
state exactly.
"""
from __future__ import annotations


def _oid(op):
    return f"{op[0]}@{op[1]}"


class RefDoc:
    """One document's reference state."""

    def __init__(self):
        self.maps = {"_root": {}}  # obj -> key -> {opid str: entry}
        self.counters = {}  # counter opid str -> value
        self.texts = {}  # obj -> [[opid tuple, value, deleted]]

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
                self.texts[_oid(me)] = []
            elif kind == "ins":
                _, obj, after, me, value = op
                self._insert(self.texts[obj], after, me, value)
            elif kind == "del":
                _, obj, elem, _me = op
                for e in self.texts[obj]:
                    if e[0] == elem:
                        e[2] = True
                        break
                else:
                    raise KeyError(f"del of unknown element {elem}")
            else:
                raise ValueError(f"unknown op {kind!r}")

    def _put(self, obj, key, me, pred, entry):
        slot = self.maps[obj].setdefault(key, {})
        for p in pred:
            slot.pop(_oid(p), None)
        slot[_oid(me)] = entry

    @staticmethod
    def _insert(elems, after, me, value):
        if after is None:
            pos = 0
        else:
            pos = next(i for i, e in enumerate(elems) if e[0] == after) + 1
        key = (me[0], me[1])
        while pos < len(elems) and (elems[pos][0][0], elems[pos][0][1]) > key:
            pos += 1
        elems.insert(pos, [me, value, False])

    def copy(self) -> "RefDoc":
        out = RefDoc()
        out.maps = {o: {k: dict(v) for k, v in m.items()}
                    for o, m in self.maps.items()}
        out.counters = dict(self.counters)
        out.texts = {o: [list(e) for e in t] for o, t in self.texts.items()}
        return out

    def state(self) -> dict:
        out = {}
        for obj, m in self.maps.items():
            rendered = {}
            for key, slot in m.items():
                if not slot:
                    continue
                rendered[key] = {
                    op: (("v", self.counters[op], "counter")
                         if e[0] == "v" and e[2] == "counter" else e)
                    for op, e in slot.items()}
            out[obj] = rendered
        for obj, elems in self.texts.items():
            out[obj] = [(_oid(e[0]), e[1]) for e in elems if not e[2]]
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
      the reference's visible sequence exactly.
    """
    props = diffs.get("props", {})
    if not touched <= set(props):
        return False
    want = ref.state()
    root = want["_root"]
    for key, values in props.items():
        mine = {op: _leaf(v) for op, v in values.items()}
        if mine != root.get(key, {}):
            return False
    got.apply(diffs)
    mine = got.state()
    return all(mine.get(obj) == want[obj] for obj in ref.texts)
