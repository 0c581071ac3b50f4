"""The indexed reference RGA against a list-scan oracle, and the per-patch
check's strictness.

``ListText`` is the RGA written out plainly: one flat list, scanned from
the start for every op. The indexed ``reference.RefDoc`` has to agree
with it on every history.
"""
import random

import pytest

from benchmark import reference

TEXT = "1@" + "aa" * 16
ACTORS = ["%02x" % k * 16 for k in (3, 7, 11, 5)]


class ListText:
    """The oracle: [[opid tuple, value, deleted]] in sequence order."""

    def __init__(self):
        self.elems = []

    def insert(self, after, me, value):
        elems = self.elems
        if after is None:
            pos = 0
        else:
            pos = next(i for i, e in enumerate(elems) if e[0] == after) + 1
        key = (me[0], me[1])
        while pos < len(elems) and (elems[pos][0][0], elems[pos][0][1]) > key:
            pos += 1
        elems.insert(pos, [me, value, False])

    def delete(self, elem):
        for e in self.elems:
            if e[0] == elem:
                e[2] = True
                break
        else:
            raise KeyError(f"del of unknown element {elem}")

    def visible(self):
        return [(f"{e[0][0]}@{e[0][1]}", e[1]) for e in self.elems
                if not e[2]]


def history(seed, n_ops=3000):
    """Random text ops: concurrent inserts after one element (counters that
    tie, actors that differ), inserts at the head, deletes of elements
    already deleted."""
    rng = random.Random(seed)
    ops = [("makeText", "_root", "text", (1, "aa" * 16), ())]
    ids = []
    ctr = {a: 1 for a in ACTORS}
    for _ in range(n_ops):
        r = rng.random()
        if ids and r < 0.25:
            ops.append(("del", TEXT, rng.choice(ids), (0, ACTORS[0])))
            continue
        a = rng.choice(ACTORS)
        if r < 0.35 or not ids:
            after = None  # the head
        elif r < 0.6:
            after = ids[-1]  # typing on
        elif r < 0.8:
            after = rng.choice(ids[-8:])  # near the end: siblings
        else:
            after = rng.choice(ids)
        # counters that often tie between actors: concurrent inserts
        ctr[a] = max(ctr[a], rng.choice(list(ctr.values()))) + 1
        me = (ctr[a], a)
        if me in ids:
            continue
        ops.append(("ins", TEXT, after, me, rng.choice("abcxyz ")))
        ids.append(me)
    return ops


def oracle_states(ops):
    """The oracle's visible sequence after every op."""
    text, out = None, []
    for op in ops:
        if op[0] == "makeText":
            text = ListText()
        elif op[0] == "ins":
            text.insert(op[2], op[3], op[4])
        else:
            text.delete(op[2])
        out.append(text.visible())
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 2**33 + 9])
def test_indexed_rga_agrees_with_the_list_scan(seed, monkeypatch):
    monkeypatch.setattr(reference, "BLOCK", 8)  # many blocks and splits
    ops = history(seed)
    want = oracle_states(ops)
    # built op by op, the visible sequence asked for from the first op on
    # (kept up to date as it goes) ...
    live = reference.RefDoc()
    for k, op in enumerate(ops):
        live.apply([op])
        assert live.texts[TEXT].visible() == want[k], k
    # ... built whole and asked for once at the end ...
    whole = reference.RefDoc()
    whole.apply(ops)
    assert whole.state()[TEXT] == want[-1]
    # ... and copied half way, each copy going on alone
    half = reference.RefDoc()
    half.apply(ops[:len(ops) // 2])
    twin = half.copy()
    half.texts[TEXT].visible()
    for doc in (half, twin):
        doc.apply(ops[len(ops) // 2:])
        assert doc.state()[TEXT] == want[-1]
    assert live.state() == whole.state()


def test_a_delete_of_an_unknown_element_raises():
    doc = reference.RefDoc()
    doc.apply(history(6, 20))
    with pytest.raises(KeyError):
        doc.apply([("del", TEXT, (999, "ff" * 16), (1000, ACTORS[0]))])


def text_patch(edits):
    return {"objectId": "_root", "type": "map", "props": {"text": {
        TEXT: {"objectId": TEXT, "type": "text", "edits": edits}}}}


def test_patch_agrees_holds_every_patch_to_the_whole_sequence():
    ref = reference.RefDoc()
    ref.apply([("makeText", "_root", "text", (1, "aa" * 16), ()),
               ("ins", TEXT, None, (2, ACTORS[0]), "a"),
               ("ins", TEXT, (2, ACTORS[0]), (3, ACTORS[0]), "b")])
    first = text_patch([{"action": "multi-insert", "index": 0,
                         "elemId": f"2@{ACTORS[0]}", "values": ["a", "b"]}])
    got = reference.PatchDoc()
    assert reference.patch_agrees(first, got, ref, {"text"})
    # a patch that leaves the touched key out
    assert not reference.patch_agrees({"objectId": "_root", "type": "map"},
                                      reference.PatchDoc(), ref, {"text"})
    # an insert the reference has, served at the wrong index
    ref.apply([("ins", TEXT, None, (4, ACTORS[1]), "c")])
    wrong = reference.PatchDoc()
    wrong.apply(first)
    assert not reference.patch_agrees(text_patch([
        {"action": "insert", "index": 1, "elemId": f"4@{ACTORS[1]}",
         "value": {"type": "value", "value": "c"}}]), wrong, ref, {"text"})
    assert reference.patch_agrees(text_patch([
        {"action": "insert", "index": 0, "elemId": f"4@{ACTORS[1]}",
         "value": {"type": "value", "value": "c"}}]), got, ref, {"text"})
    # a delete the patch never serves is seen at the next patch
    ref.apply([("del", TEXT, (2, ACTORS[0]), (5, ACTORS[1]))])
    assert not reference.patch_agrees(text_patch([]), got, ref, {"text"})
