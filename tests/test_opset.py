"""Engine tests ported from the reference backend test suite
(/root/reference/test/new_backend_test.js): exact patch JSON and exact
encoded column bytes."""
import gc

import pytest

from automerge_tpu import backend as B
from automerge_tpu.columnar import encode_change
from automerge_tpu.opset import SUCC_ACTOR, SUCC_CTR, SUCC_NUM, OpSet

from helpers import check_columns, hash_of

ACTOR = "0123456789abcdef"


def apply_all(opset, *changes):
    patches = []
    for change in changes:
        patches.append(opset.apply_changes([encode_change(change)]))
    return patches


class TestRootProperties:
    def test_overwrite_root_property(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 3, "pred": []},
            {"action": "set", "obj": "_root", "key": "y", "datatype": "uint", "value": 4, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 3, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 5, "pred": [f"1@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p1 == {
            "maxOp": 2, "clock": {ACTOR: 1}, "deps": [hash_of(change1)], "pendingChanges": 0,
            "diffs": {"objectId": "_root", "type": "map", "props": {
                "x": {f"1@{ACTOR}": {"type": "value", "value": 3, "datatype": "uint"}},
                "y": {f"2@{ACTOR}": {"type": "value", "value": 4, "datatype": "uint"}},
            }},
        }
        assert p2 == {
            "maxOp": 3, "clock": {ACTOR: 2}, "deps": [hash_of(change2)], "pendingChanges": 0,
            "diffs": {"objectId": "_root", "type": "map", "props": {
                "x": {f"3@{ACTOR}": {"type": "value", "value": 5, "datatype": "uint"}},
            }},
        }
        check_columns(backend, {
            "objActor": [], "objCtr": [], "keyActor": [], "keyCtr": [],
            "keyStr": [2, 1, 0x78, 0x7F, 1, 0x79],
            "idActor": [3, 0],
            "idCtr": [0x7D, 1, 2, 0x7F],
            "insert": [3],
            "action": [3, 1],
            "valLen": [3, 0x13],
            "valRaw": [3, 5, 4],
            "succNum": [0x7F, 1, 2, 0],
            "succActor": [0x7F, 0],
            "succCtr": [0x7F, 3],
        })

    def test_concurrent_conflict(self):
        actor1, actor2 = "01234567", "89abcdef"
        change1 = {"actor": actor1, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": actor2, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 2, "pred": []},
        ]}
        change3 = {"actor": actor1, "seq": 2, "startOp": 2, "time": 0,
                   "deps": [hash_of(change1), hash_of(change2)], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 3,
             "pred": [f"1@{actor1}", f"1@{actor2}"]},
        ]}
        backend = OpSet()
        p1, p2, p3 = apply_all(backend, change1, change2, change3)
        assert p2["diffs"]["props"]["x"] == {
            f"1@{actor1}": {"type": "value", "value": 1, "datatype": "uint"},
            f"1@{actor2}": {"type": "value", "value": 2, "datatype": "uint"},
        }
        assert p2["deps"] == sorted([hash_of(change1), hash_of(change2)])
        assert p3["diffs"]["props"]["x"] == {
            f"2@{actor1}": {"type": "value", "value": 3, "datatype": "uint"},
        }
        check_columns(backend, {
            "keyStr": [3, 1, 0x78],
            "idActor": [0x7D, 0, 1, 0],
            "idCtr": [0x7D, 1, 0, 1],
            "insert": [3],
            "action": [3, 1],
            "valLen": [3, 0x13],
            "valRaw": [1, 2, 3],
            "succNum": [2, 1, 0x7F, 0],
            "succActor": [2, 0],
            "succCtr": [0x7E, 2, 0],
        })

    def test_pred_does_not_exist(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
            {"action": "set", "obj": "_root", "key": "y", "datatype": "uint", "value": 2, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 3, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 3, "pred": [f"2@{ACTOR}"]},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        with pytest.raises(ValueError, match="no matching operation for pred"):
            backend.apply_changes([encode_change(change2)])

    def test_pred_does_not_exist_other_actor(self):
        actor1, actor2 = "01234567", "89abcdef"
        change1 = {"actor": actor1, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": actor2, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "w", "datatype": "uint", "value": 2, "pred": []},
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 2, "pred": []},
        ]}
        change3 = {"actor": actor1, "seq": 2, "startOp": 2, "time": 0,
                   "deps": [hash_of(change1), hash_of(change2)], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 3,
             "pred": [f"1@{actor2}"]},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        backend.apply_changes([encode_change(change2)])
        with pytest.raises(ValueError, match="no matching operation for pred"):
            backend.apply_changes([encode_change(change3)])


class TestNestedMaps:
    def test_create_and_update_nested_maps(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeMap", "obj": "_root", "key": "map", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "key": "x", "value": "a", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "key": "y", "value": "b", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "key": "z", "value": "c", "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 5, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": f"1@{ACTOR}", "key": "y", "value": "B", "pred": [f"3@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p1["diffs"] == {
            "objectId": "_root", "type": "map", "props": {"map": {f"1@{ACTOR}": {
                "objectId": f"1@{ACTOR}", "type": "map", "props": {
                    "x": {f"2@{ACTOR}": {"type": "value", "value": "a"}},
                    "y": {f"3@{ACTOR}": {"type": "value", "value": "b"}},
                    "z": {f"4@{ACTOR}": {"type": "value", "value": "c"}},
                },
            }}},
        }
        assert p2["diffs"] == {
            "objectId": "_root", "type": "map", "props": {"map": {f"1@{ACTOR}": {
                "objectId": f"1@{ACTOR}", "type": "map",
                "props": {"y": {f"5@{ACTOR}": {"type": "value", "value": "B"}}},
            }}},
        }
        check_columns(backend, {
            "objActor": [0, 1, 4, 0],
            "objCtr": [0, 1, 4, 1],
            "keyStr": [0x7E, 3, 0x6D, 0x61, 0x70, 1, 0x78, 2, 1, 0x79, 0x7F, 1, 0x7A],
            "idActor": [5, 0],
            "idCtr": [3, 1, 0x7E, 2, 0x7F],
            "insert": [5],
            "action": [0x7F, 0, 4, 1],
            "valLen": [0x7F, 0, 4, 0x16],
            "valRaw": [0x61, 0x62, 0x42, 0x63],
            "succNum": [2, 0, 0x7F, 1, 2, 0],
            "succActor": [0x7F, 0],
            "succCtr": [0x7F, 5],
        })

    def test_nested_maps_several_levels_deep(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeMap", "obj": "_root", "key": "a", "pred": []},
            {"action": "makeMap", "obj": f"1@{ACTOR}", "key": "b", "pred": []},
            {"action": "makeMap", "obj": f"2@{ACTOR}", "key": "c", "pred": []},
            {"action": "set", "obj": f"3@{ACTOR}", "key": "d", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 5, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": f"3@{ACTOR}", "key": "d", "datatype": "uint", "value": 2,
             "pred": [f"4@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p2["diffs"] == {
            "objectId": "_root", "type": "map", "props": {"a": {f"1@{ACTOR}": {
                "objectId": f"1@{ACTOR}", "type": "map", "props": {"b": {f"2@{ACTOR}": {
                    "objectId": f"2@{ACTOR}", "type": "map", "props": {"c": {f"3@{ACTOR}": {
                        "objectId": f"3@{ACTOR}", "type": "map", "props": {"d": {f"5@{ACTOR}": {
                            "type": "value", "value": 2, "datatype": "uint",
                        }}},
                    }}},
                }}},
            }}},
        }
        check_columns(backend, {
            "objActor": [0, 1, 4, 0],
            "objCtr": [0, 1, 0x7E, 1, 2, 2, 3],
            "keyStr": [0x7D, 1, 0x61, 1, 0x62, 1, 0x63, 2, 1, 0x64],
            "idActor": [5, 0],
            "idCtr": [5, 1],
            "insert": [5],
            "action": [3, 0, 2, 1],
            "valLen": [3, 0, 2, 0x13],
            "valRaw": [1, 2],
            "succNum": [3, 0, 0x7E, 1, 0],
            "succActor": [0x7F, 0],
            "succCtr": [0x7F, 5],
        })


class TestText:
    def test_create_text_object(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
        ]}
        backend = OpSet()
        (p1,) = apply_all(backend, change1)
        assert p1["diffs"] == {
            "objectId": "_root", "type": "map", "props": {"text": {f"1@{ACTOR}": {
                "objectId": f"1@{ACTOR}", "type": "text", "edits": [
                    {"action": "insert", "index": 0, "elemId": f"2@{ACTOR}", "opId": f"2@{ACTOR}",
                     "value": {"type": "value", "value": "a"}},
                ],
            }}},
        }
        check_columns(backend, {
            "objActor": [0, 1, 0x7F, 0],
            "objCtr": [0, 1, 0x7F, 1],
            "keyActor": [],
            "keyCtr": [0, 1, 0x7F, 0],
            "keyStr": [0x7F, 4, 0x74, 0x65, 0x78, 0x74, 0, 1],
            "idActor": [2, 0],
            "idCtr": [2, 1],
            "insert": [1, 1],
            "action": [0x7E, 4, 1],
            "valLen": [0x7E, 0, 0x16],
            "valRaw": [0x61],
            "succNum": [2, 0],
            "succActor": [],
            "succCtr": [],
        })

    def test_insert_text_characters(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": True, "value": "b", "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 4, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"3@{ACTOR}", "insert": True, "value": "c", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"4@{ACTOR}", "insert": True, "value": "d", "pred": []},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p1["diffs"]["props"]["text"][f"1@{ACTOR}"]["edits"] == [
            {"action": "multi-insert", "index": 0, "elemId": f"2@{ACTOR}", "values": ["a", "b"]},
        ]
        assert p2["diffs"]["props"]["text"][f"1@{ACTOR}"]["edits"] == [
            {"action": "multi-insert", "index": 2, "elemId": f"4@{ACTOR}", "values": ["c", "d"]},
        ]
        check_columns(backend, {
            "objActor": [0, 1, 4, 0],
            "objCtr": [0, 1, 4, 1],
            "keyActor": [0, 2, 3, 0],
            "keyCtr": [0, 1, 0x7E, 0, 2, 2, 1],
            "keyStr": [0x7F, 4, 0x74, 0x65, 0x78, 0x74, 0, 4],
            "idActor": [5, 0],
            "idCtr": [5, 1],
            "insert": [1, 4],
            "action": [0x7F, 4, 4, 1],
            "valLen": [0x7F, 0, 4, 0x16],
            "valRaw": [0x61, 0x62, 0x63, 0x64],
            "succNum": [5, 0],
            "succActor": [],
            "succCtr": [],
        })

    def test_insertion_reference_not_found(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": True, "value": "b", "pred": []},
            {"action": "makeMap", "obj": "_root", "key": "map", "insert": False, "pred": []},
            {"action": "set", "obj": f"4@{ACTOR}", "key": "foo", "insert": False, "value": "c", "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 6, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"4@{ACTOR}", "insert": True, "value": "d", "pred": []},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        with pytest.raises(ValueError, match="Reference element not found"):
            backend.apply_changes([encode_change(change2)])

    def test_non_consecutive_insertions(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": True, "value": "c", "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 4, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": True, "value": "b", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"3@{ACTOR}", "insert": True, "value": "d", "pred": []},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p2["diffs"]["props"]["text"][f"1@{ACTOR}"]["edits"] == [
            {"action": "insert", "index": 1, "elemId": f"4@{ACTOR}", "opId": f"4@{ACTOR}",
             "value": {"type": "value", "value": "b"}},
            {"action": "insert", "index": 3, "elemId": f"5@{ACTOR}", "opId": f"5@{ACTOR}",
             "value": {"type": "value", "value": "d"}},
        ]


class TestDeletion:
    def test_delete_map_key(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 2, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "del", "obj": "_root", "key": "x", "pred": [f"1@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p2["diffs"] == {"objectId": "_root", "type": "map", "props": {"x": {}}}

    def test_delete_list_element(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeList", "obj": "_root", "key": "list", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": True, "value": "b", "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 4, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "del", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": False,
             "pred": [f"2@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p2["diffs"]["props"]["list"][f"1@{ACTOR}"]["edits"] == [
            {"action": "remove", "index": 0, "count": 1},
        ]

    def test_multi_op_deletion(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True,
             "values": ["a", "b", "c"], "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 5, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "del", "obj": f"1@{ACTOR}", "elemId": f"2@{ACTOR}", "insert": False,
             "multiOp": 3, "pred": [f"2@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p1["diffs"]["props"]["text"][f"1@{ACTOR}"]["edits"] == [
            {"action": "multi-insert", "index": 0, "elemId": f"2@{ACTOR}", "values": ["a", "b", "c"]},
        ]
        assert p2["diffs"]["props"]["text"][f"1@{ACTOR}"]["edits"] == [
            {"action": "remove", "index": 0, "count": 3},
        ]


class TestCounters:
    def test_increment_counter(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "counter", "datatype": "counter", "value": 1, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 2, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "inc", "obj": "_root", "key": "counter", "value": 2, "pred": [f"1@{ACTOR}"]},
        ]}
        backend = OpSet()
        p1, p2 = apply_all(backend, change1, change2)
        assert p1["diffs"]["props"]["counter"] == {
            f"1@{ACTOR}": {"type": "value", "value": 1, "datatype": "counter"},
        }
        assert p2["diffs"]["props"]["counter"] == {
            f"1@{ACTOR}": {"type": "value", "datatype": "counter", "value": 3},
        }


class TestCausalOrdering:
    def test_enqueue_out_of_order_changes(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 2, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": "_root", "key": "y", "datatype": "uint", "value": 2, "pred": []},
        ]}
        backend = OpSet()
        patch = backend.apply_changes([encode_change(change2)])
        assert patch["pendingChanges"] == 1
        assert patch["diffs"] == {"objectId": "_root", "type": "map", "props": {}}
        patch = backend.apply_changes([encode_change(change1)])
        assert patch["pendingChanges"] == 0
        assert patch["diffs"]["props"] == {
            "x": {f"1@{ACTOR}": {"type": "value", "value": 1, "datatype": "uint"}},
            "y": {f"2@{ACTOR}": {"type": "value", "value": 2, "datatype": "uint"}},
        }
        assert backend.get_missing_deps() == []

    def test_missing_deps_reported(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 2, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": "_root", "key": "y", "datatype": "uint", "value": 2, "pred": []},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change2)])
        assert backend.get_missing_deps() == [hash_of(change1)]

    def test_duplicate_changes_ignored(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        patch = backend.apply_changes([encode_change(change1)])
        assert patch["diffs"] == {"objectId": "_root", "type": "map", "props": {}}


class TestSaveLoad:
    def _build_doc(self):
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
            {"action": "set", "obj": f"1@{ACTOR}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
            {"action": "makeMap", "obj": "_root", "key": "map", "pred": []},
            {"action": "set", "obj": f"3@{ACTOR}", "key": "x", "datatype": "uint", "value": 1, "pred": []},
            {"action": "makeList", "obj": "_root", "key": "list", "pred": []},
            {"action": "set", "obj": f"5@{ACTOR}", "elemId": "_head", "insert": True,
             "values": [1, 2, 3], "datatype": "uint", "pred": []},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        return backend

    def test_save_load_round_trip(self):
        backend = self._build_doc()
        saved = backend.save()
        loaded = OpSet(saved)
        assert loaded.get_patch() == backend.get_patch()
        assert loaded.save() == saved

    def test_load_save_reencode_identical(self):
        backend = self._build_doc()
        saved = backend.save()
        loaded = OpSet(saved)
        loaded.binary_doc = None  # force re-encoding from the op rows
        assert loaded.save() == saved

    def test_save_load_after_merge(self):
        actor1, actor2 = "01234567", "89abcdef"
        change1 = {"actor": actor1, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        change2 = {"actor": actor2, "seq": 1, "startOp": 1, "time": 0, "deps": [hash_of(change1)], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 2,
             "pred": [f"1@{actor1}"]},
        ]}
        backend = OpSet()
        backend.apply_changes([encode_change(change1)])
        backend.apply_changes([encode_change(change2)])
        loaded = OpSet(backend.save())
        assert loaded.get_patch() == backend.get_patch()
        # the full change history can be reconstructed from the document
        assert loaded.get_changes([]) == backend.get_changes([])


class TestBackendFacade:
    def test_apply_local_change(self):
        b = B.init()
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        b, patch, bin1 = B.apply_local_change(b, change1)
        assert patch["actor"] == ACTOR
        assert patch["seq"] == 1
        assert patch["deps"] == []
        change2 = {"actor": ACTOR, "seq": 2, "startOp": 2, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 2,
             "pred": [f"1@{ACTOR}"]},
        ]}
        b, patch2, bin2 = B.apply_local_change(b, change2)
        assert patch2["deps"] == []
        assert B.get_all_changes(b) == [bin1, bin2]

    def test_frozen_state_rejected(self):
        b = B.init()
        change1 = {"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "set", "obj": "_root", "key": "x", "datatype": "uint", "value": 1, "pred": []},
        ]}
        b2, _ = B.apply_changes(b, [encode_change(change1)])
        with pytest.raises(ValueError, match="outdated Automerge document"):
            B.apply_changes(b, [encode_change(change1)])


def _typed_text():
    """One actor types "abc" over three changes, then deletes the "b"."""
    text = f"1@{ACTOR}"
    changes = [{"actor": ACTOR, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
        {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
        {"action": "set", "obj": text, "elemId": "_head", "insert": True, "value": "a", "pred": []},
    ]}]
    for seq, (char, after) in enumerate((("b", f"2@{ACTOR}"), ("c", f"3@{ACTOR}")), start=2):
        changes.append({"actor": ACTOR, "seq": seq, "startOp": seq + 1, "time": 0,
                        "deps": [hash_of(changes[-1])], "ops": [
            {"action": "set", "obj": text, "elemId": after, "insert": True, "value": char, "pred": []},
        ]})
    changes.append({"actor": ACTOR, "seq": 4, "startOp": 5, "time": 0, "deps": [hash_of(changes[-1])], "ops": [
        {"action": "del", "obj": text, "elemId": f"3@{ACTOR}", "insert": False, "pred": [f"3@{ACTOR}"]},
    ]})
    backend = OpSet()
    apply_all(backend, *changes)
    return backend


def _concurrent_edits():
    """Two actors insert and delete concurrently in one list and overwrite one map key."""
    actor1, actor2 = "01234567", "89abcdef"
    base = {"actor": actor1, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
        {"action": "makeText", "obj": "_root", "key": "text", "insert": False, "pred": []},
        {"action": "set", "obj": f"1@{actor1}", "elemId": "_head", "insert": True, "value": "a", "pred": []},
        {"action": "set", "obj": f"1@{actor1}", "elemId": f"2@{actor1}", "insert": True, "value": "b", "pred": []},
        {"action": "set", "obj": "_root", "key": "title", "value": "draft", "pred": []},
    ]}
    edits = [
        {"actor": actor, "seq": seq, "startOp": 5, "time": 0, "deps": [hash_of(base)], "ops": [
            {"action": "set", "obj": f"1@{actor1}", "elemId": f"2@{actor1}", "insert": True,
             "value": char, "pred": []},
            {"action": "del", "obj": f"1@{actor1}", "elemId": f"3@{actor1}", "insert": False,
             "pred": [f"3@{actor1}"]},
            {"action": "set", "obj": "_root", "key": "title", "value": title, "pred": [f"4@{actor1}"]},
        ]}
        for actor, seq, char, title in ((actor1, 2, "x", "one"), (actor2, 1, "y", "two"))
    ]
    backend = OpSet()
    backend.apply_changes([encode_change(base)])
    backend.apply_changes([encode_change(edits[0])])
    backend.apply_changes([encode_change(edits[1])])
    return backend


@pytest.mark.parametrize("build", [
    _typed_text,
    _concurrent_edits,
    lambda: OpSet(_concurrent_edits().save()),
], ids=["typed_text", "concurrent_edits", "saved_and_loaded"])
def test_committed_rows_are_untracked_tuples(build):
    """Committed op rows are immutable tuples that the collector untracks, so
    a full collection never walks a document's ops."""
    backend = build()
    # A collection may check a row before the succ tuple it holds (its
    # traversal reorders them), so a row with successors goes untracked at
    # the next one.
    gc.collect()
    gc.collect()
    assert backend.ops
    assert any(row[SUCC_NUM] for row in backend.ops)
    for row in backend.ops:
        assert type(row) is tuple and len(row) == 16
        assert type(row[SUCC_ACTOR]) is tuple and type(row[SUCC_CTR]) is tuple
        assert len(row[SUCC_ACTOR]) == len(row[SUCC_CTR]) == row[SUCC_NUM]
        assert not gc.is_tracked(row)
