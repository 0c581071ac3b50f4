"""Native C++ codec tests: byte-identity against the pure-Python codecs on
randomized columns (differential, both directions). The library is built
from source on first load, so these tests always run."""
import os
import random
import shutil

import numpy as np
import pytest

from automerge_tpu import native
from automerge_tpu.codecs import (
    BooleanDecoder,
    BooleanEncoder,
    DeltaDecoder,
    DeltaEncoder,
    RLEDecoder,
    RLEEncoder,
)



def random_column(rng, n, null_prob=0.3, value_range=1000):
    vals = []
    while len(vals) < n:
        run = rng.randrange(1, 6)
        if rng.random() < null_prob:
            vals += [None] * run
        else:
            vals += [rng.randrange(value_range)] * run
    return vals[:n]


def to_arr(vals):
    return np.array(
        [native.NULL_SENTINEL if v is None else v for v in vals], np.int64
    )


class TestNativeCodecs:
    def test_rle_differential(self):
        rng = random.Random(1)
        for _ in range(100):
            vals = random_column(rng, rng.randrange(0, 60))
            e = RLEEncoder("uint")
            for v in vals:
                e.append_value(v)
            py_bytes = e.buffer
            assert native.rle_encode(to_arr(vals)) == py_bytes
            if py_bytes:
                decoded = native.rle_decode(py_bytes)
                assert list(decoded) == list(to_arr(vals))

    def test_delta_differential(self):
        rng = random.Random(2)
        for _ in range(100):
            vals = random_column(rng, rng.randrange(0, 60), value_range=10**6)
            e = DeltaEncoder()
            for v in vals:
                e.append_value(v)
            py_bytes = e.buffer
            assert native.delta_encode(to_arr(vals)) == py_bytes
            if py_bytes:
                assert list(native.delta_decode(py_bytes)) == list(to_arr(vals))

    def test_bool_differential(self):
        rng = random.Random(3)
        for _ in range(100):
            vals = [rng.random() < 0.5 for _ in range(rng.randrange(0, 60))]
            e = BooleanEncoder()
            for v in vals:
                e.append_value(v)
            py_bytes = e.buffer
            assert native.bool_encode(np.array(vals, np.uint8)) == py_bytes
            assert list(native.bool_decode(py_bytes)) == vals

    def test_signed_rle(self):
        vals = [-5, -5, None, 3, -100000, 7]
        arr = to_arr(vals)
        e = RLEEncoder("int")
        for v in vals:
            e.append_value(v)
        assert native.rle_encode(arr, signed=True) == e.buffer
        assert list(native.rle_decode(e.buffer, signed=True)) == list(arr)

    def test_decode_detects_truncation(self):
        e = RLEEncoder("uint")
        for v in [1, 2, 3, 4, 5]:
            e.append_value(v)
        with pytest.raises(ValueError):
            native.rle_decode(e.buffer[:-1])

    def test_document_save_via_native_matches(self):
        """The full document op-column encode gives identical bytes whether
        the numeric columns are encoded natively or in Python."""
        from automerge_tpu.columnar import encode_change
        from automerge_tpu.opset import OpSet

        actor = "0123456789abcdef"
        change = {"actor": actor, "seq": 1, "startOp": 1, "time": 0, "deps": [], "ops": [
            {"action": "makeList", "obj": "_root", "key": "list", "pred": []},
            {"action": "set", "obj": f"1@{actor}", "elemId": "_head", "insert": True,
             "values": [1, 2, 3, 4], "datatype": "uint", "pred": []},
            {"action": "set", "obj": "_root", "key": "title", "value": "hi", "pred": []},
        ]}
        opset = OpSet()
        opset.apply_changes([encode_change(change)])
        python_cols = opset._encode_ops_columns(force_python=True)
        native_cols = opset._encode_ops_columns()
        assert [(cid, bytes(buf)) for cid, buf in python_cols] == [
            (cid, bytes(buf)) for cid, buf in native_cols
        ]


def test_library_builds_from_source(tmp_path, monkeypatch):
    """A missing library is built from codecs.cpp through a temp name, and a
    newer source makes it stale again."""
    for name in ("codecs.cpp", "Makefile"):
        shutil.copy(os.path.join(native._NATIVE_DIR, name), tmp_path)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "libamcodecs.so"))
    monkeypatch.setattr(native, "_SRC_PATH", str(tmp_path / "codecs.cpp"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_tried", False)
    assert native._stale()
    assert native.available()
    assert (tmp_path / "libamcodecs.so").exists()
    assert not list(tmp_path.glob("*.tmp"))
    assert not native._stale()
    later = os.path.getmtime(native._LIB_PATH) + 10
    os.utime(native._SRC_PATH, (later, later))
    assert native._stale()
