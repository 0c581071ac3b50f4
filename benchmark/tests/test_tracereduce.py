"""The trace reduction, checked against a small recorded trace
(tests/data/trace_events.json: an excerpt of a chip run's event list, as
tracereduce.load_events returns it)."""
import json
import os

import numpy as np
import pytest

from benchmark import tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")


def synthetic():
    host, dev = "/host:CPU", "/device:TPU:0"
    ms = 1e6
    return [
        (host, "python3", "bench.window", 10 * ms, 100 * ms),
        (host, "python3", "bench.deliver", 10 * ms, 60 * ms),
        (host, "python3", "bench.wait", 70 * ms, 40 * ms),
        (dev, "XLA Modules", "jit_paged_apply_ops(7)", 20 * ms, 10 * ms),
        (dev, "XLA Ops", "sort.1", 20 * ms, 6 * ms),
        (dev, "XLA Ops", "scatter.2", 25 * ms, 5 * ms),  # overlaps sort.1
        (dev, "XLA Modules", "jit__gather_rows(9)", 50 * ms, 4 * ms),
        (dev, "XLA Ops", "gather.3", 50 * ms, 4 * ms),
        (dev, "XLA Ops", "fusion.4", 0, 12 * ms),  # before the window
        (dev, "XLA Ops", "fusion.5", 105 * ms, 10 * ms),  # runs past it
    ]


def test_busy_union_programs_and_gaps():
    out = tracereduce.reduce(synthetic())
    assert out["window_s"] == pytest.approx(0.1)
    # [10,12] + [20,30] + [50,54] + [105,110] ms
    assert out["busy_s"] == pytest.approx(0.021)
    assert out["programs"] == pytest.approx(
        {"paged_apply_ops": 0.010, "_gather_rows": 0.004})
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["sort.1"] == pytest.approx(0.006)
    assert ops["fusion.5"] == pytest.approx(0.005)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps[0] == ["wait", pytest.approx(0.051)]  # [54, 105] ms
    assert sum(g for _n, g in gaps) == pytest.approx(0.1 - 0.021)
    assert [n for n, _g in gaps] == ["wait", "deliver", "deliver"]


def test_no_window_or_no_device_reads_nothing():
    events = synthetic()
    assert tracereduce.reduce(events[1:]) is None
    assert tracereduce.reduce([e for e in events
                               if not e[0].startswith("/device")]) is None


def test_module_names():
    assert tracereduce.module_name("jit_paged_apply_ops(123)") == \
        "paged_apply_ops"
    assert tracereduce.module_name("jit__gather_rows(9)") == "_gather_rows"


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    with open(DATA) as f:
        recorded = json.load(f)
    events = [tuple(e) for e in recorded["events"]]
    out = tracereduce.reduce(events)
    assert out["busy_s"] == pytest.approx(recorded["busy_s"])
    assert out["window_s"] == pytest.approx(recorded["window_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["programs"] == pytest.approx(recorded["programs"])
    # busy time again, as a 1 us timeline of the op intervals
    w0, span = events[0][3], events[0][4]
    n = int(span // 1000)
    line = np.zeros(n, bool)
    for _p, kind, _n, s, d in events[1:]:
        if kind == "XLA Ops":
            a = max(0, int((s - w0) // 1000))
            line[a:min(n, int(np.ceil((s + d - w0) / 1000)))] = True
    assert out["busy_s"] == pytest.approx(line.sum() / 1e6, abs=1e-4)
    assert "paged_apply_ops" in out["programs"]
    assert all(" = " not in name for name, _t in
               out["breakdown"]["device_ops"])
