"""The trace reduction, checked against small recorded traces
(tests/data/trace_events.json, and trace_events_am.json with the
program's ``am.*`` marks: excerpts of chip runs' event lists, as
tracereduce.load_events returns them)."""
import json
import os

import numpy as np
import pytest

from benchmark import tracereduce

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")
DATA_AM = os.path.join(os.path.dirname(__file__), "data",
                       "trace_events_am.json")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = {"gc_pause_ms_per_kop": 10.0 / 2.0,
           "device_wait_ms_per_kop": 5.0 / 2.0,
           "idle_in_apply_share": 43.0}
MS = 1e6


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


def marked():
    """`synthetic` with the program's ``am.*`` marks."""
    host = "/host:CPU"
    return synthetic() + [
        (host, "python3", "am.apply_changes", 11 * MS, 58 * MS),
        (host, "python3", "am.decode", 12 * MS, 8 * MS),
        (host, "python3", "am.walk", 20 * MS, 25 * MS),
        (host, "python3", "am.gc.gen2", 30 * MS, 10 * MS),
        (host, "python3", "am.device_dispatch", 45 * MS, 3 * MS),
        (host, "python3", "am.visibility", 48 * MS, 20 * MS),
        (host, "python3", "am.device_wait", 50 * MS, 5 * MS),
    ]


def without_am(events):
    return [e for e in events if not e[2].startswith("am.")]


def load_recorded(path):
    with open(path) as f:
        recorded = json.load(f)
    return recorded, [tuple(e) for e in recorded["events"]]


def test_a_trace_without_am_marks_reduces_as_before():
    plain = tracereduce.reduce(synthetic())
    run = tracereduce.reduce_run(synthetic())
    assert run.pop("timeline") is None
    assert run == plain
    assert tracereduce.reduce(marked()) == plain
    assert tracereduce.timeline(synthetic()) is None
    assert tracereduce.reduce_run(marked()[1:]) is None  # no window


def test_reduce_run_names_the_span_in_each_gap():
    plain = tracereduce.reduce(marked())
    run = tracereduce.reduce_run(marked())
    assert run["timeline"] == tracereduce.timeline(marked())
    gaps = run["breakdown"]["idle_gaps"]
    assert [label for label, _s in gaps] == [
        "wait/visibility", "deliver/gc.gen2", "deliver/decode"]
    assert [s for _l, s in gaps] == pytest.approx(
        [s for _l, s in plain["breakdown"]["idle_gaps"]])
    run.pop("timeline")
    run["breakdown"]["idle_gaps"] = plain["breakdown"]["idle_gaps"]
    assert run == plain


@pytest.mark.skipif(not os.path.exists(DATA_AM), reason="no recorded trace")
def test_recorded_gap_labels_name_deliver_spans():
    recorded, events = load_recorded(DATA_AM)
    run = tracereduce.reduce_run(events)
    labels = [label for label, _s in run["breakdown"]["idle_gaps"]]
    bare = [label for label, _s in
            tracereduce.reduce(events)["breakdown"]["idle_gaps"]]
    assert [label.split("/")[0] for label in labels] == bare
    assert labels[0] == "deliver/gc.gen2"
    assert all(label.startswith("deliver/") for label in labels
               if label.split("/")[0] == "deliver")
    assert run["timeline"]["idle_s"] == pytest.approx(recorded["idle_s"])


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_take_the_run_reduction_and_open_no_file(name, monkeypatch):
    from benchmark import harness, timeline

    def no_file(*_a):
        raise AssertionError("a reader went to a file")

    monkeypatch.setattr(timeline, "newest_trace", no_file)
    monkeypatch.setattr(timeline, "load_events", no_file)
    read = harness.load_reader(BENCH, name)
    ctx = {"device": tracereduce.reduce_run(marked()), "kop": 2.0,
           "spans": {}, "counters": {}}
    assert read(ctx) == pytest.approx(READERS[name])
    # a program that leaves no am.* mark, or a run with no device trace
    assert read(dict(ctx, device=tracereduce.reduce_run(synthetic()))) is None
    assert read(dict(ctx, device=None)) is None


def test_load_events_keeps_bench_and_am_marks_under_any_dir(tmp_path):
    import jax

    trace_dir = tmp_path / "anywhere" / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("am.walk"):
            jax.numpy.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("not.kept"):
            pass
    jax.profiler.stop_trace()
    names = {e[2] for e in tracereduce.load_events(str(trace_dir))
             if not e[0].startswith("/device:")}
    assert names == {"bench.window", "am.walk"}
