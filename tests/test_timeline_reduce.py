"""The benchmark's reduction of the program's ``am.*`` timeline marks
(benchmark/timeline.py) and the three readers built on it, on synthetic
events and on a recorded chip trace
(benchmark/tests/data/trace_events_am.json, an excerpt of a traced run's
event list as ``timeline.load_events`` returns it)."""
import json
import os

import pytest

from benchmark import timeline, tracereduce

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
RECORDED = os.path.join(BENCH, "tests", "data", "trace_events_am.json")
READERS = ("gc_pause_ms_per_kop", "device_wait_ms_per_kop",
           "idle_in_apply_share")
MS = 1e6


def synthetic():
    host, dev = "/host:CPU", "/device:TPU:0"
    return [
        (host, "python3", "bench.window", 10 * MS, 100 * MS),
        (host, "python3", "bench.deliver", 10 * MS, 60 * MS),
        (host, "python3", "bench.wait", 70 * MS, 40 * MS),
        (host, "python3", "am.apply_changes", 11 * MS, 58 * MS),
        (host, "python3", "am.decode", 12 * MS, 8 * MS),
        (host, "python3", "am.walk", 20 * MS, 25 * MS),
        (host, "python3", "am.gc.gen2", 30 * MS, 10 * MS),
        (host, "python3", "am.device_dispatch", 45 * MS, 3 * MS),
        (host, "python3", "am.visibility", 48 * MS, 20 * MS),
        (host, "python3", "am.device_wait", 50 * MS, 5 * MS),
        (dev, "XLA Modules", "jit_paged_apply_ops(7)", 20 * MS, 10 * MS),
        (dev, "XLA Ops", "sort.1", 20 * MS, 6 * MS),
        (dev, "XLA Ops", "scatter.2", 25 * MS, 5 * MS),
        (dev, "XLA Modules", "jit__gather_rows(9)", 50 * MS, 4 * MS),
        (dev, "XLA Ops", "gather.3", 50 * MS, 4 * MS),
        (dev, "XLA Ops", "fusion.4", 0, 12 * MS),
        (dev, "XLA Ops", "fusion.5", 105 * MS, 10 * MS),
    ]


def without_am(events):
    return [e for e in events if not e[2].startswith("am.")]


def test_idle_split_by_innermost_span():
    out = timeline.reduce(synthetic())
    assert out["window_s"] == pytest.approx(0.1)
    assert out["idle_s"] == pytest.approx(0.079)
    assert out["idle_by_span"] == pytest.approx({
        "wait": 0.035, "visibility": 0.015, "gc.gen2": 0.010,
        "decode": 0.008, "walk": 0.005, "device_dispatch": 0.003,
        "device_wait": 0.001, "apply_changes": 0.001, "deliver": 0.001})
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    # [12,20] + [30,50] + [54,69] ms lie inside the call
    assert out["idle_in_apply_s"] == pytest.approx(0.043)


def test_gap_labels_name_the_span_over_most_of_the_gap():
    out = timeline.reduce(synthetic())
    labels = [label for label, _s in out["idle_gaps"]]
    assert labels == ["wait/visibility", "deliver/gc.gen2", "deliver/decode"]
    plain = tracereduce.reduce(synthetic())["breakdown"]["idle_gaps"]
    assert [s for _l, s in out["idle_gaps"]] == pytest.approx(
        [s for _l, s in plain])
    assert all(label.split("/")[0] == bare
               for label, (bare, _s) in zip(labels, plain))


def test_marks_and_residual():
    out = timeline.reduce(synthetic())
    marks = out["marks"]
    assert marks["gc"] == pytest.approx({"seconds": 0.010, "calls": 1})
    assert marks["device_wait"] == pytest.approx({"seconds": 0.005,
                                                  "calls": 1})
    assert marks["apply_changes"]["calls"] == 1
    # 58 ms call, root phases 8 + 25 + 3 + 20 ms
    assert out["apply_s"] == pytest.approx(0.058)
    assert out["residual_s"] == pytest.approx(0.002)
    assert out["unnamed_s"] == pytest.approx(0.002)


def test_a_collection_between_phases_is_residual_but_named():
    host = "/host:CPU"
    events = [e for e in synthetic() if e[2] != "am.gc.gen2"] + [
        (host, "python3", "am.gc.gen2", 68 * MS, 0.5 * MS)]
    out = timeline.reduce(events)
    assert out["residual_s"] == pytest.approx(0.002)
    assert out["unnamed_s"] == pytest.approx(0.0015)
    assert out["idle_by_span"]["gc.gen2"] == pytest.approx(0.0005)
    assert out["idle_by_span"]["walk"] == pytest.approx(0.015)


def test_no_am_marks_reads_nothing_and_tracereduce_is_unmoved():
    events = synthetic()
    assert timeline.reduce(without_am(events)) is None
    assert timeline.reduce(events[1:]) is None  # no window
    assert tracereduce.reduce(events) == tracereduce.reduce(
        without_am(events))


@pytest.mark.parametrize("name", READERS)
def test_readers(name, monkeypatch, tmp_path):
    from benchmark import harness

    events = synthetic()
    dev = tracereduce.reduce(events)
    ctx = {"device": dev, "kop": 2.0, "spans": {}}
    monkeypatch.setattr(timeline, "newest_trace", lambda _d: str(
        tmp_path / f"{name}.xplane.pb"))
    (tmp_path / f"{name}.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(timeline, "load_events", lambda _p: events)
    read = harness.load_reader(BENCH, name)
    want = {"gc_pause_ms_per_kop": 10.0 / 2.0,
            "device_wait_ms_per_kop": 5.0 / 2.0,
            "idle_in_apply_share": 43.0}[name]
    assert read(ctx) == pytest.approx(want)
    # a program that leaves no am.* mark, or a run with no device trace,
    # reads None
    timeline._CACHE.clear()
    monkeypatch.setattr(timeline, "load_events",
                        lambda _p: without_am(events))
    assert read(ctx) is None
    assert read(dict(ctx, device=None)) is None
    timeline._CACHE.clear()


def test_a_trace_elsewhere_or_of_another_window_raises(monkeypatch,
                                                        tmp_path):
    events = synthetic()
    dev = dict(tracereduce.reduce(events), window_s=0.2)
    path = tmp_path / "other.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(timeline, "newest_trace", lambda _d: str(path))
    monkeypatch.setattr(timeline, "load_events", lambda _p: events)
    with pytest.raises(ValueError, match="not this run's trace"):
        timeline.summary({"device": dev, "kop": 1.0}, BENCH)
    monkeypatch.setattr(timeline, "newest_trace", lambda _d: None)
    with pytest.raises(FileNotFoundError):
        timeline.summary({"device": dev, "kop": 1.0}, BENCH)
    timeline._CACHE.clear()


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_with_am_marks():
    with open(RECORDED) as f:
        recorded = json.load(f)
    events = [tuple(e) for e in recorded["events"]]
    out = timeline.reduce(events)
    assert out["idle_s"] == pytest.approx(recorded["idle_s"])
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["idle_s"], rel=1e-9)
    assert 0 < out["idle_in_apply_s"] <= out["idle_s"]
    assert all(label != "deliver" for label, _s in out["idle_gaps"])
    assert out["unnamed_s"] <= out["residual_s"] < 0.05 * out["apply_s"]
    # the device numbers do not move for the am.* host events
    plain = tracereduce.reduce(without_am(events))
    marked = tracereduce.reduce(events)
    assert marked == plain
    assert marked["busy_s"] == pytest.approx(recorded["busy_s"])
    assert marked["window_s"] == pytest.approx(recorded["window_s"])
    assert out["idle_s"] == pytest.approx(
        marked["window_s"] - marked["busy_s"])
