"""amtrace on the profiler's timeline (automerge_tpu/obs/spans.py).

- spans, intervals and garbage collections of generation 1 and up of an
  enabled trace open ``am.<name>`` annotations on the timeline factory
  (`set_timeline`), in nesting order, and a disabled trace calls nothing;
- the device layer's factory annotates only while a profiler trace
  records, and then the marks land in the trace's xplane;
- intervals (``apply_changes``, ``device_wait``) and the gc hook fill the
  flat ``Trace.counters`` and leave the span tree as it was;
- counters travel through ``to_jsonl`` / ``absorb_jsonl``;
- the farm: the root-level phase set gains ``prepare`` and
  ``prevalidate`` and nothing nests under the existing phases; readbacks
  count ``device_wait``;
- ``Observatory.modules`` maps XLA module names to amprof names.
"""
import gc

import pytest

from automerge_tpu.obs import spans as spans_mod
from automerge_tpu.obs.prof import Observatory
from automerge_tpu.obs.spans import Trace, get_trace, set_timeline, use_trace
from automerge_tpu.profiling import PhaseProfile, use_profile


class FakeTimeline:
    """An annotation factory that logs enters and exits."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        log = self.log

        class Mark:
            def __enter__(self):
                log.append(("enter", name, args))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        return Mark()

    def names(self, kind="enter"):
        return [e[1] for e in self.log if e[0] == kind]


@pytest.fixture
def timeline():
    """A FakeTimeline installed as the process's timeline factory."""
    fake = FakeTimeline()
    previous = set_timeline(fake)
    try:
        yield fake
    finally:
        set_timeline(previous)


def _stream(rounds, ops, actor="aaaaaaaa", seed=0):
    from automerge_tpu.obs.__main__ import _change_stream

    return _change_stream(actor, rounds, ops, seed=seed)


# ---------------------------------------------------------------------- #
# spans and intervals on the timeline

def test_spans_enter_the_timeline_in_nesting_order(timeline):
    trace = Trace()
    with use_trace(trace):
        with trace.span("outer", call=7):
            with trace.span("inner"):
                pass
            with trace.interval("device_wait"):
                pass
    assert [e[:2] for e in timeline.log] == [
        ("enter", "am.outer"), ("enter", "am.inner"), ("exit", "am.inner"),
        ("enter", "am.device_wait"), ("exit", "am.device_wait"),
        ("exit", "am.outer"),
    ]
    assert timeline.log[0][2] == {"call": 7}


def test_disabled_trace_calls_no_timeline_and_hooks_no_gc(timeline):
    trace = Trace(enabled=False)
    with use_trace(trace):
        assert spans_mod._on_gc not in gc.callbacks
        with trace.span("x"):
            with trace.interval("y"):
                gc.collect()
    assert timeline.log == []
    assert trace.counters == {} and trace.root.children == {}


def _visibility_with_waits(trace):
    with use_trace(trace):
        with trace.span("visibility"):
            with trace.interval("device_wait", rows=3):
                pass
        with trace.interval("device_wait"):
            pass


def test_interval_leaves_the_span_tree_untouched():
    plain, marked = Trace(), Trace()
    previous = set_timeline(None)
    try:
        _visibility_with_waits(plain)
    finally:
        set_timeline(previous)
    fake = FakeTimeline()
    previous = set_timeline(fake)
    try:
        _visibility_with_waits(marked)
    finally:
        set_timeline(previous)
    assert fake.names() == ["am.visibility", "am.device_wait",
                            "am.device_wait"]
    for trace in (plain, marked):
        assert list(trace.root.children) == ["visibility"]
        assert trace.root.children["visibility"].children == {}
        assert trace.counters["device_wait"]["calls"] == 2
        assert trace.counters["device_wait"]["seconds"] >= 0.0
    assert marked.totals_by_path().keys() == plain.totals_by_path().keys()


def test_a_factory_that_returns_none_marks_nothing():
    calls = []

    def idle(name, **args):
        calls.append(name)
        return None  # nothing records

    previous = set_timeline(idle)
    try:
        with use_trace(Trace()) as trace:
            with trace.span("a"):
                with trace.interval("b"):
                    pass
    finally:
        set_timeline(previous)
    assert calls == ["am.a", "am.b"]
    assert trace.counters["b"]["calls"] == 1
    assert trace.root.children["a"].calls == 1


def test_device_layer_marks_only_while_a_profiler_trace_records(tmp_path):
    import glob

    import jax

    from automerge_tpu.tpu import jitprof

    assert jitprof.profiler_mark("am.before") is None
    previous = set_timeline(jitprof.profiler_mark)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with use_trace(Trace()) as trace:
                with trace.span("walk", call=3):
                    with trace.interval("device_wait"):
                        pass
        finally:
            jax.profiler.stop_trace()
    finally:
        set_timeline(previous)
    assert jitprof.profiler_mark("am.after") is None
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events}
    assert {"am.walk", "am.device_wait"} <= names


# ---------------------------------------------------------------------- #
# garbage collection

def test_gc_counter_after_a_forced_collection_and_hook_removed_after(
        timeline):
    trace = Trace()
    with use_trace(trace):
        assert spans_mod._on_gc in gc.callbacks
        with trace.span("walk"):
            gc.collect()
    assert spans_mod._on_gc not in gc.callbacks
    assert trace.counters["gc"]["calls"] >= 1
    assert trace.counters["gc.gen2"]["calls"] >= 1
    assert trace.counters["gc"]["seconds"] >= trace.counters["gc.gen2"][
        "seconds"] > 0.0
    # the collection nests inside the span that was open, on the timeline
    # only: the tree keeps its one node
    names = timeline.names()
    assert names[0] == "am.walk" and "am.gc.gen2" in names
    assert trace.root.children["walk"].children == {}


def test_generation_0_collections_count_but_leave_no_mark(timeline):
    trace = Trace()
    with use_trace(trace):
        gc.collect(0)
        gc.collect(1)
    assert trace.counters["gc"]["calls"] >= 2
    assert "gc.gen2" not in trace.counters
    names = timeline.names()
    assert "am.gc.gen1" in names and "am.gc.gen0" not in names


def test_gc_hook_is_reference_counted_across_nested_traces():
    outer, inner = Trace(), Trace()
    with use_trace(outer):
        with use_trace(inner):
            gc.collect()
        assert spans_mod._on_gc in gc.callbacks
        gc.collect()
    assert spans_mod._on_gc not in gc.callbacks
    # each collection lands on the trace that was ambient at the time
    assert inner.counters["gc.gen2"]["calls"] >= 1
    assert outer.counters["gc.gen2"]["calls"] >= 1


def test_gc_hook_count_survives_threads_racing_use_trace():
    import sys
    import threading

    def work():
        for _ in range(300):
            with use_trace(Trace()):
                pass

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert spans_mod._GcHook.users == 0
    assert spans_mod._on_gc not in gc.callbacks


def test_gc_outside_any_enabled_trace_counts_nowhere():
    trace = Trace()
    with use_trace(trace):
        with use_trace(Trace(enabled=False)):
            gc.collect()
    assert "gc.gen2" not in trace.counters


# ---------------------------------------------------------------------- #
# JSON lines

def test_counters_round_trip_through_jsonl_and_merge():
    trace = Trace()
    with use_trace(trace):
        with trace.span("decode"):
            pass
        with trace.interval("device_wait"):
            pass
    trace.count("gc", 0.5, calls=2)
    text = trace.to_jsonl()
    rebuilt = Trace.from_jsonl(text)
    assert rebuilt.counters["gc"] == {"seconds": 0.5, "calls": 2}
    assert rebuilt.counters["device_wait"]["calls"] == 1
    assert list(rebuilt.root.children) == ["decode"]
    controller = Trace.from_jsonl(text)
    controller.absorb_jsonl(text)  # a second worker's dump merges
    assert controller.counters["gc"] == {"seconds": 1.0, "calls": 4}
    assert controller.root.children["decode"].calls == 2
    trace.reset()
    assert trace.counters == {} and trace.to_jsonl() == ""


# ---------------------------------------------------------------------- #
# the farm

FARM_PHASES = ("decode", "walk", "gate_verdicts", "transcode_columns",
               "gate+transcode", "pack", "visibility", "patch_assembly")


def test_farm_root_phases_gain_prepare_and_prevalidate_and_nothing_nests(
        timeline):
    from automerge_tpu.tpu.farm import TpuDocFarm

    farm = TpuDocFarm(2, capacity=32)
    bufs = _stream(2, 4)
    prof = PhaseProfile()
    with use_profile(prof):
        farm.apply_changes([[bufs[0]], [bufs[0]]])
        farm.apply_changes([[bufs[1]], []])
    assert set(prof.root.children) == set(FARM_PHASES) | {
        "device_dispatch", "prepare", "prevalidate"}
    for phase in FARM_PHASES + ("prepare", "prevalidate"):
        assert prof.root.children[phase].children == {}, phase
        assert prof.root.children[phase].calls == 2
    assert prof.counters["apply_changes"]["calls"] == 2


def test_farm_apply_changes_is_numbered_on_the_timeline(timeline):
    from automerge_tpu.tpu.farm import TpuDocFarm

    farm = TpuDocFarm(3, capacity=32)
    bufs = _stream(2, 4)
    farm.apply_changes([[bufs[0]], [], []])  # untraced, still numbered
    with use_profile(PhaseProfile()):
        farm.apply_changes([[bufs[1]], [bufs[0]], [bufs[0], bufs[1]]])
    first = timeline.log[0]
    assert first == ("enter", "am.apply_changes",
                     {"call": 2, "docs": 3, "changes": 4})
    assert timeline.log[-1] == ("exit", "am.apply_changes")
    phases = timeline.names()[1:]
    assert phases[:3] == ["am.prepare", "am.decode", "am.prevalidate"]


def test_map_doc_readback_counts_device_wait():
    from automerge_tpu.tpu.farm import TpuDocFarm

    farm = TpuDocFarm(2, capacity=32)
    bufs = _stream(3, 4)
    prof = PhaseProfile()
    with use_profile(prof):
        for buf in bufs:
            farm.apply_changes([[buf], [buf]])
    wait = prof.counters["device_wait"]
    assert wait["calls"] >= len(bufs)
    assert wait["seconds"] > 0.0
    # the wait sits inside visibility's self time, as before
    assert "device_wait" not in prof.root.children["visibility"].children


def test_untraced_apply_changes_leaves_the_ambient_trace_empty():
    from automerge_tpu.tpu.farm import TpuDocFarm

    farm = TpuDocFarm(2, capacity=32)
    farm.apply_changes([[_stream(1, 4)[0]], []])
    assert get_trace().enabled is False
    assert get_trace().counters == {}
    assert farm.apply_calls == 1


# ---------------------------------------------------------------------- #
# amprof names for the trace's module names

def test_observatory_modules_maps_xla_module_names():
    obs = Observatory()

    def paged_apply_ops():
        pass

    def _gather_rows():
        pass

    obs.register("paging.apply_ops", paged_apply_ops)
    obs.register("engine.gather_rows", _gather_rows)
    assert obs.modules() == {"jit_paged_apply_ops": "paging.apply_ops",
                             "jit__gather_rows": "engine.gather_rows"}


def test_registered_device_programs_name_their_modules():
    from automerge_tpu.obs.prof import get_observatory
    from automerge_tpu.tpu import engine  # noqa: F401  registers programs

    modules = get_observatory().modules()
    assert modules["jit_paged_apply_ops"] == "paging.apply_ops"
    assert modules["jit__gather_rows"] == "engine.gather_rows"


@pytest.mark.parametrize("module", ["obs.spans", "obs.prof", "profiling"])
def test_host_layer_imports_no_jax(module):
    import os
    import subprocess
    import sys

    code = (f"import sys, automerge_tpu.{module}; "
            "print('jax' in sys.modules)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True, cwd=root)
    assert out.stdout.strip() == "False"
