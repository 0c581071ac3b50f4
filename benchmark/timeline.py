"""The program's own spans on the profiler's timeline: what the host did in
each device-idle gap, and the marks it left there.

While a profiler trace records, every span and interval of an enabled
amtrace trace opens a profiler annotation named ``am.<name>``
(``automerge_tpu/obs/spans.py``, ``tpu/jitprof.py``): the farm's phases
(``am.decode``, ``am.walk``, ...), the whole ``am.apply_changes`` call and
each host wait on a device result (``am.device_wait``); collections of
generation 1 and up are ``am.gc.gen<N>``. They land in the same
``.xplane.pb`` as the device lines and the harness's ``bench.*``
annotations, on one clock.

A stopgap beside ``tracereduce``: that module keeps only the ``bench.*``
host events, and a reader's context holds neither the trace's path nor
the trace's counters, so the readers of these metrics load the run's
trace again from where the harness writes it by default
(``<bench>/.trace``) and reduce it here. A benchmark change that folds
``reduce`` into ``tracereduce.reduce`` and hands the readers the trace's
reduction (and ``Trace.counters``) retires this module. ``reduce`` works
on the event list alone (tests/test_timeline_reduce.py). A trace with no
``am.*`` event (a program that does not mark its spans) reads as None.
"""
from __future__ import annotations

import glob
import os
import sys

from . import tracereduce

#: timeline marks that are not span-tree phases: the call around the
#: phases, the host's waits on the device, and garbage collections
INTERVALS = ("apply_changes", "device_wait")


def _is_interval(name: str) -> bool:
    return name in INTERVALS or name.startswith("gc.")


def newest_trace(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_events(path: str) -> list:
    """Events of one ``.xplane.pb``, as ``(plane, line, name, start_ns,
    dur_ns)``: every device event and the ``bench.*`` and ``am.*`` host
    annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if is_device or e.name.startswith(("bench.", "am.")):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def _idle_gaps(device, planes, w0, w1):
    """Per plane, the stretches of the window with no device op."""
    gaps = []
    for plane in planes:
        mine = [e for e in device if e[0] == plane]
        lines = {e[1] for e in mine}
        busy_line = (tracereduce.OPS_LINE if tracereduce.OPS_LINE in lines
                     else tracereduce.MODULES_LINE)
        spans = []
        for _p, line, _n, s, d in mine:
            s, e = max(s, w0), min(s + d, w1)
            if line == busy_line and e > s:
                spans.append((s, e))
        edges = [w0]
        for s, e in tracereduce._union(spans):
            edges += [s, e]
        edges.append(w1)
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def _residual(am):
    """Over every ``apply_changes`` call, in ns: the time outside its phases
    (the union of the span marks inside it), the time outside every other
    mark inside it (phases, collections, device waits), and the calls'
    total."""
    outside = unnamed = total = 0.0
    calls = [(s, e) for _l, name, s, e in am if name == "apply_changes"]
    for a, b in calls:
        inner = [(name, s, e) for _l, name, s, e in am
                 if a <= s and e <= b and name != "apply_changes"]
        phases = [(s, e) for name, s, e in inner if not _is_interval(name)]
        marks = [(s, e) for _name, s, e in inner]
        total += b - a
        outside += b - a - sum(e - s for s, e in tracereduce._union(phases))
        unnamed += b - a - sum(e - s for s, e in tracereduce._union(marks))
    return outside, unnamed, total


def reduce(events: list, n_devices: int = 1) -> dict | None:
    """The device-idle time of the ``bench.window`` split by the innermost
    ``am.*`` span that covers it, plus the marks' totals. None when the
    trace holds no window, no device plane or no ``am.*`` mark. Times in
    seconds.

    - ``idle_s``: idle time (the window less the union of device ops),
      averaged over the planes, as ``tracereduce`` counts it;
    - ``idle_by_span``: that time by the innermost ``am.*`` span over it
      (``gc.gen2``, ``walk``, ``apply_changes`` for the call's own code
      between phases), else by the ``bench.*`` annotation over it
      (``deliver``, ``wait``), else ``other``; it sums to ``idle_s``;
    - ``idle_in_apply_s``: idle time inside an ``am.apply_changes`` call;
    - ``idle_gaps``: the ten longest gaps, each labelled
      ``<bench label>/<span over most of it>`` (``deliver/gc.gen2``), or
      the bench label alone when no ``am.*`` span covers any of it;
    - ``marks``: ``{name: {"seconds", "calls"}}`` of the ``am.*`` marks
      that start in the window, with ``gc`` the sum over generations;
    - ``residual_s`` / ``apply_s``: ``apply_changes`` time outside its
      phases, and the calls' whole time; ``unnamed_s``: the part of the
      residual under no mark at all (a collection between two phases is
      residual, but named)."""
    window = [e for e in events if e[2] == "bench.window"]
    if not window:
        return None
    w0 = window[0][3]
    w1 = w0 + window[0][4]
    device = [e for e in events if e[0].startswith("/device:TPU:")
              and "SparseCore" not in e[0]]
    planes = sorted({e[0] for e in device})[:n_devices]
    am = [(line, name[3:], s, s + d) for plane, line, name, s, d in events
          if not plane.startswith("/device:") and name.startswith("am.")
          and w0 <= s < w1]
    if not planes or not am:
        return None
    bench = [(s, s + d, name[len("bench."):]) for p, _l, name, s, d in events
             if not p.startswith("/device:")
             and name in ("bench.deliver", "bench.wait")]
    gaps = _idle_gaps(device, planes, w0, w1)
    top = sorted(range(len(gaps)), key=lambda i: gaps[i][0] - gaps[i][1])[:10]
    top_set = set(top)

    # one sweep over every edge: gaps, am spans, bench annotations
    points = []
    for i, (s, e) in enumerate(gaps):
        points += [(s, 1, "gap", i), (e, 0, "gap", i)]
    for k, (_line, name, s, e) in enumerate(am):
        points += [(s, 1, "am", k), (e, 0, "am", k)]
    for k, (s, e, _name) in enumerate(bench):
        points += [(s, 1, "bench", k), (e, 0, "bench", k)]
    points.sort()
    active = {"gap": set(), "am": set(), "bench": set()}
    active_gaps, active_am, active_bench = (active["gap"], active["am"],
                                            active["bench"])
    idle_by: dict = {}
    in_apply = 0.0
    per_gap: dict = {i: {} for i in top}
    for (t, opens, kind, k), nxt in zip(points, points[1:] + [None]):
        (active[kind].add if opens else active[kind].discard)(k)
        if nxt is None or not active_gaps or nxt[0] <= t:
            continue
        dt = nxt[0] - t
        if active_am:
            inner = max(active_am, key=lambda j: (am[j][2], -am[j][3]))
            key = am[inner][1]
            if any(am[j][1] == "apply_changes" for j in active_am):
                in_apply += dt * len(active_gaps)
        elif active_bench:
            key = bench[max(active_bench, key=lambda j: bench[j][0])][2]
        else:
            key = "other"
        idle_by[key] = idle_by.get(key, 0.0) + dt * len(active_gaps)
        if active_am:
            for g in active_gaps & top_set:
                per_gap[g][key] = per_gap[g].get(key, 0.0) + dt

    def bench_label(s, e):
        mid = (s + e) / 2
        for a, b, name in bench:
            if a <= mid < b:
                return name
        return "other"

    labelled = []
    for i in top:
        s, e = gaps[i]
        label = bench_label(s, e)
        if per_gap[i]:
            label += "/" + max(per_gap[i], key=per_gap[i].get)
        labelled.append([label, (e - s) / 1e9])

    marks: dict = {}
    for _line, name, s, e in am:
        keys = (name, "gc") if name.startswith("gc.gen") else (name,)
        for key in keys:
            m = marks.setdefault(key, {"seconds": 0.0, "calls": 0})
            m["seconds"] += (e - s) / 1e9
            m["calls"] += 1
    residual, unnamed, apply_total = _residual(am)
    n = len(planes)
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(e - s for s, e in gaps) / 1e9 / n,
        "idle_by_span": {k: v / 1e9 / n for k, v in sorted(
            idle_by.items(), key=lambda kv: -kv[1])},
        "idle_in_apply_s": in_apply / 1e9 / n,
        "idle_gaps": labelled,
        "marks": marks,
        "residual_s": residual / 1e9,
        "unnamed_s": unnamed / 1e9,
        "apply_s": apply_total / 1e9,
    }


_CACHE: dict = {}


def summary(ctx: dict, bench_dir: str) -> dict | None:
    """`reduce` over this run's trace under ``<bench_dir>/.trace``, or None
    when the run reduced no device trace or the trace holds no ``am.*``
    mark. Raises when the newest trace there is missing or is not the one
    the harness reduced (another ``bench.window``): the harness wrote this
    run's trace elsewhere. The first call per trace prints the split on
    stderr."""
    dev = ctx.get("device")
    if dev is None:
        return None
    trace_dir = os.path.join(bench_dir, ".trace")
    path = newest_trace(trace_dir)
    if path is None:
        raise FileNotFoundError(
            f"no trace under {trace_dir}: the run's trace went elsewhere")
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        events = load_events(path)
        window = [e[4] / 1e9 for e in events if e[2] == "bench.window"]
        if not window or abs(window[0] - dev["window_s"]) > 1e-6:
            raise ValueError(
                f"{path} is not this run's trace (window {window} s, the "
                f"harness reduced {dev['window_s']} s)")
        out = reduce(events)
        _CACHE[key] = out
        if out is not None:
            _say(out, dev)
    return _CACHE[key]


def _say(out: dict, dev: dict) -> None:
    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    ms = {k: round(v * 1000.0, 3) for k, v in out["idle_by_span"].items()}
    say(f"device idle by span (ms): idle={out['idle_s'] * 1000.0:.3f} "
        f"in_apply={out['idle_in_apply_s'] * 1000.0:.3f} {ms}")
    say("idle gaps by span (s): " + ", ".join(
        f"{label} {s:.4f}" for label, s in out["idle_gaps"]))
    say("am marks in the window (s, calls): " + ", ".join(
        f"{k} {m['seconds']:.4f} x{m['calls']}"
        for k, m in sorted(out["marks"].items())))
    apply_s = out["apply_s"] or 1.0
    say(f"apply_changes outside its phases: {out['residual_s']:.4f} s "
        f"of {out['apply_s']:.4f} s "
        f"({100.0 * out['residual_s'] / apply_s:.3f}%); under no mark: "
        f"{out['unnamed_s']:.4f} s "
        f"({100.0 * out['unnamed_s'] / apply_s:.3f}%)")
    try:
        from automerge_tpu.obs.prof import get_observatory

        modules = get_observatory().modules()
    except (ImportError, AttributeError):
        return
    by_name: dict = {}
    for module, seconds in dev["programs"].items():
        name = modules.get(f"jit_{module}", f"jit_{module}")
        by_name[name] = by_name.get(name, 0.0) + seconds
    say("device seconds by amprof program: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(by_name.items(),
                                           key=lambda kv: -kv[1])))
