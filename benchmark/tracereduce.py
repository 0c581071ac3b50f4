"""From a profiler trace to device numbers: busy time, time per program,
top device ops and the longest idle gaps, and what the host did in them.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three kinds of event, as ``(plane, line, name, start_ns, dur_ns)``:

- the harness's own host annotations (``bench.window``, ``bench.deliver``,
  ``bench.wait``), which put the measured window and what the host was
  doing on the trace's clock;
- the program's own host marks (``am.*``): while a profiler trace records,
  every span and interval of an enabled amtrace trace opens a profiler
  annotation named ``am.<name>`` (``automerge_tpu/obs/spans.py``,
  ``tpu/jitprof.py``): the farm's phases (``am.decode``, ``am.walk``,
  ...), the whole ``am.apply_changes`` call and each host wait on a device
  result (``am.device_wait``); collections of generation 1 and up are
  ``am.gc.gen<N>``;
- every event on a device plane (``/device:TPU:<n>``): the ``XLA Ops``
  line gives busy time and top ops, the ``XLA Modules`` line the time of
  each compiled program (HLO module, named after the jitted function).

``reduce`` takes the device numbers and reads no ``am.*`` event;
``timeline`` splits the device-idle time by the ``am.*`` span over it;
``reduce_run`` is both, as the harness hands them to the readers. Each
works on the event list alone, so a small recorded list checks it
(tests/test_tracereduce.py).
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")
#: timeline marks that are not span-tree phases: the call around the
#: phases, the host's waits on the device, and garbage collections
INTERVALS = ("apply_changes", "device_wait")


def newest_trace(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_file(path: str) -> list:
    """Events of one ``.xplane.pb``: every device event and the
    ``bench.*`` and ``am.*`` host annotations."""
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


def load_events(path: str) -> list:
    """Events of the newest ``.xplane.pb`` under `path` (a trace dir)."""
    newest = newest_trace(path)
    return load_file(newest) if newest else []


def module_name(name: str) -> str:
    """``jit_paged_apply_ops(123)`` -> ``paged_apply_ops``."""
    name = _MODULE_ID.sub("", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """``%fusion.43 = pred[262144]{...} fusion(...)`` -> ``%fusion.43``."""
    return name.split(" = ", 1)[0]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _device(events: list, n_devices: int) -> dict | None:
    """The ``bench.window`` and, over it, per plane: the busy union, each
    program's and op's time, and the idle gaps (ns). None when the trace
    holds no window or no device event."""
    window = [e for e in events if e[2] == "bench.window"]
    if not window:
        return None
    w0 = window[0][3]
    w1 = w0 + window[0][4]
    device = [e for e in events if e[0].startswith("/device:TPU:")
              and "SparseCore" not in e[0]]
    planes = sorted({e[0] for e in device})[:n_devices]
    if not planes:
        return None
    busy_total, programs, ops, gaps = 0.0, {}, {}, []
    for plane in planes:
        mine = [e for e in device if e[0] == plane]
        lines = {e[1] for e in mine}
        busy_line = OPS_LINE if OPS_LINE in lines else MODULES_LINE
        spans = []
        for _p, line, name, s, d in mine:
            s, e = _clip(s, s + d, w0, w1)
            if e <= s:
                continue
            if line == busy_line:
                spans.append((s, e))
            if line == OPS_LINE:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
            if line == MODULES_LINE:
                key = module_name(name)
                programs[key] = programs.get(key, 0.0) + (e - s) / 1e9
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i], edges[i + 1]))
    bench = [(s, s + d, name[len("bench."):]) for p, _l, name, s, d in events
             if not p.startswith("/device:")
             and name in ("bench.deliver", "bench.wait")]
    # the ten longest gaps, longest first (a stable sort: ties keep order)
    longest = sorted(range(len(gaps)),
                     key=lambda i: gaps[i][0] - gaps[i][1])[:10]
    return {"w0": w0, "w1": w1, "planes": planes, "busy_s": busy_total,
            "programs": programs, "ops": ops, "gaps": gaps,
            "longest": longest, "bench": bench}


def _bench_label(bench, s, e):
    """The ``bench.*`` annotation over the gap's midpoint."""
    mid = (s + e) / 2
    for a, b, name in bench:
        if a <= mid < b:
            return name
    return "other"


def _summary(base: dict) -> dict:
    gaps = base["gaps"]
    return {
        "busy_s": base["busy_s"] / len(base["planes"]),
        "window_s": (base["w1"] - base["w0"]) / 1e9,
        "programs": base["programs"],
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                base["ops"].items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [
                [_bench_label(base["bench"], *gaps[i]),
                 (gaps[i][1] - gaps[i][0]) / 1e9]
                for i in base["longest"]],
        },
    }


def reduce(events: list, n_devices: int = 1) -> dict | None:
    """Device numbers over the ``bench.window`` annotation, or None when the
    trace holds no window or no device event. Times in seconds. Reads no
    ``am.*`` event: each idle gap is labelled by the ``bench.*``
    annotation over it."""
    base = _device(events, n_devices)
    return None if base is None else _summary(base)


def _is_interval(name: str) -> bool:
    return name in INTERVALS or name.startswith("gc.")


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
        outside += b - a - sum(e - s for s, e in _union(phases))
        unnamed += b - a - sum(e - s for s, e in _union(marks))
    return outside, unnamed, total


def _split(events: list, base: dict) -> dict | None:
    """`timeline` over the window and gaps `_device` found."""
    w0, w1, gaps, bench = base["w0"], base["w1"], base["gaps"], base["bench"]
    am = [(line, name[3:], s, s + d) for plane, line, name, s, d in events
          if not plane.startswith("/device:") and name.startswith("am.")
          and w0 <= s < w1]
    if not am:
        return None
    top = base["longest"]
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

    labelled = []
    for i in top:
        s, e = gaps[i]
        label = _bench_label(bench, s, e)
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
    n = len(base["planes"])
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


def timeline(events: list, n_devices: int = 1) -> dict | None:
    """The device-idle time of the ``bench.window`` split by the innermost
    ``am.*`` span that covers it, plus the marks' totals. None when the
    trace holds no window, no device plane or no ``am.*`` mark. Times in
    seconds.

    - ``idle_s``: idle time (the window less the union of device ops),
      averaged over the planes, as `reduce` counts it;
    - ``idle_by_span``: that time by the innermost ``am.*`` span over it
      (``gc.gen2``, ``walk``, ``apply_changes`` for the call's own code
      between phases), else by the ``bench.*`` annotation over it
      (``deliver``, ``wait``), else ``other``; it sums to ``idle_s``;
    - ``idle_in_apply_s``: idle time inside an ``am.apply_changes`` call;
    - ``idle_gaps``: `reduce`'s ten longest gaps, in its order, each
      labelled ``<bench label>/<span over most of it>``
      (``deliver/gc.gen2``), or the bench label alone when no ``am.*``
      span covers any of it;
    - ``marks``: ``{name: {"seconds", "calls"}}`` of the ``am.*`` marks
      that start in the window, with ``gc`` the sum over generations;
    - ``residual_s`` / ``apply_s``: ``apply_changes`` time outside its
      phases, and the calls' whole time; ``unnamed_s``: the part of the
      residual under no mark at all (a collection between two phases is
      residual, but named)."""
    base = _device(events, n_devices)
    return None if base is None else _split(events, base)


def reduce_run(events: list, n_devices: int = 1) -> dict | None:
    """`reduce`, with `timeline` under ``"timeline"`` (None when the
    program left no ``am.*`` mark); where it is there, its labelled gaps
    stand in the breakdown for the bare ones."""
    base = _device(events, n_devices)
    if base is None:
        return None
    out = _summary(base)
    out["timeline"] = _split(events, base)
    if out["timeline"] is not None:
        out["breakdown"]["idle_gaps"] = out["timeline"]["idle_gaps"]
    return out


def reduce_dir(path: str, n_devices: int = 1):
    """`reduce_run` over the trace in `path`."""
    return reduce_run(load_events(path), n_devices)
