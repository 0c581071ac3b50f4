"""From a profiler trace to device numbers: busy time, time per program,
top device ops and the longest idle gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two kinds of event, as ``(plane, line, name, start_ns, dur_ns)``:

- the harness's own host annotations (``bench.window``, ``bench.deliver``,
  ``bench.wait``), which put the measured window and what the host was
  doing on the trace's clock;
- every event on a device plane (``/device:TPU:<n>``): the ``XLA Ops``
  line gives busy time and top ops, the ``XLA Modules`` line the time of
  each compiled program (HLO module, named after the jitted function).

``reduce`` works on that list alone, so a small recorded list checks it
(tests/test_tracereduce.py).
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


def load_events(path: str) -> list:
    """Events of the newest ``.xplane.pb`` under `path` (a trace dir)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if is_device or e.name.startswith("bench."):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


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


def reduce(events: list, n_devices: int = 1) -> dict | None:
    """Device numbers over the ``bench.window`` annotation, or None when the
    trace holds no window or no device event. Times in seconds."""
    window = [e for e in events if e[2] == "bench.window"]
    if not window:
        return None
    w0 = window[0][3]
    w1 = w0 + window[0][4]
    host = [e for e in events if not e[0].startswith("/device:")]
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
    labels = [(s, s + d, name) for _p, _l, name, s, d in host
              if name in ("bench.deliver", "bench.wait")]

    def label(s, e):
        mid = (s + e) / 2
        for a, b, name in labels:
            if a <= mid < b:
                return name[len("bench."):]
        return "other"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_total / len(planes),
        "window_s": (w1 - w0) / 1e9,
        "programs": programs,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
        },
    }


def reduce_dir(path: str, n_devices: int = 1):
    """`reduce` over the trace in `path`."""
    return reduce(load_events(path), n_devices)
