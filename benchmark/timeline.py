"""The readers' access to the run's ``am.*`` timeline split
(``tracereduce.timeline``).

The harness reduces the run's trace once (``tracereduce.reduce_run``) and
hands the readers that reduction as ``ctx["device"]``, the split under its
``"timeline"`` key; `summary` returns it, with no file access. Only a
device reduction made without the split (``tracereduce.reduce`` alone)
sends `summary` back to the trace under ``<bench>/.trace``, which it
loads and checks against the reduced window.
"""
from __future__ import annotations

import os

from . import tracereduce

reduce = tracereduce.timeline
newest_trace = tracereduce.newest_trace
load_events = tracereduce.load_file

_CACHE: dict = {}


def summary(ctx: dict, bench_dir: str) -> dict | None:
    """The run's `tracereduce.timeline` split, or None when the run reduced
    no device trace or the program left no ``am.*`` mark. A device
    reduction without the split reloads the newest trace under
    ``<bench_dir>/.trace``, and raises when that trace is missing or is
    not the one reduced (another ``bench.window``)."""
    dev = ctx.get("device")
    if dev is None:
        return None
    if "timeline" in dev:
        return dev["timeline"]
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
        _CACHE[key] = reduce(events)
    return _CACHE[key]
