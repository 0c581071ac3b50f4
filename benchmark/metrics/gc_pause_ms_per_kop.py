"""Python garbage collection in the window, generations 1 and 2, ms per
1,000 window ops: the program's ``am.gc.gen<N>`` marks on the profiler
timeline (timed by a ``gc.callbacks`` hook while the trace is installed;
the short generation-0 passes are in its ``gc`` counter, not on the
timeline). None when the program leaves no ``am.*`` mark."""
import os

from benchmark import timeline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    out = timeline.summary(ctx, BENCH)
    if out is None or not ctx["kop"]:
        return None
    pause = out["marks"].get("gc", {"seconds": 0.0})
    return pause["seconds"] * 1000.0 / ctx["kop"]
