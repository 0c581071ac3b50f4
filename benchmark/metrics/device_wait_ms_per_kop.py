"""The host's waits on device results on the apply path (the
``jax.device_get`` of the scoped readbacks), ms per 1,000 window ops: the
program's ``am.device_wait`` marks on the profiler timeline (its
``device_wait`` counter). None when the program leaves no ``am.*`` mark."""
import os

from benchmark import timeline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    out = timeline.summary(ctx, BENCH)
    if out is None or not ctx["kop"]:
        return None
    wait = out["marks"].get("device_wait", {"seconds": 0.0})
    return wait["seconds"] * 1000.0 / ctx["kop"]
