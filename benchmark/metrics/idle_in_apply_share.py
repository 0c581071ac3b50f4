"""100 x the device-idle time inside the program's ``am.apply_changes``
calls / the traced window: how much of the chip's idle time the host spends
serving a delivery, as against waiting to flush one. None when the program
leaves no ``am.*`` mark."""
import os

from benchmark import timeline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    out = timeline.summary(ctx, BENCH)
    if out is None or not out["window_s"]:
        return None
    return 100.0 * out["idle_in_apply_s"] / out["window_s"]
