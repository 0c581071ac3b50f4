"""Device time of the merge, visibility and patch-emit programs, from the
profiler trace's `XLA Modules` line over the window, ms per 1,000 window
ops. The programs' amprof names do not reach the trace; their HLO modules
are named after the jitted functions (PERF.md, Layers)."""

MODULES = (
    "paged_apply_ops",        # paging.apply_ops
    "paged_visible_ranked",   # paging.visible_ranked
    "paged_visible_plain",    # paging.visible_plain
    "patch_column_rows",      # paging.patch_column_rows
    "_gather_rows",           # engine.gather_rows
)


def read(ctx):
    dev = ctx["device"]
    if dev is None or not ctx["kop"]:
        return None
    found = [t for name, t in dev["programs"].items() if name in MODULES]
    if not found:
        return None
    return sum(found) * 1000.0 / ctx["kop"]
