"""Host time of the farm's `visibility` span (mirror merge and scoped
readback; it holds the wait for the device), ms per 1,000 window ops."""


def read(ctx):
    s = ctx["spans"].get("visibility")
    return None if s is None or not ctx["kop"] else s * 1000.0 / ctx["kop"]
