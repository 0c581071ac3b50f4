"""Host self time of the farm's `decode` span, ms per 1,000 window ops."""


def read(ctx):
    s = ctx["spans"].get("decode")
    return None if s is None or not ctx["kop"] else s * 1000.0 / ctx["kop"]
