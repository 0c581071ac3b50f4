"""Host self time of the OpSet walk span `walk`, ms per 1,000 window ops
(the walk serves text documents' patches)."""


def read(ctx):
    s = ctx["spans"].get("walk")
    return None if s is None or not ctx["kop"] else s * 1000.0 / ctx["kop"]
