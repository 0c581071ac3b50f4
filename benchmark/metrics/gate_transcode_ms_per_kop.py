"""Host self time of the causal gate and transcode spans (`gate_verdicts`,
`transcode_columns`, `gate+transcode`, `pack`), ms per 1,000 window ops."""

SPANS = ("gate_verdicts", "transcode_columns", "gate+transcode", "pack")


def read(ctx):
    found = [ctx["spans"][n] for n in SPANS if n in ctx["spans"]]
    if not found or not ctx["kop"]:
        return None
    return sum(found) * 1000.0 / ctx["kop"]
