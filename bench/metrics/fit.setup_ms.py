"""Fit set-up (``build_setup`` up to its arrays being ready): mean host
milliseconds per fit of the traced window. One-chip fit cells only."""


def read(ctx):
    s = ctx.layer.get("setup_s")
    return sum(s) / len(s) * 1e3 if s else None
