"""Projection program: device milliseconds of the engine's projection
programs in the traced window per drain (``EngineStats.n_flushes``)."""

PROGRAM = r"_proj"


def read(ctx):
    from bench import tracereduce
    flushes = ctx.layer.get("n_flushes")
    secs = tracereduce.module_seconds(ctx.trace, PROGRAM)
    if not flushes or secs is None:
        return None
    return secs / flushes * 1e3
