"""SPMD transport: device milliseconds per fit in collective ops
(collective-permute, all-reduce and kin) from the profiler trace, the mean
over the chips."""


def read(ctx):
    n = ctx.layer.get("n_fits")
    if not n or ctx.summary["collective_s"] <= 0:
        return None
    return ctx.summary["collective_s"] / n * 1e3
