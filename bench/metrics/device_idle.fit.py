"""Device idle share of the traced fit window: 1 - (union of device-op
intervals / window), the mean over the cell's chips, in percent."""


def read(ctx):
    if ctx.kind != "fit":
        return None
    s = ctx.summary
    return (1.0 - s["busy_s"] / s["window_s"]) * 100.0
