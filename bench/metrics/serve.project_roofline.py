"""Projection program: the roofline time of the window's projection calls
(``bench.roofline``: ``EngineStats.n_slabs`` calls over
``n_queries + n_padded`` slab rows) as a share of the device seconds of the
engine's ``_proj`` programs in the traced window, in percent."""

PROGRAM = r"_proj"
KEYS = ("n_slabs", "n_queries", "n_padded", "support_rows", "features",
        "device_kind")


def read(ctx):
    from bench import roofline, tracereduce
    lay = ctx.layer
    if any(lay.get(k) is None for k in KEYS) or not lay["n_slabs"]:
        return None
    peak = roofline.peaks(lay["device_kind"])
    secs = tracereduce.module_seconds(ctx.trace, PROGRAM)
    if peak is None or not secs:
        return None
    rows = lay["n_queries"] + lay["n_padded"]
    l, m = lay["support_rows"], lay["features"]
    t = roofline.roofline_s(roofline.project_ops(rows, l, m),
                            roofline.project_bytes(lay["n_slabs"], rows, l, m),
                            peak)
    return 100.0 * t / secs
