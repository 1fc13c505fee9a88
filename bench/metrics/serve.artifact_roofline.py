"""Artifact build: the kernel-mean pass's roofline time (``bench.roofline``)
as a share of the build's host milliseconds, in percent. Host time holds
the device's, so this is a lower bound on the pass's own share."""

KEYS = ("artifact_ms", "support_rows", "features", "kernel_mean_blocks",
        "device_kind")


def read(ctx):
    from bench import roofline
    lay = ctx.layer
    if any(lay.get(k) is None for k in KEYS) or not lay["artifact_ms"]:
        return None
    peak = roofline.peaks(lay["device_kind"])
    if peak is None:
        return None
    l, m = lay["support_rows"], lay["features"]
    t = roofline.roofline_s(roofline.kernel_means_ops(l, m),
                            roofline.kernel_means_bytes(
                                l, m, lay["kernel_mean_blocks"]), peak)
    return 100.0 * t / (lay["artifact_ms"] * 1e-3)
