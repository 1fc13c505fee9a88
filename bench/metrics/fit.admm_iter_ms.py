"""Fit driver (``run_admm``, one jitted scan): host milliseconds per ADMM
iteration, the mean over the traced window's fits. One-chip fit cells
only."""


def read(ctx):
    s = ctx.layer.get("admm_s")
    return sum(s) / len(s) / ctx.layer["n_iters"] * 1e3 if s else None
