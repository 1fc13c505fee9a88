"""Rows per request ``min(max, floor(X))``, X ~ Pareto(x_m, shape):
``{"dist": "pareto", "x_m": 1, "shape": 1.2, "max": 128}``.

``draw(spec, n, work)`` gives ``n`` whole numbers from 1 to ``max``, drawn
from the mix's ``work`` stream.
"""

import numpy as np


def draw(spec, n, work):
    x = spec["x_m"] * (1.0 + work.pareto(spec["shape"], n))
    return np.clip(np.floor(x), 1, spec["max"]).astype(np.int64)
