"""Poisson arrivals: ``{"process": "poisson", "rate_rps": r}``.

``times(spec, n, seconds, work, order)`` gives ``n`` sorted due times in
``[0, seconds)``: exponential gaps drawn from the mix's ``work`` stream
and scaled to sum to ``seconds``, so every seed gets the same gaps, which
the seed's ``order`` stream only shuffles.
"""

import numpy as np


def times(spec, n, seconds, work, order):
    gaps = work.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = gaps[order.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
