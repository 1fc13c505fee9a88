"""Plain reference for serving cells whose support is too large for the
dense Gram: numpy on the host, float64, like ``reference``.

Nothing here imports the program. ``scores`` returns what
``reference.scores`` returns, but takes the support's kernel means in row
blocks: one (block, L) tile of the support's Gram at a time, never the
(L, L) Gram, and, since K is symmetric, only the tiles on and right of
the diagonal (half the work of the dense formula).
"""

from __future__ import annotations

import numpy as np

from bench import reference

BLOCK_ROWS = 1024


def kernel_means(support: np.ndarray, gamma: float,
                 block_rows: int = BLOCK_ROWS) -> tuple:
    """(m (L,), mu ()): m_i = mean_t K(x_i, x_t) over the support and its
    grand mean, RBF, float64."""
    x = np.asarray(support, np.float64)
    n = x.shape[0]
    sq = np.sum(x * x, 1)
    sums = np.zeros(n)
    for i in range(0, n, block_rows):
        j = min(i + block_rows, n)
        # rows i..j against columns i..n: the diagonal tile and the tiles
        # right of it; those count once for their rows, once (transposed)
        # for their columns
        k = x[i:j] @ x[i:].T
        k *= -2.0
        k += sq[i:j, None]
        k += sq[None, i:]
        np.maximum(k, 0.0, out=k)
        k *= -gamma
        np.exp(k, out=k)
        sums[i:j] += k.sum(axis=1)
        sums[j:] += k[:, j - i:].sum(axis=0)
    m = sums / n
    return m, float(m.mean())


def scores(support: np.ndarray, coefs: np.ndarray, queries: np.ndarray,
           gamma: float, precision: str = reference.REFERENCE,
           means: tuple = None) -> np.ndarray:
    """Centred out-of-sample scores Kc(q, S) @ coefs, as
    ``reference.scores``; ``means`` is ``kernel_means``' answer where the
    caller holds it already."""
    m, mu = kernel_means(support, gamma) if means is None else means
    k_qs = reference.rbf(queries, support, gamma, precision)
    kc = k_qs - k_qs.mean(axis=1, keepdims=True) - m[None, :] + mu
    return kc @ np.asarray(coefs, np.float64)
