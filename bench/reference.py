"""Plain references that decide ``correct``: numpy on the host, float64.

Nothing here imports the program or takes anything it made. Each function
recomputes, from the generated data alone, what the timed path should
produce:

* the RBF bandwidth by the median rule (``gamma_median``);
* the globally centred Gram of the pooled data and its top component,
  which is central kernel PCA (paper eq. 2);
* each node's similarity to that component (paper section 6.1), and the
  top eigenvalues of each node's block of the centred Gram, which is what
  the fit's set-up decomposes;
* centred out-of-sample scores of a served model.

``precision`` selects how the distance dot products are formed: "float64"
(the reference), or "bfloat16" for the control, computed in JAX on the
default device: both operands rounded to bfloat16, accumulated in float32
(one MXU pass on a TPU, the same numbers on any backend).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse.linalg

REFERENCE = "float64"
CONTROL = "bfloat16"


def _sqdist(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    if precision == REFERENCE:
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        d2 = (np.sum(a * a, 1)[:, None] + np.sum(b * b, 1)[None, :]
              - 2.0 * a @ b.T)
        return np.maximum(d2, 0.0)
    if precision != CONTROL:
        raise ValueError(f"precision must be {REFERENCE!r} or {CONTROL!r}")
    import jax.numpy as jnp
    aj, bj = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    sq = jnp.sum(aj * aj, 1)[:, None] + jnp.sum(bj * bj, 1)[None, :]
    dot = jnp.matmul(aj.astype(jnp.bfloat16), bj.astype(jnp.bfloat16).T,
                     preferred_element_type=jnp.float32)
    return np.maximum(np.asarray(sq - 2.0 * dot, np.float64), 0.0)


def gamma_median(pooled: np.ndarray, precision: str = REFERENCE) -> float:
    """1 / median of the squared distances among the first 256 rows, the
    diagonal counted at the largest distance."""
    n = min(pooled.shape[0], 256)
    d2 = _sqdist(pooled[:n], pooled[:n], precision)
    d2 = d2 + np.eye(n) * d2.max()
    return 1.0 / max(float(np.median(d2)), 1e-12)


def rbf(a: np.ndarray, b: np.ndarray, gamma: float,
        precision: str = REFERENCE) -> np.ndarray:
    return np.exp(-gamma * _sqdist(a, b, precision))


def centred_gram(pooled: np.ndarray, gamma: float,
                 precision: str = REFERENCE) -> np.ndarray:
    """Gram of the pooled data, centred over all of it."""
    k = rbf(pooled, pooled, gamma, precision)
    row = k.mean(axis=1)
    return k - row[:, None] - row[None, :] + row.mean()


def top_component(kc: np.ndarray) -> tuple:
    """(alpha, lam): the top eigenpair of the centred Gram, alpha scaled
    to 1 / sqrt(lam) so that its feature-space vector has norm 1."""
    lam, vec = scipy.sparse.linalg.eigsh(kc, k=1, which="LA", tol=0.0,
                                         v0=np.ones(kc.shape[0]))
    lam = float(lam[0])
    return vec[:, 0] / np.sqrt(lam), lam


def node_similarity(kc: np.ndarray, alpha_nodes: np.ndarray,
                    alpha_gt: np.ndarray, lam_gt: float) -> np.ndarray:
    """|cos| between each node's w_j = phi(X_j) alpha_j and the central
    w (paper section 6.1), both in the globally centred feature space.
    Node j holds pooled rows j*N .. (j+1)*N. Uses Kc alpha_gt = lam alpha_gt
    and alpha_gt' Kc alpha_gt = 1."""
    j, n = alpha_nodes.shape
    out = np.empty(j)
    for i in range(j):
        rows = slice(i * n, (i + 1) * n)
        a = np.asarray(alpha_nodes[i], np.float64)
        num = lam_gt * float(a @ alpha_gt[rows])
        den = float(a @ kc[rows, rows] @ a)
        out[i] = abs(num) / np.sqrt(max(den, 1e-300))
    return out


def node_top_eigs(kc: np.ndarray, n_nodes: int, k: int) -> np.ndarray:
    """(J, k) largest eigenvalues, ascending, of each node's own block of
    the globally centred Gram."""
    n = kc.shape[0] // n_nodes
    out = np.empty((n_nodes, k))
    for i in range(n_nodes):
        rows = slice(i * n, (i + 1) * n)
        out[i] = np.linalg.eigvalsh(kc[rows, rows])[-k:]
    return out


def scores(support: np.ndarray, coefs: np.ndarray, queries: np.ndarray,
           gamma: float, precision: str = REFERENCE) -> np.ndarray:
    """Centred out-of-sample scores Kc(q, S) @ coefs, with the kernel means
    of the support: Kc(q, x) = K(q, x) - mean_l K(q, x_l) - m(x) + mu."""
    k_ss = rbf(support, support, gamma)
    m = k_ss.mean(axis=1)
    mu = m.mean()
    k_qs = rbf(queries, support, gamma, precision)
    kc = k_qs - k_qs.mean(axis=1, keepdims=True) - m[None, :] + mu
    return kc @ np.asarray(coefs, np.float64)


def fit(x_nodes: np.ndarray, k: int, precision: str = REFERENCE) -> dict:
    """Central kPCA and each node's top ``k`` eigenvalues for one dataset
    of (J, N, M) node blocks."""
    j, n, m = x_nodes.shape
    pooled = x_nodes.reshape(j * n, m)
    gamma = gamma_median(pooled, precision)
    kc = centred_gram(pooled, gamma, precision)
    alpha, lam = top_component(kc)
    return {"kc": kc, "alpha": alpha, "lam": lam,
            "eigs": node_top_eigs(kc, j, k)}


def eig_gap(lam: np.ndarray, ref_eigs: np.ndarray) -> float:
    """max |program - reference| over each node's top k eigenvalues, as a
    share of that node's largest."""
    k = ref_eigs.shape[1]
    top = np.asarray(lam, np.float64)[:, -k:]
    return float(np.max(np.abs(top - ref_eigs) / ref_eigs[:, -1:]))
