"""Kernel functions and Gram-matrix math for (decentralized) kernel PCA.

Everything here is pure jnp and serves as the numerical ground truth; the
Pallas kernels in ``repro.kernels.gram`` implement the same contract with
explicit VMEM tiling and are validated against these functions.

The paper (§3.1) requires the kernel to be *normalized*: K(x, x) = 1 for all
x. RBF satisfies this by construction; linear/polynomial kernels are
normalized via K(x,y)/sqrt(K(x,x) K(y,y)).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Positive-definite kernel specification.

    kind: "rbf" | "linear" | "poly"
    gamma: RBF bandwidth K(x,y)=exp(-gamma ||x-y||^2); None => median heuristic
           resolved at Gram time (see ``resolve_gamma``).
    degree/coef: polynomial kernel (x.y * scale + coef) ** degree.
    normalize: enforce K(x,x)=1 (paper §3.1). RBF is already normalized.
    """

    kind: str = "rbf"
    gamma: Optional[float] = None
    degree: int = 3
    coef: float = 1.0
    scale: float = 1.0
    normalize: bool = True

    def __post_init__(self):
        if self.kind not in ("rbf", "linear", "poly"):
            raise ValueError(f"unknown kernel kind: {self.kind}")


# Inner products of the data run at full float32 precision. On TPU the
# default float32 matmul is one bf16 pass, and both uses below need more:
# the RBF distance ||x||^2 + ||y||^2 - 2 x.y cancels (at M=784 a bf16 pass
# loses most of the distance), and the linear/poly kernels are the dot.
HIGHEST = jax.lax.Precision.HIGHEST


def _dot_t(x: jax.Array, y: jax.Array) -> jax.Array:
    """x @ y.T at float32 precision on every backend."""
    return jnp.matmul(x, y.T, precision=HIGHEST)


def pairwise_sqdist(x: jax.Array, y: jax.Array,
                    y_sq: Optional[jax.Array] = None) -> jax.Array:
    """Squared euclidean distances. x: (n, m), y: (k, m) -> (n, k).

    y_sq: optional (k,) precomputed ``sum(y * y, -1)``; a caller that holds
    it (the served artifact's support norms) saves the pass over ``y``.
    """
    sx = jnp.sum(x * x, axis=-1)
    sy = jnp.sum(y * y, axis=-1) if y_sq is None else y_sq
    d2 = sx[:, None] + sy[None, :] - 2.0 * _dot_t(x, y)
    return jnp.maximum(d2, 0.0)


def resolve_gamma(spec: KernelSpec, x: jax.Array) -> jax.Array:
    """Median heuristic: gamma = 1 / median(||x_i - x_j||^2) over a subsample."""
    if spec.gamma is not None:
        return jnp.asarray(spec.gamma, x.dtype)
    n = min(x.shape[0], 256)
    d2 = pairwise_sqdist(x[:n], x[:n])
    med = jnp.median(d2 + jnp.eye(n, dtype=x.dtype) * jnp.max(d2))
    return 1.0 / jnp.maximum(med, 1e-12)


def gram(spec: KernelSpec, x: jax.Array, y: Optional[jax.Array] = None,
         gamma: Optional[jax.Array] = None,
         y_sq: Optional[jax.Array] = None) -> jax.Array:
    """Dense Gram matrix K[i, j] = K(x_i, y_j). Pure-jnp oracle.

    y_sq: optional (k,) precomputed squared row norms of ``y`` (see
    ``pairwise_sqdist``); every kernel kind derives its ``y`` terms from it.
    """
    if y is None:
        y = x
    if spec.kind == "rbf":
        g = resolve_gamma(spec, x) if gamma is None else gamma
        return jnp.exp(-g * pairwise_sqdist(x, y, y_sq))
    k = _dot_t(x, y) * spec.scale
    if spec.kind == "poly":
        k = (k + spec.coef) ** spec.degree
    if spec.normalize:
        dx = _self_k(spec, x)
        dy = _self_k(spec, y, y_sq)
        k = k / jnp.sqrt(jnp.maximum(dx[:, None] * dy[None, :], 1e-12))
    return k


def _self_k(spec: KernelSpec, x: jax.Array,
            x_sq: Optional[jax.Array] = None) -> jax.Array:
    s = (jnp.sum(x * x, axis=-1) if x_sq is None else x_sq) * spec.scale
    if spec.kind == "poly":
        s = (s + spec.coef) ** spec.degree
    return s


def center_gram(k: jax.Array) -> jax.Array:
    """Center a Gram block per the paper's §6.1 formula.

    K_c = K - 1_m K / m - K 1_n / n + 1_m K 1_n / (mn), for K in R^{m x n}.
    (1_m K / m subtracts column means; K 1_n / n subtracts row means.)
    """
    col_mean = jnp.mean(k, axis=0, keepdims=True)
    row_mean = jnp.mean(k, axis=1, keepdims=True)
    tot_mean = jnp.mean(k)
    return k - col_mean - row_mean + tot_mean


def center_gram_global(k_xy: jax.Array, k_x_train: jax.Array,
                       k_train_y: jax.Array, k_train: jax.Array) -> jax.Array:
    """Center a cross block consistently with a reference ("train") set.

    K_c(x,y) = K(x,y) - mean_t K(x,t) - mean_t K(t,y) + mean_tt' K(t,t').
    Used when projecting new data onto components learned on train data.
    """
    return (k_xy
            - jnp.mean(k_x_train, axis=1, keepdims=True)
            - jnp.mean(k_train_y, axis=0, keepdims=True)
            + jnp.mean(k_train))


def psd_jitter_eigh(k: jax.Array, rel_eps: float = 1e-6):
    """Eigendecomposition of a symmetric PSD Gram matrix with eigenvalue
    flooring: lam_i <- max(lam_i, rel_eps * lam_max).

    Centering makes K_j singular (the all-ones vector is in the null space),
    while the paper's algebra uses K_j^{-1}; flooring keeps every solve
    well-posed without changing the top of the spectrum. Returns (lam, v)
    with k ~= v @ diag(lam) @ v.T, lam ascending.
    """
    lam, v = jnp.linalg.eigh(k)
    lam_max = jnp.maximum(lam[-1], 1e-30)
    lam = jnp.maximum(lam, rel_eps * lam_max)
    return lam, v


@partial(jax.jit, static_argnames=("k",))
def topk_eigh(kmat: jax.Array, k: int = 1):
    """Top-k eigenpairs of a symmetric matrix, descending."""
    lam, v = jnp.linalg.eigh(kmat)
    return lam[::-1][:k], v[:, ::-1][:, :k]
