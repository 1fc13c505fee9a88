"""Out-of-sample projection: the fitted-model artifact for serving kPCA.

The product of the whole fitting pipeline (central eigensolve, Alg.-1 ADMM
consensus, or top-k deflation) is a set of dual coefficient vectors; what a
*serving* system needs is the centered out-of-sample score (paper §1):

    score_c(x') = (w*)^T phi_c(x')
                = sum_i alpha_i [K(x_i, x') - m(x') - m_i + mu_bar]

with m(x') = mean_t K(x', t) over the training set, m_i = mean_t K(x_i, t)
and mu_bar the grand mean (the same ``kernel_mean_stats`` quantities the
decentralized fit centers with). Grouping terms, every model this module
produces — centered, uncentered, or landmark-compressed — serves through ONE
formula:

    score(x') = K(x', X_s) @ coefs + mean_l K(x', x_l) * row_mean_coef + bias

i.e. a single (B, L) kernel block against the support set X_s with a fused
row-mean + bias epilogue. ``repro.kernels.project`` implements exactly this
contract as a tiled Pallas kernel; this module is the numerical ground truth
and the artifact container.

Landmark compression (``compress``) projects each component w = Phi(X) a_eff
onto span{phi(z_l)} of L landmarks (Nystrom, in the spirit of Balcan et
al.'s communication-efficient distributed kPCA): beta = K_ZZ^+ K_ZX a_eff.
Because it is an orthogonal projection in the RKHS, the reconstruction error
||w - w_hat||_H is computable exactly at compress time (returned alongside
the model) and is monotonically non-increasing in L for nested landmark
sets, which ``landmark_schedule``'s fixed-seed prefixes guarantee.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace
from .kernels_math import HIGHEST, KernelSpec, gram, resolve_gamma

# Shape conventions used throughout this module:
#   B = query batch, M = features, L = support rows, C = components,
#   S = shards, Lp = per-shard padded support capacity.


@dataclasses.dataclass(frozen=True)
class FittedKpca:
    """Servable kPCA model: support set + dual coefficients + centering.

    x_support:     (L, M) training samples or landmarks.
    coefs:         (L, C) dual coefficients, one column per component.
    row_mean_coef: (C,) weight of mean_l K(x', x_l) in the score
                   (``-sum_i alpha_i`` for a centered fit; 0 otherwise).
    bias:          (C,) constant score offset (``mu_bar sum_i alpha_i
                   - m . alpha`` for a centered fit; 0 otherwise).
    gamma:         () resolved RBF bandwidth actually used at fit time.
    k_row_mean:    optional (L,) cached kernel mean statistics
                   m_i = mean_t K(x_i, t) over the training set — kept so
                   ``refresh_coefficients`` can rebuild the centering terms
                   for NEW coefficients without re-forming the training
                   Gram (None for uncentered or compressed models).
    k_grand_mean:  optional () cached grand mean mu_bar (same caveat).
    spec:          kernel spec (static pytree metadata).
    """

    x_support: jax.Array
    coefs: jax.Array
    row_mean_coef: jax.Array
    bias: jax.Array
    gamma: jax.Array
    k_row_mean: Optional[jax.Array] = None
    k_grand_mean: Optional[jax.Array] = None
    spec: KernelSpec = KernelSpec()

    @property
    def n_support(self) -> int:
        return self.x_support.shape[0]

    @property
    def n_features(self) -> int:
        return self.x_support.shape[1]

    @property
    def n_components(self) -> int:
        return self.coefs.shape[1]


def _flatten(m: FittedKpca):
    return ((m.x_support, m.coefs, m.row_mean_coef, m.bias, m.gamma,
             m.k_row_mean, m.k_grand_mean), m.spec)


def _unflatten(spec, leaves):
    return FittedKpca(*leaves, spec=spec)


jax.tree_util.register_pytree_node(FittedKpca, _flatten, _unflatten)


def _as_2d(alpha: jax.Array) -> jax.Array:
    alpha = jnp.asarray(alpha)
    return alpha[:, None] if alpha.ndim == 1 else alpha


# Gram elements per row block of the kernel-mean pass (2**26 float32, 256
# MiB): a support of L rows is read in blocks of KERNEL_MEAN_BLOCK_ELEMS // L
# rows, so the pass holds one (rows, L) tile and never the (L, L) Gram.
KERNEL_MEAN_BLOCK_ELEMS = 2 ** 26


def kernel_mean_block_rows(n: int) -> int:
    """Rows per block of ``kernel_means`` for a support of ``n`` rows."""
    return max(1, min(n, KERNEL_MEAN_BLOCK_ELEMS // n))


@partial(jax.jit, static_argnums=(0, 1))
def _kernel_means_blocks(spec: KernelSpec, rows: int, x: jax.Array,
                         gamma: jax.Array) -> Tuple[jax.Array, jax.Array]:
    n, m = x.shape
    blocks = -(-n // rows)
    xb = jnp.pad(x, ((0, blocks * rows - n), (0, 0))).reshape(blocks, rows, m)

    def row_sums(_, xi):
        return None, jnp.sum(gram(spec, xi, x, gamma=gamma), axis=1)

    _, sums = jax.lax.scan(row_sums, None, xb)
    k_row_mean = sums.reshape(-1)[:n] / n      # the padded rows drop here
    return k_row_mean, jnp.mean(k_row_mean)


def kernel_means(spec: KernelSpec, x: jax.Array,
                 gamma: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(m (N,), mu_bar ()): the kernel row means m_i = mean_t K(x_i, x_t)
    and their grand mean, in one program over row blocks of ``x`` (see
    ``KERNEL_MEAN_BLOCK_ELEMS``): O(rows * N) memory, never the (N, N)
    Gram."""
    n = x.shape[0]
    rows = kernel_mean_block_rows(n)
    with trace.span("oos.kernel_means", rows=n, block_rows=rows,
                    blocks=-(-n // rows)):
        return jax.block_until_ready(
            _kernel_means_blocks(spec, rows, x, gamma))


def from_dual(x_train: jax.Array, alpha: jax.Array, spec: KernelSpec,
              gamma: Optional[jax.Array] = None,
              center: bool = True) -> FittedKpca:
    """Build the serving artifact from any dual solution.

    Args:
      x_train: (N, M) training samples — become the support set.
      alpha: (N,) or (N, C) dual coefficients (central eigensolve, ADMM
        consensus, deflation — anything living in the dual space).
      spec: kernel spec used at fit time.
      gamma: () fit-time RBF bandwidth; resolved from ``spec`` (median
        heuristic on ``x_train``) when None.
      center: True => bake the centered-score terms (row_mean_coef/bias)
        from the kernel mean statistics.

    Returns:
      ``FittedKpca`` with coefs (N, C) float32.

    For ``center=True`` the kernel mean statistics the centered score
    needs are taken here (fit-time cost) by ``kernel_means``, in row
    blocks of the uncentered training Gram; serving never touches the
    training Gram again.
    """
    x_train = jnp.asarray(x_train)
    alpha = _as_2d(alpha).astype(jnp.float32)
    g = resolve_gamma(spec, x_train) if gamma is None else jnp.asarray(gamma)
    c = alpha.shape[1]
    if center:
        m, mu_bar = kernel_means(spec, x_train, g)
        alpha_sum = jnp.sum(alpha, axis=0)                # (C,)
        row_mean_coef = -alpha_sum
        bias = mu_bar * alpha_sum - jnp.matmul(m, alpha, precision=HIGHEST)
        stats = dict(k_row_mean=m, k_grand_mean=mu_bar)
    else:
        row_mean_coef = jnp.zeros((c,), jnp.float32)
        bias = jnp.zeros((c,), jnp.float32)
        stats = {}
    return FittedKpca(x_support=x_train, coefs=alpha,
                      row_mean_coef=row_mean_coef, bias=bias,
                      gamma=g.astype(jnp.float32), spec=spec, **stats)


def fit_central(x: jax.Array, spec: KernelSpec, n_components: int = 1,
                center: bool = True,
                gamma: Optional[jax.Array] = None) -> FittedKpca:
    """Fit central kPCA (paper problem (2)) and package it for serving.

    Args:
      x: (N, M) pooled training data.
      spec/gamma/center: as in ``from_dual``.
      n_components: C, number of kernel principal components to keep.

    Returns:
      ``FittedKpca`` with support (N, M) and coefs (N, C).
    """
    from .central import central_kpca
    x = jnp.asarray(x)
    g = resolve_gamma(spec, x) if gamma is None else jnp.asarray(gamma)
    alpha, _, _ = central_kpca(x, spec, n_components, center=center, gamma=g)
    return from_dual(x, alpha, spec, gamma=g, center=center)


def from_decentralized(x_nodes: jax.Array,
                       alpha: Union[jax.Array, Sequence[jax.Array]],
                       spec: KernelSpec, gamma: Optional[jax.Array] = None,
                       center: bool = True) -> FittedKpca:
    """Package an Alg.-1 consensus solution for serving.

    x_nodes: (J, N, M); alpha: (J, N) from ``run_admm`` or a list of (J, N)
    from ``run_admm_topk``. At consensus every node's w_j = phi(X_j) alpha_j
    approximates the same global component, so the pooled dual vector
    concat_j(alpha_j) / J represents their average on the pooled support
    set. ``center=True`` matches fits built with ``build_setup(...,
    center="global")`` (same global kernel-mean statistics).
    """
    x_nodes = jnp.asarray(x_nodes)
    j, n, m = x_nodes.shape
    if not isinstance(alpha, (list, tuple)):
        alpha = [alpha]
    pooled_alpha = jnp.stack(
        [jnp.reshape(a, (j * n,)) for a in alpha], axis=1) / j
    return from_dual(x_nodes.reshape(j * n, m), pooled_alpha, spec,
                     gamma=gamma, center=center)


def _pool_alpha(alpha: Union[jax.Array, Sequence[jax.Array]],
                l_full: int) -> jax.Array:
    """Normalize any live dual solution to pooled (L, C) float32.

    Accepts (L,) / (L, C) pooled coefficients, node-major (J, N[, C]) live
    solver state, or a list of per-component (J, N) solutions; node-major
    input is pooled exactly like ``from_decentralized`` (concat / J).
    """
    if isinstance(alpha, (list, tuple)):
        first = jnp.asarray(alpha[0])
        j = first.shape[0] if first.ndim == 2 else 1
        alpha = jnp.stack(
            [jnp.reshape(jnp.asarray(a), (-1,)) for a in alpha], axis=1)
    else:
        alpha = jnp.asarray(alpha)
        j = 1
        if alpha.ndim == 3 or (alpha.ndim == 2 and alpha.shape[0] != l_full):
            # node-major (J, N[, C]) live solver state
            j = alpha.shape[0]
            alpha = alpha.reshape(j * alpha.shape[1], -1)
    if alpha.shape[0] != l_full:
        raise ValueError(
            f"alpha with leading dim {alpha.shape[0]} does not match "
            f"the support set ({l_full} rows); compressed models "
            f"cannot be refreshed — refit and re-compress instead")
    return _as_2d(alpha).astype(jnp.float32) / j


def refresh_coefficients(model: Union[FittedKpca, "ShardedFittedKpca"],
                         alpha: Union[jax.Array, Sequence[jax.Array]]
                         ) -> Union[FittedKpca, "ShardedFittedKpca"]:
    """Rebuild a fitted model around NEW dual coefficients — the
    streaming-alpha path: a still-running ADMM driver hands its live
    ``AdmmState.alpha`` here every few chunks and publishes the result
    (``repro.serve.publisher.ModelHandle``) without ever re-forming the
    training Gram.

    The support set, bandwidth and kernel spec are reused as-is; the
    centering terms (row_mean_coef, bias) are recomputed from the CACHED
    kernel mean statistics (``k_row_mean``/``k_grand_mean``, recorded at
    fit time by ``from_dual(center=True)``) — an O(L*C) update instead of
    the O(L^2) Gram pass. A ``ShardedFittedKpca`` refreshes the same way
    per shard: each shard's coefficient rows are swapped against its own
    cached kernel-mean slice and the GLOBAL centering terms are rebuilt
    from the per-shard partial sums (see also
    ``refresh_shard_coefficients`` for swapping a single shard).

    Args:
      model: centered fit carrying its kernel-mean cache (or an uncentered
        fit, for which the centering terms stay zero). Compressed models
        lost the support-set/coefficient correspondence and are rejected.
      alpha: the new dual solution — (L,) / (L, C) on the pooled support
        set (sharded models: shard-concatenation order, which IS the
        pooled order for ``shard_fitted`` models), a node-major (J, N) /
        (J, N, C) live solver state, or a list of (J, N) per-component
        solutions; node-major input is pooled exactly like
        ``from_decentralized`` (concat / J).

    Returns:
      A new model of the same type (the input model is unchanged).
    """
    if isinstance(model, ShardedFittedKpca):
        return _refresh_sharded(model, alpha)
    if not isinstance(model, FittedKpca):
        raise TypeError(
            f"refresh_coefficients takes a FittedKpca or "
            f"ShardedFittedKpca, got {type(model).__name__}")
    alpha = _pool_alpha(alpha, model.n_support)
    c = alpha.shape[1]

    if model.k_row_mean is not None:
        alpha_sum = jnp.sum(alpha, axis=0)
        row_mean_coef = -alpha_sum
        bias = (model.k_grand_mean * alpha_sum
                - jnp.matmul(model.k_row_mean, alpha, precision=HIGHEST))
    else:
        if bool(np.any(np.asarray(model.row_mean_coef))) or \
                bool(np.any(np.asarray(model.bias))):
            raise ValueError(
                "model is centered but carries no kernel-mean cache "
                "(k_row_mean/k_grand_mean) — refit with "
                "from_dual(center=True) to enable refresh_coefficients")
        row_mean_coef = jnp.zeros((c,), jnp.float32)
        bias = jnp.zeros((c,), jnp.float32)
    return FittedKpca(x_support=model.x_support, coefs=alpha,
                      row_mean_coef=row_mean_coef, bias=bias,
                      gamma=model.gamma, k_row_mean=model.k_row_mean,
                      k_grand_mean=model.k_grand_mean, spec=model.spec)


def project(model: FittedKpca, x_query: jax.Array,
            use_pallas: bool = False,
            interpret: Optional[bool] = None) -> jax.Array:
    """Centered out-of-sample scores for a query batch.

    Args:
      model: fitted artifact (support set (L, M), coefs (L, C)).
      x_query: (B, M) query batch.
      use_pallas: route through the fused Pallas kernel
        (``repro.kernels.project.project_op``) instead of the dense jnp
        oracle below; both implement the same one-formula contract.
      interpret: forwarded to the Pallas wrapper (default: interpret
        everywhere except real TPU).

    Returns:
      (B, C) float32 scores
      ``K(x_query, X_s) @ coefs + rowmean(K) * row_mean_coef + bias``.
    """
    x_query = jnp.asarray(x_query)
    if use_pallas:
        from ..kernels.project import project_op
        return project_op(model.spec, x_query, model.x_support, model.coefs,
                          row_mean_coef=model.row_mean_coef, bias=model.bias,
                          gamma=model.gamma, interpret=interpret)
    k = gram(model.spec, x_query, model.x_support, gamma=model.gamma)
    return (jnp.matmul(k, model.coefs, precision=HIGHEST)
            + jnp.mean(k, axis=1, keepdims=True) * model.row_mean_coef[None]
            + model.bias[None, :])


def effective_coefs(model: FittedKpca) -> jax.Array:
    """Fold the row-mean term into the dual coefficients.

    mean_l K(x', x_l) * c == K(x', X_s) @ (c/L * 1), so
    w = Phi(X_s) @ (coefs + row_mean_coef / L). Returns the (L, C) folded
    coefficients; used by ``compress`` and per-shard compression in
    ``shard_fitted`` (the folded form has no row-mean term left to center).
    """
    return model.coefs + model.row_mean_coef[None, :] / model.n_support


def landmark_schedule(n_support: int, seed: int = 0) -> np.ndarray:
    """Fixed random permutation (length ``n_support``) of support indices;
    taking prefixes of it yields NESTED landmark sets, so compression error
    is monotone non-increasing in the landmark count for a fixed seed."""
    return np.random.default_rng(seed).permutation(n_support)


def _nystrom_project(spec: KernelSpec, gamma: jax.Array, x: jax.Array,
                     a_eff: jax.Array, idx, rel_thresh: float
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project w = Phi(x) a_eff onto span{phi(x[idx])} in the RKHS.

    Returns (z, beta, wh2): landmarks z = x[idx], landmark coefficients
    beta = K_ZZ^+ K_ZX a_eff, and wh2_c = ||w_hat_c||_H^2 (exact — used with
    ||w||_H^2 and the Pythagorean identity to get the projection error).
    """
    z = x[jnp.asarray(idx)]
    kzz = gram(spec, z, gamma=gamma)
    kzx = gram(spec, z, x, gamma=gamma)
    t = kzx @ a_eff                                      # (L, C) = Phi(Z)^T w
    lam, v = jnp.linalg.eigh(kzz)
    inv = jnp.where(lam > rel_thresh * jnp.maximum(lam[-1], 1e-30),
                    1.0 / lam, 0.0)
    beta = v @ (inv[:, None] * (v.T @ t))                # K_ZZ^+ Phi(Z)^T w
    wh2 = jnp.sum(beta * (kzz @ beta), axis=0)           # ||w_hat||_H^2
    return z, beta, wh2


def compress(model: FittedKpca, n_landmarks: int,
             seed: int = 0, rel_thresh: float = 1e-7
             ) -> Tuple[FittedKpca, jax.Array]:
    """Nystrom landmark compression of the support set.

    Projects each component w = Phi(X_s) a_eff onto span{phi(z_l)} of
    ``n_landmarks`` support points: beta = K_ZZ^+ K_ZX a_eff. Serving cost
    per query drops from O(L_full * M) to O(n_landmarks * M).

    Args:
      model: fitted artifact to compress.
      n_landmarks: landmark count in [1, model.n_support].
      seed: landmark-schedule seed; same seed => nested landmark sets.
      rel_thresh: relative eigenvalue cutoff for the K_ZZ pseudo-inverse.

    Returns:
      (compressed model, rel_err (C,)) with
      rel_err_c = ||w_c - w_hat_c||_H / ||w_c||_H, exact (computed from the
      Pythagorean identity for the RKHS projection).

    The error forms the support's whole (L, L) Gram, unlike ``from_dual``:
    compression is an offline step, not on the serving path.
    """
    l_full = model.n_support
    if not 0 < n_landmarks <= l_full:
        raise ValueError(f"n_landmarks={n_landmarks} not in [1, {l_full}]")
    idx = landmark_schedule(l_full, seed)[:n_landmarks]
    a_eff = effective_coefs(model)
    z, beta, wh2 = _nystrom_project(model.spec, model.gamma, model.x_support,
                                    a_eff, idx, rel_thresh)

    kxx = gram(model.spec, model.x_support, gamma=model.gamma)
    w2 = jnp.sum(a_eff * (kxx @ a_eff), axis=0)          # ||w||_H^2
    rel_err = jnp.sqrt(jnp.clip(w2 - wh2, 0.0) / jnp.maximum(w2, 1e-30))

    compressed = FittedKpca(
        x_support=z, coefs=beta,
        row_mean_coef=jnp.zeros_like(model.row_mean_coef),
        bias=model.bias, gamma=model.gamma, spec=model.spec)
    return compressed, rel_err


# ---- sharded artifact (multi-device serving) ------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedFittedKpca:
    """Device-sharded servable kPCA model (support-set partition).

    The projection score is a sum over support points, so it shards
    embarrassingly: shard j holds a contiguous slice of the support set and
    the matching dual-coefficient rows, computes the raw partial
    ``K(x', X_j) @ coefs_j`` plus the raw kernel row-sum (via the indicator
    column), and partials are psum-reduced across shards. The global
    centering terms — row-mean weight and bias, which depend on the FULL
    support set — are applied exactly once after the reduction
    (``finalize_partial_scores``). ``repro.serve.sharded`` is the execution
    path (shard_map over a device mesh, with a same-math single-device
    fallback).

    x_support:     (S, Lp, M) per-shard support slices, zero-padded to the
                   common per-shard capacity Lp.
    coefs_ext:     (S, Lp, C+1) per-shard coefficient rows; column C is the
                   valid-row indicator (1.0 on real rows, 0.0 on padding),
                   which makes each shard's raw kernel row-sum come out as
                   one extra column of the same matmul.
    row_mean_coef: (C,) global centering weight (zeros for models built
                   with per-shard landmark compression — the row-mean term
                   is folded into the coefficients first).
    bias:          (C,) global score offset, applied once post-reduction.
    gamma:         () fit-time RBF bandwidth, shared by all shards.
    n_support:     total TRUE support rows across shards (static; the 1/L
                   of the row-mean term).
    shard_sizes:   per-shard true row counts (static).
    k_row_mean:    optional (S, Lp) per-shard slices of the cached kernel
                   mean statistics m_i (zero on padding rows) — lets each
                   shard's coefficients refresh independently
                   (``refresh_coefficients``/``refresh_shard_coefficients``)
                   without re-forming any Gram (None for compressed or
                   uncentered models).
    k_grand_mean:  optional () cached grand mean mu_bar (same caveat).
    spec:          kernel spec (static pytree metadata).
    """

    x_support: jax.Array
    coefs_ext: jax.Array
    row_mean_coef: jax.Array
    bias: jax.Array
    gamma: jax.Array
    n_support: int
    shard_sizes: Tuple[int, ...]
    k_row_mean: Optional[jax.Array] = None
    k_grand_mean: Optional[jax.Array] = None
    spec: KernelSpec = KernelSpec()

    @property
    def n_shards(self) -> int:
        return self.x_support.shape[0]

    @property
    def shard_capacity(self) -> int:
        return self.x_support.shape[1]

    @property
    def n_features(self) -> int:
        return self.x_support.shape[2]

    @property
    def n_components(self) -> int:
        return self.coefs_ext.shape[2] - 1


def _flatten_sharded(m: ShardedFittedKpca):
    return ((m.x_support, m.coefs_ext, m.row_mean_coef, m.bias, m.gamma,
             m.k_row_mean, m.k_grand_mean),
            (m.n_support, m.shard_sizes, m.spec))


def _unflatten_sharded(aux, leaves):
    n_support, shard_sizes, spec = aux
    return ShardedFittedKpca(*leaves[:5], n_support=n_support,
                             shard_sizes=shard_sizes, k_row_mean=leaves[5],
                             k_grand_mean=leaves[6], spec=spec)


jax.tree_util.register_pytree_node(ShardedFittedKpca, _flatten_sharded,
                                   _unflatten_sharded)


def finalize_partial_scores(partials: jax.Array, row_mean_coef: jax.Array,
                            bias: jax.Array, n_support: int) -> jax.Array:
    """Global centering epilogue for reduced per-shard partials.

    Args:
      partials: (B, C+1) SUM over shards of ``K(x', X_j) @ coefs_ext_j`` —
        columns :C raw scores, column C the raw kernel row-sum over all
        true support rows.
      row_mean_coef: (C,) global centering weight.
      bias: (C,) global score offset.
      n_support: total true support rows (turns the row-sum into the mean).

    Returns:
      (B, C) final scores — identical to ``project`` on the gathered model.
    """
    c = partials.shape[-1] - 1
    kmean = partials[:, c] / n_support
    return (partials[:, :c] + kmean[:, None] * row_mean_coef[None, :]
            + bias[None, :])


def shard_fitted(model: FittedKpca, n_shards: int,
                 landmarks_per_shard: Optional[int] = None, seed: int = 0,
                 rel_thresh: float = 1e-7
                 ) -> Tuple[ShardedFittedKpca, jax.Array]:
    """Partition a ``FittedKpca`` across ``n_shards`` for sharded serving.

    The support set (and the matching dual-coefficient rows) is split into
    contiguous row slices; uneven L is handled by zero-padding every shard
    to the largest slice, with the indicator column zeroed on padding rows
    so padded rows contribute nothing to scores or row-sums.

    With ``landmarks_per_shard`` set, each shard's slice of the EFFECTIVE
    coefficients (row-mean term folded in — see ``effective_coefs``) is
    Nystrom-compressed onto min(landmarks_per_shard, shard size) landmarks
    chosen by a per-shard fixed-seed schedule (nested across landmark
    counts), in the spirit of the per-node subsampling of
    communication-efficient distributed kPCA (Balcan et al.) / COKE.

    Args:
      model: fitted artifact to shard.
      n_shards: shard count S in [1, model.n_support].
      landmarks_per_shard: per-shard landmark budget; None = no compression.
      seed: base seed for the per-shard landmark schedules.
      rel_thresh: pseudo-inverse cutoff (see ``compress``).

    Returns:
      (sharded model, rel_err_bound (C,)). The bound is the aggregate
      relative RKHS error sum_j ||w_j - w_hat_j||_H / ||w||_H — each
      per-shard term is exact (Pythagorean identity) and the sum bounds the
      error of the summed component by the triangle inequality. Zeros when
      no compression is requested (sharding alone is exact).

    With ``landmarks_per_shard`` set, the error bound forms the support's
    whole (L, L) Gram, unlike ``from_dual``: an offline step, not on the
    serving path.
    """
    l_full, c = model.n_support, model.n_components
    if not 0 < n_shards <= l_full:
        raise ValueError(f"n_shards={n_shards} not in [1, {l_full}]")
    splits = np.array_split(np.arange(l_full), n_shards)

    if landmarks_per_shard is None:
        parts = [(np.asarray(model.x_support[jnp.asarray(ix)]),
                  np.asarray(model.coefs[jnp.asarray(ix)])) for ix in splits]
        row_mean_coef, bias = model.row_mean_coef, model.bias
        rel_err = jnp.zeros((c,), jnp.float32)
    else:
        if landmarks_per_shard < 1:
            raise ValueError(f"landmarks_per_shard={landmarks_per_shard} < 1")
        a_eff = effective_coefs(model)
        kxx = gram(model.spec, model.x_support, gamma=model.gamma)
        w2 = jnp.sum(a_eff * (kxx @ a_eff), axis=0)      # ||w||_H^2, global
        parts, err_abs = [], jnp.zeros((c,), jnp.float32)
        for j, ix in enumerate(splits):
            xj = model.x_support[jnp.asarray(ix)]
            aj = a_eff[jnp.asarray(ix)]
            order = landmark_schedule(len(ix), seed=seed + 7919 * j)
            z, beta, wh2 = _nystrom_project(
                model.spec, model.gamma, xj, aj,
                order[:min(landmarks_per_shard, len(ix))], rel_thresh)
            kjj = kxx[jnp.asarray(ix)][:, jnp.asarray(ix)]
            wj2 = jnp.sum(aj * (kjj @ aj), axis=0)       # ||w_j||_H^2
            err_abs = err_abs + jnp.sqrt(jnp.clip(wj2 - wh2, 0.0))
            parts.append((np.asarray(z), np.asarray(beta)))
        # The row-mean term was folded into a_eff, so it (and the per-query
        # row-sum it needs) vanishes from the compressed model.
        row_mean_coef = jnp.zeros_like(model.row_mean_coef)
        bias = model.bias
        rel_err = err_abs / jnp.sqrt(jnp.maximum(w2, 1e-30))

    sizes = tuple(int(x.shape[0]) for x, _ in parts)
    lp, m = max(sizes), model.n_features
    xs = np.zeros((n_shards, lp, m), np.float32)
    ae = np.zeros((n_shards, lp, c + 1), np.float32)
    for j, (xj, aj) in enumerate(parts):
        xs[j, :sizes[j]] = xj
        ae[j, :sizes[j], :c] = aj
        ae[j, :sizes[j], c] = 1.0                        # indicator column
    # Carry the kernel-mean cache per shard (zero on padding rows) so each
    # shard's coefficients can refresh independently; compression breaks
    # the support/coefficient correspondence, so the cache is dropped.
    stats = {}
    if landmarks_per_shard is None and model.k_row_mean is not None:
        kr = np.zeros((n_shards, lp), np.float32)
        m_full = np.asarray(model.k_row_mean, np.float32)
        for j, ix in enumerate(splits):
            kr[j, :sizes[j]] = m_full[ix]
        stats = dict(k_row_mean=jnp.asarray(kr),
                     k_grand_mean=model.k_grand_mean)
    return ShardedFittedKpca(
        x_support=jnp.asarray(xs), coefs_ext=jnp.asarray(ae),
        row_mean_coef=jnp.asarray(row_mean_coef, jnp.float32),
        bias=jnp.asarray(bias, jnp.float32), gamma=model.gamma,
        n_support=int(sum(sizes)), shard_sizes=sizes,
        spec=model.spec, **stats), rel_err


def _sharded_centering(model: ShardedFittedKpca, coefs_pad: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Global (row_mean_coef, bias) for new per-shard padded (S, Lp, C)
    coefficients, from the per-shard cached kernel-mean slices. Padding
    rows are zero in both the coefficients and the cache, so plain sums
    over (S, Lp) ARE the true-row sums."""
    alpha_sum = jnp.sum(coefs_pad, axis=(0, 1))          # (C,)
    m_dot = jnp.einsum("sl,slc->c", model.k_row_mean, coefs_pad,
                       precision=HIGHEST)
    return -alpha_sum, model.k_grand_mean * alpha_sum - m_dot


def _require_sharded_cache(model: ShardedFittedKpca, c: int
                           ) -> Tuple[jax.Array, jax.Array, bool]:
    """(row_mean_coef, bias, centered) guard shared by the sharded refresh
    paths: with no cache, only an UNCENTERED model (all-zero centering
    terms) may refresh — its terms stay zero."""
    if model.k_row_mean is not None:
        return None, None, True
    if bool(np.any(np.asarray(model.row_mean_coef))) or \
            bool(np.any(np.asarray(model.bias))):
        raise ValueError(
            "sharded model carries centering terms but no per-shard "
            "kernel-mean cache (k_row_mean/k_grand_mean) — re-shard an "
            "uncompressed centered fit to enable coefficient refresh")
    return (jnp.zeros((c,), jnp.float32), jnp.zeros((c,), jnp.float32),
            False)


def _refresh_sharded(model: ShardedFittedKpca,
                     alpha: Union[jax.Array, Sequence[jax.Array]]
                     ) -> ShardedFittedKpca:
    """All-shard coefficient swap (see ``refresh_coefficients``)."""
    alpha = _pool_alpha(alpha, model.n_support)          # (L, C)
    c = alpha.shape[1]
    lp = model.shard_capacity
    rows, off = [], 0
    for n in model.shard_sizes:
        rows.append(jnp.pad(alpha[off:off + n], ((0, lp - n), (0, 0))))
        off += n
    coefs_pad = jnp.stack(rows)                          # (S, Lp, C)
    row_mean_coef, bias, centered = _require_sharded_cache(model, c)
    if centered:
        row_mean_coef, bias = _sharded_centering(model, coefs_pad)
    coefs_ext = jnp.concatenate(
        [coefs_pad, model.coefs_ext[..., -1:]], axis=-1)
    return dataclasses.replace(model, coefs_ext=coefs_ext,
                               row_mean_coef=row_mean_coef, bias=bias)


def refresh_shard_coefficients(model: ShardedFittedKpca, shard: int,
                               alpha: jax.Array) -> ShardedFittedKpca:
    """Swap ONE shard's dual-coefficient rows; all other shards keep
    theirs. The global centering terms are rebuilt from the per-shard
    cached kernel-mean slices — an O(S*Lp*C) update with no Gram contact —
    so the result is exactly ``refresh_coefficients`` with the other
    shards' current coefficients left in place. The returned model is a
    complete new artifact: publishing it through a ``ModelHandle`` is one
    atomic swap, so no request can observe a mix of shard versions.

    Args:
      model: uncompressed sharded artifact carrying its per-shard cache
        (or an uncentered one, whose centering terms stay zero).
      shard: shard index in [0, model.n_shards).
      alpha: (n_j,) or (n_j, C) new coefficients for that shard's TRUE
        rows, n_j = model.shard_sizes[shard]; C must match the model (the
        other shards' column count is fixed).

    Returns:
      A new ``ShardedFittedKpca`` (the input model is unchanged).
    """
    if not isinstance(model, ShardedFittedKpca):
        raise TypeError(f"refresh_shard_coefficients takes a "
                        f"ShardedFittedKpca, got {type(model).__name__}")
    if not 0 <= shard < model.n_shards:
        raise ValueError(
            f"shard {shard} not in [0, {model.n_shards})")
    n_j, c = model.shard_sizes[shard], model.n_components
    alpha = _as_2d(jnp.asarray(alpha)).astype(jnp.float32)
    if alpha.shape != (n_j, c):
        raise ValueError(
            f"shard {shard} takes ({n_j}, {c}) coefficients, "
            f"got {alpha.shape}")
    rows = jnp.pad(alpha, ((0, model.shard_capacity - n_j), (0, 0)))
    coefs_ext = model.coefs_ext.at[shard, :, :c].set(rows)
    row_mean_coef, bias, centered = _require_sharded_cache(model, c)
    if centered:
        row_mean_coef, bias = _sharded_centering(model, coefs_ext[..., :c])
    return dataclasses.replace(model, coefs_ext=coefs_ext,
                               row_mean_coef=row_mean_coef, bias=bias)


def drop_shard(model: ShardedFittedKpca, shard: int) -> ShardedFittedKpca:
    """Shard-loss re-balance: serve on without the lost shard's rows.

    Keeps the shard axis at S — a ``ModelHandle`` pins ``n_shards`` (and
    the engine's mesh matches it), so recovery must not re-shard; instead
    the lost shard becomes an empty participant: its support rows,
    coefficient rows AND indicator column are zeroed, so its psum
    contribution is exactly zero (``K @ 0``), and ``shard_sizes[shard]``
    drops to 0. The global centering epilogue is rebuilt for the
    SURVIVOR support set — ``n_support`` shrinks and, when the model
    carries its per-shard kernel-mean cache, (row_mean_coef, bias) are
    recomputed from the surviving shards' cached sums
    (``_sharded_centering`` with the lost shard's slices zeroed). The
    result equals ``shard_fitted`` of a fresh fit on the survivor
    support set up to the zero padding — pinned by
    tests/test_fault_injection.py against ``gather_fitted`` + central
    ``project``.

    Models without the cache (landmark-compressed, or uncentered) keep
    their existing centering constants: for uncentered fits they are
    zero anyway; for compressed fits the folded row-mean/bias terms are
    per-row and the lost rows are simply gone — a documented
    approximation (docs/FAULT_TOLERANCE.md), not an error, because
    recovery must not refuse to serve.

    Idempotent: dropping an already-empty shard returns the model
    unchanged, which is what makes the re-balance publish exactly-once
    under concurrent retries (``repro.faults.serving.ShardRebalancer``).
    """
    if not isinstance(model, ShardedFittedKpca):
        raise TypeError(
            f"drop_shard takes a ShardedFittedKpca, got "
            f"{type(model).__name__}")
    if not 0 <= shard < model.n_shards:
        raise ValueError(f"shard {shard} not in [0, {model.n_shards})")
    if model.shard_sizes[shard] == 0:
        return model
    sizes = tuple(0 if j == shard else n
                  for j, n in enumerate(model.shard_sizes))
    n_support = int(sum(sizes))
    if n_support == 0:
        raise ValueError("cannot drop the last non-empty shard")
    c = model.n_components
    x_support = model.x_support.at[shard].set(0.0)
    coefs_ext = model.coefs_ext.at[shard].set(0.0)
    k_row_mean = model.k_row_mean
    row_mean_coef, bias = model.row_mean_coef, model.bias
    if k_row_mean is not None:
        k_row_mean = k_row_mean.at[shard].set(0.0)
        survivor = dataclasses.replace(model, k_row_mean=k_row_mean)
        row_mean_coef, bias = _sharded_centering(survivor,
                                                 coefs_ext[..., :c])
    return dataclasses.replace(
        model, x_support=x_support, coefs_ext=coefs_ext,
        row_mean_coef=row_mean_coef, bias=bias, n_support=n_support,
        shard_sizes=sizes, k_row_mean=k_row_mean)


def gather_fitted(sharded: ShardedFittedKpca) -> FittedKpca:
    """Reassemble a single-device ``FittedKpca`` from a sharded model.

    Drops per-shard padding rows and concatenates the true support slices
    and coefficient rows; the gathered model's ``project`` output is
    bit-identical in exact arithmetic to the psum-reduced sharded scores
    (tested to fp32 tolerance in tests/test_sharded_serving.py).
    """
    xs = jnp.concatenate(
        [sharded.x_support[j, :n]
         for j, n in enumerate(sharded.shard_sizes)], axis=0)
    coefs = jnp.concatenate(
        [sharded.coefs_ext[j, :n, :-1]
         for j, n in enumerate(sharded.shard_sizes)], axis=0)
    stats = {}
    if sharded.k_row_mean is not None:
        stats = dict(
            k_row_mean=jnp.concatenate(
                [sharded.k_row_mean[j, :n]
                 for j, n in enumerate(sharded.shard_sizes)]),
            k_grand_mean=sharded.k_grand_mean)
    return FittedKpca(x_support=xs, coefs=coefs,
                      row_mean_coef=sharded.row_mean_coef, bias=sharded.bias,
                      gamma=sharded.gamma, spec=sharded.spec, **stats)


# ---- persistence (repro.checkpoint layout) --------------------------------

def save_fitted(ckpt_dir: str, model: FittedKpca) -> str:
    """Write the artifact with the atomic checkpoint writer (step 0).

    Layout: one ``step_00000000`` directory under ``ckpt_dir`` with a
    manifest (shapes/dtypes + ``kind``/``spec`` metadata) and one .npy per
    field — see ``repro.checkpoint``. Returns the checkpoint path.
    """
    from ..checkpoint import save_checkpoint
    tree = {"x_support": model.x_support, "coefs": model.coefs,
            "row_mean_coef": model.row_mean_coef, "bias": model.bias,
            "gamma": model.gamma}
    if model.k_row_mean is not None:
        tree["k_row_mean"] = model.k_row_mean
        tree["k_grand_mean"] = model.k_grand_mean
    meta = {"kind": "fitted_kpca", "spec": dataclasses.asdict(model.spec)}
    return save_checkpoint(ckpt_dir, 0, tree, metadata=meta, keep_last=1)


def load_fitted(ckpt_dir: str) -> FittedKpca:
    """Restore a ``save_fitted`` checkpoint; validates the artifact kind."""
    from ..checkpoint import restore_checkpoint
    tree, meta, _ = restore_checkpoint(ckpt_dir)
    if meta.get("kind") != "fitted_kpca":
        raise ValueError(f"{ckpt_dir} is not a FittedKpca checkpoint: {meta}")
    spec = KernelSpec(**meta["spec"])
    return FittedKpca(x_support=tree["x_support"], coefs=tree["coefs"],
                      row_mean_coef=tree["row_mean_coef"],
                      bias=tree["bias"], gamma=tree["gamma"],
                      k_row_mean=tree.get("k_row_mean"),
                      k_grand_mean=tree.get("k_grand_mean"), spec=spec)


def save_sharded(ckpt_dir: str, model: ShardedFittedKpca) -> str:
    """Write a sharded artifact (same atomic layout as ``save_fitted``;
    static partition metadata rides in the manifest). Returns the path."""
    from ..checkpoint import save_checkpoint
    tree = {"x_support": model.x_support, "coefs_ext": model.coefs_ext,
            "row_mean_coef": model.row_mean_coef, "bias": model.bias,
            "gamma": model.gamma}
    if model.k_row_mean is not None:
        tree["k_row_mean"] = model.k_row_mean
        tree["k_grand_mean"] = model.k_grand_mean
    meta = {"kind": "sharded_fitted_kpca",
            "spec": dataclasses.asdict(model.spec),
            "n_support": model.n_support,
            "shard_sizes": list(model.shard_sizes)}
    return save_checkpoint(ckpt_dir, 0, tree, metadata=meta, keep_last=1)


def load_sharded(ckpt_dir: str) -> ShardedFittedKpca:
    """Restore a ``save_sharded`` checkpoint; validates the artifact kind.

    The restored model is mesh-independent (full logical arrays); re-placing
    it on a device mesh is the serving path's job (``repro.serve.sharded``).
    """
    from ..checkpoint import restore_checkpoint
    tree, meta, _ = restore_checkpoint(ckpt_dir)
    if meta.get("kind") != "sharded_fitted_kpca":
        raise ValueError(
            f"{ckpt_dir} is not a ShardedFittedKpca checkpoint: {meta}")
    return ShardedFittedKpca(
        x_support=tree["x_support"], coefs_ext=tree["coefs_ext"],
        row_mean_coef=tree["row_mean_coef"], bias=tree["bias"],
        gamma=tree["gamma"], n_support=int(meta["n_support"]),
        shard_sizes=tuple(int(s) for s in meta["shard_sizes"]),
        k_row_mean=tree.get("k_row_mean"),
        k_grand_mean=tree.get("k_grand_mean"),
        spec=KernelSpec(**meta["spec"]))


__all__ = [
    "FittedKpca", "ShardedFittedKpca", "compress", "drop_shard",
    "effective_coefs", "finalize_partial_scores", "fit_central", "from_dual",
    "from_decentralized", "gather_fitted", "landmark_schedule", "load_fitted",
    "load_sharded", "project", "refresh_coefficients",
    "refresh_shard_coefficients", "save_fitted", "save_sharded",
    "shard_fitted",
]
