"""Jitted public wrappers around the projection Pallas kernel.

Two entry points share one Pallas kernel (``project_tiles``):

  * ``project_op`` — the single-device serving path: fused scores with the
    centering epilogue applied inside the kernel.
  * ``project_partial_op`` — the sharded serving path: raw per-shard partial
    scores plus the raw kernel row-sum, with NO epilogue; callers ``psum``
    partials across shards and apply the global centering terms exactly once
    after the reduction (see ``repro.serve.sharded``).

Both handle padding to block multiples (features zero-pad exactly; padded
support rows carry zero coefficients AND a zero entry in the fused ones-
column, so they contribute nothing to scores or row-means; padded query
rows are sliced off), sq-norm/self-kernel precomputation, component-axis
padding to the 128-lane boundary, gamma resolution and backend dispatch
(interpret=True everywhere except real TPU)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.kernels_math import KernelSpec, resolve_gamma, _self_k
from ..autotune import get_tiles
from .._util import _on_tpu, _pad_to, _round_up
from .project import project_tiles


def _resolve_tiles(op: str, x_query: jax.Array, x_support: jax.Array,
                   block_q: Optional[int], block_l: Optional[int],
                   block_m: Optional[int]) -> Tuple[int, int, int]:
    """Fill unspecified tile sizes from the autotune table (fallback: the
    historical 128x128x512); explicit kwargs always win."""
    if block_q is None or block_l is None or block_m is None:
        tiles = get_tiles(op, (x_query.shape[0], x_support.shape[0],
                               x_query.shape[1]), x_query.dtype)
        block_q = block_q or tiles["block_q"]
        block_l = block_l or tiles["block_l"]
        block_m = block_m or tiles["block_m"]
    return block_q, block_l, block_m


def _prepare_operands(spec: KernelSpec, x_query: jax.Array,
                      x_support: jax.Array, gamma: Optional[jax.Array],
                      block_q: int, block_l: int, block_m: int
                      ) -> Tuple[jax.Array, ...]:
    """Shared preamble: gamma resolution, sq-norm/self-kernel precompute,
    block-size adaptation for small problems, and query/support padding.

    Returns (xq_pad, xs_pad, sq_pad, ss_pad, gamma, bq, bl, bm).
    """
    b_n, m = x_query.shape
    l = x_support.shape[0]
    if spec.kind == "rbf":
        g = resolve_gamma(spec, x_support) if gamma is None \
            else jnp.asarray(gamma)
        sq = jnp.sum(x_query.astype(jnp.float32) ** 2, axis=-1)
        ss = jnp.sum(x_support.astype(jnp.float32) ** 2, axis=-1)
    else:
        g = jnp.zeros((), jnp.float32)
        sq = _self_k(spec, x_query.astype(jnp.float32))
        ss = _self_k(spec, x_support.astype(jnp.float32))

    # adapt block sizes for small problems (interpret/test shapes)
    bq = min(block_q, _round_up(b_n, 8))
    bl = min(block_l, _round_up(l, 8))
    bm = min(block_m, _round_up(m, 128))

    xq = _pad_to(_pad_to(x_query, bm, 1), bq, 0)
    xs = _pad_to(_pad_to(x_support, bm, 1), bl, 0)
    sqp = _pad_to(sq, bq, 0)[:, None]
    ssp = _pad_to(ss, bl, 0)[None, :]
    return xq, xs, sqp, ssp, g, bq, bl, bm


def project_op(spec: KernelSpec, x_query: jax.Array, x_support: jax.Array,
               coefs: jax.Array,
               row_mean_coef: Optional[jax.Array] = None,
               bias: Optional[jax.Array] = None,
               gamma: Optional[jax.Array] = None,
               block_q: Optional[int] = None, block_l: Optional[int] = None,
               block_m: Optional[int] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """scores = K(x_query, x_support) @ coefs + rowmean(K) * c + b, fused.

    Args:
      spec: kernel specification (kind/gamma/degree/... — static metadata).
      x_query: (B, M) query batch.
      x_support: (L, M) support set (training samples or landmarks).
      coefs: (L, C) dual coefficients, one column per component.
      row_mean_coef: (C,) weight of mean_l K(x', x_l) in the score; default
        zeros (raw uncentered projection).
      bias: (C,) constant score offset; default zeros.
      gamma: () RBF bandwidth; resolved from ``spec``/median heuristic on
        ``x_support`` when None.
      block_q/block_l/block_m: Pallas tile sizes over the query/support/
        feature axes (auto-shrunk for small problems).
      interpret: force Pallas interpret mode; default: interpret everywhere
        except real TPU.

    Returns:
      (B, C) float32 scores. Matches
      ``repro.kernels.project.ref.project_reference`` (tested across shapes
      in tests/test_oos_projection.py).

    The row-mean needed for the centering term rides along as one extra
    all-ones column of the coefficient matrix (the "ones-column trick", see
    ``repro.kernels.project.project``), so the (B, L) kernel block is formed
    once and never materialized in HBM.
    """
    if interpret is None:
        interpret = not _on_tpu()
    block_q, block_l, block_m = _resolve_tiles(
        "project", x_query, x_support, block_q, block_l, block_m)
    b_n, m = x_query.shape
    l, c = coefs.shape
    assert x_support.shape == (l, m), (x_query.shape, x_support.shape,
                                       coefs.shape)
    if row_mean_coef is None:
        row_mean_coef = jnp.zeros((c,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((c,), jnp.float32)

    xq, xs, sqp, ssp, g, bq, bl, bm = _prepare_operands(
        spec, x_query, x_support, gamma, block_q, block_l, block_m)
    cp = _round_up(c + 1, 128)

    # A extended with the row-sum ones-column at index c (zero on padded
    # support rows), then padded to (L_pad, CP).
    ones = jnp.ones((l, 1), jnp.float32)
    a_ext = jnp.concatenate([coefs.astype(jnp.float32), ones], axis=1)
    a_ext = _pad_to(_pad_to(a_ext, cp, 1), bl, 0)
    c_ext = _pad_to(row_mean_coef.astype(jnp.float32), cp, 0)[None, :]
    b_ext = _pad_to(bias.astype(jnp.float32), cp, 0)[None, :]

    out = project_tiles(
        xq, xs, a_ext, sqp, ssp,
        jnp.reshape(g, (1,)).astype(jnp.float32),
        jnp.full((1,), 1.0 / l, jnp.float32), c_ext, b_ext,
        kind=spec.kind, degree=spec.degree, coef=spec.coef, scale=spec.scale,
        normalize=spec.normalize, block_q=bq, block_l=bl, block_m=bm,
        sum_col=c, interpret=interpret)
    return out[:b_n, :c]


def project_partial_op(spec: KernelSpec, x_query: jax.Array,
                       x_support: jax.Array, coefs_ext: jax.Array,
                       gamma: Optional[jax.Array] = None,
                       block_q: Optional[int] = None,
                       block_l: Optional[int] = None,
                       block_m: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Per-shard partial scores: K(x_query, x_support) @ coefs_ext, raw.

    Args:
      spec: kernel specification (static metadata).
      x_query: (B, M) query batch (replicated across shards).
      x_support: (L_j, M) THIS shard's slice of the support set (possibly
        zero-padded to a common per-shard length).
      coefs_ext: (L_j, C+1) this shard's dual-coefficient rows with one
        extra indicator column at index C: 1.0 on valid support rows, 0.0 on
        shard-padding rows. The indicator column makes the output's last
        column the raw kernel row-sum over exactly the valid rows.
      gamma: () RBF bandwidth; must be the fit-time value for sharded
        serving (per-shard median heuristics would disagree across shards).
      block_q/block_l/block_m, interpret: as in ``project_op``.

    Returns:
      (B, C+1) float32: columns :C are the partial scores
      sum_{l in shard} K(x_q, x_l) coefs[l, c]; column C is the partial raw
      row-sum sum_{l in shard} K(x_q, x_l). NO centering epilogue is applied
      — the global row-mean/bias terms depend on the FULL support set, so
      callers ``psum`` the (B, C+1) partials over the shard axis and apply
      them exactly once after the reduction (``repro.core.oos
      .finalize_partial_scores``).
    """
    if interpret is None:
        interpret = not _on_tpu()
    block_q, block_l, block_m = _resolve_tiles(
        "project_partial", x_query, x_support, block_q, block_l, block_m)
    b_n, m = x_query.shape
    l, cp1 = coefs_ext.shape
    assert x_support.shape == (l, m), (x_query.shape, x_support.shape,
                                       coefs_ext.shape)

    xq, xs, sqp, ssp, g, bq, bl, bm = _prepare_operands(
        spec, x_query, x_support, gamma, block_q, block_l, block_m)
    cp = _round_up(cp1, 128)
    a_ext = _pad_to(_pad_to(coefs_ext.astype(jnp.float32), cp, 1), bl, 0)
    zeros = jnp.zeros((1, cp), jnp.float32)

    # row_mean_coef/bias are all-zero, so the kernel's in-tile centering
    # epilogue is the identity and every output column comes out raw.
    out = project_tiles(
        xq, xs, a_ext, sqp, ssp,
        jnp.reshape(g, (1,)).astype(jnp.float32),
        jnp.ones((1,), jnp.float32), zeros, zeros,
        kind=spec.kind, degree=spec.degree, coef=spec.coef, scale=spec.scale,
        normalize=spec.normalize, block_q=bq, block_l=bl, block_m=bm,
        sum_col=cp1 - 1, interpret=interpret)
    return out[:b_n, :cp1]
