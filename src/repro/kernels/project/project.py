"""Pallas TPU kernel: fused out-of-sample kPCA projection (serving hot path).

Computes scores = K(X_query, X_support) @ A with the centering epilogue
fused in (see ``repro.core.oos``):

    scores[q, c] = sum_l K(x_q, x_l) A[l, c]
                   + (1/L) sum_l K(x_q, x_l) * row_mean_coef[c] + bias[c]

The (B, L) kernel block is never materialized in HBM: the grid walks
(B/bq, L/bl, M/bm); a VMEM scratch accumulates the query x support dot
products over the feature axis, the kernel epilogue (exp for RBF) runs once
per (q, l) tile, and each tile's contribution K_tile @ A_tile is accumulated
straight into the (bq, C) output block. The row-sum needed for the centering
term rides along as one extra column of A (an all-ones column over the VALID
support rows — this also makes zero-padding of the support axis exact), so
no second pass or extra scratch is needed.

Grid: (B/bq, L/bl, M/bm), dimension_semantics = (parallel, arbitrary,
arbitrary) — the output block for a fixed q is revisited across the l/m
axes. Defaults 128x128x512 match the gram kernel's MXU-aligned tiling.

Operand layout, as in the gram kernel: query norms are a (B, 1) column,
support norms a (1, L) row, the centering vectors (1, CP) rows and the
scalars (gamma, 1/L) live in SMEM; ``bl`` is a lane dimension (a multiple
of 128 or the whole padded support axis). The ones-column is read back
with a lane mask rather than a one-lane slice. Both dots run at
``Precision.HIGHEST`` (the distance expansion cancels; see the gram
kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _project_kernel(sq_ref, ss_ref, gamma_ref, invl_ref, c_ref, b_ref,
                    xq_ref, xs_ref, a_ref, o_ref, acc_ref, *,
                    kind: str, degree: int, coef: float, scale: float,
                    normalize: bool, n_l_blocks: int, n_m_blocks: int,
                    sum_col: int):
    lb = pl.program_id(1)
    mb = pl.program_id(2)

    @pl.when((lb == 0) & (mb == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(mb == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xq = xq_ref[...].astype(jnp.float32)            # (bq, bm)
    xs = xs_ref[...].astype(jnp.float32)            # (bl, bm)
    acc_ref[...] += jax.lax.dot_general(
        xq, xs, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)         # (bq, bl)

    @pl.when(mb == n_m_blocks - 1)
    def _kernel_epilogue_and_matvec():
        acc = acc_ref[...]
        sq = sq_ref[...].astype(jnp.float32)        # (bq, 1)
        ss = ss_ref[...].astype(jnp.float32)        # (1, bl)
        if kind == "rbf":
            d2 = jnp.maximum(sq + ss - 2.0 * acc, 0.0)
            k = jnp.exp(-gamma_ref[0] * d2)
        else:
            k = acc * scale
            if kind == "poly":
                k = (k + coef) ** degree
            if normalize:
                # sq/ss hold the *self-kernel* values for linear/poly.
                denom = jnp.maximum(sq * ss, 1e-12)
                k = k * jax.lax.rsqrt(denom)
        o_ref[...] += jnp.dot(k, a_ref[...].astype(jnp.float32),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when((lb == n_l_blocks - 1) & (mb == n_m_blocks - 1))
    def _centering_epilogue():
        scores = o_ref[...]                         # (bq, cp)
        # column ``sum_col`` of A was all-ones over valid support rows, so
        # it accumulated the row-sums of K; turn them into the centering
        # term. c/b are zero there, so the column itself stays harmless
        # (the wrapper slices it off).
        lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        ksum = jnp.sum(jnp.where(lane == sum_col, scores, 0.0), axis=1,
                       keepdims=True)               # (bq, 1)
        kmean = ksum * invl_ref[0]
        o_ref[...] = scores + kmean * c_ref[...] + b_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("kind", "degree", "coef", "scale", "normalize",
                     "block_q", "block_l", "block_m", "sum_col", "interpret"))
def project_tiles(xq: jax.Array, xs: jax.Array, a_ext: jax.Array,
                  sq: jax.Array, ss: jax.Array, gamma: jax.Array,
                  inv_l: jax.Array, c_ext: jax.Array, b_ext: jax.Array, *,
                  kind: str = "rbf", degree: int = 3, coef: float = 1.0,
                  scale: float = 1.0, normalize: bool = True,
                  block_q: int = 128, block_l: int = 128, block_m: int = 512,
                  sum_col: int = 0, interpret: bool = False) -> jax.Array:
    """Fused projection over pre-padded operands.

    xq (B, M) queries; xs (L, M) support; a_ext (L, CP) coefficients with
    the ones-column at ``sum_col``; sq (B, 1), ss (1, L) sq-norms (RBF) or
    self-kernels; gamma (1,); inv_l (1,) = 1/L_true; c_ext, b_ext (1, CP).
    Returns (B, CP) float32 scores.
    """
    bq_n, m = xq.shape
    l, cp = a_ext.shape
    assert bq_n % block_q == 0 and l % block_l == 0 and m % block_m == 0, \
        (xq.shape, xs.shape, (block_q, block_l, block_m))
    assert cp % 128 == 0, cp
    n_l_blocks = l // block_l
    n_m_blocks = m // block_m
    grid = (bq_n // block_q, n_l_blocks, n_m_blocks)

    kernel = functools.partial(
        _project_kernel, kind=kind, degree=degree, coef=coef, scale=scale,
        normalize=normalize, n_l_blocks=n_l_blocks, n_m_blocks=n_m_blocks,
        sum_col=sum_col)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j, b: (i, 0)),      # sq
            pl.BlockSpec((1, block_l), lambda i, j, b: (0, j)),      # ss
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # gamma
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # inv_l
            pl.BlockSpec((1, cp), lambda i, j, b: (0, 0)),           # c_ext
            pl.BlockSpec((1, cp), lambda i, j, b: (0, 0)),           # b_ext
            pl.BlockSpec((block_q, block_m), lambda i, j, b: (i, b)),
            pl.BlockSpec((block_l, block_m), lambda i, j, b: (j, b)),
            pl.BlockSpec((block_l, cp), lambda i, j, b: (j, 0)),     # a_ext
        ],
        out_specs=pl.BlockSpec((block_q, cp), lambda i, j, b: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bq_n, cp), jnp.float32),
        scratch_shapes=[
            # persists across the sequential l/m axes for a fixed q block
            pltpu.VMEM((block_q, block_l), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(sq, ss, gamma, inv_l, c_ext, b_ext, xq, xs, a_ext)
