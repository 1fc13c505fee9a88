"""Pallas TPU kernel: blocked Gram (kernel) matrix computation.

The paper's setup phase computes K(X_p, X_q) for all neighbor pairs — a
matmul-shaped hotspot: ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y, with an exp
epilogue for RBF. On TPU we tile the (n, k) output into MXU-aligned VMEM
blocks, loop the contraction (feature) dimension as the innermost grid axis
accumulating into the output block, and fuse the distance/exp epilogue into
the final contraction step — one HBM write per output tile, no materialized
distance matrix.

Grid: (n/bn, k/bk, m/bm), dimension_semantics = (parallel, parallel,
arbitrary). Block shapes default to 128x128x512 (MXU lane/sublane aligned,
~0.5 MB per operand tile in fp32 — three tiles + output fit well within the
~16 MB VMEM budget with double buffering).

Operand layout (Mosaic tiles VMEM as (8, 128) float32 blocks and refuses
1-D blocks): the per-row norms arrive as a (n, 1) column and the per-column
norms as a (1, k) row, so they broadcast against the (bn, bk) tile without
a relayout; ``gamma`` is a scalar in SMEM. ``bk`` is therefore a lane
dimension: a multiple of 128 or the whole padded axis. The contraction runs
at ``Precision.HIGHEST``: the RBF epilogue forms ||x||^2 + ||y||^2 - 2 x.y,
which cancels, and a single bf16 pass would lose most of the distance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(sx_ref, sy_ref, gamma_ref, x_ref, y_ref, o_ref, *,
                 kind: str, degree: int, coef: float, scale: float,
                 normalize: bool, n_m_blocks: int):
    """One (bn, bk) output tile; accumulates x @ y^T over the m grid axis."""
    mb = pl.program_id(2)

    @pl.when(mb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # (bn, bm)
    y = y_ref[...].astype(jnp.float32)          # (bk, bm)
    o_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (bn, bk)

    @pl.when(mb == n_m_blocks - 1)
    def _epilogue():
        acc = o_ref[...]
        if kind == "rbf":
            sx = sx_ref[...].astype(jnp.float32)    # (bn, 1)
            sy = sy_ref[...].astype(jnp.float32)    # (1, bk)
            d2 = sx + sy - 2.0 * acc
            d2 = jnp.maximum(d2, 0.0)
            o_ref[...] = jnp.exp(-gamma_ref[0] * d2)
        else:
            k = acc * scale
            if kind == "poly":
                k = (k + coef) ** degree
            if normalize:
                # sx/sy hold the *self-kernel* values for linear/poly.
                sx = sx_ref[...].astype(jnp.float32)
                sy = sy_ref[...].astype(jnp.float32)
                denom = jnp.maximum(sx * sy, 1e-12)
                k = k * jax.lax.rsqrt(denom)
            o_ref[...] = k


@functools.partial(
    jax.jit,
    static_argnames=("kind", "degree", "coef", "scale", "normalize",
                     "block_n", "block_k", "block_m", "interpret"))
def gram_tiles(x: jax.Array, y: jax.Array, sx: jax.Array, sy: jax.Array,
               gamma: jax.Array, *, kind: str = "rbf", degree: int = 3,
               coef: float = 1.0, scale: float = 1.0, normalize: bool = True,
               block_n: int = 128, block_k: int = 128, block_m: int = 512,
               interpret: bool = False) -> jax.Array:
    """Tiled Gram matrix. Shapes must be pre-padded to block multiples:
    x (n, m), y (k, m), sx (n, 1), sy (1, k), gamma (1,) -> (n, k)
    float32."""
    n, m = x.shape
    k = y.shape[0]
    assert n % block_n == 0 and k % block_k == 0 and m % block_m == 0, \
        (x.shape, y.shape, (block_n, block_k, block_m))
    n_m_blocks = m // block_m
    grid = (n // block_n, k // block_k, n_m_blocks)

    kernel = functools.partial(
        _gram_kernel, kind=kind, degree=degree, coef=coef, scale=scale,
        normalize=normalize, n_m_blocks=n_m_blocks)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j, b: (i, 0)),     # sx
            pl.BlockSpec((1, block_k), lambda i, j, b: (0, j)),     # sy
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # gamma
            pl.BlockSpec((block_n, block_m), lambda i, j, b: (i, b)),
            pl.BlockSpec((block_k, block_m), lambda i, j, b: (j, b)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k), lambda i, j, b: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(sx, sy, gamma, x, y)
