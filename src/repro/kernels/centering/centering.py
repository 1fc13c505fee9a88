"""Pallas TPU kernel: fused Gram-matrix centering (paper §6.1).

K_c = K - rowmean - colmean + totalmean, tiled so each output block is read
and written exactly once (single HBM pass; the naive jnp version makes XLA
materialize broadcasted mean matrices under some fusion decisions). Means
are cheap O(n^2) reductions computed by the wrapper.

Operand layout: row means arrive as a (n, 1) column, column means as a
(1, m) row and the total mean as an SMEM scalar — Mosaic refuses 1-D VMEM
blocks. ``block_k`` is a lane dimension: a multiple of 128 or the whole
padded axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _center_kernel(row_ref, col_ref, tot_ref, k_ref, o_ref):
    r = row_ref[...].astype(jnp.float32)     # (bn, 1) row means
    c = col_ref[...].astype(jnp.float32)     # (1, bk) col means
    o_ref[...] = k_ref[...].astype(jnp.float32) - r - c + tot_ref[0]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def center_tiles(k: jax.Array, row_mean: jax.Array, col_mean: jax.Array,
                 tot_mean: jax.Array, *, block_n: int = 256,
                 block_k: int = 256, interpret: bool = False) -> jax.Array:
    """k (n, m), row_mean (n, 1), col_mean (1, m), tot_mean (1,) ->
    centered (n, m) float32; shapes pre-padded to block multiples."""
    n, m = k.shape
    assert n % block_n == 0 and m % block_k == 0
    grid = (n // block_n, m // block_k)
    return pl.pallas_call(
        _center_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_k), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, block_k), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(row_mean, col_mean, tot_mean, k)
