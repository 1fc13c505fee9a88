"""Jitted public wrapper around the Gram Pallas kernel.

Handles padding to block multiples (zero-padding the feature axis is exact:
dot products and squared norms are unchanged; padded rows/cols are sliced
off), self-kernel/sq-norm precomputation, gamma resolution and backend
dispatch (interpret=True everywhere except real TPU).

Tile sizes default to the autotuner's table (``repro.kernels.autotune``)
keyed by (op, shape-bucket, dtype, backend); explicit ``block_*`` kwargs
override, and an untuned key falls back to the historical 128x128x512."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.kernels_math import KernelSpec, resolve_gamma, _self_k
from ..autotune import get_tiles
from .._util import _on_tpu, _pad_to, _round_up
from .gram import gram_tiles


def gram_op(spec: KernelSpec, x: jax.Array, y: Optional[jax.Array] = None,
            gamma: Optional[jax.Array] = None,
            block_n: Optional[int] = None, block_k: Optional[int] = None,
            block_m: Optional[int] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """Gram matrix K[i, j] = K(x_i, y_j) via the Pallas kernel.

    Matches ``repro.kernels.gram.ref.gram_reference`` (tested across shapes
    and dtypes in tests/test_kernels_gram.py).
    """
    if y is None:
        y = x
    if interpret is None:
        interpret = not _on_tpu()
    if block_n is None or block_k is None or block_m is None:
        tiles = get_tiles("gram", (x.shape[0], y.shape[0], x.shape[1]),
                          x.dtype)
        block_n = block_n or tiles["block_n"]
        block_k = block_k or tiles["block_k"]
        block_m = block_m or tiles["block_m"]
    if spec.kind == "rbf":
        g = resolve_gamma(spec, x) if gamma is None else jnp.asarray(gamma)
        sx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)
        sy = jnp.sum(y.astype(jnp.float32) ** 2, axis=-1)
    else:
        g = jnp.zeros((), jnp.float32)
        sx = _self_k(spec, x.astype(jnp.float32))
        sy = _self_k(spec, y.astype(jnp.float32))
    n, k = x.shape[0], y.shape[0]
    # adapt block sizes for small problems (interpret/test shapes)
    bn = min(block_n, _round_up(n, 8))
    bk = min(block_k, _round_up(k, 8))
    bm = min(block_m, _round_up(x.shape[1], 128))
    xp = _pad_to(_pad_to(x, bm, 1), bn, 0)
    yp = _pad_to(_pad_to(y, bm, 1), bk, 0)
    sxp = _pad_to(sx, bn, 0)[:, None]
    syp = _pad_to(sy, bk, 0)[None, :]
    out = gram_tiles(xp, yp, sxp, syp, jnp.reshape(g, (1,)).astype(jnp.float32),
                     kind=spec.kind, degree=spec.degree, coef=spec.coef,
                     scale=spec.scale, normalize=spec.normalize,
                     block_n=bn, block_k=bk, block_m=bm, interpret=interpret)
    return out[:n, :k]
