"""Jitted wrapper for the centering Pallas kernel (padding + dispatch).

``block`` defaults to the autotuner's table entry for this shape/dtype/
backend (``repro.kernels.autotune``), falling back to 256 when untuned."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..autotune import get_tiles
from .._util import _on_tpu, _pad_to, _round_up
from .centering import center_tiles


def center_op(k: jax.Array, block: Optional[int] = None,
              interpret: Optional[bool] = None) -> jax.Array:
    """Fused K_c = K - rowmean - colmean + totalmean (paper §6.1 formula)."""
    if interpret is None:
        interpret = not _on_tpu()
    if block is None:
        block = get_tiles("centering", k.shape, k.dtype)["block"]
    n, m = k.shape
    kf = k.astype(jnp.float32)
    row = jnp.mean(kf, axis=1)
    col = jnp.mean(kf, axis=0)
    tot = jnp.mean(kf)[None]
    bn = min(block, _round_up(n, 8))
    bk = min(block, _round_up(m, 128))
    kp = _pad_to(_pad_to(kf, bn, 0), bk, 1)
    rp = _pad_to(row, bn, 0)[:, None]
    cp = _pad_to(col, bk, 0)[None, :]
    out = center_tiles(kp, rp, cp, tot, block_n=bn, block_k=bk,
                       interpret=interpret)
    return out[:n, :m]
