"""Pallas TPU kernel: fused local ADMM update (paper eq. 12-13).

Per node and iteration, after the Z-exchange, the *local* math is a chain of
small matmuls over the same N x N operands:

    rhs   = sum_s (rho_s G[:, s] - B[:, s])
    alpha = V diag(inv_den) V^T rhs          (eigh-factorized eq. 12 solve)
    ka    = K alpha
    B'    = B + rho_s (ka 1^T - G)           (eq. 13)

Unfused, each step round-trips N^2/N*S data through HBM. This kernel keeps
V, K, B, G resident in VMEM and performs the whole chain in one invocation —
one read of each operand, one write of (alpha, B'). N_j <= 1024 keeps
V + K + scratch within the ~16 MB VMEM budget (2 * 4 MB fp32 + tiles).
The grid iterates over nodes so the same kernel serves the vmapped
simulator and the per-device shard_map path (J_local = 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _admm_kernel(v_ref, invd_ref, k_ref, b_ref, g_ref, rho_ref,
                 alpha_ref, bout_ref):
    v = v_ref[0]                                   # (N, N)
    k = k_ref[0]                                   # (N, N)
    b = b_ref[0]                                   # (N, S)
    g = g_ref[0]                                   # (N, S)
    invd = invd_ref[0]                             # (N, 1)
    rho = rho_ref[0]                               # (1, S)

    rhs = jnp.sum(rho * g - b, axis=1, keepdims=True)          # (N, 1)
    t = jax.lax.dot_general(v, rhs, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # V^T rhs
    t = t * invd
    alpha = jnp.dot(v, t, preferred_element_type=jnp.float32)   # (N, 1)
    ka = jnp.dot(k, alpha, preferred_element_type=jnp.float32)  # (N, 1)
    alpha_ref[0] = alpha
    bout_ref[0] = b + rho * (ka - g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def admm_local_update(v: jax.Array, inv_den: jax.Array, k: jax.Array,
                      b: jax.Array, g: jax.Array, rho_slots: jax.Array,
                      *, interpret: bool = False):
    """Fused eq. 12-13. Shapes: v,k (J,N,N); inv_den (J,N,1); b,g (J,N,S);
    rho_slots (J,1,S). Returns (alpha (J,N,1), b_new (J,N,S))."""
    j, n, _ = v.shape
    s = b.shape[-1]
    whole = lambda shape: pl.BlockSpec((1,) + shape, lambda i: (i,) + (0,) * len(shape))
    return pl.pallas_call(
        _admm_kernel,
        grid=(j,),
        in_specs=[whole((n, n)), whole((n, 1)), whole((n, n)),
                  whole((n, s)), whole((n, s)), whole((1, s))],
        out_specs=[whole((n, 1)), whole((n, s))],
        out_shape=[jax.ShapeDtypeStruct((j, n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((j, n, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(v, inv_den, k, b, g, rho_slots)
