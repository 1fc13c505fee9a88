"""ADMM penalty-parameter policies.

Assumption 2 of the paper gives a closed-form lower bound on rho that
guarantees monotone decrease of the augmented Lagrangian (Theorem 2):

    rho >= ( sqrt(lam1^4 + 8 |Omega_j| lam1 * sum_n lam_n^3) + lam1^2 )
           / ( |Omega_j| * lam1 )

per node j, where lam_n are the eigenvalues of K_j. We take the max over
nodes, times ``SAFETY``. That constant is the drivers' default rho2: it
follows the spectrum of the data, so it holds at any per-node sample
count. The paper's experiments instead use a hand-tuned warm-up schedule
(rho(1)=100 fixed; rho(2): 10 -> 50 -> 100), kept as ``RhoSchedule()``.
Its absolute values break Assumption 2 once the local Gram's top
eigenvalue outgrows them: on the synthetic data (lam_max about 0.26 N)
from N of about 250, where the fit then collapses (mean similarity 0.865
after 30 iterations at J=10, N=300, against 0.9998 with the bound).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# Margin over the Assumption-2 bound for the Theorem-2 constant.
SAFETY = 1.05


def assumption2_rho(eigvals: jnp.ndarray, degree: jnp.ndarray) -> jnp.ndarray:
    """Per-node Theorem-2 rho bound.

    eigvals: (..., N) eigenvalues of (centered) K_j, any order.
    degree:  (...,) |Omega_j|.
    """
    lam = jnp.asarray(eigvals)
    lam1 = jnp.max(lam, axis=-1)
    s3 = jnp.sum(jnp.maximum(lam, 0.0) ** 3, axis=-1)
    d = jnp.asarray(degree, lam.dtype)
    return (jnp.sqrt(lam1 ** 4 + 8.0 * d * lam1 * s3) + lam1 ** 2) / (d * lam1)


@dataclasses.dataclass(frozen=True)
class RhoSchedule:
    """Paper §6.1 warm-up: start small, increase to rho_final at given steps.

    values[i] applies from iteration boundaries[i] onward;
    boundaries[0] must be 0.
    """

    boundaries: tuple = (0, 10, 20)
    values: tuple = (10.0, 50.0, 100.0)

    def __post_init__(self):
        assert len(self.boundaries) == len(self.values) and self.boundaries[0] == 0

    def at(self, t) -> jnp.ndarray:
        b = jnp.asarray(self.boundaries)
        v = jnp.asarray(self.values, jnp.float32)
        idx = jnp.sum(jnp.asarray(t) >= b) - 1
        return v[idx]

    @staticmethod
    def constant(rho: float) -> "RhoSchedule":
        return RhoSchedule(boundaries=(0,), values=(float(rho),))


def auto_rho(eigvals_per_node: np.ndarray, degrees: np.ndarray,
             safety: float = SAFETY) -> float:
    """Global constant rho satisfying Assumption 2 on every node."""
    r = assumption2_rho(jnp.asarray(eigvals_per_node), jnp.asarray(degrees))
    return float(jnp.max(r) * safety)
