"""Paper Alg. 1 — ADMM-based decentralized kernel PCA (reference simulator).

This is the faithful, graph-general implementation of the paper's algorithm,
fully in the dual (kernel) space. All J nodes are simulated in one process
with vectorized updates; ``repro.core.dkpca`` is the SPMD (shard_map +
collective_permute) production version, validated against this module.

Variables per node j (paper §4.2): all live in dual space —
  alpha_j in R^{N_j}
  B_j = phi(X_j)^T eta_j in R^{N_j x S_j}   (one column per constraint slot)
  G_j = phi(X_j)^T Z xi_j in R^{N_j x S_j}

Constraint slots: the paper's problem (7) has a self constraint
(w_j = P_j z_j, weight rho1) and neighbor consensus constraints
(phi(X_j)alpha_j = P_j z_q, q in Omega_j, weight rho2); its eq. (10)-(13)
write only the neighbor part with uniform rho. We implement the general
per-slot-rho form (slot 0 = self, slots 1..D = neighbors); with
``include_self=False`` and constant rho this reduces exactly to eq. (10)-(13).

One ADMM iteration (uniform-rho form for reference):
  Z:    z_hat_m = sum_{l in slots^-1(m)} phi(X_l)(K_l^-1 B_l[:,m] + rho alpha_l)/rho_bar_m
        z_m = z_hat_m / max(1, ||z_hat_m||)                        (eq. 10-11)
  alpha: alpha_j = [rho_bar K_j - 2 K_j^2]^-1 (rho G_j - B_j) 1    (eq. 12)
  eta:  B_j[:,s] += rho_s (K_j alpha_j - G_j[:,s])                 (eq. 13)

The iteration BODY lives in ``repro.core.solver.admm_step`` (one shared
implementation for this module and the SPMD ``repro.core.dkpca``); this
module supplies the dense transport (all nodes in-process, slot routing by
(src, rsl) indexing), the setup phase, and the whole-history run loop.
``repro.core.solver.run_chunked`` is the resumable chunked driver over the
same step.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .kernels_math import KernelSpec, center_gram, gram, psd_jitter_eigh, resolve_gamma
from .rho import SAFETY, RhoSchedule, auto_rho
from .solver import admm_step, dense_parts, init_state, lagrangian
from .topology import Graph


@dataclasses.dataclass(frozen=True)
class DkpcaSetup:
    """Static per-run tensors (trace-time constants are numpy; traced are jnp).

    Slot layout: S = D + 1 where D = max degree. Slot 0 is the self slot
    (masked out when include_self=False), slots 1..D are neighbors in graph
    order. src[j, s] = data-owner node of slot s of node j;
    rsl[j, s] = the slot index of node j inside node src[j,s]'s slot list.
    """

    x: jax.Array          # (J, N, M) node data
    k: jax.Array          # (J, N, N) (centered) local Gram K_j
    lam: jax.Array        # (J, N) floored eigenvalues of K_j (ascending)
    vec: jax.Array        # (J, N, N) eigenvectors of K_j
    kcross: jax.Array     # (J, S, S, N, N) kcross[j,a,b] = K(X_src[j,a], X_src[j,b])
    src: jax.Array        # (J, S) int32
    rsl: jax.Array        # (J, S) int32
    mask: jax.Array       # (J, S) bool — valid slots
    gamma: jax.Array      # scalar RBF bandwidth actually used
    include_self: bool = True

    @property
    def n_nodes(self):
        return self.x.shape[0]

    @property
    def n_local(self):
        return self.x.shape[1]

    @property
    def n_slots(self):
        return self.mask.shape[1]


@dataclasses.dataclass
class DkpcaResult:
    alpha: jax.Array            # (J, N) final local solutions
    alpha_hist: jax.Array       # (T, J, N)
    lagrangian: jax.Array       # (T,) augmented Lagrangian value
    primal_residual: jax.Array  # (T,) ||K alpha 1 - G||_F total
    rho_hist: jax.Array         # (T,) rho2 used per iteration


def _masked_center(kfull: jax.Array, valid: jax.Array) -> jax.Array:
    """Center a square Gram over the valid rows/cols only (then zero the
    invalid ones). kfull: (P, P); valid: (P,) bool."""
    v = valid.astype(kfull.dtype)
    nv = jnp.maximum(jnp.sum(v), 1.0)
    row = (kfull @ v) / nv                               # mean over valid cols
    col = (v @ kfull) / nv
    tot = (v @ kfull @ v) / (nv * nv)
    kc = kfull - row[:, None] - col[None, :] + tot
    return kc * v[:, None] * v[None, :]


def kernel_mean_stats(x_nodes: jax.Array, spec: KernelSpec, gamma):
    """Global kernel mean statistics for consistent centering.

    Returns (m, mu_bar): m[j, i] = mean_t K(x_i^(j), t) over ALL samples t in
    the network, mu_bar = mean over all pairs.

    Decentralized realization (one-time, before ADMM): node j computes
    psi_j(x) = mean_i K(x, x_i^(j)) for every x it can evaluate, and the
    network runs ONE consensus-averaging round on the per-sample partial
    means (a gossip average; a single ``jax.lax.pmean`` on TPU). The paper
    centers per-block instead, which makes cross-blocks inconsistent — see
    EXPERIMENTS.md §Paper-validation for the measured bias.
    """
    j, n, _ = x_nodes.shape

    def row_stats(x_j):
        def acc(carry, x_l):
            s = carry + jnp.sum(gram(spec, x_j, x_l, gamma=gamma), axis=1)
            return s, None
        s, _ = jax.lax.scan(acc, jnp.zeros((n,), x_nodes.dtype), x_nodes)
        return s / (j * n)

    m = jax.lax.map(row_stats, x_nodes)                  # (J, N)
    mu_bar = jnp.mean(m)
    return m, mu_bar


def build_setup(x_nodes: jax.Array, graph: Graph, spec: KernelSpec,
                center: str | bool = "global", include_self: bool = True,
                rel_eps: float = 1e-6,
                gamma: float | None = None) -> DkpcaSetup:
    """Precompute Gram blocks / factorizations; mirrors the paper's setup
    phase where raw data is exchanged with neighbors and all K(X_p, X_q),
    p,q in Omega_j, are formed once.

    center:
      "global" (default) — center every block with the *same* global kernel
        mean statistics (one extra consensus-averaging round at setup, see
        ``kernel_mean_stats``). All nodes then share one centered feature
        space phi(x) - mu, and the consensus fixed point matches centered
        central kPCA (measured similarity -> 1.0).
      "neighborhood" — node j centers the Gram over the data it holds; the
        feature-space offset mu_j then differs per node, which biases the
        consensus fixed point. Kept for ablation.
      "block" — the paper's §6.1 formula applied to every block separately.
        Faithful to the text, but cross-blocks are then centered with
        *different* means per side, which is not a valid Gram of any single
        feature map; we measured the consensus fixed point drifting away
        from the central solution (similarity 0.81 at iter 30 -> 0.70 at
        iter 100 while the primal residual -> 0). Kept for comparison.
      "none"/False — raw kernel (fixed point matches *uncentered* central
        kPCA exactly; similarity 1.000 in our validation).
    """
    if center is True:
        center = "global"
    if center is False:
        center = "none"
    assert center in ("global", "neighborhood", "block", "none")
    x_nodes = jnp.asarray(x_nodes)
    j, n, _ = x_nodes.shape
    assert j == graph.n_nodes
    ids, rev, nmask = graph.neighbor_array()
    d = ids.shape[1]
    s = d + 1
    src = np.concatenate([np.arange(j, dtype=np.int32)[:, None], ids], axis=1)
    rsl = np.concatenate([np.zeros((j, 1), np.int32), rev + 1], axis=1)
    mask = np.concatenate([np.full((j, 1), include_self), nmask], axis=1)
    # slot-0 blocks (K_j) are always needed even when the self *constraint*
    # is disabled, so Gram validity masking uses a mask with slot 0 on.
    gmask = np.concatenate([np.full((j, 1), True), nmask], axis=1)

    # gamma is normally resolved from the pooled data; a caller that
    # REBUILDS a setup mid-run (e.g. the fault driver after a re-knit, on
    # survivor data only) must pin the original value so the kernel — and
    # therefore the warm-started iterate — stays the same operator.
    if gamma is None:
        gamma = resolve_gamma(spec, x_nodes.reshape(j * n, -1))

    xs = x_nodes[src]                                    # (J, S, N, M)

    if center == "global":
        m_glob, mu_bar = kernel_mean_stats(x_nodes, spec, gamma)
        ms = m_glob[src]                                 # (J, S, N)
    else:
        ms = None

    def node_gram(xs_j, gmask_j, ms_j):
        xflat = xs_j.reshape(s * n, -1)
        kfull = gram(spec, xflat, gamma=gamma)           # (S*N, S*N)
        valid = jnp.repeat(gmask_j, n)
        if center == "neighborhood":
            kfull = _masked_center(kfull, valid)
        elif center == "global":
            mf = ms_j.reshape(s * n)
            kfull = kfull - mf[:, None] - mf[None, :] + mu_bar
            kfull = kfull * valid[:, None] * valid[None, :]
        kb = kfull.reshape(s, n, s, n).transpose(0, 2, 1, 3)
        if center == "block":
            kb = jax.vmap(jax.vmap(center_gram))(kb)
        return kb                                        # (S, S, N, N)

    ms_arg = ms if ms is not None else jnp.zeros((j, s, n), x_nodes.dtype)
    kcross = jax.vmap(node_gram)(xs, jnp.asarray(gmask), ms_arg)

    kj = kcross[:, 0, 0]                                 # (J, N, N)
    lam, vec = jax.vmap(lambda kk: psd_jitter_eigh(kk, rel_eps))(kj)
    return DkpcaSetup(x=x_nodes, k=kj, lam=lam, vec=vec, kcross=kcross,
                      src=jnp.asarray(src), rsl=jnp.asarray(rsl),
                      mask=jnp.asarray(mask), gamma=gamma,
                      include_self=include_self)


def _slot_rho(setup: DkpcaSetup, rho1, rho2):
    """(J, S) per-slot rho (0 on invalid slots)."""
    j, s = setup.mask.shape
    r = jnp.concatenate(
        [jnp.full((j, 1), rho1), jnp.full((j, s - 1), rho2)], axis=1)
    return r * setup.mask


def admm_iteration(setup: DkpcaSetup, alpha, b, rho1, rho2,
                   project: str = "ball"):
    """One ADMM iteration (eq. 10-13, per-slot-rho generalization) through
    the shared step body (``repro.core.solver.admm_step``) over the dense
    transport.

    alpha: (J, N); b: (J, N, S). Returns (alpha', b', g, znorm2).
    """
    ops, comm = dense_parts(setup)
    rho_slots = _slot_rho(setup, rho1, rho2)              # (J, S)
    state = init_state(alpha, setup.n_slots)
    state = dataclasses.replace(state, b=jnp.asarray(b))
    new, _ = admm_step(ops, comm, state, rho_slots, project)
    return new.alpha, new.b, new.g, new.znorm2


def augmented_lagrangian(setup: DkpcaSetup, alpha, b, g, rho1, rho2):
    """Dual-space evaluation of eq. (8):
    L = sum_j [ -a^T K^2 a + sum_s B_s^T C_s + sum_s rho_s/2 C_s^T K C_s ],
    C_s = alpha - K^{-1} G_s (constraint residual coefficients)."""
    ops, _ = dense_parts(setup)
    return lagrangian(ops, alpha, b, g, _slot_rho(setup, rho1, rho2))


@partial(jax.jit, static_argnames=("setup_static", "n_iters", "project"))
def _run_jit(setup_static, setup_arrays, alpha0, rho1_arr, rho2_arr, n_iters,
             project):
    setup = dataclasses.replace(setup_static, **setup_arrays)
    ops, comm = dense_parts(setup)

    def step(carry, t):
        st = carry
        rho_slots = _slot_rho(setup, rho1_arr[t], rho2_arr[t])
        new, res = admm_step(ops, comm, st, rho_slots, project)
        # Theorem-2 pairing: L(alpha^t, Z^t, eta^t) with Z^t generated from
        # (alpha^t, eta^t) — i.e. the *incoming* alpha/b with the g computed
        # from them inside this iteration.
        lag = lagrangian(ops, st.alpha, st.b, new.g, rho_slots)
        return new, (new.alpha, lag, res)

    final, (ahist, lhist, rhist) = jax.lax.scan(
        step, init_state(alpha0, setup.n_slots), jnp.arange(n_iters))
    return final.alpha, ahist, lhist, rhist


def initial_alpha(setup: DkpcaSetup, init: str = "local", seed: int = 0):
    """alpha^(0).

    "paper": entrywise standard normal, *unnormalized* — the scale matters:
      ||alpha0|| ~ sqrt(N) puts ||z_hat|| well above 1 so the ball projection
      (the iteration's only normalization) engages from step one.
    "local": warm start at the local kPCA solution (v1/sqrt(lam1) of K_j),
      i.e. each node starts at its own best guess; ||w_j|| = 1 exactly.
      This warm-starts the consensus variable z at the pooled local
      components, which removes the m=24 transient entirely (measured in
      docs/ADMM_CONVERGENCE.md §Ablations) — hence the default. Requires no
      extra communication: each node eigendecomposes its own K_j, which the
      setup phase already does.
    """
    if init == "paper":
        key = jax.random.PRNGKey(seed)
        return jax.random.normal(key, setup.x.shape[:2], setup.k.dtype)
    if init == "local":
        return jax.vmap(local_solution_alpha)(setup.lam, setup.vec)
    raise ValueError(init)


def local_solution_alpha(lam: jax.Array, vec: jax.Array) -> jax.Array:
    """One node's local kPCA solution v1/sqrt(lam1) (so ||w_j|| = 1).
    lam: (N,) ascending; vec: (N, N). Shared by the reference
    ``initial_alpha(init="local")`` and the SPMD in-node default init.

    The eigenvector sign is whatever eigh returns. Do NOT "canonicalize"
    it per-node (e.g. largest-|entry| positive): a node-local sign rule
    keys on node-specific sample indices and de-correlates the signs
    ACROSS nodes, which makes neighbors' warm starts partially cancel in
    the z-update (measured: m=24 similarity drops from 0.997 back to 0.59
    @ 30 iters). LAPACK's sign is a deterministic function of the matrix,
    and nodes drawing data from one distribution get consistently-signed
    top eigenvectors — the reference and SPMD paths also agree because
    they eigendecompose the same (up to fp noise) centered K_j."""
    return vec[:, -1] / jnp.sqrt(jnp.maximum(lam[-1], 1e-12))


def run_admm(setup: DkpcaSetup, n_iters: int = 30,
             rho1: float = 100.0,
             rho2: Optional[RhoSchedule] = None,
             seed: int = 0,
             alpha0: Optional[jax.Array] = None,
             init: str = "local",
             project: str = "ball") -> DkpcaResult:
    """Run Alg. 1 (whole history in one jitted scan; see
    ``repro.core.solver.run_chunked`` for the resumable chunked driver).

    rho2 defaults to the Theorem-2 constant (``theorem2_rho``); pass
    ``RhoSchedule()`` for the paper's §6.1 warm-up (10 -> 50 -> 100).
    ``init`` defaults to the local-solution z warm-start (the measured fix
    for the slow low-m transient — docs/ADMM_CONVERGENCE.md §Ablations);
    ``init="paper"`` restores the paper's Gaussian initialization.
    ``project="sphere"`` enables the beyond-paper renormalization that
    removes the degenerate z=0 attractor."""
    if rho2 is None:
        rho2 = RhoSchedule.constant(theorem2_rho(setup))
    if alpha0 is None:
        alpha0 = initial_alpha(setup, init, seed)
    ts = np.arange(n_iters)
    rho2_arr = jnp.asarray([rho2.at(t) for t in ts], setup.k.dtype)
    rho1_arr = jnp.full((n_iters,), rho1, setup.k.dtype) \
        if setup.include_self else jnp.zeros((n_iters,), setup.k.dtype)

    arrays = {f.name: getattr(setup, f.name)
              for f in dataclasses.fields(DkpcaSetup)
              if f.name != "include_self"}
    static = dataclasses.replace(
        setup, **{k: None for k in arrays})
    alpha, ahist, lhist, rhist = _run_jit(
        static, arrays, alpha0, rho1_arr, rho2_arr, n_iters, project)
    return DkpcaResult(alpha=alpha, alpha_hist=ahist, lagrangian=lhist,
                       primal_residual=rhist, rho_hist=rho2_arr)


def theorem2_rho(setup: DkpcaSetup, safety: float = SAFETY) -> float:
    """Assumption-2-satisfying constant rho for this setup."""
    degrees = np.asarray(jnp.sum(setup.mask, axis=1))
    return auto_rho(np.asarray(setup.lam), degrees, safety)
