"""Shared ADMM solver-driver layer: one step body, two transports.

The paper's Alg. 1 is implemented twice in this repo — the graph-general
reference simulator (``repro.core.admm``, all nodes vectorized in one
process) and the SPMD production path (``repro.core.dkpca``, one node per
device, ``ppermute`` messaging). Both run the SAME per-node math; only the
way slot messages move differs. This module owns that shared math:

  * ``AdmmState`` — the full iterate pytree (alpha, dual B, last z
    projections G, per-node ||z_hat||^2, iteration counter, per-slot rho),
    checkpointable via ``save_state``/``load_state``;
  * ``admm_step`` — ONE pure iteration (paper eq. 10-13 in the per-slot-rho
    generalization), written against a ``Communicator`` protocol:
      - ``DenseComm``: gather/scatter by (src, rsl) indexing over a leading
        node axis; per-node math is ``jax.vmap``-ed (reference simulator);
      - ``RingComm``: ``jax.lax.ppermute`` ring hops inside ``shard_map``;
        per-node math runs directly on the device's block (SPMD path);
  * ``run_chunked`` — the resumable driver: scans ``chunk`` iterations per
    jitted call and yields the live state between chunks, so callers can
    observe residuals, checkpoint (``repro.checkpoint`` layout), re-tune or
    switch rho (pluggable ``RhoSchedule`` / Theorem-2 constant / arbitrary
    ``t -> rho`` callable) and publish serving snapshots mid-run
    (``repro.serve.publisher``) — with residual-based early stopping.

Warm starts: ``AdmmState`` carries (alpha, B) across chunk boundaries, so a
rho switch at a boundary continues from the warm z (the Z-update is a pure
function of the carried state). For a FRESH run, ``init="local"`` starts
alpha at each node's local kPCA solution, which warm-starts z at the pooled
local components — measured to remove the m=24 transient entirely (see
docs/ADMM_CONVERGENCE.md §Ablations).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Iterator, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .rho import RhoSchedule
from ..obs import metrics, trace
from ..obs.comm import CommLedger


# ---- state ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdmmState:
    """Full ADMM iterate. Shapes are per-node; the reference simulator adds
    a leading node axis J to every field (t stays scalar).

    alpha:  (..., N) primal dual-space coefficients.
    b:      (..., N, S) dual variables B_j = phi(X_j)^T eta_j, slot-major.
    g:      (..., N, S) last z projections G_j = phi(X_j)^T Z xi_j.
    znorm2: (...,) last ||z_hat||^2 per node (diagnostic; drives the
            "rescale" gauge).
    t:      () int32 — iterations completed.
    rho:    (..., S) per-slot rho applied at the last step (0 before it).
    """

    alpha: jax.Array
    b: jax.Array
    g: jax.Array
    znorm2: jax.Array
    t: jax.Array
    rho: jax.Array


jax.tree_util.register_pytree_node(
    AdmmState,
    lambda s: ((s.alpha, s.b, s.g, s.znorm2, s.t, s.rho), None),
    lambda _, leaves: AdmmState(*leaves))


def init_state(alpha0: jax.Array, n_slots: int, t0: int = 0) -> AdmmState:
    """Fresh state at iteration ``t0`` with zero duals/projections."""
    alpha0 = jnp.asarray(alpha0)
    b = jnp.zeros(alpha0.shape + (n_slots,), alpha0.dtype)
    return AdmmState(
        alpha=alpha0, b=b, g=jnp.zeros_like(b),
        znorm2=jnp.zeros(alpha0.shape[:-1], alpha0.dtype),
        t=jnp.asarray(t0, jnp.int32),
        rho=jnp.zeros(alpha0.shape[:-1] + (n_slots,), alpha0.dtype))


@dataclasses.dataclass(frozen=True)
class SolverOps:
    """Per-node constants the step needs (leading node axis in DenseComm).

    kcross: (S, S, N, N) Gram blocks between slot owners' data.
    k:      (N, N) own (centered) Gram K_j == kcross[0, 0].
    lam:    (N,) floored eigenvalues of K_j, ascending.
    vec:    (N, N) eigenvectors of K_j.
    mask:   (S,) float 1/0 — valid constraint slots.
    """

    kcross: jax.Array
    k: jax.Array
    lam: jax.Array
    vec: jax.Array
    mask: jax.Array


jax.tree_util.register_pytree_node(
    SolverOps,
    lambda o: ((o.kcross, o.k, o.lam, o.vec, o.mask), None),
    lambda _, leaves: SolverOps(*leaves))


# ---- communicators --------------------------------------------------------

class DenseComm:
    """All nodes in one process: exchange == advanced indexing by the
    (src, rsl) slot routing tables; per-node math is vmapped over axis 0.

    Communication accounting (``repro.obs.comm``): the routing tables may
    be tracers here, so the off-node entry count — the number of directed
    edges an exchange actually moves data over — is computed host-side by
    the driver and passed in as ``wire_entries``; each traced ``exchange``
    then reports NETWORK-WIDE bytes (every edge, payload only) into the
    ledger.
    """

    def __init__(self, src: jax.Array, rsl: jax.Array,
                 ledger: Optional[CommLedger] = None,
                 wire_entries: int = 0):
        self.src, self.rsl = src, rsl
        self.ledger = ledger
        self.wire_entries = wire_entries

    def local(self, fn):
        return jax.vmap(fn)

    def exchange(self, cols: jax.Array) -> jax.Array:
        """cols: (J, S, N) per-out-slot columns -> (J, S, N) where in-slot s
        of node j receives cols[src[j,s], rsl[j,s]]."""
        if self.ledger is not None:
            payload = cols.shape[-1] * jnp.dtype(cols.dtype).itemsize
            self.ledger.record_exchange(self.wire_entries * payload,
                                        self.wire_entries)
        return cols[self.src, self.rsl]

    def all_sum(self, x):
        return jnp.sum(x)

    def all_max(self, x):
        return jnp.max(x)


class RingComm:
    """One node per device inside ``shard_map``: exchange == one ppermute
    ring shift per neighbor slot; per-node math runs unmapped.

    message_dtype (e.g. bfloat16) casts neighbor payloads before the wire
    (halving ICI bytes); the self slot and all accumulation stay fp32.

    Communication accounting (``repro.obs.comm``): every ppermute and
    psum/pmax reports its WIRE payload (post-``message_dtype`` cast) into
    the ledger at trace time. The recorded profile is per NODE — this
    class runs inside shard_map, one node per device — so multiply by J
    for network totals.
    """

    def __init__(self, axes: Sequence[str], n_nodes: int,
                 offsets: Sequence[int], rev_slots: Sequence[int],
                 message_dtype=None, ledger: Optional[CommLedger] = None):
        self.axes = tuple(axes)
        self.n_nodes = n_nodes
        self.offsets = tuple(offsets)
        self.rev_slots = tuple(rev_slots)
        self.message_dtype = message_dtype
        self.ledger = ledger

    def local(self, fn):
        return fn

    def _shift(self, v: jax.Array, offset: int) -> jax.Array:
        """result on node m = v from node (m + offset) % J."""
        perm = [((m + offset) % self.n_nodes, m)
                for m in range(self.n_nodes)]
        if self.message_dtype is not None:
            v = v.astype(self.message_dtype)
        if self.ledger is not None:
            self.ledger.record_exchange(
                v.size * jnp.dtype(v.dtype).itemsize)
        r = jax.lax.ppermute(v, self.axes, perm)
        return r.astype(jnp.float32) if self.message_dtype is not None else r

    def exchange(self, cols: jax.Array) -> jax.Array:
        """cols: (S, N) my per-out-slot columns -> (S, N) received values:
        in-slot 0 is self; in-slot d+1 (offset o) receives the sender's
        column rev_slots[d] (its out-slot pointing back at us)."""
        outs = [cols[0]]
        for d, off in enumerate(self.offsets):
            outs.append(self._shift(cols[self.rev_slots[d]], off))
        return jnp.stack(outs)

    def all_sum(self, x):
        if self.ledger is not None:
            self.ledger.record_collective(
                jnp.size(x) * jnp.dtype(jnp.result_type(x)).itemsize)
        return jax.lax.psum(x, self.axes)

    def all_max(self, x):
        if self.ledger is not None:
            self.ledger.record_collective(
                jnp.size(x) * jnp.dtype(jnp.result_type(x)).itemsize)
        return jax.lax.pmax(x, self.axes)


def dense_parts(setup) -> tuple:
    """(SolverOps, DenseComm) for a ``repro.core.admm.DkpcaSetup``."""
    ops = SolverOps(kcross=setup.kcross, k=setup.k, lam=setup.lam,
                    vec=setup.vec,
                    mask=jnp.asarray(setup.mask, setup.k.dtype))
    return ops, DenseComm(setup.src, setup.rsl)


# ---- the shared step ------------------------------------------------------

def _pinv_lam(lam: jax.Array, rel_thresh: float = 1e-5) -> jax.Array:
    """Pseudo-inverse eigenvalues of K_j (drop the null space)."""
    return jnp.where(lam > rel_thresh * lam[-1], 1.0 / lam, 0.0)


def admm_step(ops: SolverOps, comm, state: AdmmState, rho_slots: jax.Array,
              project: str = "ball",
              slot_mask: Optional[jax.Array] = None):
    """One ADMM iteration (paper eq. 10-13, per-slot-rho generalization).

    Args:
      ops: per-node constants (DenseComm: leading J axis on every field).
      comm: ``DenseComm`` or ``RingComm`` transport.
      state: incoming iterate; only (alpha, b, t) drive the update — g,
        znorm2, rho are refreshed outputs.
      rho_slots: (S,) per-node per-slot rho for THIS iteration (DenseComm:
        (J, S)); zero on invalid slots.
      project: "ball" (paper eq. 11), "sphere" (always renormalize), or
        "rescale" (ball + global gauge renormalization; needs comm.all_max).
      slot_mask: optional {0,1} mask over slots (same shape as
        ``rho_slots``) censoring links down for THIS iteration — the
        COKE-style degradation under faults (docs/FAULT_TOLERANCE.md):
        ``rho_bar`` renormalizes over the slots actually heard, the
        censored constraints leave the z/alpha updates AND the residual,
        and their duals freeze (rho = 0 ⇒ the eq. 13 update is a no-op).
        Slot 0 (self) must never be masked. A node isolated outright this
        iteration (every slot censored, possible only without a self
        slot) holds its (alpha, B) instead of collapsing to zero.

    Returns:
      (state', primal_residual) — state' has t+1 and the g/znorm2/rho
      produced by this iteration; the residual is the global
      ||K alpha 1 - G||_F over valid slots.
    """
    ledger = getattr(comm, "ledger", None)
    if ledger is not None:
        # trace-time bracket: everything the transport records until
        # end_iteration is exactly one iteration's traffic (repro.obs.comm)
        ledger.begin_iteration()
    faulty = slot_mask is not None
    if faulty:
        ops = dataclasses.replace(ops, mask=ops.mask * slot_mask)
        rho_slots = rho_slots * slot_mask
    alpha, b = state.alpha, state.b

    # ---- message round 1: K^-1 B columns + alpha --------------------------
    def pack(o, alpha_j, b_j):
        m1 = o.vec @ ((o.vec.T @ b_j) * _pinv_lam(o.lam)[:, None])  # (N, S)
        s, n = b_j.shape[1], b_j.shape[0]
        return (jnp.swapaxes(m1, 0, 1),
                jnp.broadcast_to(alpha_j[None, :], (s, n)))

    cols_m1, cols_a = comm.local(pack)(ops, alpha, b)
    recv_m1 = comm.exchange(cols_m1)
    recv_a = comm.exchange(cols_a)

    # ---- Z-update (eq. 10-11) --------------------------------------------
    def z_update(o, rho_j, rm1, ra):
        rho_bar = jnp.sum(rho_j)
        if faulty:
            # fully-censored node: avoid 0/0 (its update is discarded below)
            rho_bar = jnp.maximum(rho_bar, 1e-30)
        c = ((rm1 + rho_j[:, None] * ra) / rho_bar) * o.mask[:, None]
        znorm2 = jnp.einsum("an,abnm,bm->", c, o.kcross, c)
        rs = jax.lax.rsqrt(jnp.maximum(znorm2, 1e-30))
        if project == "sphere":
            scale = rs
        else:
            scale = jnp.where(znorm2 > 1.0, rs, 1.0)
        p = scale * jnp.einsum("abnm,bm->an", o.kcross, c)     # (S, N)
        return p, znorm2

    p, znorm2 = comm.local(z_update)(ops, rho_slots, recv_m1, recv_a)

    # ---- message round 2: z projections ----------------------------------
    g_slots = comm.exchange(p)

    # ---- alpha-update (eq. 12) + eta-update (eq. 13) ---------------------
    def primal_dual(o, alpha_j, b_j, rho_j, g_s):
        g = jnp.swapaxes(g_s, 0, 1) * o.mask[None, :]          # (N, S)
        rho_bar = jnp.sum(rho_j)
        rhs = jnp.sum(rho_j[None, :] * g - b_j * o.mask[None, :], axis=1)
        lam = o.lam
        den = rho_bar * lam - 2.0 * lam * lam
        # drop (don't invert) directions where the alpha-Hessian is not PD —
        # during rho warm-up large-N kernels can violate Assumption 2 for a
        # few iterations; clamping would amplify those modes into divergence.
        inv = jnp.where((lam > 1e-5 * lam[-1]) & (den > 0), 1.0 / den, 0.0)
        alpha_n = o.vec @ ((o.vec.T @ rhs) * inv)
        ka = o.k @ alpha_n
        b_n = (b_j + rho_j[None, :] * (ka[:, None] - g)) * o.mask[None, :]
        res_part = jnp.sum(o.mask[None, :] * (ka[:, None] - g) ** 2)
        return alpha_n, b_n, g, res_part

    alpha_n, b_n, g, res_part = comm.local(primal_dual)(
        ops, alpha, b, rho_slots, g_slots)
    if faulty:
        # A node that heard nobody this iteration (rho_bar = 0) has no
        # consensus information: den <= 0 zeroes every direction and the
        # naive update would collapse alpha to 0. Hold its state instead.
        live = jnp.sum(rho_slots, axis=-1) > 0.0
        alpha_n = jnp.where(live[..., None], alpha_n, alpha)
        b_n = jnp.where(live[..., None, None], b_n, b)
    res = jnp.sqrt(comm.all_sum(res_part))

    if project == "rescale":
        # Beyond-paper gauge renormalization: while no node's ||z_hat||
        # exceeds 1 the iteration is 1-homogeneous in (alpha, B) jointly, so
        # a global rescale replays the same trajectory in a different gauge —
        # removing the slow decay into the degenerate z=0 stationary point.
        zmax = jnp.sqrt(jnp.maximum(comm.all_max(znorm2), 1e-30))
        gain = jnp.where(zmax < 1.0, 1.0 / zmax, 1.0)
        alpha_n = alpha_n * gain
        b_n = b_n * gain

    new_state = AdmmState(alpha=alpha_n, b=b_n, g=g, znorm2=znorm2,
                          t=state.t + 1, rho=rho_slots)
    if ledger is not None:
        ledger.end_iteration()
    return new_state, res


def lagrangian(ops: SolverOps, alpha, b, g, rho_slots) -> jax.Array:
    """Dual-space augmented Lagrangian eq. (8), summed over nodes
    (DenseComm layout: leading J axis on every argument):
    L = sum_j [ -a^T K^2 a + sum_s B_s^T C_s + sum_s rho_s/2 C_s^T K C_s ],
    C_s = alpha - K^{-1} G_s."""
    def node(o, alpha_j, b_j, g_j, rho_j):
        ka = o.k @ alpha_j
        kinv_g = o.vec @ ((o.vec.T @ g_j) * _pinv_lam(o.lam)[:, None])
        cres = (alpha_j[:, None] - kinv_g) * o.mask[None, :]
        return (-jnp.sum(ka * ka) + jnp.sum(b_j * cres)
                + 0.5 * jnp.sum(rho_j[None, :] * cres * (o.k @ cres)))

    return jnp.sum(jax.vmap(node)(ops, alpha, b, g, rho_slots))


# ---- chunked, resumable driver -------------------------------------------

@dataclasses.dataclass
class ChunkResult:
    """One driver chunk: the live state plus this chunk's per-iteration
    histories (alpha (c, J, N), Lagrangian/residual/rho2 (c,) each)."""

    state: AdmmState
    alpha_hist: jax.Array
    lagrangian: jax.Array
    primal_residual: jax.Array
    rho_hist: jax.Array
    ckpt_path: Optional[str] = None
    stopped: bool = False          # residual-based early stop fired here
    # communication accounting for THIS chunk (0 without a ledger):
    # point-to-point payload bytes / messages moved by its iterations
    comm_bytes: int = 0
    comm_messages: int = 0


# ---- refresh cadence policies ---------------------------------------------
#
# The serving side republishes a refreshed model from the driver's live
# state (``repro.serve.publisher.stream_chunks``). How often is a policy on
# the DRIVER's chunk stream: any object with ``should_refresh(ChunkResult)
# -> bool``, consulted once per chunk (the final chunk always publishes so
# the served model never lags the finished fit).

class EveryK:
    """Fixed cadence: fire on every k-th chunk."""

    def __init__(self, k: int = 1):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = k
        self._n = 0

    def should_refresh(self, chunk: "ChunkResult") -> bool:
        self._n += 1
        return self._n % self.k == 0


class ResidualImprovement:
    """Residual-driven cadence: fire only when the primal residual has
    IMPROVED by at least ``rel_drop`` (fractional) since the last firing.

    The serving analogue of COKE's communication censoring: a refresh that
    barely moves the iterate is not worth a publish, while a plateau-then-
    drop (e.g. after a rho switch) publishes immediately. The first chunk
    always fires (there is no baseline yet), so a freshly started stream
    serves real coefficients as soon as possible.
    """

    def __init__(self, rel_drop: float = 0.1):
        if not 0.0 <= rel_drop < 1.0:
            raise ValueError(f"rel_drop must be in [0, 1), got {rel_drop}")
        self.rel_drop = rel_drop
        self._last: Optional[float] = None

    def should_refresh(self, chunk: "ChunkResult") -> bool:
        res = float(chunk.primal_residual[-1])
        if self._last is None or res <= (1.0 - self.rel_drop) * self._last:
            self._last = res
            return True
        return False


def resolve_refresh_policy(policy) -> object:
    """Normalize a refresh-cadence spec to a policy object.

    Accepts an int (every k chunks), the string "residual"
    (``ResidualImprovement`` defaults), any object already exposing
    ``should_refresh``, a bare ``ChunkResult -> bool`` callable, or None
    (every chunk).
    """
    if policy is None:
        return EveryK(1)
    if isinstance(policy, int):
        return EveryK(policy)
    if isinstance(policy, str):
        if policy != "residual":
            raise ValueError(f"unknown refresh policy {policy!r}")
        return ResidualImprovement()
    if hasattr(policy, "should_refresh"):
        return policy
    if callable(policy):
        class _Fn:
            def should_refresh(self, chunk, _fn=policy):
                return bool(_fn(chunk))
        return _Fn()
    raise TypeError(f"cannot interpret refresh policy {policy!r}")


def _slot_rho_dense(mask: jax.Array, rho1, rho2) -> jax.Array:
    """(J, S) per-slot rho from a (J, S) float mask."""
    j, s = mask.shape
    r = jnp.concatenate(
        [jnp.full((j, 1), rho1), jnp.full((j, s - 1), rho2)], axis=1)
    return r * mask


@partial(jax.jit, static_argnames=("n_steps", "project", "ledger",
                                   "wire_entries"))
def _dense_chunk(ops: SolverOps, src, rsl, state: AdmmState,
                 rho1_arr, rho2_arr, n_steps: int, project: str,
                 ledger: Optional[CommLedger] = None,
                 wire_entries: int = 0,
                 link_mask: Optional[jax.Array] = None):
    # ledger/wire_entries are static: the ledger records at trace time
    # (hashed by identity — one ledger per run_chunked call, so at most
    # one extra compilation per run vs the unledgered path).
    # link_mask is a TRACED (n_steps, J, S) {0,1} array (or None — the
    # fault-free trace is byte-identical to before the fault layer
    # existed); row i censors iteration i's links, transport-level via
    # FaultyComm and consensus-level via admm_step(slot_mask=...).
    comm = DenseComm(src, rsl, ledger=ledger, wire_entries=wire_entries)
    if link_mask is not None:
        from ..faults.comm import FaultyComm  # lazy: leaf module, no cycle
        base_comm = comm

    def step(carry, i):
        st = carry
        rho_slots = _slot_rho_dense(ops.mask, rho1_arr[i], rho2_arr[i])
        if link_mask is None:
            new, res = admm_step(ops, comm, st, rho_slots, project)
        else:
            sm = link_mask[i]
            new, res = admm_step(ops, FaultyComm(base_comm, sm), st,
                                 rho_slots, project, slot_mask=sm)
        # Theorem-2 pairing: L(alpha^t, Z^t, eta^t) with Z^t generated from
        # the incoming (alpha^t, eta^t) — i.e. this step's g.
        lag = lagrangian(ops, st.alpha, st.b, new.g, rho_slots)
        return new, (new.alpha, lag, res)

    final, (ahist, lhist, rhist) = jax.lax.scan(
        step, state, jnp.arange(n_steps))
    return final, ahist, lhist, rhist


def resolve_rho2(rho2, setup) -> Callable[[int], float]:
    """Normalize a rho2 policy to a host-side ``t -> float``.

    Accepts a ``RhoSchedule``, the string "theorem2" (Assumption-2 constant
    for this setup; also what None means), a plain number, or any callable
    ``t -> rho``.
    """
    if rho2 is None:
        rho2 = "theorem2"
    if isinstance(rho2, str):
        if rho2 != "theorem2":
            raise ValueError(f"unknown rho2 policy {rho2!r}")
        from .admm import theorem2_rho
        r = theorem2_rho(setup)
        return lambda t: r
    if isinstance(rho2, RhoSchedule):
        return lambda t: float(rho2.at(t))
    if callable(rho2):
        return rho2
    r = float(rho2)
    return lambda t: r


def run_chunked(setup, n_iters: int = 30, chunk: int = 10,
                rho1: float = 100.0,
                rho2: Union[RhoSchedule, str, float, Callable, None] = None,
                project: str = "ball", init: str = "local", seed: int = 0,
                alpha0: Optional[jax.Array] = None,
                state: Optional[AdmmState] = None,
                tol: float = 0.0,
                ckpt_dir: Optional[str] = None,
                ckpt_every: int = 1,
                ledger: Optional[CommLedger] = None,
                link_mask: Optional[np.ndarray] = None
                ) -> Iterator[ChunkResult]:
    """Resumable chunked driver for the reference path (Alg. 1).

    Scans ``chunk`` iterations per jitted call and yields a ``ChunkResult``
    after each, so callers can observe/checkpoint/re-tune/publish mid-run.
    The SPMD equivalent is threading (alpha, b, t0) through repeated
    ``repro.core.dkpca.dkpca_distributed`` calls.

    Concurrency contract: the driver itself is single-threaded and holds no
    locks — it must be advanced from ONE thread. Yielded ``ChunkResult``s
    are immutable snapshots (device arrays are never mutated in place), so
    handing ``result.state.alpha`` to another thread — e.g.
    ``repro.serve.publisher.BackgroundPublisher.refresh`` — is safe without
    synchronization on this side; the publisher's own condition variable
    guards the handoff.

    Args:
      setup: ``repro.core.admm.DkpcaSetup``.
      n_iters: total iteration budget (across all chunks, including any
        completed by a resumed ``state``).
      chunk: iterations per jitted chunk (the yield granularity).
      rho1: self-slot rho (ignored when setup.include_self is False).
      rho2: neighbor rho policy — ``RhoSchedule``, "theorem2" (the
        default), a constant, or a callable ``t -> rho``; evaluated
        host-side at chunk boundaries, so switching policy mid-run between
        driver invocations is well-defined (the warm (alpha, B) state
        carries the z warm-start across the switch).
      project: see ``admm_step``.
      init/seed/alpha0: initial alpha when ``state`` is None —
        ``init="local"`` (default) warm-starts z at the pooled local kPCA
        solutions (see module docstring); ``init="paper"`` is the paper's
        unnormalized Gaussian.
      state: resume from a live/restored ``AdmmState`` (its ``t`` counts
        against ``n_iters``).
      tol: early stop when the primal residual drops below this (0 = off).
      ckpt_dir: checkpoint the state every ``ckpt_every`` chunks (and at the
        final chunk) via ``save_state``.
      ledger: a ``repro.obs.CommLedger`` to account per-iteration
        communication into (network-wide bytes for this dense transport);
        each yielded chunk then carries ``comm_bytes``/``comm_messages``.
      link_mask: optional ``(n_iters, J, S)`` {0,1} array censoring links
        per ABSOLUTE iteration index (compiled from a
        ``repro.faults.FaultPlan`` via ``plan.link_mask``); row t is
        applied at iteration t via ``admm_step(slot_mask=...)`` plus a
        transport-level ``FaultyComm`` wrap. None (default) keeps the
        fault-free jit trace unchanged.

    Yields:
      ``ChunkResult`` per chunk; generator ends after the final chunk or
      the first chunk whose result has ``stopped=True``.
    """
    from .admm import initial_alpha  # lazy: admm imports this module
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if ckpt_every < 1:
        raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
    rho2_fn = resolve_rho2(rho2, setup)
    if state is None:
        if alpha0 is None:
            alpha0 = initial_alpha(setup, init, seed)
        state = init_state(alpha0, setup.n_slots)
    ops, comm = dense_parts(setup)
    rho1_eff = float(rho1) if setup.include_self else 0.0
    if link_mask is not None:
        link_mask = np.asarray(link_mask, np.float32)
        j, s = np.asarray(setup.src).shape
        if link_mask.shape != (n_iters, j, s):
            raise ValueError(
                f"link_mask shape {link_mask.shape} != {(n_iters, j, s)}")

    wire_entries = 0
    if ledger is not None:
        # Off-node routing entries = directed edges one exchange moves
        # data over: slot s of node j is remote iff its source is another
        # node AND the slot is valid. Host-side (setup tables are
        # concrete); DenseComm multiplies by payload size at trace time.
        src_np = np.asarray(setup.src)
        mask_np = np.asarray(setup.mask).astype(bool)
        own = np.arange(src_np.shape[0], dtype=src_np.dtype)[:, None]
        wire_entries = int(np.sum((src_np != own) & mask_np))

    m_iters = metrics.counter(
        "solver_iterations_total", "ADMM iterations executed",
        transport="dense")
    m_chunks = metrics.counter(
        "solver_chunks_total", "driver chunks yielded", transport="dense")
    m_bytes = metrics.counter(
        "comm_bytes_total", "point-to-point ADMM payload bytes",
        transport="dense")
    m_res = metrics.gauge(
        "solver_primal_residual", "last observed primal residual")

    t = int(state.t)
    chunk_idx = 0
    while t < n_iters:
        c = min(chunk, n_iters - t)
        with trace.span("solver.rho2", t=t, steps=c):
            rho2_arr = jnp.asarray([rho2_fn(tt) for tt in range(t, t + c)],
                                   jnp.float32)
        rho1_arr = jnp.full((c,), rho1_eff, jnp.float32)
        # The span times trace + dispatch; execution is async (the device
        # is only awaited where a host value is read, e.g. the residual).
        lm_chunk = None
        if link_mask is not None:
            lm_chunk = jnp.asarray(link_mask[t:t + c])
        with trace.span("solver.step", t=t, steps=c):
            state, ahist, lhist, rhist = _dense_chunk(
                ops, comm.src, comm.rsl, state, rho1_arr, rho2_arr, c,
                project, ledger=ledger, wire_entries=wire_entries,
                link_mask=lm_chunk)
        t += c
        chunk_idx += 1
        comm_bytes = comm_msgs = 0
        if ledger is not None:
            ledger.add_iterations(c)
            per = ledger.per_iter
            comm_bytes, comm_msgs = per.bytes * c, per.messages * c
            m_bytes.inc(comm_bytes)
        m_iters.inc(c)
        m_chunks.inc()
        stopped = False
        if tol > 0.0:
            with trace.span("solver.residual", t=t):
                res_last = float(rhist[-1])
            m_res.set(res_last)
            stopped = res_last < tol
        ckpt_path = None
        if ckpt_dir and (chunk_idx % ckpt_every == 0 or t >= n_iters
                         or stopped):
            with trace.span("solver.checkpoint", t=t):
                ckpt_path = save_state(ckpt_dir, state)
        yield ChunkResult(state=state, alpha_hist=ahist, lagrangian=lhist,
                          primal_residual=rhist, rho_hist=rho2_arr,
                          ckpt_path=ckpt_path, stopped=stopped,
                          comm_bytes=comm_bytes, comm_messages=comm_msgs)
        if stopped:
            return


# ---- persistence (repro.checkpoint layout) --------------------------------

def save_state(ckpt_dir: str, state: AdmmState, keep_last: int = 3) -> str:
    """Checkpoint a live ``AdmmState`` (step number == iteration count)."""
    from ..checkpoint import save_checkpoint
    t = int(state.t)
    tree = {"alpha": state.alpha, "b": state.b, "g": state.g,
            "znorm2": state.znorm2, "rho": state.rho}
    return save_checkpoint(ckpt_dir, t, tree,
                           metadata={"kind": "admm_state", "t": t},
                           keep_last=keep_last)


def load_state(ckpt_dir: str, step: Optional[int] = None) -> AdmmState:
    """Restore an ``AdmmState`` checkpoint (latest step by default)."""
    from ..checkpoint import restore_checkpoint
    tree, meta, step = restore_checkpoint(ckpt_dir, step)
    if meta.get("kind") != "admm_state":
        raise ValueError(f"{ckpt_dir} is not an AdmmState checkpoint: {meta}")
    return AdmmState(alpha=tree["alpha"], b=tree["b"], g=tree["g"],
                     znorm2=tree["znorm2"],
                     t=jnp.asarray(int(meta.get("t", step)), jnp.int32),
                     rho=tree["rho"])


__all__ = [
    "AdmmState", "ChunkResult", "DenseComm", "EveryK", "ResidualImprovement",
    "RingComm", "SolverOps", "admm_step", "dense_parts", "init_state",
    "lagrangian", "load_state", "resolve_refresh_policy", "resolve_rho2",
    "run_chunked", "save_state",
]
