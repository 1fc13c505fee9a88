"""Distributed (SPMD) decentralized kernel PCA — paper Alg. 1 on a device
mesh.

Mapping (DESIGN.md §3): network node j == device j on the flattened mesh
axes; the paper's k-nearest-neighbor ring becomes ``jax.lax.ppermute``
shifts, i.e. nearest-neighbor hops on the TPU ICI torus. One program runs on
every node (bulk-synchronous SPMD, exactly the ADMM's communication
structure):

  setup:  r ppermute hops each direction exchange raw X_j (paper's setup
          phase); Gram blocks are computed locally (Pallas kernel on TPU);
          global-centering row-mean statistics use one ring sweep
          (J ppermute steps) + one pmean — the "consensus averaging round".
  iterate (lax.scan):
          2 message rounds per iteration, each 2r ppermutes of N-vectors:
          (alpha_l, K_l^-1 B_l column)  ->  Z-update (eq. 10-11)
          (phi(X_l)^T z_j projections)  ->  alpha/eta updates (eq. 12-13)

The iteration BODY is the shared ``repro.core.solver.admm_step`` — the same
code the reference simulator runs, here over the ``RingComm`` (ppermute)
transport instead of dense indexing.

Per-node per-iteration communication is O(|Omega_j| N) numbers — matching
the paper's §4.2 cost analysis — and is independent of the network size J.

Resumable runs: ``dkpca_distributed(alpha0=..., b0=..., t0=...)`` continues
from a mid-run iterate (the returned ``DistDkpcaResult.b`` plus ``alpha``
is the full restart state; ``t0`` offsets the rho schedule), which is the
SPMD equivalent of the reference path's ``repro.core.solver.run_chunked``
chunk boundaries. Fault tolerance: the ring is re-knit around failed nodes
by re-launching with the survivor mesh (see ``repro.core.topology.reknit``
and tests/test_fault_tolerance.py); state checkpoints via
``repro.checkpoint`` (``repro.core.solver.save_state``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .admm import initial_alpha, local_solution_alpha  # noqa: F401
from .kernels_math import KernelSpec, gram, psd_jitter_eigh, resolve_gamma
from .rho import SAFETY, RhoSchedule, assumption2_rho
from .solver import AdmmState, RingComm, SolverOps, admm_step
from ..obs.comm import CommLedger
from .topology import ring_shifts


@dataclasses.dataclass
class DistDkpcaResult:
    alpha: jax.Array           # (J, N)
    alpha_hist: jax.Array      # (T, J, N)
    primal_residual: jax.Array  # (T,)
    znorm2_hist: jax.Array     # (T, J)
    b: Optional[jax.Array] = None  # (J, N, S) final duals (restart state)


def _ring_recv(v, axes, offset: int, j: int):
    """result[m] = v[(m + offset) % J] over the flattened mesh axes."""
    perm = [((m + offset) % j, m) for m in range(j)]
    return jax.lax.ppermute(v, axes, perm)


def dkpca_distributed(
    x_nodes,
    mesh: Mesh,
    axis_names: Sequence[str] = ("data", "model"),
    hops: int = 2,
    spec: KernelSpec = KernelSpec(),
    center: str = "global",
    include_self: bool = True,
    rho1: float = 100.0,
    rho2: Optional[RhoSchedule] = None,
    n_iters: int = 30,
    seed: int = 0,
    init: str = "local",
    alpha0: Optional[jax.Array] = None,
    b0: Optional[jax.Array] = None,
    t0: int = 0,
    project: str = "ball",
    gamma: Optional[float] = None,
    use_pallas: bool = False,
    message_dtype=None,
    unroll_iters: bool = False,
    ledger: Optional[CommLedger] = None,
    link_mask=None,
) -> DistDkpcaResult:
    """Run decentralized kPCA with one network node per device.

    x_nodes: (J, N, M) with J == prod(mesh axis sizes for axis_names).
    init (used when alpha0 is None): "local" (default, same semantics as
    ``repro.core.admm.initial_alpha``) starts each node at its own local
    kPCA solution — computed INSIDE the node program from the
    eigendecomposition the setup phase already does, so it costs no extra
    communication and warm-starts z at the pooled local components (the
    measured m=24 transient fix, docs/ADMM_CONVERGENCE.md); "paper" is the
    paper's unnormalized Gaussian.
    rho2: neighbor rho schedule; None (default) is the Theorem-2 constant
    of the dense drivers (``repro.core.admm.theorem2_rho``), formed inside
    the node program from each node's own spectrum and one scalar pmax at
    setup.
    b0/t0: resume a run from iteration ``t0`` with duals ``b0`` (J, N, S)
    — pass the previous call's ``result.b``/``result.alpha``; the rho2
    schedule is evaluated at the global iteration indices [t0, t0+n_iters).
    ledger: a ``repro.obs.CommLedger`` accounting PER-NODE wire traffic —
    setup-phase exchanges land in ``ledger.setup``, the iterate phase in
    ``ledger.per_iter`` (recorded at trace time; see repro.obs.comm).
    link_mask: optional (n_iters, J, S) {0,1} per-iteration slot mask
    censoring lost/delayed links (repro.faults.FaultPlan.link_mask) —
    same COKE-style semantics as the dense driver: the received columns
    are zeroed at the transport AND the censored slots leave the
    consensus weights (admm_step(slot_mask=...)), so the SPMD trajectory
    matches the dense path under the same mask (parity-tested in
    tests/test_fault_injection.py). Node DROPOUT is not handled here:
    the mesh is fixed for the life of one call, so recovery is a
    re-launch on the survivor mesh with the shrunk state
    (repro.faults.shrink_state) passed via alpha0/b0/t0.
    """
    axis_names = tuple(axis_names)
    j_nodes = int(np.prod([mesh.shape[a] for a in axis_names]))
    x_nodes = jnp.asarray(x_nodes, jnp.float32)
    jj, n, m = x_nodes.shape
    assert jj == j_nodes, (jj, j_nodes)
    assert center in ("global", "none")
    theorem2 = rho2 is None
    if gamma is None:
        g = resolve_gamma(spec, x_nodes.reshape(jj * n, m))
    else:
        g = jnp.asarray(gamma, jnp.float32)
    local_init = False
    if alpha0 is None:
        if init == "local":
            # placeholder shard_map operand; overwritten per-node by the
            # local kPCA solution once K_j's eigendecomposition exists.
            local_init = True
            alpha0 = jnp.zeros((jj, n), jnp.float32)
        elif init == "paper":
            alpha0 = jax.random.normal(jax.random.PRNGKey(seed), (jj, n),
                                       jnp.float32)
        else:
            raise ValueError(f"unknown init {init!r}")
    if theorem2:
        rho2_arr = jnp.zeros((n_iters,), jnp.float32)  # set in the program
    else:
        rho2_arr = jnp.asarray(
            [rho2.at(t) for t in range(t0, t0 + n_iters)], jnp.float32)
    rho_self = float(rho1) if include_self else 0.0

    offsets = ring_shifts(hops)                 # [-r..-1, 1..r]
    s_slots = len(offsets) + 1                  # slot 0 = self
    # rev_static[d]: for in-slot d (offset o), the sender's out-slot index
    # pointing back at us = slot of offset -o (in the same 0=self layout).
    slot_of = {0: 0}
    slot_of.update({o: i + 1 for i, o in enumerate(offsets)})
    rev_static = [slot_of[-o] for o in offsets]

    if b0 is None:
        b0 = jnp.zeros((jj, n, s_slots), jnp.float32)
    else:
        b0 = jnp.asarray(b0, jnp.float32)
        assert b0.shape == (jj, n, s_slots), (b0.shape, (jj, n, s_slots))

    fn = partial(_node_fn, axes=axis_names, j_nodes=j_nodes,
                 offsets=tuple(offsets), rev_static=tuple(rev_static),
                 s_slots=s_slots, spec=spec, center=center,
                 rho_self=rho_self, include_self=include_self,
                 theorem2=theorem2, project=project, n_iters=n_iters, t0=t0,
                 local_init=local_init, use_pallas=use_pallas,
                 message_dtype=message_dtype, unroll_iters=unroll_iters,
                 ledger=ledger)
    in_specs = [P(axis_names, None, None), P(axis_names, None),
                P(axis_names, None, None), P(), P()]
    args = [x_nodes, alpha0, b0, g, rho2_arr]
    if link_mask is not None:
        # extra sharded operand ONLY when faults are injected: the
        # fault-free program stays byte-identical to the pre-fault trace.
        lm = jnp.asarray(link_mask, jnp.float32)
        assert lm.shape == (n_iters, jj, s_slots), \
            (lm.shape, (n_iters, jj, s_slots))
        in_specs.append(P(None, axis_names, None))
        args.append(lm)
    shmap = jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(axis_names, None), P(axis_names, None, None),
                   P(None, axis_names, None), P(None), P(None, axis_names)),
        # Pallas calls inside the body produce ShapeDtypeStructs without vma
        # annotations; disable the varying-mesh-axes checker for this map.
        check_vma=False,
    )
    with mesh:
        alpha, b_f, hist, res, zn = jax.jit(shmap)(*args)
    if ledger is not None:
        ledger.add_iterations(n_iters)
    return DistDkpcaResult(alpha=alpha, alpha_hist=hist, primal_residual=res,
                           znorm2_hist=zn, b=b_f)


def _node_fn(x_blk, a_blk, b_blk, g, rho2_arr, *extra, axes, j_nodes,
             offsets, rev_static, s_slots, spec, center, rho_self,
             include_self, theorem2, project, n_iters, t0, local_init=False,
             use_pallas=False, message_dtype=None, unroll_iters=False,
             ledger=None):
    """Per-node SPMD program. x_blk: (1, N, M); a_blk: (1, N);
    b_blk: (1, N, S); extra: optionally one (n_iters, 1, S) per-node
    fault link mask (this node's censored slots per iteration).

    message_dtype (e.g. jnp.bfloat16): §Perf knob — cast per-iteration
    ppermute payloads (alpha, K^-1 B columns, z-projections) to a narrower
    dtype before the wire, halving ICI bytes; accumulation stays fp32."""
    x = x_blk[0]
    alpha = a_blk[0]
    b0 = b_blk[0]
    n = x.shape[0]
    lm = extra[0][:, 0] if extra else None               # (n_iters, S)

    def varying_zeros(shape):
        # fresh zeros are replicated; a scan carry that starts from them
        # must have the type of the per-node values: varying over the axes
        return jax.lax.pcast(jnp.zeros(shape, jnp.float32), axes,
                             to="varying")

    def gram_fn(xa, xb):
        if use_pallas:
            from ..kernels.gram import gram_op
            return gram_op(spec, xa, xb, gamma=g)
        return gram(spec, xa, xb, gamma=g)

    # ---- setup: exchange raw data with r-hop neighbors (paper Alg. 1) ----
    xs = [x] + [_ring_recv(x, axes, o, j_nodes) for o in offsets]
    xs = jnp.stack(xs)                                     # (S, N, M)
    itemsize = jnp.dtype(x.dtype).itemsize
    if ledger is not None:
        ledger.record_exchange(len(offsets) * x.size * itemsize, len(offsets))

    # ---- global centering statistics: one ring sweep + pmean -------------
    if center == "global":
        if ledger is not None:
            # The sweep's scan body traces once but represents j_nodes
            # single-hop rotations of x, plus one scalar pmean and the
            # m_slots neighbor shifts — recorded explicitly here since
            # _ring_recv has no per-call hook inside the scan.
            ledger.record_exchange(j_nodes * x.size * itemsize, j_nodes)
            ledger.record_collective(jnp.dtype(jnp.float32).itemsize)
            ledger.record_exchange(len(offsets) * n * itemsize, len(offsets))
        def sweep(carry, _):
            rot, macc, mubar = carry
            kb = gram_fn(x, rot)                           # (N, N)
            macc = macc + jnp.sum(kb, axis=1)
            mubar = mubar + jnp.sum(kb)
            rot = _ring_recv(rot, axes, 1, j_nodes)
            return (rot, macc, mubar), None

        zero_n = varying_zeros((n,))
        zero_s = varying_zeros(())
        (_, macc, mubar), _ = jax.lax.scan(
            sweep, (x, zero_n, zero_s), None, length=j_nodes)
        m_own = macc / (j_nodes * n)                       # m(x) for own rows
        mu_bar = jax.lax.pmean(mubar / (j_nodes * n * n), axes)
        m_slots = [m_own] + [_ring_recv(m_own, axes, o, j_nodes)
                             for o in offsets]
        m_slots = jnp.stack(m_slots)                       # (S, N)
    else:
        m_slots = jnp.zeros((s_slots, n), jnp.float32)
        mu_bar = jnp.zeros((), jnp.float32)

    # ---- Gram blocks over slot data (Pallas hotspot on TPU) --------------
    xflat = xs.reshape(s_slots * n, -1)
    kfull = gram_fn(xflat, xflat)
    if center == "global":
        mf = m_slots.reshape(s_slots * n)
        kfull = kfull - mf[:, None] - mf[None, :] + mu_bar
    kcross = kfull.reshape(s_slots, n, s_slots, n).transpose(0, 2, 1, 3)

    k_loc = kcross[0, 0]
    lam, vec = psd_jitter_eigh(k_loc)
    if local_init:
        # initial_alpha(setup, "local") semantics: each node's own top
        # kernel principal component, v1 / sqrt(lam1), so ||w_j|| = 1.
        alpha = local_solution_alpha(lam, vec)

    n_nbr = len(offsets)
    maskf = jnp.concatenate(
        [jnp.full((1,), 1.0 if include_self else 0.0, jnp.float32),
         jnp.ones((n_nbr,), jnp.float32)])
    if theorem2:
        # theorem2_rho of the dense drivers: the largest per-node
        # Assumption-2 bound over the network, one scalar pmax
        bound = assumption2_rho(lam, jnp.sum(maskf))
        rho2_arr = jnp.full_like(rho2_arr, jax.lax.pmax(bound, axes) * SAFETY)
        if ledger is not None:
            ledger.record_collective(jnp.dtype(jnp.float32).itemsize)
    ops = SolverOps(kcross=kcross, k=k_loc, lam=lam, vec=vec, mask=maskf)
    comm = RingComm(axes, j_nodes, offsets, rev_static,
                    message_dtype=message_dtype, ledger=ledger)

    def iteration(carry, t):
        st = carry
        rho_slots = jnp.concatenate(
            [jnp.full((1,), rho_self), jnp.full((n_nbr,), rho2_arr[t])])
        if lm is None:
            new, res = admm_step(ops, comm, st, rho_slots, project)
        else:
            from ..faults.comm import FaultyComm  # lazy: leaf, no cycle
            sm = lm[t]
            new, res = admm_step(ops, FaultyComm(comm, sm), st, rho_slots,
                                 project, slot_mask=sm)
        return new, (new.alpha, res, new.znorm2)

    state0 = AdmmState(
        alpha=alpha, b=b0, g=varying_zeros((n, s_slots)),
        znorm2=varying_zeros(()), t=jnp.asarray(t0, jnp.int32),
        rho=varying_zeros((s_slots,)))
    final, (ahist, rhist, znhist) = jax.lax.scan(
        iteration, state0, jnp.arange(n_iters), unroll=unroll_iters)
    return (final.alpha[None], final.b[None], ahist[:, None, :], rhist,
            znhist[:, None])
