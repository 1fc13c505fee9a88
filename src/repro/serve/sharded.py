"""Multi-device sharded kPCA projection serving with adaptive routing.

The out-of-sample score is a sum over support points (paper §1), so it
shards embarrassingly along EITHER operand of the kernel matrix — and the
two choices have opposite communication shapes:

  * **model-parallel** (``"mp"``): each device holds one slice of a
    ``ShardedFittedKpca`` — a contiguous block of support rows and the
    matching dual-coefficient rows — and computes the raw partial

        P_j = K(X_query, X_j) @ coefs_ext_j          # (B, C+1)

    with the fused projection kernel
    (``repro.kernels.project.project_partial_op``; the extra column is the
    raw kernel row-sum via the indicator column). Partials are
    ``psum``-reduced over the shard mesh axis and the GLOBAL centering
    terms (row-mean weight, bias), which depend on the full support set,
    are applied exactly once after the reduction
    (``repro.core.oos.finalize_partial_scores``). Per-query traffic is one
    (B, C+1) all-reduce regardless of support-set size — the communication
    shape COKE/Balcan-style distributed kPCA exploits. Wins when the
    support set is large relative to the batch.

  * **data-parallel** (``"dp"``): the model is replicated on every device
    and the QUERY rows are partitioned instead. No cross-device reduction
    at all — each device finishes its own rows, including the centering
    epilogue. Wins at large batches: the per-device kernel-matrix
    intermediate is 1/S the size, so it stays cache-resident where the
    single-device one spills.

  * **single-device** (``"single"``): the same-math loop-over-shards
    reduction on one device. Wins at small/compressed support sets, where
    any multi-device choreography costs more than it saves — and is the
    only choice when the host exposes fewer devices than shards.

``CrossoverTable`` picks between them per slab, keyed on (slab rows,
support rows); its defaults are measured on the CI container and
``measure_crossover`` re-measures them for a concrete model/mesh/host.
``ShardedRouter`` owns the dispatch hot path for ``KpcaEngine``: per-policy
donated jit entry points and a per-model-version placement cache, so
steady-state serving never re-transfers the model (the per-drain
replication that made BENCH_9's shards4 rows LOSE to shards1 — see
docs/PERFORMANCE.md, "sharded drain anatomy").

Live updates: a sharded model refreshes per shard
(``repro.core.oos.refresh_shard_coefficients`` — per-shard cached
kernel-mean stats, global centering rebuilt post-hoc) and is republished as
ONE atomic ``ModelHandle`` swap, so this module never sees a model whose
shards disagree about the version; the placement cache is keyed on that
version, so a publish invalidates it atomically too.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.kernels_math import HIGHEST, gram
from ..core.oos import ShardedFittedKpca, finalize_partial_scores
from ..launch.mesh import make_serving_mesh, mesh_shardings, replicate_on_mesh
from ..obs import metrics, trace

POLICIES = ("mp", "dp", "single")

# One dispatch's device result plus the routing decision that produced it
# (the engine's drain surfaces the policy in stats/trace without another
# router round trip).
ShardedScores = collections.namedtuple("ShardedScores", "scores policy")


def _shard_partial(spec, xq, xs, coefs_ext, gamma, use_pallas, interpret):
    """One shard's raw (B, C+1) partial: K(xq, xs) @ coefs_ext."""
    if use_pallas:
        from ..kernels.project import project_partial_op
        return project_partial_op(spec, xq, xs, coefs_ext, gamma=gamma,
                                  interpret=interpret)
    return jnp.matmul(gram(spec, xq, xs, gamma=gamma), coefs_ext,
                      precision=HIGHEST)


def _pow2(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class CrossoverTable:
    """Routing decision table: (slab rows, support rows) -> policy.

    ``table`` holds MEASURED winners keyed by pow2-bucketed
    (rows, support) pairs (``measure_crossover`` fills it for a concrete
    model/mesh/host). Unmeasured keys fall back to two thresholds whose
    defaults come from the 1-core CI container sweep behind BENCH_10:

      * support <= ``single_max_support``: the single-device reduction wins
        — at small/compressed support sets (e.g. the lm64 rows) every
        multi-device choreography costs more than it saves;
      * above that, slabs with >= ``dp_min_rows`` rows go data-parallel
        (per-device kernel intermediates stay cache-resident), smaller
        slabs go model-parallel (support slicing is the only useful cut).

    Data-parallel additionally requires the row count to divide evenly
    over the shards (``shard_map`` partitions the leading axis exactly);
    pow2 slab buckets make that automatic on pow2 shard counts, and
    ``choose`` degrades to "mp"/"single" otherwise.
    """

    single_max_support: int = 2048
    dp_min_rows: int = 2048
    table: Mapping[Tuple[int, int], str] = \
        dataclasses.field(default_factory=dict)

    def choose(self, n_rows: int, n_support: int, n_shards: int, *,
               has_mesh: bool) -> str:
        if not has_mesh or n_shards <= 1:
            return "single"
        policy = self.table.get((_pow2(n_rows), _pow2(n_support)))
        if policy is None:
            if n_support <= self.single_max_support:
                policy = "single"
            elif n_rows >= self.dp_min_rows:
                policy = "dp"
            else:
                policy = "mp"
        if policy == "dp" and n_rows % n_shards:
            policy = "mp" if n_support > self.single_max_support \
                else "single"
        return policy


class ShardedRouter:
    """Policy-routed, placement-cached dispatch for sharded serving.

    Owns the three pieces the engine's sharded hot path needs:

      * ``choose``: the per-slab routing decision (``CrossoverTable``, or
        a forced policy for benchmarking/parity tests);
      * a per-policy jitted entry point, compiled once per slab bucket with
        the query slab donated (``donate_argnums``) exactly like the
        single-device path;
      * a placement cache keyed on the model VERSION: "mp" wants the
        per-shard arrays one slice per device, "dp" wants the whole model
        replicated, and both placements are paid once per publish instead
        of once per drain — re-transferring the model every call is what
        made sharded serving lose to one shard before this layer existed.

    Thread-safety: ``dispatch`` runs on the engine's single device-runner
    thread (or under its dispatch lock), so the internal lock only guards
    the placement dict against the measure/warmup paths; a racy duplicate
    placement is wasted work, never wrong results.
    """

    _GROUPS = {"mp": "sliced", "dp": "replicated", "single": None}

    def __init__(self, mesh, *, use_pallas: bool = False,
                 interpret: Optional[bool] = None, policy: str = "auto",
                 crossover: Optional[CrossoverTable] = None,
                 donate: bool = True):
        if policy != "auto" and policy not in POLICIES:
            raise ValueError(f"policy must be 'auto' or one of {POLICIES}, "
                             f"got {policy!r}")
        self.mesh = mesh
        self.policy = policy
        self.crossover = crossover if crossover is not None \
            else CrossoverTable()
        self.donate = donate
        self._use_pallas = use_pallas
        self._interpret = interpret
        self._lock = threading.Lock()
        self._placed: Dict[str, Tuple[int, ShardedFittedKpca]] = {}
        self.n_placements = 0              # placement-cache fill count
        self._entries: Dict[str, object] = {}
        self._m_routed = {p: metrics.counter(
            "serve_routing_total",
            "Sharded slabs dispatched, by routing policy", policy=p)
            for p in POLICIES}

    # -- routing ------------------------------------------------------------

    def choose(self, n_rows: int, model: ShardedFittedKpca) -> str:
        """The policy this slab will dispatch under (deterministic in
        (rows, model) — warmup relies on that to pre-compile exactly the
        programs traffic will hit)."""
        has_mesh = self.mesh is not None
        if self.policy == "auto":
            return self.crossover.choose(n_rows, int(model.n_support),
                                         model.n_shards, has_mesh=has_mesh)
        if not has_mesh or model.n_shards <= 1:
            return "single"
        if self.policy == "dp" and n_rows % model.n_shards:
            return "mp"
        return self.policy

    # -- placement cache ----------------------------------------------------

    def _place(self, model: ShardedFittedKpca, version: int, policy: str):
        group = self._GROUPS[policy]
        if group is None:        # single-device: the model's home placement
            return model
        with self._lock:
            hit = self._placed.get(group)
            if hit is not None and hit[0] == version:
                return hit[1]
        # Build OUTSIDE the lock: device_put moves real bytes, and a racy
        # duplicate placement is idempotent (same values, last write wins).
        placed = place_sharded_model(model, self.mesh) \
            if group == "sliced" else replicate_on_mesh(model, self.mesh)
        with self._lock:
            self._placed[group] = (version, placed)
            self.n_placements += 1
        return placed

    def placed_devices(self, policy: str) -> frozenset:
        """Devices holding the cached placement ``policy`` dispatches to
        (empty before its first dispatch, and for "single")."""
        group = self._GROUPS[policy]
        with self._lock:
            hit = self._placed.get(group) if group is not None else None
        if hit is None:
            return frozenset()
        return frozenset(hit[1].x_support.sharding.device_set)

    # -- jitted entry points ------------------------------------------------

    def _build(self, policy: str):
        mesh, up, ip = self.mesh, self._use_pallas, self._interpret

        if policy == "mp":
            def f(m, xq):
                parts = _partials_shard_map(m, xq, mesh, up, ip)
                return finalize_partial_scores(parts, m.row_mean_coef,
                                               m.bias, m.n_support)
        elif policy == "dp":
            def f(m, xq):
                return _scores_data_parallel(m, xq, mesh, up, ip)
        else:
            def f(m, xq):
                parts = _partials_local(m, xq, up, ip)
                return finalize_partial_scores(parts, m.row_mean_coef,
                                               m.bias, m.n_support)
        if self.donate:
            return jax.jit(f, donate_argnums=(1,))
        return jax.jit(f)

    def dispatch(self, model: ShardedFittedKpca, version: int, xq,
                 policy: Optional[str] = None) -> ShardedScores:
        """Route one staged slab: pick/honor the policy, fetch the cached
        placement for this model version, call the policy's jitted entry
        point (slab donated). Returns the DEVICE scores plus the policy —
        the blocking device->host read stays with the caller so pipelined
        drains overlap it with the next dispatch."""
        if policy is None:
            policy = self.choose(int(xq.shape[0]), model)
        placed = self._place(model, version, policy)
        entry = self._entries.get(policy)
        if entry is None:
            entry = self._entries.setdefault(policy, self._build(policy))
        with trace.span("serve.shard_dispatch", policy=policy,
                        rows=int(xq.shape[0])):
            out = entry(placed, xq)
        self._m_routed[policy].inc()
        return ShardedScores(out, policy)


def place_sharded_model(model: ShardedFittedKpca,
                        mesh) -> ShardedFittedKpca:
    """Pin one ``ShardedFittedKpca`` onto a 1-D mesh, field-precise: the
    per-shard arrays (leading axis S — support slices, coefficient rows,
    cached kernel means) get one slice per device; the global centering
    terms and scalars are replicated. Field names, not a leading-dim
    heuristic: ``bias`` is (C,) and C can coincide with S."""
    sliced, replicated = mesh_shardings(mesh)

    def put(leaf, sharding):
        return None if leaf is None else jax.device_put(leaf, sharding)

    return dataclasses.replace(
        model,
        x_support=put(model.x_support, sliced),
        coefs_ext=put(model.coefs_ext, sliced),
        k_row_mean=put(model.k_row_mean, sliced),
        row_mean_coef=put(model.row_mean_coef, replicated),
        bias=put(model.bias, replicated),
        gamma=put(model.gamma, replicated),
        k_grand_mean=put(model.k_grand_mean, replicated))


def measure_crossover(model: ShardedFittedKpca, *, mesh=None,
                      row_buckets=(256, 1024, 4096), reps: int = 3,
                      use_pallas: bool = False,
                      interpret: Optional[bool] = None) -> CrossoverTable:
    """Time every feasible policy at each row bucket for THIS model on
    THIS host and return a ``CrossoverTable`` whose measured entries pin
    the winners (unmeasured keys keep the threshold defaults).

    Slabs are zeros: the kernel math is data-independent in cost, and the
    measurement wants placement + compute + gather, exactly what a drain
    pays. Compile time is excluded by an untimed first call per policy.
    """
    if mesh is None:
        mesh = make_serving_mesh(model.n_shards)
    router = ShardedRouter(mesh, use_pallas=use_pallas, interpret=interpret,
                           donate=False)
    support_key = _pow2(int(model.n_support))
    table = {}
    for rows in row_buckets:
        xq = np.zeros((int(rows), model.n_features), np.float32)
        best, best_t = "single", float("inf")
        for policy in POLICIES:
            if policy != "single" and mesh is None:
                continue
            if policy == "dp" and rows % model.n_shards:
                continue
            np.asarray(router.dispatch(model, 0, xq, policy).scores)
            t = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                np.asarray(router.dispatch(model, 0, xq, policy).scores)
                t = min(t, time.perf_counter() - t0)
            if t < best_t:
                best, best_t = policy, t
        table[(_pow2(int(rows)), support_key)] = best
    return CrossoverTable(table=table)


def project_sharded(model: ShardedFittedKpca, x_query: jax.Array, *,
                    mesh=None, axis_name: str = "shard",
                    use_pallas: bool = False,
                    interpret: Optional[bool] = None,
                    policy: str = "mp",
                    crossover: Optional[CrossoverTable] = None) -> jax.Array:
    """Sharded centered out-of-sample scores: (B, M) -> (B, C).

    Args:
      model: sharded artifact (see ``repro.core.oos.shard_fitted``).
      x_query: (B, M) query batch.
      mesh: 1-D ``jax.sharding.Mesh`` whose single axis has size
        ``model.n_shards``. None = build one over the first n_shards local
        devices, falling back to the single-device reduction when the
        machine has fewer devices than shards.
      axis_name: mesh axis to reduce over (when building the default mesh).
      use_pallas: per-shard partials via the fused Pallas kernel instead of
        the dense jnp path.
      interpret: forwarded to the Pallas wrapper.
      policy: "mp" (default — queries replicated, support sharded, psum),
        "dp" (query rows sharded, model replicated, no reduction),
        "single" (loop-over-shards on one device), or "auto" (route via
        ``crossover``). Infeasible choices (no mesh; "dp" with a row count
        that doesn't divide over the shards) degrade to the same-math
        fallback instead of raising.
      crossover: routing table for ``policy="auto"`` (None: defaults).

    Returns:
      (B, C) float32 scores, equal to ``oos.project(gather_fitted(model))``
      to fp32 tolerance for every policy (tests/test_sharded_serving.py).
    """
    x_query = jnp.asarray(x_query)
    if policy != "auto" and policy not in POLICIES:
        raise ValueError(f"policy must be 'auto' or one of {POLICIES}, "
                         f"got {policy!r}")
    if mesh is None:
        mesh = make_serving_mesh(model.n_shards, axis_name)
    if policy == "auto":
        policy = (crossover if crossover is not None else CrossoverTable()) \
            .choose(int(x_query.shape[0]), int(model.n_support),
                    model.n_shards, has_mesh=mesh is not None)
    if policy == "dp" and mesh is not None \
            and x_query.shape[0] % model.n_shards == 0:
        return _scores_data_parallel(model, x_query, mesh, use_pallas,
                                     interpret)
    if mesh is None or policy == "single":
        partials = _partials_local(model, x_query, use_pallas, interpret)
    else:                                 # "mp" (and infeasible-"dp")
        partials = _partials_shard_map(model, x_query, mesh, use_pallas,
                                       interpret)
    return finalize_partial_scores(partials, model.row_mean_coef,
                                   model.bias, model.n_support)


def _partials_shard_map(model: ShardedFittedKpca, x_query: jax.Array, mesh,
                        use_pallas: bool,
                        interpret: Optional[bool]) -> jax.Array:
    """psum-reduced (B, C+1) partials over the mesh's shard axis."""
    (axis_name,) = mesh.axis_names
    spec = model.spec

    def fn(xs, ae, xq, g):
        # xs (1, Lp, M), ae (1, Lp, C+1): this device's shard slice.
        part = _shard_partial(spec, xq, xs[0], ae[0], g, use_pallas,
                              interpret)
        return jax.lax.psum(part, axis_name)

    f = jax.shard_map(fn, mesh=mesh,
                  in_specs=(P(axis_name), P(axis_name), P(None, None), P()),
                  out_specs=P(None, None), check_vma=False)
    return f(model.x_support, model.coefs_ext, x_query, model.gamma)


def _scores_data_parallel(model: ShardedFittedKpca, x_query: jax.Array,
                          mesh, use_pallas: bool,
                          interpret: Optional[bool]) -> jax.Array:
    """Data-parallel FULL scores: query rows partitioned over the mesh,
    model replicated, each device running the complete loop-over-shards
    reduction AND the centering epilogue on its own rows. No psum — row
    independence of the score math is what makes the cut free."""
    (axis_name,) = mesh.axis_names
    spec, n_shards = model.spec, model.n_shards
    n_support = model.n_support

    def fn(xs, ae, xq, g, rmc, bias):
        total = jnp.zeros((xq.shape[0], ae.shape[2]), jnp.float32)
        for j in range(n_shards):
            total = total + _shard_partial(spec, xq, xs[j], ae[j], g,
                                           use_pallas, interpret)
        return finalize_partial_scores(total, rmc, bias, n_support)

    f = jax.shard_map(fn, mesh=mesh,
                  in_specs=(P(), P(), P(axis_name, None), P(), P(), P()),
                  out_specs=P(axis_name, None), check_vma=False)
    return f(model.x_support, model.coefs_ext, x_query, model.gamma,
             model.row_mean_coef, model.bias)


def _partials_local(model: ShardedFittedKpca, x_query: jax.Array,
                    use_pallas: bool,
                    interpret: Optional[bool]) -> jax.Array:
    """Single-device reduction: loop shards, sum partials (== psum)."""
    spec = model.spec
    total = jnp.zeros((x_query.shape[0], model.n_components + 1),
                      jnp.float32)
    for j in range(model.n_shards):
        total = total + _shard_partial(
            spec, x_query, model.x_support[j], model.coefs_ext[j],
            model.gamma, use_pallas, interpret)
    return total


__all__ = ["CrossoverTable", "POLICIES", "ShardedRouter", "ShardedScores",
           "measure_crossover", "place_sharded_model", "project_sharded"]
