"""Batched kPCA projection-serving engine (fit once, serve many).

The serving workload is the mirror image of ``DecodeEngine``: stateless
per-query math instead of a KV cache, so the engine's whole job is shaping
traffic for the compiled step. Variable-size requests are packed head-to-
tail into fixed-width slabs and padded up to POWER-OF-TWO shape buckets, so
a bounded set of compiled programs (log2(max_batch) of them) serves any
request mix with zero recompiles in steady state — the classic bucketing
trick from LM serving applied to kernel projection. The queue/bucket/slab
machinery itself lives in ``repro.serve.batching`` (shared with the decode
engine).

The request path is an ASYNC pipeline: ``submit`` returns a
``concurrent.futures`` future immediately; a background flusher thread
(``start``/``close``) drains the queue on a size-OR-deadline trigger and
resolves the futures, so query batching overlaps with callers' work the
same way the solver overlaps computation with communication. ``flush`` is
the synchronous drain (same packing, same math — the async path is
result-exact against it), and ``project_many`` the one-call convenience.

Guarantees and knobs:
  * results are exactly what ``repro.core.oos.project`` returns for each
    request alone — padding rows are sliced off and row-wise kernel math
    makes valid rows independent of them (asserted to float32 resolution in
    tests/test_kpca_engine.py; the only packing residue is XLA choosing a
    different gemm code path per slab shape, <= 4e-9 observed);
  * admission control: ``queue_factor=k`` bounds the queue at
    ``max_batch * k`` rows — beyond it ``submit`` rejects
    (``QueueFullError``) or sheds the oldest queued requests, per
    ``cfg.admission``; counters surface in ``EngineStats``;
  * ``use_pallas`` routes through the fused Pallas projection kernel;
  * ``query_dtype=jnp.bfloat16`` halves query-slab HBM traffic (accumulation
    stays fp32 inside the kernel) for throughput-bound fleets;
  * per-request latency/queue-wait and queries/s accounting built in
    (served straight into benchmarks/bench_serve_async.py).
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import itertools
import threading
import time
import warnings
from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import oos
from ..core.oos import FittedKpca, ShardedFittedKpca
from ..faults.errors import DeadlineExceededError
from ..obs import metrics, trace
from .batching import (EngineStats, FlushSlots, QueueFullError,
                       RequestFuture, RequestQueue, RequestStats, SlabArena,
                       SlotFuture, pack_slabs, pow2_buckets)
from .publisher import ModelHandle
from .sharded import ShardedRouter, ShardedScores

# Donation is declared unconditionally on the serve entry points; backends
# that cannot reuse the query slab's buffer for the output (CPU: shapes
# differ) silently fall back to a copy, which XLA reports per compiled
# shape. That fallback is this engine's documented behavior, not a bug to
# surface on every warmup — keep the filter as narrow as the message.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def drains_inline(platform: str) -> bool:
    """Whether the background flusher runs each whole drain itself on a
    model held by devices of ``platform``, with no device-runner thread.

    On CPU a jit call blocks on compute inline, so a device-runner thread
    lets the flusher pack the next drain while this one computes. On TPU
    and GPU the call returns before the program runs: the runner overlaps
    nothing there, and only adds a thread hop and a polled hold to every
    drain."""
    return platform != "cpu"


def _platform(model, mesh) -> str:
    """Platform of the devices holding ``model`` (the mesh's when given);
    a model on the host goes where jit puts it, the default backend."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    devices = getattr(model.x_support, "devices", None)
    if devices is None:
        return jax.default_backend()
    return next(iter(devices())).platform


@dataclasses.dataclass
class KpcaServeConfig:
    max_batch: int = 128          # widest bucket = compiled slab width
    min_bucket: int = 8           # narrowest bucket (absorbs tiny tails)
    use_pallas: bool = False      # fused Pallas kernel (interpret off-TPU)
    query_dtype: Any = None       # e.g. jnp.bfloat16 for cheaper slabs
    interpret: Optional[bool] = None  # forwarded to the Pallas wrapper
    # -- async flusher / admission control --------------------------------
    queue_factor: Optional[int] = None  # queue bound = max_batch * k rows;
    #                                     None = unbounded, no admission
    admission: str = "reject"     # "reject" new or "shed" oldest when full
    flush_max_wait_s: float = 0.005   # deadline trigger: max queue wait of
    #                                   the oldest request before a flush
    flush_min_queries: Optional[int] = None  # size trigger (None: max_batch)
    flush_eager: bool = True      # idle flusher drains on ANY queued work
    #                               instead of sleeping out the deadline;
    #                               batching still emerges under load (the
    #                               queue fills while a flush is in flight)
    flush_coalesce_s: float = 0.0002  # pipelined-mode arrival damper: while
    #                               a previous drain still occupies the
    #                               device runner, keep waiting in slices of
    #                               this quantum as long as rows keep
    #                               arriving, so one wave of submitters
    #                               drains as one slab. Only charged when
    #                               the wait is free (device busy); an idle
    #                               pipeline never waits (0: off)
    # -- hot-path plumbing (docs/PERFORMANCE.md) ---------------------------
    donate: bool = True           # dispatch via donate_argnums entry points
    warmup: bool = True           # compile all pow2 buckets at start()
    arena_factor: int = 4         # staging ring >= max_batch * factor rows
    pipeline_depth: int = 2       # max in-flight drains when the flusher
    #                               pipelines resolve through the device-
    #                               runner thread (fail-fast configs only)
    # -- sharded routing (docs/PERFORMANCE.md: sharded drain anatomy) ------
    routing: str = "auto"         # sharded models: "auto" routes per slab
    #                               via the crossover table; "mp"/"dp"/
    #                               "single" force one policy
    crossover: Any = None         # CrossoverTable override for "auto"
    #                               (None: container-measured defaults;
    #                               repro.serve.sharded.measure_crossover
    #                               builds a host-specific one)
    # -- fault tolerance (docs/FAULT_TOLERANCE.md) -------------------------
    max_retries: int = 0          # extra serve attempts per drain; 0 keeps
    #                               the fail-fast contract (a failed batch
    #                               fails exactly its own futures)
    retry_backoff_s: float = 0.02     # base backoff, doubled per attempt
    #                                   (skipped when on_fault healed it)
    request_deadline_s: Optional[float] = None  # submit -> serve budget;
    #                               expired requests fail with
    #                               DeadlineExceededError instead of being
    #                               served late (None = no deadline)

    def buckets(self) -> List[int]:
        """Power-of-two widths: min_bucket, 2*min_bucket, ..., max_batch."""
        return pow2_buckets(self.min_bucket, self.max_batch)

    def queue_capacity(self) -> Optional[int]:
        if self.queue_factor is None:
            return None
        if self.queue_factor < 1:
            raise ValueError(
                f"queue_factor must be >= 1, got {self.queue_factor}")
        return self.max_batch * self.queue_factor


class KpcaEngine:
    """Micro-batching projection server over a fitted kPCA artifact.

    Accepts either a single-device ``FittedKpca`` (scored via
    ``repro.core.oos.project``) or a multi-device ``ShardedFittedKpca``,
    dispatched through a ``repro.serve.sharded.ShardedRouter``: each slab
    is routed model-parallel (support sharded, queries replicated, psum),
    data-parallel (query rows sharded, no reduction), or single-device per
    ``cfg.routing`` and the measured crossover table, against a
    per-version cached device placement of the model. The
    batching/bucketing layer is identical for both model kinds, so the
    engine's traffic shaping composes with device sharding unchanged.

    Request API: ``submit`` enqueues and returns a future; results arrive
    when a drain happens — synchronously via ``flush`` (or ``project_many``),
    or from the background flusher thread between ``start`` and ``close``
    (the engine is also a context manager doing exactly that). Both drains
    run the same packing and the same compiled programs, so async results
    are exact against the synchronous path.

    Live updates: the engine reads its model THROUGH a versioned
    ``repro.serve.publisher.ModelHandle`` (a bare model is wrapped in a
    private one). Each drain snapshots (model, version) once, so every
    slab of that drain — and therefore every in-flight request — is scored
    against one consistent version even if a publish lands mid-drain; the
    next drain picks up the new version. For sharded models a per-shard
    coefficient refresh is still one atomic whole-model publish
    (``ModelHandle.refresh_shard``), so no request can ever see a mix of
    shard versions. ``RequestStats.model_version`` records which version
    served each request.
    """

    def __init__(self,
                 model: Union[FittedKpca, ShardedFittedKpca, ModelHandle],
                 cfg: KpcaServeConfig = None, mesh=None,
                 inject_fault=None, on_fault=None):
        """Args:
          model: servable artifact (plain or sharded) or a ``ModelHandle``
            wrapping one (live-publishable).
          cfg: batching/bucketing/backend/admission knobs
            (``KpcaServeConfig``).
          mesh: for sharded models only — 1-D device mesh with
            ``model.n_shards`` devices; None builds one over local devices
            (or falls back to a same-math single-device reduction).
          inject_fault: optional ``model -> None`` hook called at the top
            of every drain attempt with the snapshotted model; raising
            aborts the attempt. The deterministic chaos tests use it
            (``repro.faults.serving.ShardLossInjector``) to stand in for
            a dead shard host — production engines leave it None.
          on_fault: optional ``(exc, handle) -> bool`` recovery hook
            called when a drain attempt fails and retries remain.
            Returning True means "handled — retry immediately" (e.g.
            ``ShardRebalancer`` republished a survivor model, which the
            next attempt picks up because every attempt re-reads the
            handle); False falls back to exponential backoff.
        """
        self.handle = model if isinstance(model, ModelHandle) \
            else ModelHandle(model)
        model = self.handle.current()
        self.cfg = cfg or KpcaServeConfig()
        self._inject_fault = inject_fault
        self._on_fault = on_fault
        self._buckets = self.cfg.buckets()
        # _dispatch_lock orders concurrent drains' device programs; it is
        # held only across the (async) dispatch calls, never across a
        # device sync — the blocking host<->device copies happen outside
        # it (see _serve). _stats_lock guards the host-side accounting
        # that submitters and drains both touch.
        self._dispatch_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._compiled_shapes = set()         # guarded-by: _stats_lock
        self.stats = EngineStats()            # guarded-by: _stats_lock
        # Submit-time staging ring: sized to hold at least the queue bound
        # (so an admitted request practically always fits) and never less
        # than arena_factor full slabs.
        cap = self.cfg.queue_capacity()
        arena_rows = max(cap or 0, self.cfg.max_batch * self.cfg.arena_factor)
        self._arena = SlabArena(model.n_features, arena_rows)
        self._queue = RequestQueue(max_queries=cap,
                                   policy=self.cfg.admission,
                                   slot_futures=True,
                                   on_shed=self._release_entries)
        self._stop = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        # Device-runner thread (created by start() only where jit calls
        # block on compute inline, CPU: see ``drains_inline``): it keeps
        # the flusher's dispatch phase enqueue-only so packing the next
        # drain overlaps the device work of this one.
        self._device_pool: Optional[concurrent.futures.ThreadPoolExecutor] \
            = None
        # Cached metric handles, resolved once: the hot path must not pay
        # a registry lookup per drain (and pays nothing per submit — all
        # metric publication happens at the per-drain commit point).
        self._m_requests = metrics.counter(
            "serve_requests_total", "Requests served")
        self._m_queries = metrics.counter(
            "serve_queries_total", "Query rows served")
        self._m_padded = metrics.counter(
            "serve_padded_rows_total", "Wasted pad rows computed")
        self._m_rejected = metrics.counter(
            "serve_rejected_total", "Admissions refused (QueueFullError)")
        self._m_shed = metrics.counter(
            "serve_shed_total", "Queued requests shed to admit newer ones")
        self._m_flushes = metrics.counter(
            "serve_flushes_total", "Drain cycles that served >= 1 request")
        self._m_depth = metrics.gauge(
            "serve_queue_depth_rows", "Queued rows after the last drain")
        self._m_version = metrics.gauge(
            "serve_model_version", "Model version the last drain served")
        self._m_latency = metrics.histogram(
            "serve_request_latency_seconds", "Per-request device wall time")
        self._m_wait = metrics.histogram(
            "serve_queue_wait_seconds", "Submit -> start-of-serve wait")
        # Drain numbers: every span of one drain carries the same
        # ``drain`` attribute, across the flusher and device-runner
        # threads (``next`` on a count is atomic under the GIL).
        self._drain_ids = itertools.count(1)

        if isinstance(model, ShardedFittedKpca):
            from ..launch.mesh import make_serving_mesh
            if mesh is None:
                mesh = make_serving_mesh(model.n_shards)
            # The router owns the whole sharded hot path: the per-slab
            # policy decision (model-parallel psum vs data-parallel vs
            # single-device), per-policy donated jit entry points, and a
            # model placement cache keyed on the handle version — so
            # steady-state drains never re-transfer the model.
            self._router = ShardedRouter(
                mesh, use_pallas=self.cfg.use_pallas,
                interpret=self.cfg.interpret, policy=self.cfg.routing,
                crossover=self.cfg.crossover, donate=self.cfg.donate)
            self._proj = self._proj_donated = None
        else:
            if mesh is not None:
                raise ValueError("mesh is only meaningful for a "
                                 "ShardedFittedKpca model")
            if self.cfg.routing != "auto":
                raise ValueError("cfg.routing is only meaningful for a "
                                 "ShardedFittedKpca model")
            self._router = None

            def _proj(m, xq):
                return oos.project(m, xq, use_pallas=self.cfg.use_pallas,
                                   interpret=self.cfg.interpret)

            self._proj = jax.jit(_proj)
            # Donated twin: XLA may reuse the query slab's buffer for an
            # intermediate/output instead of allocating. The slab is
            # staged fresh per dispatch and never read afterwards, so
            # donation is unconditionally safe; ``cfg.donate`` picks which
            # entry point the serve path (and the start() warmup) uses.
            self._proj_donated = jax.jit(_proj, donate_argnums=(1,)) \
                if self.cfg.donate else self._proj

    @property
    def model(self):
        """The live model (read through the handle)."""
        return self.handle.current()

    def _release_entries(self, entries) -> None:
        """Return entries' staged arena rows (shed/expired/failed/served)."""
        for e in entries:
            if e.arena_start is not None:
                self._arena.release(e.arena_start)
                e.arena_start = None

    # ---- request API -----------------------------------------------------

    def submit(self, x_query) -> SlotFuture:
        """Enqueue one request; returns its result future immediately.

        Args:
          x_query: (Q, M) array-like, M = model.n_features; cast to fp32
            host-side (the engine re-casts per ``cfg.query_dtype`` at slab
            build time).

        Returns:
          A ``concurrent.futures`` future resolving to this request's
          (Q, C) float32 scores at the next drain — the background
          flusher's (when running) or an explicit ``flush``. The future
          also carries ``request_id``, the request's key in the dict
          ``flush`` returns.

        Raises:
          QueueFullError: admission control refused the request
            (``cfg.queue_factor`` bound exceeded under policy "reject", or
            the request alone exceeds the whole queue capacity).
        """
        x = np.asarray(x_query, np.float32)
        if x.ndim != 2 or x.shape[1] != self.model.n_features:
            raise ValueError(
                f"request must be (Q, {self.model.n_features}), "
                f"got {x.shape}")
        # Stage the rows into the arena NOW so the flusher's pack is a
        # slice; a full ring falls back to the request's own array.
        arena_start = self._arena.stage(x) if x.shape[0] else None
        try:
            fut, shed = self._queue.put(x, n=x.shape[0],
                                        arena_start=arena_start)
        except QueueFullError:
            if arena_start is not None:
                self._arena.release(arena_start)
            with self._stats_lock:
                self.stats.n_rejected += 1
            self._m_rejected.inc()
            trace.instant("serve.rejected", n=x.shape[0])
            raise
        if shed:
            with self._stats_lock:
                self.stats.n_shed += len(shed)
            self._m_shed.inc(len(shed))
        return fut

    def flush(self) -> dict:
        """Serve every queued request synchronously; resolves the futures
        and returns {request_id: (Q, C) scores}.

        On failure (after ``cfg.max_retries`` attempts) the still-live
        queued requests are restored (ahead of anything submitted
        meanwhile), so a crashed flush can simply be retried. Requests
        past ``cfg.request_deadline_s`` fail with
        ``DeadlineExceededError`` instead of being restored.
        """
        entries = self._queue.drain()
        if not entries:
            return {}
        entries = list(entries)
        drain = next(self._drain_ids)
        try:
            out, served = self._serve_with_recovery(entries, drain)
        except BaseException:
            # `entries` was pruned in place: expired futures are already
            # failed and must not re-enter the queue.
            self._queue.restore(entries)
            raise
        self._resolve(served, out, drain)
        return out

    def project_many(self, requests: Sequence[Any]) -> List[np.ndarray]:
        """Convenience: submit + flush a list of (Q_i, M) arrays; returns
        the per-request (Q_i, C) score arrays in submission order."""
        futs = [self.submit(x) for x in requests]
        self.flush()
        return [f.result() for f in futs]

    # ---- background flusher ----------------------------------------------

    def start(self) -> "KpcaEngine":
        """Start the background flusher thread (idempotent).

        The flusher sleeps on the queue and drains it whenever a trigger
        fires: with ``cfg.flush_eager`` (default) any queued work wakes an
        idle flusher immediately — batching emerges from backpressure
        while a flush is in flight; otherwise it waits for
        ``cfg.flush_min_queries`` rows (default: one full ``max_batch``
        slab) or the oldest request hitting ``cfg.flush_max_wait_s``. A
        failed drain fails exactly the futures of that batch (no retry
        loop) and keeps serving.

        Also brings up the rest of the steady-state hot path: where the
        model's devices run jit calls inline (CPU; ``drains_inline``), the
        device-runner thread (dispatch becomes enqueue-only) — elsewhere
        the flusher runs each drain itself — and, unless ``cfg.warmup`` is
        off, a warmup pass compiling every pow2 bucket's program so
        traffic never sees a compile (``stats.n_compiles`` stays 0;
        warmup builds are counted in ``stats.n_warmup_compiles``).
        """
        if self._flusher is not None:
            return self
        mesh = self._router.mesh if self._router is not None else None
        if (self._device_pool is None
                and not drains_inline(_platform(self.model, mesh))):
            self._device_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kpca-device")
        if self.cfg.warmup:
            self.warmup()
        self._stop.clear()
        self._flusher = threading.Thread(
            target=self._flush_loop, name="kpca-engine-flusher", daemon=True)
        self._flusher.start()
        return self

    def warmup(self) -> int:
        """Compile the serve entry point for every pow2 bucket (idempotent
        per shape); returns the number of programs built. Runs the REAL
        dispatch path (donated entry point included; for sharded models
        the router's policy-and-placement path, so the policy the router
        will pick for each bucket is the one compiled) — steady-state
        traffic is guaranteed cache hits."""
        model, version = self.handle.get()
        with self._stats_lock:
            built0 = self.stats.n_warmup_compiles
        with trace.span("serve.warmup", n_buckets=len(self._buckets)):
            for b in self._buckets:
                slab = np.zeros((b, model.n_features), np.float32)
                # The dispatch entry itself, not _run_slab: the
                # fault-injection seam wraps _run_slab and must only see
                # real traffic, while the compile cache this fills is
                # keyed on the entry point + shapes either way. Routing is
                # deterministic in (rows, model), so warming the chosen
                # policy per bucket covers everything traffic can hit.
                if self._router is not None:
                    policy = self._router.choose(b, model)
                    xq = self._stage_slab(slab, warmup=True, policy=policy)
                    np.asarray(self._router.dispatch(
                        model, version, xq, policy).scores)
                else:
                    xq = self._stage_slab(slab, warmup=True)
                    np.asarray(self._proj_donated(model, xq))
        with self._stats_lock:
            return self.stats.n_warmup_compiles - built0

    def close(self, drain: bool = True) -> None:
        """Stop the flusher thread (joined) and settle the queue: serve
        everything still queued when ``drain`` (default), else cancel the
        pending futures. Safe to call twice; ``flush``/``submit`` keep
        working afterwards (synchronous mode)."""
        if self._flusher is not None:
            self._stop.set()
            self._queue.kick()
            self._flusher.join(timeout=30.0)
            if self._flusher.is_alive():       # pragma: no cover
                raise RuntimeError("flusher thread failed to stop")
            self._flusher = None
        if drain:
            self.flush()
        else:
            dropped = self._queue.drain()
            self._release_entries(dropped)
            for e in dropped:
                e.future.cancel()
        if self._device_pool is not None:
            self._device_pool.shutdown(wait=True)
            self._device_pool = None

    @property
    def running(self) -> bool:
        return self._flusher is not None

    def compiled_text(self, rows: Optional[int] = None) -> str:
        """The compiled program (``as_text()``) that serves a ``rows``-row
        slab (default ``cfg.max_batch``) of the live model: it shows which
        kernel runs, e.g. a Mosaic ``tpu_custom_call`` on TPU when
        ``cfg.use_pallas``. Single-device models only."""
        if self._router is not None:
            raise ValueError("compiled_text is for single-device models")
        model = self.model
        slab = np.zeros((rows or self.cfg.max_batch, model.n_features),
                        np.float32)
        if self.cfg.query_dtype is not None:
            slab = slab.astype(self.cfg.query_dtype)
        return self._proj.lower(model, slab).compile().as_text()

    def placed_devices(self, policy: str) -> frozenset:
        """Devices holding the sharded model's cached placement for
        routing ``policy`` (empty before its first dispatch)."""
        if self._router is None:
            raise ValueError("placed_devices is for sharded models")
        return self._router.placed_devices(policy)

    def __enter__(self) -> "KpcaEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close(drain=exc[0] is None)

    def _flush_loop(self) -> None:
        # Eager mode: an idle flusher drains on ANY queued work instead of
        # sleeping toward flush_max_wait_s waiting for a full slab. Under
        # load the queue refills while a flush is in flight, so big slabs
        # still form — without load there is nothing to batch against and
        # waiting only adds latency.
        trigger = 1 if self.cfg.flush_eager \
            else (self.cfg.flush_min_queries or self.cfg.max_batch)
        # Pipelined drains hand the device wait + result assembly + future
        # resolution to the device-runner thread, so submitter wakeups and
        # the NEXT drain's pack overlap this drain's compute. Without a
        # runner (asynchronous jit dispatch) the flusher runs each drain
        # whole, then cuts the next. Retries, deadlines, and recovery
        # hooks need the synchronous drain (they re-attempt with restored
        # state), so those configs keep it.
        inline = self._device_pool is None    # drains end on this thread
        fail_fast = (self.cfg.max_retries == 0
                     and self.cfg.request_deadline_s is None
                     and self._on_fault is None)
        pipelined = fail_fast and not inline
        inflight: collections.deque = collections.deque()
        last_n = 0                    # requests in the previous drain
        # The number of the drain this loop is gathering: its wait and
        # hold spans carry it, and it is taken when the drain is cut.
        drain = next(self._drain_ids)
        try:
            while True:
                with trace.span("serve.wait", drain=drain):
                    has_work = self._queue.wait_for_work(
                        trigger, self.cfg.flush_max_wait_s, self._stop)
                if self._stop.is_set():
                    return            # close() settles whatever remains
                if not has_work:
                    continue
                while inflight and inflight[0].done():
                    inflight.popleft().result()
                if inflight or last_n > 1:
                    with trace.span("serve.hold", drain=drain):
                        self._hold(inflight, last_n)
                entries = self._queue.drain()
                if not entries:
                    continue
                entries = list(entries)
                last_n = len(entries)
                cut, drain = drain, next(self._drain_ids)
                if fail_fast and inline:
                    self._drain_inline(entries, cut)
                    continue
                if pipelined:
                    while len(inflight) >= self.cfg.pipeline_depth:
                        inflight.popleft().result()
                    try:
                        inflight.append(self._dispatch_async(entries, cut))
                    except BaseException as e:   # fail THIS batch only
                        self._fail_entries(entries, e)
                    with self._stats_lock:
                        if len(inflight) > self.stats.max_inflight_drains:
                            self.stats.max_inflight_drains = len(inflight)
                    continue
                try:
                    out, served = self._serve_with_recovery(
                        entries, cut, inline=inline)
                except BaseException as e:   # fail THIS batch, keep serving
                    self._fail_entries(entries, e)
                    continue
                self._resolve(served, out, cut)
        finally:
            # Settle in-flight pipelined drains before the thread exits,
            # so close() observes every submitted future resolved.
            while inflight:
                inflight.popleft().result()

    def _hold(self, inflight: collections.deque, last_n: int) -> None:
        """Hold a drain open before cutting it, while waiting is free.
        With no device runner (inline drains) nothing is ever in flight,
        so only the wave coalesce applies."""
        if inflight:
            # Dynamic batching: the device runner is busy, so cutting a
            # drain now buys nothing — the new slab would only queue
            # behind it. Hold the drain open until the runner frees or a
            # full batch forms; every request arriving meanwhile rides
            # one slab.
            while (not inflight[0].done() and not self._stop.is_set()
                   and self._queue.depth < self.cfg.max_batch):
                time.sleep(5e-5)
            while inflight and inflight[0].done():
                inflight.popleft().result()
        if last_n > 1:
            # The last drain resolved a WAVE of submitters, who are all
            # waking to resubmit right now: give them one stall window so
            # the wave drains as one slab instead of splitting across two
            # half-size drains. A lone submitter (last_n <= 1) never
            # waits: there is no wave to collect, only latency to add.
            self._queue.coalesce(self.cfg.max_batch,
                                 self.cfg.flush_coalesce_s, self._stop)

    def _fail_entries(self, entries, exc: BaseException) -> None:
        """Fail one drain's futures with ``exc`` (arena rows released)."""
        self._release_entries(entries)
        for en in entries:
            if not en.future.done():
                en.future.set_exception(exc)

    @staticmethod
    def _resolve(entries, out: dict, drain: int) -> None:
        """Resolve one drain's futures. SlotFutures resolve through a
        shared per-flush slot table — one list publish + ONE event
        broadcast for the whole drain; anything else (decode-style
        RequestFutures) falls back to per-future set_result."""
        with trace.span("serve.resolve", drain=drain,
                        n_requests=len(entries)):
            slot_pairs, results = [], []
            for e in entries:
                if isinstance(e.future, SlotFuture):
                    slot_pairs.append((e.future, len(results)))
                    results.append(out[e.rid])
                elif not e.future.done():    # skip caller-cancelled futures
                    e.future.set_result(out[e.rid])
            if slot_pairs:
                slots = FlushSlots()
                slots.results = results
                SlotFuture.bind(slot_pairs, slots)   # skips cancelled
                slots.event.set()

    # ---- internals -------------------------------------------------------

    def _expire(self, entries: list) -> list:
        """Split off deadline-expired requests; their futures fail NOW
        with ``DeadlineExceededError`` (typed, never served late).
        Returns the still-live entries."""
        ddl = self.cfg.request_deadline_s
        if ddl is None:
            return entries
        now = time.monotonic()
        live, expired = [], []
        for e in entries:
            waited = now - e.t_submit
            if waited > ddl:
                expired.append(e)
                if not e.future.done():
                    e.future.set_exception(DeadlineExceededError(waited, ddl))
            else:
                live.append(e)
        n_expired = len(expired)
        if n_expired:
            self._release_entries(expired)
            with self._stats_lock:
                self.stats.n_deadline_expired += n_expired
            if trace.is_enabled():
                trace.instant("serve.deadline_expired", n=n_expired)
        return live

    def _serve_with_recovery(self, entries: list, drain: int,
                             inline: bool = False) -> tuple:
        """``_serve`` under the fault-tolerance contract: drop expired
        requests before every attempt, retry up to ``cfg.max_retries``
        times after a failure (invoking ``on_fault`` between attempts —
        every attempt re-reads the handle, so a recovery publish heals
        the retry), and raise only once retries are exhausted.

        Prunes ``entries`` IN PLACE to the still-live subset (callers
        use it for restore-on-error) and returns ``(out, served)``.
        With ``max_retries=0`` and no deadline this is exactly one
        ``_serve`` call — the pre-fault-layer behavior. ``inline`` counts
        the drain in ``stats.n_inline_drains``.
        """
        attempt = 0
        while True:
            live = self._expire(entries)
            entries[:] = live
            if not live:
                return {}, []
            try:
                return self._serve(live, drain, inline), live
            except BaseException as e:
                if attempt >= self.cfg.max_retries:
                    raise
                attempt += 1
                handled = False
                if self._on_fault is not None:
                    # A recovery-hook crash must not eat the original
                    # fault: log it into the trace and fall back to
                    # plain backoff.
                    try:
                        handled = bool(self._on_fault(e, self.handle))
                    except BaseException:
                        handled = False
                with self._stats_lock:
                    self.stats.n_retries += 1
                if trace.is_enabled():
                    trace.instant("serve.retry", attempt=attempt,
                                  error=type(e).__name__, handled=handled)
                if not handled:
                    # Interruptible backoff: close() must not wait it out.
                    self._stop.wait(
                        self.cfg.retry_backoff_s * (2 ** (attempt - 1)))

    def _serve(self, entries, drain: int, inline: bool = False) -> dict:
        """One drain, accounted before the caller resolves it; returns
        {request id: scores}."""
        out, account = self._drain(entries, drain)
        account(inline=inline)
        return out

    def _drain_inline(self, entries, drain: int) -> None:
        """A whole fail-fast drain on the flusher thread, where jit
        dispatch is asynchronous (no device runner): pack, one jit call
        per slab, blocking gets, assembly, then the futures, then the
        accounting in the shadow of their next submit. Never raises: a
        failure fails exactly this drain's futures."""
        try:
            out, account = self._drain(entries, drain)
        except BaseException as e:
            self._fail_entries(entries, e)
            return
        self._resolve(entries, out, drain)
        account(inline=True)

    def _drain(self, entries, drain: int) -> tuple:
        """Pack, dispatch, gather and assemble one drain (the slabs on the
        device runner when it is up). Returns ``(out, account)``: the
        results by request id, and the drain's ``_account`` call, left to
        the caller so it picks the order of accounting and resolution."""
        # One consistent (model, version) snapshot for the whole drain:
        # in-flight slabs finish on it even if a publish lands mid-drain.
        model, version = self.handle.get()
        if self._inject_fault is not None:
            self._inject_fault(model)
        t_start = time.monotonic()

        # Three-phase drain so no device sync ever happens under a lock:
        #   1. plan-pack (arena slices, not gather-concat) — pure slicing;
        #   2. dispatch every slab under _dispatch_lock — enqueue-only:
        #      with the device-runner thread up (start() on CPU), the
        #      critical section is a handful of executor submits even
        #      though a jit call blocks on compute inline there (staging
        #      and the jit call both happen in ``_run_slab`` on that
        #      thread); elsewhere the jit calls themselves return before
        #      the programs run;
        #   3. blocking gather (no lock), plan-based result assembly
        #      (pure slicing), then one stats commit.
        with trace.span("serve.pack", drain=drain, n_requests=len(entries),
                        rows=sum(e.n for e in entries)):
            slabs, plan, frames = pack_slabs(
                entries, self.cfg.max_batch, self._buckets, self._arena)
        try:
            pool = self._device_pool
            with trace.span("serve.dispatch", drain=drain,
                            n_slabs=len(slabs)):
                with self._dispatch_lock:
                    if pool is not None:
                        launched = [pool.submit(self._run_traced, drain,
                                                model, version, slab)
                                    for slab, _, _ in slabs]
                    else:
                        launched = [self._run_traced(drain, model, version,
                                                     slab)
                                    for slab, _, _ in slabs]
            with trace.span("serve.gather", drain=drain, n_slabs=len(slabs)):
                done = [d.result() if pool is not None else d
                        for d in launched]
                dts, host, padded, zero_copy, policies = \
                    self._collect(slabs, done)
        finally:
            # Frames go back to the pool even when a dispatch fails — the
            # staged device copies already happened, nothing reads them.
            for f in frames:
                self._arena.release_frame(f)
        out, touched = self._assemble(entries, plan, dts, host, model, drain)
        # Served: the staged rows are consumable again.
        self._release_entries(entries)
        return out, functools.partial(
            self._account, entries, dts, touched, padded, zero_copy,
            policies, len(slabs), version, t_start, drain)

    def _dispatch_async(self, entries, drain: int):
        """Pipelined drain (background flusher, fail-fast configs): pack
        and enqueue here, then hand the gather + assembly + future
        resolution to the device-runner thread as one more pool task —
        FIFO pool order guarantees it runs after this drain's slabs.
        Returns that task's future (the flusher bounds how many are
        in flight via ``cfg.pipeline_depth``)."""
        model, version = self.handle.get()
        if self._inject_fault is not None:
            self._inject_fault(model)
        t_start = time.monotonic()
        with trace.span("serve.pack", drain=drain, n_requests=len(entries),
                        rows=sum(e.n for e in entries)):
            slabs, plan, frames = pack_slabs(
                entries, self.cfg.max_batch, self._buckets, self._arena)
        pool = self._device_pool
        with trace.span("serve.dispatch", drain=drain, n_slabs=len(slabs)):
            with self._dispatch_lock:
                launched = [pool.submit(self._run_traced, drain, model,
                                        version, slab)
                            for slab, _, _ in slabs]
        return pool.submit(self._finalize, entries, slabs, plan, frames,
                           launched, model, version, t_start, drain)

    def _finalize(self, entries, slabs, plan, frames, launched, model,
                  version, t_start, drain: int) -> None:
        """Device-runner half of a pipelined drain: gather (instant — the
        slab tasks ran before this one on the same serial pool), assemble,
        commit stats, resolve futures. Never raises: a failed slab fails
        exactly this drain's futures, matching the synchronous flusher
        contract."""
        try:
            try:
                with trace.span("serve.gather", drain=drain,
                                n_slabs=len(slabs)):
                    done = [d.result() for d in launched]
                    dts, host, padded, zero_copy, policies = \
                        self._collect(slabs, done)
            finally:
                for f in frames:
                    self._arena.release_frame(f)
            out, touched = self._assemble(entries, plan, dts, host, model,
                                          drain)
            self._release_entries(entries)
        except BaseException as e:           # fail THIS batch only
            self._fail_entries(entries, e)
            return
        # Wake submitters FIRST: the stats/metrics tail runs in the shadow
        # of their next submit instead of on the request's critical path.
        self._resolve(entries, out, drain)
        self._account(entries, dts, touched, padded, zero_copy, policies,
                      len(slabs), version, t_start, drain)

    @staticmethod
    def _collect(slabs, done):
        """Device->host gets for one drain's finished slabs. Returns
        (per-slab seconds, host score arrays, pad rows, zero-copy count,
        per-slab routing policies — None for single-device models).

        For a model-parallel slab the blocking read IS the psum drain —
        dispatch returned before the reduction ran — so it gets its own
        ``serve.psum`` span; the flight recorder shows it overlapping the
        next slab's ``serve.shard_dispatch`` when drains pipeline.
        """
        dts, host, policies = [], [], []
        padded, zero_copy = 0, 0
        for (slab, take, zc), (dev, dt) in zip(slabs, done):
            policy = None
            if isinstance(dev, ShardedScores):
                dev, policy = dev.scores, dev.policy
            t0 = time.perf_counter()
            if policy == "mp" and trace.is_enabled():
                with trace.span("serve.psum", rows=int(slab.shape[0])):
                    scores = np.asarray(dev)     # device->host (+ psum)
            else:
                scores = np.asarray(dev)         # device->host
            dts.append(dt + time.perf_counter() - t0)
            host.append(scores)
            policies.append(policy)
            padded += slab.shape[0] - take
            zero_copy += bool(zc)
        return dts, host, padded, zero_copy, policies

    @staticmethod
    def _assemble(entries, plan, dts, host, model, drain: int):
        """Build per-request results straight off the pack plan: a request
        living in one slab gets a VIEW of that slab's scores, split
        requests copy each segment once. Returns (rid->scores,
        rid->device seconds touched)."""
        with trace.span("serve.assemble", drain=drain):
            empty = np.zeros((0, model.n_components), np.float32)
            out, touched = {}, {}
            for e, segs in zip(entries, plan):
                if not segs:
                    out[e.rid] = empty
                    touched[e.rid] = 0.0
                    continue
                if len(segs) == 1:
                    si, row, _off, m = segs[0]
                    out[e.rid] = host[si][row:row + m]
                else:
                    buf = np.empty((e.n, host[segs[0][0]].shape[1]),
                                   np.float32)
                    for si, row, off, m in segs:
                        buf[off:off + m] = host[si][row:row + m]
                    out[e.rid] = buf
                touched[e.rid] = sum(dts[si] for si in {s[0] for s in segs})
            return out, touched

    def _account(self, entries, dts, touched, padded, zero_copy, policies,
                 n_slabs, version, t_start, drain: int,
                 inline: bool = False) -> None:
        """Stats + metric publication for one served drain (``inline``:
        cut and finished on the flusher thread). Runs only after every
        slab resolved, so a failed-then-retried flush doesn't double-count
        its slabs."""
        with trace.span("serve.account", drain=drain):
            waits = [max(0.0, t_start - e.t_submit) for e in entries]
            donated = n_slabs if self.cfg.donate else 0
            routed = collections.Counter(p for p in policies if p)
            with self._stats_lock:
                self.stats.n_routed_mp += routed.get("mp", 0)
                self.stats.n_routed_dp += routed.get("dp", 0)
                self.stats.n_routed_single += routed.get("single", 0)
                self.stats.n_padded += padded
                self.stats.total_time_s += sum(dts)
                self.stats.n_requests += len(entries)
                self.stats.n_queries += sum(e.n for e in entries)
                self.stats.n_flushes += 1
                self.stats.n_inline_drains += inline
                self.stats.n_zero_copy_slabs += zero_copy
                self.stats.n_donated += donated
                self.stats.n_arena_fallback = self._arena.n_fallback
                for e, wait in zip(entries, waits):
                    self.stats.per_request.append(RequestStats(
                        e.rid, e.n, touched[e.rid], version,
                        queue_wait_s=wait))
            # Metric publication rides the same per-drain commit point
            # (one batch of updates per drain, nothing on the submit hot
            # path).
            self._m_requests.inc(len(entries))
            self._m_queries.inc(sum(e.n for e in entries))
            self._m_padded.inc(padded)
            self._m_flushes.inc()
            self._m_depth.set(self._queue.depth)
            self._m_version.set(version)
            self._m_latency.observe_many(list(touched.values()))
            self._m_wait.observe_many(waits)
            if trace.active() is not None:
                for e, wait in zip(entries, waits):
                    # Backdated complete event (ring buffer only): the
                    # submit->serve gap renders as its own "queue_wait"
                    # phase without any submit-side instrumentation.
                    trace.complete("serve.queue_wait", wait, rid=e.rid,
                                   n=e.n)

    def _stage_slab(self, slab: np.ndarray, warmup: bool = False,
                    policy: Optional[str] = None) -> np.ndarray:
        """Dtype cast + compile-cache bookkeeping for one packed slab —
        runs outside every lock but the stats lock, on whichever thread
        dispatches the slab. The slab stays HOST numpy: jit dispatch does
        the host->device transfer inline, which is one dispatch instead
        of an explicit ``jnp.asarray`` put followed by the call (~2x
        cheaper per slab on CPU). The transfer copies, so arena rows are
        free for reuse the moment their entries resolve.

        Compile bookkeeping is keyed (shape, policy): a sharded engine
        compiles one program per (bucket, routing policy), so a warmup
        that only touched the single-device entry must not mask an mp/dp
        compile as "steady state" — this key is what the ``n_compiles==0``
        regression tests actually check."""
        if self.cfg.query_dtype is not None:
            xq = slab.astype(self.cfg.query_dtype, copy=False)
        else:
            xq = slab
        key = (xq.shape, policy)
        with self._stats_lock:
            if key not in self._compiled_shapes:
                self._compiled_shapes.add(key)
                if warmup:
                    self.stats.n_warmup_compiles += 1
                else:
                    self.stats.n_compiles += 1
        return xq

    def _run_traced(self, drain: int, model, version, slab):
        """``_run_slab`` in the drain's ``serve.device`` span: staging and
        the jit call (the host->device copy and the enqueue), not the
        device's compute, which ends in the gather. Kept apart so that
        ``_run_slab(model, version, slab)`` stays the seam fault tests
        replace."""
        with trace.span("serve.device", drain=drain,
                        rows=int(slab.shape[0])):
            return self._run_slab(model, version, slab)

    def _run_slab(self, model, version, slab):
        """Stage + dispatch one packed slab on the CALLING thread (the
        device-runner where ``start()`` brought one up, so the ~flat
        per-transfer cost overlaps the flusher's next pack). Returns
        ``(device scores, seconds)``; for sharded models the scores carry
        the routing policy (``ShardedScores``) and the version keys the
        router's placement cache. Dispatch transfers the host slab
        itself; the on-device copy it makes is dead after the call when
        donation is on, and the caller owns the device->host get."""
        t0 = time.perf_counter()
        if self._router is not None:
            policy = self._router.choose(int(slab.shape[0]), model)
            xq = self._stage_slab(slab, policy=policy)
            out = self._router.dispatch(model, version, xq, policy)
        else:
            xq = self._stage_slab(slab)
            out = self._proj_donated(model, xq)
        with self._stats_lock:
            self.stats.n_slabs += 1
        return out, time.perf_counter() - t0


__all__ = ["EngineStats", "KpcaEngine", "KpcaServeConfig", "QueueFullError",
           "RequestFuture", "RequestStats"]
