"""Tests for the async request pipeline: futures-based ``KpcaEngine``
(background flusher, size-or-deadline triggers), admission control, and
version consistency of concurrent requests against per-shard publishes.

Every test that starts a thread joins it on teardown (the engine fixture
closes the flusher; publishers are context-managed), so a deadlock shows
up as a pytest-timeout failure, not a hung CI job.
"""

import collections
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelSpec, oos
from repro.obs import trace
from repro.serve import (KpcaEngine, KpcaServeConfig, ModelHandle,
                         QueueFullError, ShedError, kpca_engine)
from repro.serve.sharded import project_sharded

SPEC = KernelSpec(kind="rbf", gamma=0.25)
WAIT = 30.0                                    # generous future timeout

# Instrument every serve-layer lock and fail on a recorded AB/BA
# acquisition cycle (tests/helpers/lockcheck.py).
pytestmark = pytest.mark.lockcheck


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


@pytest.fixture(scope="module")
def model():
    x = jnp.asarray(_rand((48, 12), seed=0))
    return oos.fit_central(x, SPEC, n_components=2, center=True)


@pytest.fixture
def engine(model, request):
    """Engine factory that guarantees flusher-thread teardown."""
    engines = []

    def make(cfg):
        eng = KpcaEngine(model, cfg)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.close(drain=False)


class TestAsyncExactness:
    def test_concurrent_futures_bitwise_vs_sync(self, model, engine):
        """Aligned requests (one full slab each) from concurrent submitter
        threads must resolve to BITWISE-identical scores vs serving each
        request alone through the synchronous path: same bucket shape =>
        same compiled program => same floats, regardless of how the
        flusher interleaved the batches."""
        cfg = KpcaServeConfig(max_batch=16, min_bucket=16,
                              flush_max_wait_s=0.002)
        eng = engine(cfg).start()
        sync_eng = KpcaEngine(model, cfg)      # never started: sync path
        reqs = [_rand((16, 12), seed=100 + i) for i in range(12)]

        futs = [None] * len(reqs)

        def submitter(lo, hi):
            for i in range(lo, hi):
                futs[i] = eng.submit(reqs[i])

        threads = [threading.Thread(target=submitter, args=(i * 4, i * 4 + 4))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT)
        got = [f.result(timeout=WAIT) for f in futs]
        for r, g in zip(reqs, got):
            want = sync_eng.project_many([r])[0]
            np.testing.assert_array_equal(g, want)   # bitwise

    def test_mixed_sizes_concurrent_vs_oracle(self, model, engine):
        """Arbitrary request sizes across concurrent submitters: packing
        may split requests across slab boundaries, so pin to float32
        resolution against the unbatched oracle (same bar as the sync
        engine's own exactness test)."""
        eng = engine(KpcaServeConfig(max_batch=16, min_bucket=4,
                                     flush_max_wait_s=0.002)).start()
        sizes = [1, 3, 5, 17, 31, 33, 2, 8]
        reqs = [_rand((q, 12), seed=200 + q) for q in sizes]
        futs = [None] * len(reqs)

        def submitter(idx):
            futs[idx] = eng.submit(reqs[idx])

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT)
        for r, f in zip(reqs, futs):
            want = np.asarray(oos.project(model, jnp.asarray(r)))
            np.testing.assert_allclose(f.result(timeout=WAIT), want,
                                       rtol=1e-6, atol=1e-7)

    def test_deadline_trigger_resolves_small_batch(self, engine):
        """A lone sub-batch request must not wait for a full slab: the
        deadline trigger flushes it within flush_max_wait_s."""
        eng = engine(KpcaServeConfig(max_batch=128, min_bucket=8,
                                     flush_max_wait_s=0.01)).start()
        fut = eng.submit(_rand((3, 12), seed=1))
        assert fut.result(timeout=WAIT).shape == (3, 2)
        assert eng.stats.per_request[-1].queue_wait_s < WAIT

    def test_size_trigger_beats_deadline(self, engine):
        """A full max_batch of queued rows flushes immediately even under
        an absurdly long deadline."""
        eng = engine(KpcaServeConfig(max_batch=8, min_bucket=8,
                                     flush_max_wait_s=60.0)).start()
        futs = [eng.submit(_rand((4, 12), seed=2 + i)) for i in range(2)]
        for f in futs:
            assert f.result(timeout=WAIT).shape == (4, 2)


class TestLifecycle:
    def test_context_manager_drains_on_exit(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(
            max_batch=64, min_bucket=8, flush_max_wait_s=30.0))
        with eng:
            assert eng.running
            fut = eng.submit(_rand((5, 12), seed=3))
        assert not eng.running                 # thread joined
        assert fut.result(timeout=0).shape == (5, 2)

    def test_close_without_drain_cancels(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(
            max_batch=64, min_bucket=8, flush_max_wait_s=30.0))
        fut = eng.submit(_rand((5, 12), seed=4))
        eng.close(drain=False)
        assert fut.cancelled()

    def test_start_is_idempotent_and_restartable(self, model, engine):
        eng = engine(KpcaServeConfig(max_batch=8, min_bucket=8,
                                     flush_max_wait_s=0.005))
        assert eng.start() is eng.start()
        eng.close()
        assert not eng.running
        eng.start()                            # fresh thread after close
        fut = eng.submit(_rand((2, 12), seed=5))
        assert fut.result(timeout=WAIT).shape == (2, 2)

    def test_failed_async_batch_fails_only_its_futures(self, model, engine):
        """A flusher-side failure must fail exactly that batch's futures
        (no silent retry loop) and keep the engine serving."""
        eng = engine(KpcaServeConfig(max_batch=8, min_bucket=8,
                                     flush_max_wait_s=0.005))
        run_slab = eng._run_slab
        boom = dict(armed=True)

        def maybe_boom(mdl, version, slab):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected")
            return run_slab(mdl, version, slab)

        eng._run_slab = maybe_boom
        eng.start()
        bad = eng.submit(_rand((3, 12), seed=6))
        with pytest.raises(RuntimeError):
            bad.result(timeout=WAIT)
        good = eng.submit(_rand((3, 12), seed=7))
        assert good.result(timeout=WAIT).shape == (3, 2)


class TestAdmissionControl:
    def test_reject_policy_and_counter(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(
            max_batch=8, min_bucket=8, queue_factor=2))  # 16-row bound
        eng.submit(_rand((10, 12), seed=8))
        eng.submit(_rand((6, 12), seed=9))     # exactly at capacity
        with pytest.raises(QueueFullError):
            eng.submit(_rand((1, 12), seed=10))
        assert eng.stats.n_rejected == 1
        out = eng.flush()                      # draining frees capacity
        assert len(out) == 2
        eng.submit(_rand((1, 12), seed=10))    # admitted now
        eng.flush()

    def test_shed_policy_fails_oldest_future(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(
            max_batch=8, min_bucket=8, queue_factor=1, admission="shed"))
        old = eng.submit(_rand((6, 12), seed=11))
        new = eng.submit(_rand((5, 12), seed=12))   # sheds `old`
        with pytest.raises(ShedError):
            old.result(timeout=0)
        assert eng.stats.n_shed == 1
        eng.flush()
        assert new.result(timeout=0).shape == (5, 2)

    def test_oversize_request_rejected_up_front(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(
            max_batch=8, min_bucket=8, queue_factor=1, admission="shed"))
        keep = eng.submit(_rand((2, 12), seed=13))
        with pytest.raises(QueueFullError):    # 9 rows > 8-row capacity
            eng.submit(_rand((9, 12), seed=14))
        assert eng.stats.n_rejected == 1 and eng.stats.n_shed == 0
        eng.flush()
        assert keep.result(timeout=0).shape == (2, 2)

    def test_queue_factor_validation(self, model):
        with pytest.raises(ValueError):
            KpcaEngine(model, KpcaServeConfig(max_batch=8, queue_factor=0))


class TestVersionConsistencyUnderRefresh:
    def test_per_shard_publishes_never_mix_within_a_request(self, model):
        """Requests racing a stream of per-shard coefficient publishes must
        each observe EXACTLY one published model version — the scores must
        bitwise-match a direct projection through the version recorded in
        that request's stats, for every request."""
        sharded, _ = oos.shard_fitted(model, 3)
        handle = ModelHandle(sharded)
        cfg = KpcaServeConfig(max_batch=16, min_bucket=16,
                              flush_max_wait_s=0.002)
        eng = KpcaEngine(handle, cfg)
        versions = [sharded]                   # version v -> model
        xq = _rand((16, 12), seed=15)

        futs = []
        try:
            eng.start()
            rng = np.random.default_rng(16)
            for i in range(10):
                futs.append(eng.submit(xq))
                shard = i % sharded.n_shards
                a = rng.normal(size=(sharded.shard_sizes[shard], 2)) \
                    .astype(np.float32)
                handle.refresh_shard(shard, jnp.asarray(a))
                versions.append(handle.current())
            results = [f.result(timeout=WAIT) for f in futs]
        finally:
            eng.close(drain=False)

        by_rid = {s.request_id: s for s in eng.stats.per_request}
        assert len(by_rid) == len(futs)
        # Same program the router's auto policy compiles for this model
        # (support 48 -> "single"), minus donation — the bitwise oracle.
        ref = jax.jit(lambda m, q: project_sharded(m, q, policy="single"))
        seen = set()
        for f, got in zip(futs, results):
            v = by_rid[f.request_id].model_version
            seen.add(v)
            want = np.asarray(ref(versions[v], jnp.asarray(xq)))
            np.testing.assert_array_equal(got, want)
        assert seen                            # every request attributed

def _runner_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("kpca-device")}


class TestDrainPlacement:
    """Which thread finishes a drain follows the platform of the model's
    devices: a device-runner thread on CPU (jit calls block on compute),
    the flusher itself where jit dispatch is asynchronous."""

    @pytest.mark.parametrize("platform,inline", [
        ("cpu", False), ("tpu", True), ("gpu", True)])
    def test_decision_by_platform(self, platform, inline):
        assert kpca_engine.drains_inline(platform) is inline

    def test_model_platform_is_read_off_its_devices(self, model):
        assert kpca_engine._platform(model, None) == "cpu"
        sharded, _ = oos.shard_fitted(model, 2)
        eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=8, min_bucket=8))
        assert kpca_engine._platform(sharded, eng._router.mesh) == "cpu"

    def test_cpu_default_keeps_the_device_runner(self, model, engine):
        eng = engine(KpcaServeConfig(max_batch=16, min_bucket=8,
                                     flush_max_wait_s=0.002))
        before = _runner_threads()
        eng.start()
        futs = [eng.submit(_rand((q, 12), seed=300 + q)) for q in (1, 9)]
        for f in futs:
            f.result(timeout=WAIT)
        assert _runner_threads() - before     # started by the first slab
        eng.close()
        assert eng.stats.n_flushes > 0
        assert eng.stats.n_inline_drains == 0


@pytest.fixture
def inline_drains(monkeypatch):
    """Select the flusher-only drain path on the CPU, as on TPU."""
    monkeypatch.setattr(kpca_engine, "drains_inline", lambda platform: True)


@pytest.mark.usefixtures("inline_drains")
class TestInlineDrains:
    def test_results_match_project_per_request(self, model, engine):
        eng = engine(KpcaServeConfig(max_batch=128, min_bucket=8,
                                     flush_max_wait_s=0.002))
        before = _runner_threads()
        eng.start()
        assert eng._device_pool is None
        sizes = [1, 7, 128, 300, 7, 1, 300, 128, 1]
        reqs = [_rand((q, 12), seed=400 + i) for i, q in enumerate(sizes)]
        futs = [eng.submit(r) for r in reqs]
        got = [f.result(timeout=WAIT) for f in futs]
        assert not _runner_threads() - before
        eng.close()
        for r, g in zip(reqs, got):
            want = np.asarray(oos.project(model, jnp.asarray(r)))
            np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-7)
        assert eng.stats.n_inline_drains == eng.stats.n_flushes > 0
        assert eng.stats.n_requests == len(sizes)
        assert eng.stats.max_inflight_drains == 0

    def test_sharded_model_drains_inline(self, model):
        sharded, _ = oos.shard_fitted(model, 2)
        eng = KpcaEngine(sharded, KpcaServeConfig(
            max_batch=16, min_bucket=8, routing="mp",
            flush_max_wait_s=0.002))
        try:
            eng.start()
            reqs = [_rand((q, 12), seed=500 + q) for q in (3, 16, 21)]
            futs = [eng.submit(r) for r in reqs]
            got = [f.result(timeout=WAIT) for f in futs]
        finally:
            eng.close(drain=False)
        for r, g in zip(reqs, got):
            want = np.asarray(oos.project(model, jnp.asarray(r)))
            np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
        assert eng.stats.n_inline_drains == eng.stats.n_flushes > 0
        assert eng.stats.n_routed_mp > 0

    def test_resolve_ends_before_account_starts(self, model, engine):
        eng = engine(KpcaServeConfig(max_batch=16, min_bucket=8,
                                     flush_max_wait_s=0.002))
        tracer = trace.Tracer()
        outer = trace.active()
        trace.install(tracer)
        try:
            eng.start()
            for w in range(6):
                futs = [eng.submit(_rand((1 + (w + i) % 9, 12), seed=w))
                        for i in range(4)]
                for f in futs:
                    f.result(timeout=WAIT)
            eng.close()
        finally:
            trace.install(outer)
        spans = collections.defaultdict(dict)
        threads = collections.defaultdict(set)
        for ph, name, t0, dur, tid, attrs in tracer.events():
            if ph == "X" and "drain" in attrs:
                spans[attrs["drain"]][name] = (t0, t0 + dur)
                threads[attrs["drain"]].add(tid)
        served = [d for d, s in spans.items() if "serve.account" in s]
        assert len(served) == eng.stats.n_flushes > 1
        for d in served:
            assert spans[d]["serve.resolve"][1] <= \
                spans[d]["serve.account"][0]
            assert len(threads[d]) == 1       # the flusher's, start to end

    @pytest.mark.parametrize("drain", [True, False])
    def test_close_settles_every_future(self, model, engine, drain):
        eng = engine(KpcaServeConfig(max_batch=16, min_bucket=8,
                                     flush_max_wait_s=0.002)).start()
        futs = [eng.submit(_rand((1 + i % 20, 12), seed=600 + i))
                for i in range(60)]
        eng.close(drain=drain)
        assert all(f.done() for f in futs)
        for f in futs:
            if drain or not f.cancelled():
                assert f.result(timeout=0).shape[1] == 2
        if drain:
            assert not any(f.cancelled() for f in futs)

    def test_retrying_engine_serves_and_retries(self, model, engine):
        eng = engine(KpcaServeConfig(max_batch=8, min_bucket=8,
                                     flush_max_wait_s=0.002, max_retries=1,
                                     retry_backoff_s=0.001))
        run_slab = eng._run_slab
        boom = dict(armed=True)

        def fail_once(mdl, version, slab):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected")
            return run_slab(mdl, version, slab)

        eng._run_slab = fail_once
        eng.start()
        assert eng._device_pool is None
        x = _rand((5, 12), seed=700)
        got = eng.submit(x).result(timeout=WAIT)
        eng.close()
        np.testing.assert_allclose(
            got, np.asarray(oos.project(model, jnp.asarray(x))),
            rtol=1e-6, atol=1e-7)
        assert eng.stats.n_retries == 1
        assert eng.stats.n_inline_drains == eng.stats.n_flushes == 1

    def test_drain_spans_sit_on_one_thread_line(self, engine, tmp_path):
        """Under a profiler capture every span of a drain, from its wait
        to its account, is on the flusher's line (the inline twin of
        tests/bench/test_bench_drains.py's cross-thread check)."""
        from jax.profiler import ProfileData
        eng = engine(KpcaServeConfig(max_batch=16, min_bucket=8)).start()
        rng = np.random.default_rng(0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for w in range(8):
                futs = [eng.submit(rng.normal(size=(1 + (w * 5 + i) % 20, 12))
                                   .astype(np.float32)) for i in range(5)]
                for f in futs:
                    f.result(timeout=WAIT)
            eng.close()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        lines = collections.defaultdict(set)
        names = collections.defaultdict(list)
        for plane in ProfileData.from_file(str(path)).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    d = dict(e.stats).get("drain")
                    if e.name.startswith("serve.") and d is not None:
                        lines[int(d)].add(f"{plane.name}#{i}")
                        names[int(d)].append(e.name)
        served = [d for d, n in names.items() if "serve.account" in n]
        assert len(served) == eng.stats.n_flushes > 1
        assert eng.stats.n_inline_drains == eng.stats.n_flushes
        for d in served:
            for name in ("serve.pack", "serve.dispatch", "serve.gather",
                         "serve.assemble", "serve.resolve", "serve.account"):
                assert names[d].count(name) == 1, (name, names[d])
            assert names[d].count("serve.device") >= 1
            assert len(lines[d]) == 1, lines[d]
        assert any("serve.wait" in n for n in names.values())


class TestSlabCount:
    """``EngineStats.n_slabs`` counts projection program calls: one per
    slab a drain dispatches, on the device-runner and the inline path."""

    @pytest.mark.parametrize("inline", [False, True])
    def test_a_drain_over_max_batch_rows_is_two_calls(self, engine, inline,
                                                      monkeypatch):
        monkeypatch.setattr(kpca_engine, "drains_inline",
                            lambda platform: inline)
        eng = engine(KpcaServeConfig(max_batch=128, min_bucket=8))
        eng.start()
        assert eng.stats.n_slabs == 0             # warmup calls not counted
        eng.submit(_rand((192, 12), seed=600)).result(timeout=WAIT)
        assert eng.stats.n_flushes == 1
        assert eng.stats.n_slabs == 2                 # 128 + 64 rows
        eng.submit(_rand((5, 12), seed=601)).result(timeout=WAIT)
        eng.close()
        assert eng.stats.n_slabs == 3                 # + one 8-row slab
        assert eng.stats.n_queries + eng.stats.n_padded == 128 + 64 + 8
        assert eng.stats.n_inline_drains == (2 if inline else 0)
