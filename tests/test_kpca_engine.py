"""Tests for the batched kPCA projection-serving engine."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelSpec, oos
from repro.serve import KpcaEngine, KpcaServeConfig

SPEC = KernelSpec(kind="rbf", gamma=0.25)


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


@pytest.fixture(scope="module")
def model():
    x = jnp.asarray(_rand((48, 12), seed=0))
    return oos.fit_central(x, SPEC, n_components=2, center=True)


class TestBuckets:
    def test_power_of_two_ladder(self):
        cfg = KpcaServeConfig(max_batch=64, min_bucket=8)
        assert cfg.buckets() == [8, 16, 32, 64]

    def test_non_pow2_max_is_widest(self):
        cfg = KpcaServeConfig(max_batch=48, min_bucket=8)
        assert cfg.buckets() == [8, 16, 32, 48]


class TestEngineCorrectness:
    def test_identical_to_direct_across_bucket_boundaries(self, model):
        """Request sizes straddling every bucket boundary (and slab
        boundaries) must give exactly the unbatched per-request scores."""
        cfg = KpcaServeConfig(max_batch=32, min_bucket=4)
        eng = KpcaEngine(model, cfg)
        sizes = [1, 3, 4, 5, 8, 9, 16, 17, 31, 32, 33, 64, 65]
        reqs = [_rand((q, 12), seed=100 + q) for q in sizes]
        got = eng.project_many(reqs)
        for r, g in zip(reqs, got):
            want = np.asarray(oos.project(model, jnp.asarray(r)))
            # row-wise kernel math is independent of batch packing; the only
            # residue is XLA picking a different gemm path per shape
            # (observed <= 4e-9), so pin to float32 resolution, not bits.
            np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-7)

    def test_empty_request_yields_empty_scores(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=16, min_bucket=4))
        r0 = eng.submit(np.zeros((0, 12), np.float32))
        r1 = eng.submit(_rand((4, 12), seed=8))
        eng.flush()
        assert r0.result().shape == (0, 2)
        want = np.asarray(oos.project(model, jnp.asarray(
            _rand((4, 12), seed=8))))
        np.testing.assert_allclose(r1.result(), want, rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_compiled_text_is_the_serving_program(self, model, use_pallas):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=16, min_bucket=4,
                                                use_pallas=use_pallas))
        text = eng.compiled_text()
        assert "HloModule" in text and "f32[16,12]" in text
        assert "f32[4,12]" in eng.compiled_text(4)
        with pytest.raises(ValueError):
            eng.placed_devices("mp")

    def test_serving_program_reads_no_support_norms(self):
        """The projection program takes the support's norms from the
        artifact: no reduce runs over the (L, M) support. L=300 is no
        slab's row count, so the support's shape names it alone."""
        xs = jnp.asarray(_rand((300, 12), seed=30))
        m = oos.from_dual(xs, jnp.asarray(_rand((300, 2), seed=31)), SPEC)
        eng = KpcaEngine(m, KpcaServeConfig(max_batch=16, min_bucket=4))
        slab = jnp.zeros((16, 12), jnp.float32)
        text = eng._proj.lower(m, slab).as_text()
        reduces = [ln for ln in text.splitlines() if "stablehlo.reduce" in ln]
        assert any("tensor<16x12xf32>" in ln for ln in reduces)  # query norms
        assert not any("tensor<300x12xf32>" in ln for ln in reduces)

    def test_support_norms_span_once_per_artifact(self):
        from repro.obs import trace
        tracer = trace.Tracer()
        outer = trace.active()
        trace.install(tracer)
        try:
            m = oos.from_dual(jnp.asarray(_rand((40, 12), seed=32)),
                              jnp.asarray(_rand((40, 2), seed=33)), SPEC)
            eng = KpcaEngine(m, KpcaServeConfig(max_batch=8, min_bucket=4))
            eng.project_many([_rand((q, 12), seed=34 + q)
                              for q in (1, 5, 8, 13)])
        finally:
            trace.install(outer)
        names = [name for _ph, name, *_ in tracer.events()]
        assert any(n.startswith("serve.") for n in names)  # drains traced
        spans = [attrs for _ph, name, _t0, _dur, _tid, attrs
                 in tracer.events() if name == "oos.support_norms"]
        assert spans == [{"rows": 40}]

    def test_interleaved_submit_flush(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=16, min_bucket=4))
        r1 = eng.submit(_rand((5, 12), seed=1))
        r2 = eng.submit(_rand((20, 12), seed=2))
        out = eng.flush()
        assert set(out) == {r1.request_id, r2.request_id}
        assert r1.result().shape == (5, 2) and r2.result().shape == (20, 2)
        assert eng.flush() == {}  # queue drained

    def test_compressed_model_serving(self, model):
        cm, _ = oos.compress(model, 24, seed=0)
        eng = KpcaEngine(cm, KpcaServeConfig(max_batch=16, min_bucket=4))
        xq = _rand((10, 12), seed=3)
        [got] = eng.project_many([xq])
        want = np.asarray(oos.project(cm, jnp.asarray(xq)))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    def test_pallas_path(self, model):
        cfg = KpcaServeConfig(max_batch=16, min_bucket=8, use_pallas=True,
                              interpret=True)
        eng = KpcaEngine(cfg=cfg, model=model)
        xq = _rand((13, 12), seed=4)
        [got] = eng.project_many([xq])
        want = np.asarray(oos.project(model, jnp.asarray(xq)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_poly_kernel_end_to_end(self):
        """Non-RBF spec (normalized poly, §3.1) through the full serving
        path: fit -> engine buckets/slabs -> fused Pallas kernel."""
        spec = KernelSpec(kind="poly", degree=2, scale=0.5)
        x = jnp.asarray(_rand((40, 8), seed=50))
        pmodel = oos.fit_central(x, spec, n_components=2, center=True)
        eng = KpcaEngine(pmodel, KpcaServeConfig(
            max_batch=16, min_bucket=4, use_pallas=True, interpret=True))
        reqs = [_rand((q, 8), seed=51 + q) for q in (3, 16, 21)]
        got = eng.project_many(reqs)
        for r, g in zip(reqs, got):
            want = np.asarray(oos.project(pmodel, jnp.asarray(r)))
            np.testing.assert_allclose(g, want, rtol=2e-4, atol=2e-4)

    def test_bf16_query_cast(self, model):
        cfg = KpcaServeConfig(max_batch=16, min_bucket=8,
                              query_dtype=jnp.bfloat16)
        eng = KpcaEngine(model, cfg)
        xq = _rand((6, 12), seed=5)
        [got] = eng.project_many([xq])
        want = np.asarray(oos.project(model, jnp.asarray(xq)))
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


class TestEngineAccounting:
    def test_bucket_reuse_bounds_compiles(self, model):
        """Any request mix compiles at most len(buckets) programs."""
        cfg = KpcaServeConfig(max_batch=16, min_bucket=4)
        eng = KpcaEngine(model, cfg)
        for seed, q in enumerate([1, 2, 3, 5, 7, 11, 13, 16, 20, 40, 6, 9]):
            eng.submit(_rand((q, 12), seed=200 + seed))
        eng.flush()
        assert eng.stats.n_compiles <= len(cfg.buckets())
        assert eng.stats.n_queries == sum([1, 2, 3, 5, 7, 11, 13, 16, 20,
                                           40, 6, 9])
        assert eng.stats.n_requests == 12

    def test_failed_flush_restores_queue(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=8, min_bucket=8))
        fut = eng.submit(_rand((3, 12), seed=8))

        def boom(_model, _version, _slab):
            raise RuntimeError("injected")

        run_slab, eng._run_slab = eng._run_slab, boom
        with pytest.raises(RuntimeError):
            eng.flush()
        assert not fut.done()                  # sync failure keeps it queued
        eng._run_slab = run_slab
        eng.flush()                            # retry serves the request
        assert fut.result().shape == (3, 2)
        # the failed attempt must not contaminate the accounting
        assert eng.stats.n_requests == 1
        assert len(eng.stats.per_request) == 1

    def test_rejects_bad_shapes_and_config(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=8, min_bucket=8))
        with pytest.raises(ValueError):
            eng.submit(_rand((12,), seed=9))        # 1-D
        with pytest.raises(ValueError):
            eng.submit(_rand((3, 7), seed=9))       # wrong feature width
        with pytest.raises(ValueError):
            KpcaServeConfig(max_batch=4, min_bucket=8).buckets()

    def test_latency_stats_populated(self, model):
        eng = KpcaEngine(model, KpcaServeConfig(max_batch=8, min_bucket=8))
        eng.project_many([_rand((3, 12), seed=6), _rand((9, 12), seed=7)])
        assert len(eng.stats.per_request) == 2
        p50, p99 = eng.stats.latency_percentiles()
        assert 0 < p50 <= p99
        assert eng.stats.queries_per_s > 0
