"""Tests for sharded multi-device kPCA serving: ShardedFittedKpca
(repro.core.oos), the shard_map + psum execution path (repro.serve.sharded),
per-shard landmark compression, the adaptive mp/dp/single routing layer
(CrossoverTable + ShardedRouter: placement cache, donated per-policy entry
points, warmup coverage), and the engine integration.

tests/conftest.py exposes 4 host CPU devices, so shard counts 1/2/4 all run
on a REAL mesh (shard_map + psum / data-parallel row partitioning), not just
the single-device fallback.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelSpec, oos
from repro.core.kernels_math import gram
from repro.launch.mesh import make_serving_mesh
from repro.serve import KpcaEngine, KpcaServeConfig, ModelHandle
from repro.serve.sharded import (POLICIES, CrossoverTable, ShardedRouter,
                                 measure_crossover, project_sharded)

SPEC = KernelSpec(kind="rbf", gamma=0.25)
N, M, C = 90, 12, 3                       # N chosen indivisible by 4


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


@pytest.fixture(scope="module")
def fitted():
    x = jnp.asarray(_rand((N, M), seed=0))
    return oos.fit_central(x, SPEC, n_components=C, center=True)


@pytest.fixture(scope="module")
def queries():
    return jnp.asarray(_rand((17, M), seed=1))


class TestShardingParity:
    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    def test_matches_unsharded_on_mesh(self, fitted, queries, n_shards):
        """Sharded psum scores == FittedKpca.transform to fp32 tolerance,
        on a real CPU device mesh."""
        assert jax.device_count() >= 4, "conftest should expose 4 devices"
        sharded, err = oos.shard_fitted(fitted, n_shards)
        assert np.all(np.asarray(err) == 0.0)     # sharding alone is exact
        mesh = make_serving_mesh(n_shards)
        assert mesh is not None and mesh.devices.size == n_shards
        got = np.asarray(project_sharded(sharded, queries, mesh=mesh))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_pallas_partials_match(self, fitted, queries, n_shards):
        sharded, _ = oos.shard_fitted(fitted, n_shards)
        got = np.asarray(project_sharded(sharded, queries, use_pallas=True,
                                         interpret=True))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_uneven_partition(self, fitted, queries):
        """N=90 over 4 shards: sizes (23, 23, 22, 22), padding rows must
        contribute nothing."""
        sharded, _ = oos.shard_fitted(fitted, 4)
        assert sum(sharded.shard_sizes) == N
        assert sharded.shard_capacity == max(sharded.shard_sizes)
        assert len(set(sharded.shard_sizes)) > 1   # actually uneven
        # indicator column is 0 exactly on padding rows
        ind = np.asarray(sharded.coefs_ext[..., -1])
        for j, n in enumerate(sharded.shard_sizes):
            assert ind[j, :n].all() and not ind[j, n:].any()
        got = np.asarray(project_sharded(sharded, queries))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_single_device_fallback_same_math(self, fitted, queries):
        """mesh=None with more shards than devices falls back to the local
        reduction; scores identical to the mesh path."""
        sharded, _ = oos.shard_fitted(fitted, 8)   # > 4 devices
        assert make_serving_mesh(8) is None
        got = np.asarray(project_sharded(sharded, queries))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


class TestGatherAndCheckpoint:
    def test_shard_gather_roundtrip(self, fitted, queries):
        sharded, _ = oos.shard_fitted(fitted, 3)
        back = oos.gather_fitted(sharded)
        np.testing.assert_array_equal(np.asarray(back.x_support),
                                      np.asarray(fitted.x_support))
        np.testing.assert_array_equal(np.asarray(back.coefs),
                                      np.asarray(fitted.coefs))
        np.testing.assert_array_equal(np.asarray(oos.project(back, queries)),
                                      np.asarray(oos.project(fitted, queries)))

    def test_checkpoint_roundtrip(self, fitted, queries, tmp_path):
        """save -> load -> gather recovers the exact serving behavior."""
        sharded, _ = oos.shard_fitted(fitted, 4)
        oos.save_sharded(str(tmp_path / "ck"), sharded)
        back = oos.load_sharded(str(tmp_path / "ck"))
        assert back.spec == sharded.spec
        assert back.shard_sizes == sharded.shard_sizes
        assert back.n_support == sharded.n_support
        np.testing.assert_array_equal(np.asarray(back.coefs_ext),
                                      np.asarray(sharded.coefs_ext))
        np.testing.assert_array_equal(
            np.asarray(project_sharded(back, queries)),
            np.asarray(project_sharded(sharded, queries)))
        gathered = oos.gather_fitted(back)
        np.testing.assert_allclose(
            np.asarray(oos.project(gathered, queries)),
            np.asarray(oos.project(fitted, queries)), rtol=1e-6, atol=1e-6)

    def test_load_rejects_wrong_kind(self, fitted, tmp_path):
        oos.save_fitted(str(tmp_path / "ck"), fitted)
        with pytest.raises(ValueError):
            oos.load_sharded(str(tmp_path / "ck"))


class TestPerShardCompression:
    def test_bound_dominates_actual_error(self, fitted):
        """The aggregate triangle-inequality bound must upper-bound the true
        relative RKHS error of the summed compressed component."""
        sharded, bound = oos.shard_fitted(fitted, 2, landmarks_per_shard=16)
        a_eff = np.asarray(oos.effective_coefs(fitted))
        x, g = fitted.x_support, fitted.gamma
        cm = oos.gather_fitted(sharded)               # row_mean_coef == 0
        z, beta = cm.x_support, np.asarray(cm.coefs)
        kxx = np.asarray(gram(SPEC, x, gamma=g))
        kzz = np.asarray(gram(SPEC, z, gamma=g))
        kxz = np.asarray(gram(SPEC, x, z, gamma=g))
        w2 = np.sum(a_eff * (kxx @ a_eff), axis=0)
        wh2 = np.sum(beta * (kzz @ beta), axis=0)
        cross = np.sum(a_eff * (kxz @ beta), axis=0)
        actual = np.sqrt(np.clip(w2 + wh2 - 2 * cross, 0.0, None) / w2)
        assert (np.asarray(bound) >= actual - 1e-5).all(), (bound, actual)

    def test_bound_monotone_in_landmarks(self, fitted):
        """Per-shard nested landmark schedules => the aggregate bound is
        monotone non-increasing in the per-shard budget."""
        bounds = []
        for n_l in (8, 16, 32, 45):
            _, b = oos.shard_fitted(fitted, 2, landmarks_per_shard=n_l,
                                    seed=0)
            bounds.append(np.asarray(b))
        for lo, hi in zip(bounds[1:], bounds[:-1]):
            assert (lo <= hi + 1e-5).all(), (lo, hi)

    def test_full_budget_recovers_exact_scores(self, fitted, queries):
        """landmarks_per_shard >= every shard size => projection is onto the
        full span, so scores match the uncompressed model."""
        sharded, bound = oos.shard_fitted(fitted, 3, landmarks_per_shard=N)
        assert float(np.max(np.asarray(bound))) < 1e-2
        got = np.asarray(project_sharded(sharded, queries))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_compressed_serving_cost_shrinks(self, fitted):
        sharded, _ = oos.shard_fitted(fitted, 4, landmarks_per_shard=8)
        assert sharded.shard_capacity == 8
        assert sharded.n_support == 32
        assert np.all(np.asarray(sharded.row_mean_coef) == 0.0)


class TestEngineRouting:
    def test_engine_serves_sharded_model(self, fitted):
        """KpcaEngine results over a sharded model match the unsharded
        engine request-for-request."""
        sharded, _ = oos.shard_fitted(fitted, 4)
        reqs = [_rand((q, M), seed=10 + q) for q in (3, 11, 26)]
        ref_eng = KpcaEngine(fitted, KpcaServeConfig(max_batch=16,
                                                     min_bucket=8))
        sh_eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=16,
                                                     min_bucket=8))
        want = ref_eng.project_many(reqs)
        got = sh_eng.project_many(reqs)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=3e-4, atol=3e-4)
        assert sh_eng.stats.n_requests == 3
        assert sh_eng.stats.n_queries == 3 + 11 + 26

    def test_engine_rejects_mesh_for_plain_model(self, fitted):
        mesh = make_serving_mesh(1)
        with pytest.raises(ValueError):
            KpcaEngine(fitted, mesh=mesh)


class TestCrossoverTable:
    def test_no_mesh_or_single_shard_routes_single(self):
        t = CrossoverTable()
        assert t.choose(4096, 4096, 4, has_mesh=False) == "single"
        assert t.choose(4096, 4096, 1, has_mesh=True) == "single"

    def test_threshold_defaults(self):
        t = CrossoverTable()
        assert t.choose(64, 512, 4, has_mesh=True) == "single"
        assert t.choose(256, 4096, 4, has_mesh=True) == "mp"
        assert t.choose(4096, 4096, 4, has_mesh=True) == "dp"

    def test_measured_entry_overrides_thresholds(self):
        t = CrossoverTable(table={(256, 4096): "dp"})
        assert t.choose(256, 4096, 4, has_mesh=True) == "dp"
        assert t.choose(256, 8192, 4, has_mesh=True) == "mp"  # unmeasured

    def test_dp_requires_divisible_rows(self):
        t = CrossoverTable()
        # default choice would be dp, but 4097 rows don't divide over 4
        assert t.choose(4097, 4096, 4, has_mesh=True) == "mp"
        # measured dp at a SMALL support degrades to single, not mp
        t2 = CrossoverTable(table={(16, 512): "dp"})
        assert t2.choose(9, 512, 4, has_mesh=True) == "single"


class TestRoutingParity:
    """fp32 parity of every policy against the unsharded reference, on the
    real 4-device CPU mesh (acceptance: routing is a perf decision, never a
    numerics one)."""

    @pytest.mark.parametrize("policy", ["mp", "dp", "single", "auto"])
    def test_project_sharded_policies_match(self, fitted, queries, policy):
        sharded, _ = oos.shard_fitted(fitted, 4)
        mesh = make_serving_mesh(4)
        assert mesh is not None and mesh.devices.size == 4
        q16 = queries[:16]                    # divisible by 4 (dp-feasible)
        got = np.asarray(project_sharded(sharded, q16, mesh=mesh,
                                         policy=policy))
        want = np.asarray(oos.project(fitted, q16))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_dp_indivisible_rows_degrade_same_math(self, fitted, queries):
        sharded, _ = oos.shard_fitted(fitted, 4)   # 17 rows % 4 != 0
        got = np.asarray(project_sharded(sharded, queries, policy="dp"))
        want = np.asarray(oos.project(fitted, queries))
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)

    def test_invalid_policy_rejected(self, fitted, queries):
        sharded, _ = oos.shard_fitted(fitted, 2)
        with pytest.raises(ValueError):
            project_sharded(sharded, queries, policy="fastest")
        with pytest.raises(ValueError):
            ShardedRouter(make_serving_mesh(2), policy="fastest")

    @pytest.mark.parametrize("routing", ["auto", "mp", "dp", "single"])
    def test_engine_routing_parity(self, fitted, routing):
        sharded, _ = oos.shard_fitted(fitted, 4)
        reqs = [_rand((q, M), seed=20 + q) for q in (8, 16, 32)]
        ref = KpcaEngine(fitted, KpcaServeConfig(max_batch=32, min_bucket=8))
        eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=32, min_bucket=8,
                                                  routing=routing))
        want = ref.project_many([r.copy() for r in reqs])
        got = eng.project_many([r.copy() for r in reqs])
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=3e-4, atol=3e-4)
        if routing != "auto":     # forced policy taken for EVERY slab
            for p in POLICIES:
                n = getattr(eng.stats, f"n_routed_{p}")
                assert (n > 0) == (p == routing), eng.stats.routing_summary()

    def test_engine_rejects_routing_for_plain_model(self, fitted):
        with pytest.raises(ValueError):
            KpcaEngine(fitted, KpcaServeConfig(routing="mp"))


class TestPlacementCache:
    def test_placement_paid_once_per_version_and_group(self, fitted,
                                                       queries):
        sharded, _ = oos.shard_fitted(fitted, 4)
        router = ShardedRouter(make_serving_mesh(4), donate=False)
        q = jnp.asarray(queries[:16])
        router.dispatch(sharded, 0, q, "mp")
        router.dispatch(sharded, 0, q, "mp")       # cache hit
        assert router.n_placements == 1
        router.dispatch(sharded, 0, q, "dp")       # second group
        assert router.n_placements == 2
        router.dispatch(sharded, 1, q, "mp")       # new version invalidates
        assert router.n_placements == 3
        router.dispatch(sharded, 1, q, "single")   # home placement: free
        assert router.n_placements == 3

    @pytest.mark.parametrize("routing", ["mp", "dp"])
    def test_engine_reports_placed_devices(self, fitted, routing):
        sharded, _ = oos.shard_fitted(fitted, 4)
        eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=16, min_bucket=8,
                                                  routing=routing))
        assert eng.placed_devices(routing) == frozenset()
        eng.project_many([_rand((16, M), seed=60)])
        assert eng.placed_devices(routing) \
            == frozenset(eng._router.mesh.devices.flat)
        assert eng.placed_devices("single") == frozenset()
        with pytest.raises(ValueError):
            eng.compiled_text()

    def test_engine_drains_reuse_placement(self, fitted):
        sharded, _ = oos.shard_fitted(fitted, 4)
        eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=16, min_bucket=8,
                                                  routing="mp"))
        for i in range(3):
            eng.project_many([_rand((16, M), seed=50 + i)])
        assert eng._router.n_placements == 1


class TestShardedWarmup:
    def test_warmup_reaches_sharded_dispatch(self, fitted):
        """Regression: warmup must go through the ROUTER (policy choice +
        placement + donated entry), so the first sharded drain after
        warmup compiles nothing."""
        sharded, _ = oos.shard_fitted(fitted, 4)
        eng = KpcaEngine(sharded, KpcaServeConfig(max_batch=32, min_bucket=8,
                                                  warmup=False))
        built = eng.warmup()
        assert built == len(eng._buckets) > 0
        assert eng.warmup() == 0               # idempotent
        eng.project_many([_rand((q, M), seed=30) for q in (8, 16, 32)])
        assert eng.stats.n_compiles == 0

    @pytest.mark.parametrize("routing", ["mp", "dp"])
    def test_warmup_covers_forced_policies(self, fitted, routing):
        sharded, _ = oos.shard_fitted(fitted, 4)
        eng = KpcaEngine(sharded, KpcaServeConfig(
            max_batch=16, min_bucket=16, routing=routing, warmup=False))
        eng.warmup()
        eng.project_many([_rand((16, M), seed=31)])
        assert eng.stats.n_compiles == 0
        assert getattr(eng.stats, f"n_routed_{routing}") == 1


class TestMeasureCrossover:
    def test_measures_feasible_policies_per_bucket(self, fitted):
        sharded, _ = oos.shard_fitted(fitted, 4)
        t = measure_crossover(sharded, row_buckets=(8, 16), reps=1)
        assert set(t.table) == {(8, 128), (16, 128)}   # pow2(N=90) == 128
        assert all(p in POLICIES for p in t.table.values())
        # the measured entry drives choose() for its bucket
        for (rows, _), policy in t.table.items():
            assert t.choose(rows, N, 4, has_mesh=True) == policy


@pytest.mark.lockcheck
class TestOverlappedShardedDrainHammer:
    WAIT = 30.0

    def test_hammer_no_stale_version_no_clobber(self, fitted):
        """4 submitter threads over a STARTED sharded engine, racing a
        stream of per-shard coefficient publishes through the overlapped
        (pipelined) drain. Every result must match the oracle for the
        version recorded in its request stats (no stale shard, no mixed
        versions), no submitted array may be clobbered by donation, and
        version churn must never recompile (placement is re-paid, programs
        are not)."""
        sharded, _ = oos.shard_fitted(fitted, 4)
        handle = ModelHandle(sharded)
        eng = KpcaEngine(handle, KpcaServeConfig(
            max_batch=16, min_bucket=16, flush_max_wait_s=0.002,
            routing="mp", warmup=False))
        eng.warmup()
        eng.stats = type(eng.stats)()
        versions = [sharded]                   # version v -> model
        n_threads, n_per = 4, 5
        outs = [[] for _ in range(n_threads)]
        errors = []

        def submitter(tid):
            try:
                for i in range(n_per):
                    x = _rand((16, M), seed=100 + tid * n_per + i)
                    keep = x.copy()
                    fut = eng.submit(x)
                    r = fut.result(timeout=self.WAIT)
                    outs[tid].append((fut.request_id, x, keep, r))
            except Exception as e:             # surfaces after join
                errors.append(e)

        def publisher():
            rng = np.random.default_rng(41)
            try:
                for i in range(8):
                    shard = i % sharded.n_shards
                    a = rng.normal(size=(sharded.shard_sizes[shard], C)) \
                        .astype(np.float32)
                    handle.refresh_shard(shard, jnp.asarray(a))
                    versions.append(handle.current())
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(n_threads)]
        threads.append(threading.Thread(target=publisher))
        with eng:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors, errors

        by_rid = {s.request_id: s for s in eng.stats.per_request}
        ref = jax.jit(lambda m, q: project_sharded(m, q, policy="mp"))
        seen = set()
        for tid in range(n_threads):
            assert len(outs[tid]) == n_per
            for rid, x, keep, r in outs[tid]:
                np.testing.assert_array_equal(x, keep)     # no clobber
                v = by_rid[rid].model_version
                seen.add(v)
                want = np.asarray(ref(versions[v], jnp.asarray(keep)))
                # a stale shard would be off by O(1); 1e-6 is program skew
                np.testing.assert_allclose(r, want, rtol=1e-6, atol=1e-6)
        assert seen                            # every request attributed
        assert eng.stats.n_compiles == 0       # churn re-places, not re-jits
        assert eng.stats.n_routed_mp > 0       # the forced policy was taken
        assert eng._router.n_placements >= len(seen)   # re-placed per version


class TestValidation:
    def test_rejects_bad_shard_count(self, fitted):
        with pytest.raises(ValueError):
            oos.shard_fitted(fitted, 0)
        with pytest.raises(ValueError):
            oos.shard_fitted(fitted, N + 1)
        with pytest.raises(ValueError):
            oos.shard_fitted(fitted, 2, landmarks_per_shard=0)
