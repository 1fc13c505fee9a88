"""Tests for the serving artifact (repro.core.oos) and the fused Pallas
projection kernel (repro.kernels.project)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KernelSpec, central_kpca, kpca_project, oos
from repro.core.kernels_math import gram
from repro.kernels import (project_op, project_partial_op,
                           project_partial_reference, project_reference)

SPEC = KernelSpec(kind="rbf", gamma=0.25)


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


@pytest.fixture(scope="module")
def fitted():
    x = jnp.asarray(_rand((64, 16), seed=0))
    model = oos.fit_central(x, SPEC, n_components=3, center=True)
    return x, model


class TestFittedKpca:
    def test_training_points_reproduce_centered_scores(self, fitted):
        """score(x_i) must equal (K_c alpha)_i — the defining property of
        the centered out-of-sample formula."""
        x, model = fitted
        alpha, _, k_c = central_kpca(x, SPEC, 3, center=True,
                                     gamma=model.gamma)
        want = np.asarray(k_c @ alpha)
        got = np.asarray(oos.project(model, x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_uncentered_matches_raw_projection(self):
        x = jnp.asarray(_rand((40, 8), seed=1))
        xq = jnp.asarray(_rand((11, 8), seed=2))
        model = oos.fit_central(x, SPEC, 2, center=False)
        from repro.core.kernels_math import gram
        want = np.asarray(gram(SPEC, xq, x, gamma=model.gamma) @ model.coefs)
        got = np.asarray(oos.project(model, xq))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_kpca_project_is_centered_now(self, fitted):
        """The old raw path silently disagreed with a centered fit; the
        routed-through-oos version must match the centered eigen-scores,
        and the deprecated ``center=`` kwarg is gone (deprecation cycle
        finished — build ``oos.from_dual(center=False)`` for a raw fit)."""
        x, model = fitted
        alpha, _, k_c = central_kpca(x, SPEC, 3, center=True,
                                     gamma=model.gamma)
        got = np.asarray(kpca_project(x, x, alpha, SPEC, gamma=model.gamma))
        np.testing.assert_allclose(got, np.asarray(k_c @ alpha),
                                   rtol=1e-5, atol=1e-5)
        with pytest.raises(TypeError):
            kpca_project(x, x, alpha, SPEC, gamma=model.gamma, center=False)

    def test_from_decentralized_pools_nodes(self):
        """Packaging semantics: (J, N) node solutions (single or top-k
        list) pool to the averaged dual vector on the pooled support set.
        (Consensus *quality* is the fitting pipeline's concern — see
        tests/test_admm_convergence.py.)"""
        nodes = jnp.asarray(_rand((6, 20, 10), seed=3))
        a1 = jnp.asarray(_rand((6, 20), seed=4))
        a2 = jnp.asarray(_rand((6, 20), seed=5))
        model = oos.from_decentralized(nodes, [a1, a2], SPEC, gamma=0.3,
                                       center=True)
        assert model.n_support == 120 and model.n_components == 2
        pooled_alpha = jnp.stack([a1.reshape(-1), a2.reshape(-1)],
                                 axis=1) / 6
        want = oos.from_dual(nodes.reshape(-1, 10), pooled_alpha, SPEC,
                             gamma=0.3, center=True)
        xq = jnp.asarray(_rand((7, 10), seed=6))
        np.testing.assert_array_equal(np.asarray(oos.project(model, xq)),
                                      np.asarray(oos.project(want, xq)))

    def test_save_load_roundtrip(self, fitted, tmp_path):
        x, model = fitted
        oos.save_fitted(str(tmp_path / "ck"), model)
        back = oos.load_fitted(str(tmp_path / "ck"))
        assert back.spec == model.spec
        xq = jnp.asarray(_rand((9, 16), seed=4))
        np.testing.assert_array_equal(np.asarray(oos.project(model, xq)),
                                      np.asarray(oos.project(back, xq)))


class TestBlockwiseKernelMeans:
    """``from_dual(center=True)`` takes the kernel means in row blocks of
    the training Gram, in one program that never holds the (L, L) Gram."""

    @staticmethod
    def _dense_stats(x, alpha, spec, gamma):
        x = np.asarray(x, np.float64)
        if spec.kind == "rbf":
            sq = np.sum(x * x, 1)
            d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0)
            k = np.exp(-gamma * d2)
        else:
            k = x @ x.T * spec.scale
            diag = np.diag(k).copy()
            if spec.kind == "poly":
                k = (k + spec.coef) ** spec.degree
                diag = (diag + spec.coef) ** spec.degree
            k = k / np.sqrt(diag[:, None] * diag[None, :])
        m = k.mean(axis=1)
        mu = k.mean()
        a = np.asarray(alpha, np.float64)
        return {"k_row_mean": m, "k_grand_mean": mu,
                "bias": mu * a.sum(0) - m @ a, "row_mean_coef": -a.sum(0)}

    @pytest.mark.parametrize("kind", ["rbf", "linear", "poly"])
    def test_forced_small_blocks_match_the_dense_float64_stats(
            self, kind, monkeypatch):
        n = 1000
        monkeypatch.setattr(oos, "KERNEL_MEAN_BLOCK_ELEMS", 96 * n)
        assert oos.kernel_mean_block_rows(n) == 96       # 10 blocks + 40
        x = np.random.default_rng(3).uniform(size=(n, 24)).astype(np.float32)
        alpha = _rand((n, 2), seed=4) / np.sqrt(n)
        spec = KernelSpec(kind=kind, degree=2)
        model = oos.from_dual(jnp.asarray(x), jnp.asarray(alpha), spec)
        want = self._dense_stats(x, alpha, spec, float(model.gamma))
        for name, ref in want.items():
            got = np.asarray(getattr(model, name), np.float64)
            err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
            assert err < 1e-5, (name, err)

    @staticmethod
    def _largest_value(jaxpr) -> int:
        """Elements of the largest value any equation of ``jaxpr`` (and
        of every program nested in it) makes."""
        import jax
        from jax.extend.core import ClosedJaxpr, Jaxpr
        biggest = 0
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                biggest = max(biggest, int(np.prod(v.aval.shape)))
            for p in jax.tree_util.tree_leaves(
                    list(eqn.params.values()),
                    is_leaf=lambda t: isinstance(t, (Jaxpr, ClosedJaxpr))):
                if isinstance(p, ClosedJaxpr):
                    p = p.jaxpr
                if isinstance(p, Jaxpr):
                    biggest = max(biggest,
                                  TestBlockwiseKernelMeans._largest_value(p))
        return biggest

    @pytest.mark.parametrize("n,budget", [(4096, 2 ** 20),
                                          (60000, None)])
    def test_program_holds_no_support_by_support_array(self, n, budget,
                                                       monkeypatch):
        import jax
        if budget is not None:
            monkeypatch.setattr(oos, "KERNEL_MEAN_BLOCK_ELEMS", budget)
        rows = oos.kernel_mean_block_rows(n)
        assert 1 < rows < n
        closed = jax.make_jaxpr(
            lambda x, g: oos._kernel_means_blocks(SPEC, rows, x, g))(
            jax.ShapeDtypeStruct((n, 784), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
        biggest = self._largest_value(closed.jaxpr)
        assert rows * n <= biggest < n * n            # the block's tile

    def test_the_pass_is_one_span(self):
        from repro.obs import trace
        tracer = trace.Tracer()
        outer = trace.active()
        trace.install(tracer)
        try:
            oos.from_dual(jnp.asarray(_rand((50, 8), seed=5)),
                          jnp.asarray(_rand((50,), seed=6)), SPEC)
        finally:
            trace.install(outer)
        spans = [attrs for ph, name, _t0, _dur, _tid, attrs
                 in tracer.events() if name == "oos.kernel_means"]
        assert spans == [{"rows": 50, "block_rows": 50, "blocks": 1}]


class TestSupportNorms:
    """The artifact carries its support's squared row norms, taken once
    when it is built, and ``project`` reads them instead of the support."""

    @staticmethod
    def _built(how, fitted, tmp_path):
        _, model = fitted
        if how == "from_dual":
            return model
        if how == "refresh_coefficients":
            return oos.refresh_coefficients(model, model.coefs * 2.0)
        if how == "compress":
            return oos.compress(model, 20, seed=1)[0]
        if how == "gather_fitted":
            return oos.gather_fitted(oos.shard_fitted(model, 3)[0])
        oos.save_fitted(str(tmp_path / "ck"), model)
        return oos.load_fitted(str(tmp_path / "ck"))

    @pytest.mark.parametrize("how", ["from_dual", "refresh_coefficients",
                                     "compress", "gather_fitted",
                                     "save_load"])
    def test_every_constructor_sets_the_norms(self, how, fitted, tmp_path):
        model = self._built(how, fitted, tmp_path)
        xs = jnp.asarray(model.x_support)
        assert model.support_sq_norm.shape == (model.n_support,)
        assert model.support_sq_norm.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(model.support_sq_norm),
                                   np.asarray(jnp.sum(xs * xs, -1)),
                                   rtol=1e-6)

    @pytest.mark.parametrize("kind", ["rbf", "linear", "poly"])
    def test_scores_match_the_gram_oracle(self, kind):
        import jax
        spec = KernelSpec(kind=kind, gamma=0.2, degree=2, coef=0.5,
                          scale=0.3)
        xs = jnp.asarray(_rand((70, 10), seed=20))
        xq = jnp.asarray(_rand((9, 10), seed=21))
        model = oos.from_dual(xs, jnp.asarray(_rand((70, 2), seed=22)), spec)
        with jax.default_matmul_precision("highest"):
            k = gram(spec, xq, xs, gamma=model.gamma)
            want = (k @ model.coefs
                    + jnp.mean(k, axis=1, keepdims=True)
                    * model.row_mean_coef[None] + model.bias[None])
            got = oos.project(model, xq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


class TestCompression:
    def test_error_monotone_in_landmarks(self, fitted):
        """Nested landmark sets => RKHS reconstruction error is monotone
        non-increasing in L, and exact recovery at full L."""
        x, model = fitted
        errs = []
        for n_l in (8, 16, 32, 48, 64):
            _, err = oos.compress(model, n_l, seed=0)
            errs.append(np.asarray(err))
        for lo, hi in zip(errs[1:], errs[:-1]):
            assert (lo <= hi + 1e-5).all(), (lo, hi)
        assert (errs[-1] < 1e-2).all(), errs[-1]

    def test_compressed_projection_approaches_exact(self, fitted):
        x, model = fitted
        xq = jnp.asarray(_rand((12, 16), seed=5))
        want = np.asarray(oos.project(model, xq))
        cm, _ = oos.compress(model, model.n_support, seed=0)
        got = np.asarray(oos.project(cm, xq))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)

    def test_rejects_bad_landmark_count(self, fitted):
        _, model = fitted
        with pytest.raises(ValueError):
            oos.compress(model, 0)
        with pytest.raises(ValueError):
            oos.compress(model, model.n_support + 1)


class TestProjectPallasKernel:
    SHAPES = [(8, 8, 4, 1), (17, 23, 9, 3), (1, 64, 16, 2),
              (130, 100, 300, 2), (5, 300, 37, 1), (64, 256, 784, 4)]

    @pytest.mark.parametrize("bq,ls,m,c", SHAPES)
    @pytest.mark.parametrize("kind", ["rbf", "linear", "poly"])
    def test_allclose_to_reference(self, bq, ls, m, c, kind):
        spec = KernelSpec(kind=kind, gamma=0.3, degree=2, scale=0.1)
        rng = np.random.default_rng(bq * 1000 + ls)
        xq = jnp.asarray(rng.normal(size=(bq, m)).astype(np.float32))
        xs = jnp.asarray(rng.normal(size=(ls, m)).astype(np.float32))
        a = jnp.asarray(rng.normal(size=(ls, c)).astype(np.float32))
        rc = jnp.asarray(rng.normal(size=(c,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(c,)).astype(np.float32))
        got = np.asarray(project_op(spec, xq, xs, a, row_mean_coef=rc,
                                    bias=b, interpret=True))
        want = np.asarray(project_reference(spec, xq, xs, a,
                                            row_mean_coef=rc, bias=b))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_defaults_are_raw_projection(self):
        spec = KernelSpec(kind="rbf", gamma=0.5)
        xq = jnp.asarray(_rand((10, 12), seed=6))
        xs = jnp.asarray(_rand((30, 12), seed=7))
        a = jnp.asarray(_rand((30, 2), seed=8))
        got = np.asarray(project_op(spec, xq, xs, a, interpret=True))
        want = np.asarray(project_reference(spec, xq, xs, a))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_custom_blocks_multi_tile(self):
        """Force >1 tile on every grid axis."""
        spec = KernelSpec(kind="rbf", gamma=0.2)
        xq = jnp.asarray(_rand((70, 260), seed=9))
        xs = jnp.asarray(_rand((90, 260), seed=10))
        a = jnp.asarray(_rand((90, 1), seed=11))
        rc = jnp.asarray(_rand((1,), seed=12))
        b = jnp.asarray(_rand((1,), seed=13))
        got = np.asarray(project_op(spec, xq, xs, a, row_mean_coef=rc,
                                    bias=b, block_q=32, block_l=32,
                                    block_m=128, interpret=True))
        want = np.asarray(project_reference(spec, xq, xs, a,
                                            row_mean_coef=rc, bias=b))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kind", ["linear", "poly"])
    def test_partial_op_non_rbf_matches_oracle(self, kind):
        """Sharded serving's raw-partials entry point through the fused
        kernel, for the normalized (§3.1) linear/poly kernels — including
        zero-indicator padding rows, which must contribute nothing."""
        spec = KernelSpec(kind=kind, degree=3, coef=0.5, scale=0.2)
        assert spec.normalize                  # paper §3.1 normalization
        rng = np.random.default_rng(41)
        xq = jnp.asarray(rng.normal(size=(13, 9)).astype(np.float32))
        xs = jnp.asarray(rng.normal(size=(21, 9)).astype(np.float32))
        ae = rng.normal(size=(21, 3)).astype(np.float32)
        ae[:, -1] = 1.0
        ae[17:] = 0.0                          # shard-padding rows
        ae = jnp.asarray(ae)
        got = np.asarray(project_partial_op(spec, xq, xs, ae,
                                            interpret=True))
        want = np.asarray(project_partial_reference(spec, xq, xs, ae))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        # last column really is the raw row-sum over the valid rows
        np.testing.assert_allclose(
            got[:, -1],
            np.asarray(jnp.sum(gram(spec, xq, xs[:17]), axis=1)),
            rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kind", ["linear", "poly"])
    def test_non_rbf_centered_fit_serves_through_pallas(self, kind):
        """A CENTERED fit of a normalized non-RBF kernel must score
        identically through the fused Pallas path and the jnp oracle."""
        spec = KernelSpec(kind=kind, degree=2, scale=0.5)
        x = jnp.asarray(_rand((40, 8), seed=42))
        model = oos.fit_central(x, spec, n_components=2, center=True)
        xq = jnp.asarray(_rand((11, 8), seed=43))
        got = np.asarray(oos.project(model, xq, use_pallas=True,
                                     interpret=True))
        want = np.asarray(oos.project(model, xq))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_model_pallas_path_matches_jnp_path(self, fitted):
        x, model = fitted
        xq = jnp.asarray(_rand((21, 16), seed=14))
        got = np.asarray(oos.project(model, xq, use_pallas=True,
                                     interpret=True))
        want = np.asarray(oos.project(model, xq))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_bf16_queries(self):
        spec = KernelSpec(kind="rbf", gamma=0.5)
        xq = jnp.asarray(_rand((16, 32), seed=15)).astype(jnp.bfloat16)
        xs = jnp.asarray(_rand((48, 32), seed=16))
        a = jnp.asarray(_rand((48, 2), seed=17))
        got = np.asarray(project_op(spec, xq, xs, a, interpret=True))
        want = np.asarray(project_reference(
            spec, xq.astype(jnp.float32), xs, a))
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
