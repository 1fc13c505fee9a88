"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny sizes, the
four-chip phase on the 4 virtual host devices ``tests/conftest.py``
exposes, Pallas in interpret mode (the kernels' default off the TPU); and
its guard, which must refuse any backend but the TPU."""

import importlib.util
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# The convergence tests' small regime (test_admm_convergence.py): large
# enough that 30 iterations reach the phase's 0.99 similarity bound.
TINY_FIT = dict(nodes=8, per_node=60, features=24, hops=2, iters=30,
                chunk=10)
TINY_SERVE = dict(request_rows=(1, 5, 16), max_batch=16)


@pytest.fixture(scope="module")
def fit(smoke):
    return smoke.phase_fit(0, **TINY_FIT)


@pytest.fixture
def mosaic_probe(smoke, monkeypatch):
    """Off the TPU no program holds a Mosaic kernel: stand in for the
    probe, and record what the phase asked it about."""
    seen = []

    def probe(engine):
        seen.append((engine.cfg.use_pallas,
                     "HloModule" in engine.compiled_text()))
        return True

    monkeypatch.setattr(smoke, "_holds_mosaic_kernel", probe)
    return seen


def test_fit_phase(fit, smoke):
    assert fit["phase"] == "fit"
    assert fit["mean_similarity"] >= smoke.MIN_SIMILARITY
    assert fit["chunked_max_abs_diff"] == 0.0
    assert fit["state"]["alpha"].shape == (8, 60)


def test_serve_phase(fit, smoke, mosaic_probe):
    rep = smoke.phase_serve(fit, 0, **TINY_SERVE)
    for path in ("xla", "pallas"):
        assert len(rep[path]["request_s"]) == 3
        assert rep[path]["max_rel_err"] <= smoke.SERVE_REL_ERR
    # the Pallas engine's compiled program, and only that one, was probed
    assert mosaic_probe == [(True, True)]


def test_serve_phase_fails_without_a_mosaic_kernel(fit, smoke):
    """A Pallas kernel that runs in interpret mode fails the phase."""
    with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
        smoke.phase_serve(fit, 0, **TINY_SERVE)


def test_four_chip_phase(smoke):
    assert len(jax.devices()) >= 4
    rep = smoke.phase_four_chip(0, nodes=4, per_node=16, features=12,
                                hops=1, iters=6, **TINY_SERVE)
    assert rep["spmd_max_abs_diff"] < 1e-3
    for name in ("mp_xla", "mp_pallas", "dp_xla", "dp_pallas"):
        assert rep[name]["placed_devices"] == 4
        assert rep[name]["max_rel_err"] <= smoke.SERVE_REL_ERR


def test_serve_phase_fails_on_a_wrong_answer(fit, smoke, monkeypatch,
                                             mosaic_probe):
    """A phase raises when a check fails; it never reports success."""
    monkeypatch.setattr(smoke, "_reference_scores",
                        lambda model, xq: 0.0 * xq[:, :1] + 1.0)
    with pytest.raises(smoke.SmokeFailure, match="max\\|d\\|"):
        smoke.phase_serve(fit, 0, **TINY_SERVE)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_a_non_tpu_backend(smoke, argv, monkeypatch, tmp_path,
                                        capsys):
    assert jax.default_backend() != "tpu"
    # keep the env layer's compile-cache default out of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.warns(RuntimeWarning, match="AFTER jax was imported"):
        with pytest.raises(SystemExit) as exc:
            smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
