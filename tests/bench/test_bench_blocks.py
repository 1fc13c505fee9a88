"""The serving driver for supports too large for the dense reference
(``open_loop_blocks``), its blockwise reference, its controls and the
artifact and projection readers, at tiny sizes on the CPU."""

import json
import os
import types

import numpy as np
import pytest

from benchtiny import SEED, write_json

from bench import control_blocks, reference, reference_blocks, roofline  # noqa: E402
from bench import run, traffic  # noqa: E402
from bench.data import kpca_dataset  # noqa: E402

V5E = "TPU v5 lite"
NEW = ("serve.artifact_ms", "serve.artifact_roofline",
       "serve.project_roofline")


def _support(n, seed=3):
    return kpca_dataset(n, 784, 4, seed=seed)


@pytest.mark.parametrize("block_rows", [96, 700, 1024])
def test_blockwise_reference_is_the_dense_formula(block_rows):
    x = _support(700)
    coefs = np.random.default_rng(1).standard_normal((700, 2)) / np.sqrt(700)
    q = kpca_dataset(40, 784, 4, seed=4)
    g = reference.gamma_median(x)
    k = reference.rbf(x, x, g)
    m, mu = reference_blocks.kernel_means(x, g, block_rows)
    assert np.max(np.abs(m - k.mean(axis=1))) < 1e-12
    assert abs(mu - k.mean()) < 1e-12
    want = reference.scores(x, coefs, q, g)
    got = reference_blocks.scores(x, coefs, q, g, means=(m, mu))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-12


def test_blockwise_built_model_serves_the_reference_scores(monkeypatch):
    import jax.numpy as jnp
    from repro.core import KernelSpec, oos
    n = 900
    monkeypatch.setattr(oos, "KERNEL_MEAN_BLOCK_ELEMS", 128 * n)
    x = _support(n, seed=5)
    coefs = (np.random.default_rng(6).standard_normal((n, 2))
             / np.sqrt(n)).astype(np.float32)
    q = kpca_dataset(64, 784, 4, seed=7)
    model = oos.from_dual(jnp.asarray(x), jnp.asarray(coefs),
                          KernelSpec(kind="rbf"))
    got = np.asarray(oos.project(model, jnp.asarray(q)))
    want = reference_blocks.scores(x, coefs, q, reference.gamma_median(x))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4
    m_ref, _ = reference_blocks.kernel_means(x, reference.gamma_median(x))
    assert control_blocks.mean_err(model.k_row_mean, m_ref) < 1e-5


def _blocks_cell(bench_json, home, limits=None):
    """A tiny cell of the ``open_loop_blocks`` driver beside ``t.serve``,
    reporting what the serving cells report and the new readers."""
    mix = json.load(open(os.path.join(home, "traffic", "poisson.json")))
    write_json(os.path.join(home, "traffic", "poisson_blocks.json"),
               dict(mix, kind="open_loop_blocks"))
    name = "tiny-dense"
    if limits:
        cfg = json.load(open(os.path.join(home, "configs", name + ".json")))
        cfg["limits"] = dict(cfg["limits"], **limits)
        name = "tiny-blocks"
        write_json(os.path.join(home, "configs", name + ".json"), cfg)
    bench_json["workloads"].append({"name": "b.serve", "config": name,
                                    "traffic": "poisson_blocks",
                                    "chips": 1})
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        if "t.serve" in m.get("workloads", ()):
            m["workloads"].append("b.serve")
    return "b.serve"


def test_blocks_cell_runs_correct_and_reports_its_metrics(bench_json, home,
                                                           standin):
    cell = _blocks_cell(bench_json, home, limits={"mean_err": 1e-4})
    out = run.run_cell(bench_json, cell, SEED, 1.0, False, home=home)
    assert set(out["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert out["attempted"] == 100 and out["failed"] == 0
    assert out["correct"], out["checks"]
    assert list(out["checks"]) == ["score_err", "unanswered", "mean_err"]
    traced = run.run_cell(bench_json, cell, SEED, 1.0, True, home=home)
    # the CPU has no peaks and no program-level device lines: both
    # rooflines find nothing there and are left out
    assert set(traced["metrics"]) == {"serve.queue_wait_ms",
                                      "device_idle.serve",
                                      "serve.artifact_ms"}
    assert traced["metrics"]["serve.artifact_ms"]["value"] > 0
    assert traced["correct"], traced["checks"]


def test_blocks_cell_without_mean_limit_checks_what_open_loop_does(
        bench_json, home):
    cell = _blocks_cell(bench_json, home)
    out = run.run_cell(bench_json, cell, SEED, 0.5, False, home=home)
    assert list(out["checks"]) == ["score_err", "unanswered"]
    assert out["correct"], out["checks"]


def test_blocks_cell_refuses_answers_altered_where_produced(bench_json,
                                                           home):
    from repro.serve import kpca_engine
    cell = _blocks_cell(bench_json, home)
    orig = kpca_engine.KpcaEngine._collect

    def skewed(slabs, done):
        dts, host, *rest = orig(slabs, done)
        return (dts, [h + 1e-2 * np.max(np.abs(h)) for h in host], *rest)
    kpca_engine.KpcaEngine._collect = staticmethod(skewed)
    try:
        out = run.run_cell(bench_json, cell, SEED, 0.5, False, home=home)
    finally:
        kpca_engine.KpcaEngine._collect = staticmethod(orig)
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > 1e-4


def _found(home, config):
    return {"cfg": json.load(open(os.path.join(home, "configs",
                                               config + ".json"))),
            "mix": dict(traffic.load(os.path.join(home, "traffic",
                                                  "poisson.json")),
                        kind="open_loop_blocks")}


def test_controls_fail_their_checks(home, monkeypatch):
    monkeypatch.setattr(traffic, "HOME", home)
    found = _found(home, "tiny-dense")
    r = control_blocks.readings(found, SEED, 0.5)
    lim = found["cfg"]["limits"]["score_err"]
    assert r["program_score_err"] < lim and r["program_unanswered"] == 0
    assert r["query_score_err"] > lim
    assert r["artifact_mean_err"] > 30 * r["program_mean_err"]
    means = control_blocks.means_only(found, SEED)
    assert means["program_mean_err"] == pytest.approx(
        r["program_mean_err"], rel=1e-6)
    assert means["artifact_mean_err"] > 30 * means["program_mean_err"]


def _ctx(layer, trace=None):
    return types.SimpleNamespace(workload="x", kind="serve", layer=layer,
                                 trace=trace, summary={})


def _read(name, ctx):
    return run.read_metric(name, ctx)


ARTIFACT = {"artifact_ms": 400.0, "support_rows": 60000, "features": 784,
            "kernel_mean_blocks": 54, "device_kind": V5E}


def test_artifact_roofline_reads_the_pass_against_the_peaks():
    peak = roofline.peaks(V5E)
    ops = 2.0 * 60000 ** 2 * 784
    assert roofline.kernel_means_ops(60000, 784) == ops
    want = 100 * max(ops / peak["bf16_flops_per_s"],
                     4.0 * 60000 * 784 * 55 / peak["hbm_bytes_per_s"]) / 0.4
    got = _read("serve.artifact_roofline", _ctx(ARTIFACT))
    assert got == pytest.approx(want) and 0 < got < 100
    assert _read("serve.artifact_ms", _ctx(ARTIFACT)) == 400.0


@pytest.mark.parametrize("missing", ["kernel_mean_blocks", "artifact_ms",
                                     "device_kind"])
def test_artifact_readers_leave_out_what_the_run_lacks(missing):
    layer = {k: v for k, v in ARTIFACT.items() if k != missing}
    assert _read("serve.artifact_roofline", _ctx(layer)) is None
    assert _read("serve.artifact_roofline",
                 _ctx(dict(ARTIFACT, device_kind="cpu"))) is None


def _trace(seconds):
    from bench import tracereduce
    dev = tracereduce.Device("/device:TPU:0", [],
                             [(0, int(seconds * 1e9), "jit__proj")])
    return tracereduce.Trace((0, int(1e10)), [dev], [])


PROJECT = {"n_slabs": 1000, "n_queries": 20000, "n_padded": 4000,
           "support_rows": 60000, "features": 784, "device_kind": V5E}


def test_project_roofline_reads_the_slabs_against_the_program_time():
    peak = roofline.peaks(V5E)
    rows, l, m = 24000, 60000, 784
    t = max(2.0 * rows * l * m / peak["bf16_flops_per_s"],
            4.0 * (1000 * l * m + rows * m) / peak["hbm_bytes_per_s"])
    got = _read("serve.project_roofline", _ctx(PROJECT, _trace(1.0)))
    assert got == pytest.approx(100 * t / 1.0) and 0 < got < 100


@pytest.mark.parametrize("layer", [
    {k: v for k, v in PROJECT.items() if k != "n_slabs"},
    dict(PROJECT, n_slabs=0), dict(PROJECT, device_kind="cpu")])
def test_project_roofline_leaves_out_what_the_run_lacks(layer):
    assert _read("serve.project_roofline", _ctx(layer, _trace(1.0))) is None
    assert _read("serve.project_roofline", _ctx(PROJECT, _trace(0.0))) is None
