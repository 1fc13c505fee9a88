"""The benchmark harness at tiny sizes on the CPU: cells, mixes and metrics
found by name, the shape of the result line, and refusal off the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchtiny import BENCH, ROOT, SEED, write_json

from bench import run, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_result_line_keys_untraced(bench_json, home):
    out = run.run_cell(bench_json, "t.fit", SEED, 0.5, False, home=home)
    assert list(out) == KEYS + ["checks"]
    assert set(out["metrics"]) == {"setup_s", "fit_s"}
    assert out["metrics"]["fit_s"]["unit"] == "s"
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == 1
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["checks"]) == {"sim_gap", "lam_gap"}
    json.dumps(out)


def test_result_line_keys_traced(bench_json, home, standin, capsys):
    out = run.run_cell(bench_json, "t.fit", SEED, 0.5, True, home=home)
    assert list(out) == KEYS + ["breakdown", "checks"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert set(out["metrics"]) == {"fit.setup_ms", "fit.admm_iter_ms",
                                   "device_idle.fit"}
    assert "fit_s" not in out["metrics"]
    # the numbers compared are the last lines on standard error
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("check sim_gap = ")
    assert err[-1].startswith("check lam_gap = ")


def test_serve_cell_reports_its_own_metrics(bench_json, home, standin):
    out = run.run_cell(bench_json, "t.serve", SEED, 1.0, False, home=home)
    assert set(out["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert out["attempted"] == 100 and out["failed"] == 0
    assert out["correct"], out["checks"]
    traced = run.run_cell(bench_json, "t.serve", SEED, 1.0, True, home=home)
    # no program-level device lines on the CPU: the per-drain reader finds
    # nothing there and is left out
    assert set(traced["metrics"]) == {"serve.queue_wait_ms",
                                      "device_idle.serve"}


def test_new_config_mix_and_metric_are_found_by_name(bench_json, home,
                                                    standin):
    """A cell added as files plus entries, with no edit to any file."""
    cfg = json.load(open(os.path.join(home, "configs", "tiny-dense.json")))
    write_json(os.path.join(home, "configs", "brand-new.json"),
               dict(cfg, name="brand-new", nodes=5))
    write_json(os.path.join(home, "traffic", "one_dataset.json"),
               {"kind": "fit", "datasets": 1})
    with open(os.path.join(home, "metrics", "fit.count.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.layer['n_fits'])\n")
    with open(os.path.join(home, "metrics", "fit.silent.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    bench_json["workloads"].append({"name": "new.fit", "config": "brand-new",
                                    "traffic": "one_dataset", "chips": 1})
    for name in ("fit.count", "fit.silent"):
        bench_json["per_layer"].append(
            {"name": name, "unit": "1", "better": "higher",
             "source": "host_clock", "layer": "fit driver",
             "moves": "fit_s", "workloads": ["new.fit"]})
    out = run.run_cell(bench_json, "new.fit", SEED, 0.3, True, home=home)
    assert out["metrics"]["fit.count"]["value"] == out["attempted"]
    assert "fit.silent" not in out["metrics"]     # nothing to read: left out
    assert "fit.count" not in run.run_cell(
        bench_json, "t.fit", SEED, 0.3, True, home=home)["metrics"]


ONE_FIT = """
import time

from bench import traffic

Fit = traffic.plugin(traffic.HOME, "drivers", "fit").Cell


class Cell(Fit):
    def window(self, seconds):
        t0 = time.perf_counter()
        self._fit(0)
        return time.perf_counter() - t0
"""


def test_new_driver_is_found_by_name(bench_json, home):
    """A mix whose ``kind`` names a driver file added beside the others."""
    with open(os.path.join(home, "drivers", "one_fit.py"), "w") as f:
        f.write(ONE_FIT)
    write_json(os.path.join(home, "traffic", "single.json"),
               {"kind": "one_fit", "datasets": 1})
    bench_json["workloads"].append({"name": "one.fit", "config": "tiny-dense",
                                    "traffic": "single", "chips": 1})
    fit_s = [m for m in bench_json["end_to_end"] if m["name"] == "fit_s"]
    fit_s[0]["workloads"].append("one.fit")
    out = run.run_cell(bench_json, "one.fit", SEED, 5.0, False, home=home)
    assert out["attempted"] == 1 and out["correct"], out["checks"]
    write_json(os.path.join(home, "traffic", "nobody.json"),
               {"kind": "no_such_driver"})
    with pytest.raises(ValueError, match="no_such_driver"):
        run.resolve(dict(bench_json, workloads=[
            {"name": "x", "config": "tiny-dense", "traffic": "nobody",
             "chips": 1}]), "x", home)


UNIFORM = """
import numpy as np


def times(spec, n, seconds, work, order):
    return np.arange(n) * (seconds / n)
"""
FIXED = """
import numpy as np


def draw(spec, n, work):
    return np.full(n, spec["rows"], np.int64)
"""


def test_new_arrival_process_and_rows_are_found_by_name(home):
    with open(os.path.join(home, "arrivals", "uniform.py"), "w") as f:
        f.write(UNIFORM)
    with open(os.path.join(home, "rows", "fixed.py"), "w") as f:
        f.write(FIXED)
    mix = {"kind": "open_loop", "work_seed": 3,
           "arrival": {"process": "uniform", "rate_rps": 100.0},
           "rows": {"dist": "fixed", "rows": 4}}
    s = traffic.open_loop(mix, 2.0, SEED, 64, home)
    assert len(s.rows) == 200 and set(s.rows) == {4}
    assert np.allclose(np.diff(s.arrival_s), 0.01)
    assert np.all(s.offset + s.rows <= 64)
    with pytest.raises(ValueError, match="onoff"):
        traffic.open_loop(dict(mix, arrival={"process": "onoff",
                                             "rate_rps": 1.0}),
                          1.0, SEED, 64, home)


def test_metric_without_workloads_follows_what_it_moves(bench_json):
    entry = {"name": "x", "moves": "serve_p50_ms"}
    assert run.applies(entry, "t.serve", {"setup_s", "serve_p50_ms"})
    assert not run.applies(entry, "t.fit", {"setup_s", "fit_s"})


def test_unknown_workload_is_refused(bench_json, home):
    with pytest.raises(run.Refused):
        run.resolve(bench_json, "no.such.cell", home)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.Refused) as e:
        run.peaks_for("TPU v99")
    assert "peaks.json" in e.value.msg
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run_script(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig4.serve",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_measuring_entry_refuses_a_cpu_backend():
    res = _run_script(ROOT)
    assert res.returncode == 2, res.stderr
    assert res.stdout == ""
    assert "tpu" in res.stderr


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), str(tmp_path / p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_script(str(tmp_path))
    assert res.returncode != 0
    assert res.stdout == ""


def test_benchmark_json_is_whole():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert list(bench) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "configs",
                                           w["config"] + ".json"))
        traffic.load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        reported = {m["name"] for m in bench["end_to_end"]
                    if run.applies(m, w["name"], e2e)}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(run.applies(m, w["name"], reported)
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_open_loop_work_is_the_same_for_every_seed():
    mix = traffic.load(os.path.join(BENCH, "traffic",
                                    "poisson_pareto.json"))
    a = traffic.open_loop(mix, 2.0, SEED, 8192)
    b = traffic.open_loop(mix, 2.0, SEED + 1, 8192)
    again = traffic.open_loop(mix, 2.0, SEED, 8192)
    assert np.array_equal(a.rows, again.rows)
    assert np.array_equal(a.arrival_s, again.arrival_s)
    assert len(a.rows) == len(b.rows) == round(mix["arrival"]["rate_rps"]
                                               * 2.0)
    assert sorted(a.rows) == sorted(b.rows)
    assert not np.array_equal(a.rows, b.rows)
    assert np.all(np.diff(a.arrival_s) >= 0) and a.arrival_s[-1] < 2.0
    assert np.all(a.offset + a.rows <= 8192)
    assert 1 <= a.rows.min() and a.rows.max() <= 128
