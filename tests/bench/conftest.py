"""Tiny cells for the benchmark's CPU tests.

``home`` is a directory laid out as ``bench/`` is (``configs/``,
``traffic/``, ``drivers/``, ``arrivals/``, ``rows/``, ``metrics/``) holding
configurations small enough for the CPU; ``bench_json`` is a
``BENCHMARK.json`` whose cells use them. The drivers, generators and metric
readers are the benchmark's own. ``standin`` lets a traced run on the CPU
be reduced.
"""

import copy
import json
import os
import shutil

import pytest

from benchtiny import (BENCH, CELLS, FIT_METRICS, RENAME, ROOT, SERVE,
                       TINY, standin_trace, write_json)

CODE = ("drivers", "arrivals", "rows", "metrics")


@pytest.fixture
def home(tmp_path):
    base = json.load(open(os.path.join(BENCH, "configs",
                                       "fig4-j20-n300.json")))
    for name, over in TINY.items():
        cfg = dict(copy.deepcopy(base), name=name, **over)
        cfg["serve"] = dict(SERVE)
        write_json(str(tmp_path / "configs" / f"{name}.json"), cfg)
    write_json(str(tmp_path / "traffic" / "fits.json"),
               {"kind": "fit", "datasets": 2})
    write_json(str(tmp_path / "traffic" / "poisson.json"),
               {"kind": "open_loop",
                "arrival": {"process": "poisson", "rate_rps": 100.0},
                "rows": {"dist": "pareto", "x_m": 1, "shape": 1.2,
                         "max": 32},
                "work_seed": 7})
    for sub in CODE:
        shutil.copytree(os.path.join(BENCH, sub), str(tmp_path / sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    return str(tmp_path)


@pytest.fixture
def standin(monkeypatch):
    from bench import tracereduce
    monkeypatch.setattr(tracereduce, "load", standin_trace)


@pytest.fixture
def bench_json():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"] = copy.deepcopy(CELLS)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in RENAME[w]})
    for key, entries in FIT_METRICS.items():
        bench[key] += copy.deepcopy(entries)
    return bench
