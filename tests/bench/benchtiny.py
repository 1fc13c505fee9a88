"""Sizes and names shared by the benchmark's CPU tests (fixtures in
``conftest.py``)."""

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "bench")
SEED = 2**31 + 12345                 # beyond 32 signed bits, as the driver's

# Sizes at which the sound fits converge (CPU float32, seed SEED): 30
# iterations bring the nodes' mean similarity to central kPCA within the
# limits below, while their starting points (local solutions) lie outside
# them, and so do bfloat16 ring messages (3.5e-3 against 1.6e-4 sound).
TINY = {
    "tiny-dense": {"transport": "dense", "nodes": 6, "per_node": 64,
                   "features": 64, "hops": 1, "eig_k": 3,
                   "limits": {"sim_gap": 5e-3, "lam_gap": 1e-5,
                              "score_err": 1e-4, "unanswered": 0}},
    "tiny-ring": {"transport": "ring", "nodes": 4, "per_node": 128,
                  "features": 64, "hops": 1,
                  "limits": {"sim_gap": 1e-3}},
}
SERVE = {"n_components": 2, "query_pool_rows": 512, "check_requests": 64,
         "engine": {"max_batch": 32}}
CELLS = [
    {"name": "t.fit", "config": "tiny-dense", "traffic": "fits", "chips": 1},
    {"name": "t.serve", "config": "tiny-dense", "traffic": "poisson",
     "chips": 1},
    {"name": "t.ring", "config": "tiny-ring", "traffic": "fits", "chips": 4},
]
# The serving cell of BENCHMARK.json stands for the tiny one; the fit
# cells, whose entries BENCHMARK.json does not hold yet, get these.
RENAME = {"fig4.serve": ["t.serve"]}
FIT_METRICS = {
    "end_to_end": [
        {"name": "fit_s", "unit": "s", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["t.fit", "t.ring"]}],
    "per_layer": [
        {"name": n, "unit": u, "better": "lower", "source": src,
         "layer": layer, "moves": "fit_s", "workloads": cells}
        for n, u, src, layer, cells in [
            ("fit.setup_ms", "ms", "host_clock", "fit setup", ["t.fit"]),
            ("fit.admm_iter_ms", "ms", "host_clock", "fit driver",
             ["t.fit"]),
            ("fit.collective_ms", "ms", "device_trace", "SPMD transport",
             ["t.ring"]),
            ("device_idle.fit", "%", "device_trace", "device",
             ["t.fit", "t.ring"])]],
}


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def standin_trace(log_dir):
    """``tracereduce.load`` for a run on the CPU, which has no device
    plane: the host's XLA op events (those carrying an ``hlo_op`` stat)
    stand in for one device's ops."""
    from jax.profiler import ProfileData

    from bench import tracereduce
    prof = ProfileData.from_file(tracereduce.find_xplane(log_dir))
    planes = list(prof.planes)
    ops = [ev for p in planes if p.name == "/host:CPU" for line in p.lines
           if line.name.startswith("tf_XLA") for ev in line.events
           if "hlo_op" in dict(ev.stats)]
    dev = types.SimpleNamespace(name="/device:STANDIN:0", lines=[
        types.SimpleNamespace(name=tracereduce.OPS_LINE, events=ops)])
    return tracereduce.parse(types.SimpleNamespace(planes=planes + [dev]))
