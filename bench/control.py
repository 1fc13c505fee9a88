"""Readings that set the limits of ``correct``: the program's numbers on
many seeds, the control's, and the answer of a fit whose ADMM steps return
their state unchanged.

    python3 bench/control.py --workload fig4.serve --seeds 11 12 13

Run on the chip, at the cell's own size, for a cell of ``BENCHMARK.json``;
the benchmark's own runs never run it. One JSON line per seed. Fit cells
first read the program: one whole fit through the benchmark's own path
(``program_*``).

* One-chip fit cells: the reference put in the program's place with its
  distance dot products at one bfloat16 pass, computed on the chip:
  ``lam_gap`` of its node eigenvalues against the float64 reference. And
  ``sim_gap`` of each node's starting point (the local kPCA solution that
  ``run_admm`` starts from), which is what a fit whose steps change
  nothing returns, with the number of nodes whose start points against
  the others.
* The ring fit: the program's own lower-precision path, bfloat16 messages
  (``message_dtype``), over a short window; and ``sim_gap`` of the
  starting point as above.
* Serving: the program's own lower-precision path, bfloat16 query slabs
  (``query_dtype``), over a short window at the cell's rate.
"""

import argparse
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Run as a script, this directory leads sys.path: its module names
# (data, fit, serve, ...) must not shadow others; import as bench.*.
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    del sys.path[0]


def start_point(cfg, x_nodes, ref) -> dict:
    """The fit's starting point, each node's local solution: its sim_gap
    (what a fit whose steps change nothing returns) and the nodes whose
    starting direction points against the central one."""
    import numpy as np
    from repro.core import KernelSpec, build_setup
    from repro.core.admm import initial_alpha
    from repro.core.topology import ring
    from bench import reference
    setup = build_setup(x_nodes, ring(cfg["nodes"], hops=cfg["hops"]),
                        KernelSpec(kind=cfg["kernel"]))
    alpha = np.asarray(initial_alpha(setup, "local"))
    sims = reference.node_similarity(ref["kc"], alpha, ref["alpha"],
                                     ref["lam"])
    n = cfg["per_node"]
    signs = [float(np.sign(a @ ref["alpha"][j * n:(j + 1) * n]))
             for j, a in enumerate(alpha)]
    flipped = min(signs.count(1.0), signs.count(-1.0))
    return {"start_point_sim_gap": 1.0 - float(np.mean(sims)),
            "start_nodes_against_majority": flipped}


def readings(found, seed: int, seconds: float,
             program_only: bool = False) -> dict:
    import jax
    from bench import reference, traffic
    cfg, mix = found["cfg"], found["mix"]
    driver = traffic.plugin(traffic.HOME, "drivers", mix["kind"]).Cell
    out = {"seed": seed}
    if mix["kind"] == "open_loop":
        low = copy.deepcopy(cfg)
        low["serve"]["engine"]["query_dtype"] = "bfloat16"
        cell = driver(low, mix, seed, False, jax.profiler.TraceAnnotation)
        cell.window(seconds)
        cell.release()
        out["control"] = "query_dtype=bfloat16"
        out.update({n: v for n, v, _ in cell.check()})
        return out
    # the program: one whole fit through the benchmark's own path
    cell = driver(dict(cfg), dict(mix, datasets=1), seed, False,
                  jax.profiler.TraceAnnotation)
    cell.window(0.0)
    cell.release()
    out.update({f"program_{n}": v for n, v, _ in cell.check()})
    out["program_sim_gap_worst_node"] = cell.worst_node
    if program_only:
        return out
    x = cell.host[0]
    k = cfg.get("eig_k", 1)
    ref = reference.fit(x, k)
    out.update(start_point(cfg, x, ref))
    if cfg["transport"] == "ring":
        low = dict(cfg, message_dtype="bfloat16")
        cell = driver(low, dict(mix, datasets=1), seed, False,
                      jax.profiler.TraceAnnotation)
        cell.window(0.0)
        cell.release()
        out["control"] = "message_dtype=bfloat16"
        out.update({n: v for n, v, _ in cell.check()})
        return out
    low = reference.fit(x, k, precision=reference.CONTROL)
    out["control"] = f"reference at {reference.CONTROL} distance dots"
    out["lam_gap"] = reference.eig_gap(low["eigs"], ref["eigs"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program-only", action="store_true",
                    help="fit cells: read the program alone")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    found = run.resolve(bench, args.workload)
    run.require_chips(found["cell"]["chips"])
    for seed in args.seeds:
        print(json.dumps(readings(found, seed, args.seconds,
                                  args.program_only)), flush=True)


if __name__ == "__main__":
    main()
