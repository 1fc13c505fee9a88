"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``, the driver
that the mix's ``kind`` names, ``bench/drivers/<kind>.py`` (what the mix
names in turn is found by ``bench.traffic``), and for ``--trace 1`` one
reader per per-layer metric, ``bench/metrics/<metric>.py``.

Set-up (imports, JAX start-up, data from the seed, compilation or the
compile cache, warm-up) is ``setup_s``; then the window runs for
``--seconds``. With ``--trace 1`` the window runs under the JAX profiler and
the line carries the per-layer metrics instead of the end-to-end ones. The
plain reference then decides ``correct``; each number compared is printed
with its limit, as the last lines on standard error and under ``checks``,
the last key of the result line, which is the last line on standard output.
Counts that are not metrics (requests sent, how late the sender ran,
compilations in the window) go to standard error as ``note`` lines.

It refuses to run (exit code 2, no result) unless JAX's backend is ``tpu``
with exactly the cell's number of chips. The compile cache is
``<checkout>/.jax_cache``, whatever the environment says.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
PLATFORM = "tpu"
# Run as a script, this directory leads sys.path: its module names
# (data, fit, serve, ...) must not shadow others; import as bench.*.
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    del sys.path[0]


class Refused(SystemExit):
    """The run cannot be made here; exit code 2, no result."""

    def __init__(self, msg: str):
        super().__init__(2)
        self.msg = msg


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, home: str = BENCH) -> dict:
    """The cell, its configuration and its traffic mix, by name, from the
    ``configs/`` and ``traffic/`` directories under ``home``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = load_json(os.path.join(home, "configs", cell["config"] + ".json"))
    from bench import traffic
    mix = traffic.load(os.path.join(home, "traffic",
                                    cell["traffic"] + ".json"), home)
    return {"cell": cell, "cfg": cfg, "mix": mix}


def applies(entry: dict, workload: str, reported: set) -> bool:
    """Whether a metric belongs in this cell's line: listed for it, or
    listing no cells and moving (or being) a metric the cell reports."""
    if "workloads" in entry:
        return workload in entry["workloads"]
    return entry.get("moves", entry["name"]) in reported


def read_metric(name: str, ctx, home: str = BENCH):
    """Run ``<home>/metrics/<name>.py``'s ``read(ctx)``: a number, or None
    where the run holds nothing for it to read."""
    from bench import traffic
    return traffic.plugin(home, "metrics", name).read(ctx)


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def require_chips(n: int) -> None:
    import jax
    backend = jax.default_backend()
    if backend != PLATFORM:
        raise Refused(f"JAX found no {PLATFORM} (backend {backend!r})")
    if jax.device_count() != n:
        raise Refused(f"the cell needs {n} chip(s), JAX found "
                      f"{jax.device_count()}")
    peaks_for(jax.devices()[0].device_kind)


def device_info(n: int) -> dict:
    import jax
    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t0: float = None, home: str = BENCH) -> dict:
    """Set up, run and check one cell; returns the result line's object.
    Configurations, mixes and metric readers come from under ``home``."""
    import jax
    from bench import traffic

    t0 = time.perf_counter() if t0 is None else t0
    found = resolve(bench, workload, home)
    cell, cfg, mix = found["cell"], found["cfg"], found["mix"]
    driver = traffic.plugin(home, "drivers", mix["kind"]).Cell
    annotate = jax.profiler.TraceAnnotation
    run = driver(cfg, mix, seed, trace, annotate, home=home)
    setup_s = time.perf_counter() - t0

    # Set-up's objects leave the collector's view: a full collection in
    # the window then walks only what the window made.
    gc.collect()
    gc.freeze()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with annotate("bench.window"):
            wall_s = run.window(seconds)
    finally:
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    device = device_info(cell["chips"])
    run.release()

    e2e = {"setup_s": setup_s, **run.end_to_end(wall_s)}
    reported = {m["name"] for m in bench["end_to_end"]
                if applies(m, workload, set(e2e)) and m["name"] in e2e}
    metrics = {}
    breakdown = None
    if not trace:
        for m in bench["end_to_end"]:
            if m["name"] in reported:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import tracereduce
        try:
            tr = tracereduce.load(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        summary = tracereduce.summarize(tr)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        ctx = types.SimpleNamespace(workload=workload, kind=run.kind,
                                    layer=run.layer_inputs(), trace=tr,
                                    summary=summary)
        for m in bench["per_layer"]:
            if applies(m, workload, reported):
                v = read_metric(m["name"], ctx, home)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = run.check()
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    out = {"correct": correct, "attempted": run.attempted,
           "failed": getattr(run, "failed", 0), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": _finite(v), "limit": lim}
                     for name, v, lim in checks}
    for name, v in run.notes().items():
        print(f"note {name} = {v!r}", file=sys.stderr)
    for name, v, lim in checks:
        print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the program is not in this checkout ({src})")
    sys.path[:0] = [ROOT, src]
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = resolve(bench, args.workload)["cell"]

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    require_chips(cell["chips"])
    print(f"note jax_start_s = {time.perf_counter() - T0!r}",
          file=sys.stderr)

    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t0=T0)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Refused as e:
        print(f"bench/run.py: {e.msg}", file=sys.stderr)
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
