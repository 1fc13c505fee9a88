"""The serving engine's drains, phase by phase, from its spans in a JAX
profiler trace.

While a profiler capture runs, each ``repro.obs.trace`` span of the program
is an annotation on its thread's line of a host plane, with its attributes
as stats. Every ``serve.*`` span of one drain carries the same ``drain``
stat, on the flusher's line (``serve.wait``, ``serve.hold``, ``serve.pack``,
``serve.dispatch``) and on the device runner's (``serve.device`` once per
slab, ``serve.gather``, ``serve.assemble``, ``serve.resolve``,
``serve.account``). A drain is kept when its chain is whole (a pack, at
least one device span, a resolve) and every span of it lies inside the
``bench.window`` annotation.

    python3 bench/drains.py --workload fig4.serve --seed <n> --seconds <s>

runs a serving cell's window under a capture, as ``bench/run.py --trace 1``
does, and prints one JSON line: the phases of the window's drains against
the mean due-to-answer latency, and the share of the device's idle time
under each program span. It needs the chip, as ``bench/run.py`` does.
"""

import bisect
import gc
import json
import os
import shutil
import sys
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if __name__ == "__main__":     # as a script: import bench.* from the root
    if sys.path and os.path.abspath(sys.path[0]) == BENCH:
        del sys.path[0]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run, tracereduce  # noqa: E402

# (start_ns, end_ns, name, line, args): line names the plane and the line's
# place in it, since the profiler names every Python thread's line alike.
Span = Tuple[int, int, str, str, Dict]

CHAIN = ("serve.pack", "serve.device", "serve.resolve")
FINALIZE = ("serve.gather", "serve.assemble", "serve.resolve",
            "serve.account")


def program_spans(profile, prefix: str = "serve.") -> List[Span]:
    """Every event named ``prefix...`` on a line of a host plane (any plane
    that ``tracereduce`` does not take for a device), oldest first."""
    out = []
    for plane in profile.planes:
        m = tracereduce._DEVICE.match(plane.name)
        if m and m.group(1) != "CPU":
            continue
        for i, line in enumerate(plane.lines):
            key = f"{plane.name}#{i}"
            for e in line.events:
                if e.name.startswith(prefix):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name, key,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: sp[0])


def by_drain(spans: List[Span], window) -> Dict[int, List[Span]]:
    """The spans of each whole drain lying inside ``window``, by drain."""
    lo, hi = window
    groups: Dict[int, List[Span]] = defaultdict(list)
    for sp in spans:
        d = sp[4].get("drain")
        if d is not None:
            groups[int(d)].append(sp)
    return {d: g for d, g in sorted(groups.items())
            if all(lo <= s and e <= hi for s, e, *_ in g)
            and set(CHAIN) <= {sp[2] for sp in g}}


def _named(group: List[Span]) -> Dict[str, List[Span]]:
    out: Dict[str, List[Span]] = defaultdict(list)
    for sp in group:
        out[sp[2]].append(sp)
    return out


def phases(drains: Dict[int, List[Span]]) -> Optional[Dict[str, float]]:
    """The per-layer readings of the drains, or None where there are none:

    serve.hold_ms_per_drain: mean over drains of the summed ``serve.hold``.
    serve.launch_ms: request-weighted mean of (end of the drain's last
      ``serve.device``) - (start of its ``serve.pack``).
    serve.return_ms: request-weighted mean of (end of its ``serve.resolve``)
      - (end of its last ``serve.device``).
    serve.requests_per_drain: mean ``n_requests`` of ``serve.pack``.
    """
    if not drains:
        return None
    hold = launch = ret = n = 0
    for group in drains.values():
        by = _named(group)
        (pack,), (resolve,) = by["serve.pack"], by["serve.resolve"]
        k = int(pack[4]["n_requests"])
        dev_end = max(sp[1] for sp in by["serve.device"])
        hold += sum(e - s for s, e, *_ in by["serve.hold"])
        launch += k * (dev_end - pack[0])
        ret += k * (resolve[1] - dev_end)
        n += k
    m = len(drains)
    return {"serve.hold_ms_per_drain": hold / m * 1e-6,
            "serve.launch_ms": launch / n * 1e-6,
            "serve.return_ms": ret / n * 1e-6,
            "serve.requests_per_drain": n / m}


def launch_parts(drains: Dict[int, List[Span]]) -> Dict[str, float]:
    """Where a drain's launch and return go, in request-weighted mean ms:
    each span's own time, and the device runner's queue (dispatch end to
    first device span), with the part of that queue during which the
    runner was finishing another drain."""
    runner = sorted(sp for g in drains.values() for sp in g
                    if sp[2] in FINALIZE)
    ends = [sp[1] for sp in runner]     # one thread: sorted as the starts
    tot: Dict[str, float] = defaultdict(float)
    n = 0
    for d, group in drains.items():
        by = _named(group)
        k = int(by["serve.pack"][0][4]["n_requests"])
        n += k
        for name, sps in by.items():
            tot[name] += k * sum(e - s for s, e, *_ in sps)
        if by["serve.dispatch"]:
            q0 = by["serve.dispatch"][0][1]
            q1 = min(sp[0] for sp in by["serve.device"])
            tot["runner_queue"] += k * max(0, q1 - q0)
            for s, e, _, _, a in runner[bisect.bisect_right(ends, q0):]:
                if s >= q1:
                    break
                if a.get("drain") != d:
                    tot["runner_queue_behind_finalize"] += \
                        k * (min(e, q1) - max(s, q0))
    return {name: v / n * 1e-6 for name, v in sorted(tot.items())} if n \
        else {}


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Summed overlap of two sorted lists of disjoint intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        tot += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_under(tr, spans: List[Span]) -> Dict[str, float]:
    """Share of chip 0's idle time in the window under each span name
    (spans of different threads overlap, so the shares may sum past 1),
    and under none of them (``none``)."""
    idle = tracereduce.gaps(tr.devices[0], tr.window)
    total = sum(e - s for s, e in idle)
    if not total:
        return {}
    names: Dict[str, list] = defaultdict(list)
    for s, e, name, *_ in spans:
        names[name].append((s, e, name))
    out = {name: _overlap(idle, tracereduce.union(ivs)) / total
           for name, ivs in sorted(names.items())}
    cover = tracereduce.union([sp[:2] for sp in spans])
    out["none"] = 1 - _overlap(idle, cover) / total
    return out


def measure(bench: dict, workload: str, seed: int, seconds: float,
            home: str = BENCH) -> dict:
    """Set up a serving cell, run its window under a profiler capture as
    ``bench/run.py --trace 1`` does, and reduce the trace to drains."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from bench import traffic
    found = run.resolve(bench, workload, home)
    annotate = jax.profiler.TraceAnnotation
    cell = traffic.plugin(home, "drivers", found["mix"]["kind"]).Cell(
        found["cfg"], found["mix"], seed, True, annotate, home=home)
    gc.collect()
    gc.freeze()
    log_dir = tempfile.mkdtemp(prefix="bench-drains-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with annotate("bench.window"):
            cell.window(seconds)
    finally:
        gc.unfreeze()
        jax.profiler.stop_trace()
    cell.release()
    try:
        profile = ProfileData.from_file(tracereduce.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    tr = tracereduce.parse(profile)
    spans = program_spans(profile)
    drains = by_drain(spans, tr.window)
    layer = cell.layer_inputs()
    lat_ms = cell.latencies_s() * 1e3
    out = {"drains": len(drains), **(phases(drains) or {})}
    out["serve.queue_wait_ms"] = (layer["queue_wait_sum_s"]
                                  / max(1, layer["queue_wait_count"]) * 1e3)
    dev_s = tracereduce.module_seconds(tr, r"_proj")
    if dev_s is not None and layer["n_flushes"]:
        out["serve.device_ms_per_drain"] = dev_s / layer["n_flushes"] * 1e3
    out["engine_requests_per_drain"] = (layer["n_requests"]
                                        / max(1, layer["n_flushes"]))
    out["due_to_answer_mean_ms"] = float(np.mean(lat_ms))
    out["serve_p50_ms"] = float(np.percentile(lat_ms, 50))
    out["sender_late_mean_ms"] = float(np.mean(cell.late)) * 1e3
    if "serve.launch_ms" in out:
        out["remainder_ms"] = out["due_to_answer_mean_ms"] - (
            out["serve.queue_wait_ms"] + out["serve.launch_ms"]
            + out["serve.return_ms"])
    out["launch_parts_ms"] = launch_parts(drains)
    summary = tracereduce.summarize(tr)
    out["device_idle_pct"] = (1 - summary["busy_s"] / summary["window_s"]) \
        * 100
    out["idle_under"] = idle_under(tr, spans)
    out["idle_gaps"] = summary["idle_gaps"]
    out["checks"] = {name: [v, lim] for name, v, lim in cell.check()}
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.resolve(bench, args.workload)["cell"]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run.require_chips(cell["chips"])
    print(json.dumps(measure(bench, args.workload, args.seed,
                             args.seconds)))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except run.Refused as e:
        print(f"bench/drains.py: {e.msg}", file=sys.stderr)
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
