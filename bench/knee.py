"""Find a serving cell's knee: the highest offered rate that the engine
keeps up with. Run once, on the chip, to fix the cell's rate.

    python3 bench/knee.py --workload fig4.serve --seed 7 --seconds 5 \\
        --rates 500 1000 2000 4000

One process builds the cell once and sends its mix at each rate in turn.
Per rate it prints one JSON line: requests sent, answered, the window until
the last answer, the answer rate, p50/p99 from each request's due time, and
the p99 of the first and last fifth of the requests (a backlog that grows
through the window shows as a later fifth far above the first).
"""

import argparse
import gc
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Run as a script, this directory leads sys.path: its module names
# (data, fit, serve, ...) must not shadow others; import as bench.*.
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    del sys.path[0]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    found = run.resolve(bench, args.workload)
    run.require_chips(found["cell"]["chips"])
    import jax
    from bench import traffic
    driver = traffic.plugin(traffic.HOME, "drivers", found["mix"]["kind"])
    cell = driver.Cell(found["cfg"], found["mix"], args.seed, False,
                       jax.profiler.TraceAnnotation)
    gc.collect()
    gc.freeze()                       # as bench/run.py does before a window
    for rate in args.rates:
        mix = dict(found["mix"])
        mix["arrival"] = dict(mix["arrival"], rate_rps=rate)
        cell.mix = mix
        wall = cell.window(args.seconds)
        lat = cell.latencies_s() * 1e3
        fifth = max(1, len(lat) // 5)
        print(json.dumps({
            "rate_rps": rate, "sent": int(len(lat)),
            "answered": int(len(lat) - cell.failed), "window_s": wall,
            "answer_rps": (len(lat) - cell.failed) / wall,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p99_first_fifth_ms": float(np.percentile(lat[:fifth], 99)),
            "p99_last_fifth_ms": float(np.percentile(lat[-fifth:], 99)),
            "sender_late_p99_ms": float(np.percentile(cell.late, 99) * 1e3),
            "sender_late_max_ms": float(np.max(cell.late) * 1e3),
            "sent_over_20ms_late": int(np.sum(cell.late > 0.02)),
        }), flush=True)
    cell.release()


if __name__ == "__main__":
    main()
