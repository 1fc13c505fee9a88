"""Readings that set the limits of ``correct`` in a cell of the
``open_loop_blocks`` driver: the program's numbers and two controls one
precision below the configuration's, seed by seed.

    python3 bench/control_blocks.py --workload mnist60k.serve --seeds 11 12

Run on the chip, at the cell's own size; the benchmark's own runs never
run it. One JSON line per seed:

* ``program_*``: a window at the cell's rate, then ``score_err``, and
  ``mean_err`` = max |m - m_ref| / max m_ref of the artifact's kernel row
  means against the float64 reference's.
* ``artifact_*``: the artifact's kernel means taken with one bfloat16 pass
  of the distance dots (computed here, in JAX, on the chip, at the
  program's gamma), served in the program's place over a window.
* ``query_*``: the program's own lower-precision path, bfloat16 query
  slabs (``query_dtype``), over a window.

``--means-only`` reads ``mean_err`` of the program and of the bfloat16
means alone, with no engine and no window.
"""

import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Run as a script, this directory leads sys.path: its module names
# (data, fit, serve, ...) must not shadow others; import as bench.*.
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    del sys.path[0]

BLOCK_ROWS = 1024


def bf16_kernel_means(x, gamma, block_rows: int = BLOCK_ROWS):
    """(m (L,), mu ()) of the RBF Gram of ``x`` (L, M) float32, its
    distance dots at one bfloat16 pass accumulated in float32, in row
    blocks on the default device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tile_sums(xi, xs, gamma):
        sq_i = jnp.sum(xi * xi, 1)
        sq = jnp.sum(xs * xs, 1)
        dot = jnp.matmul(xi.astype(jnp.bfloat16),
                         xs.astype(jnp.bfloat16).T,
                         preferred_element_type=jnp.float32)
        d2 = jnp.maximum(sq_i[:, None] + sq[None, :] - 2.0 * dot, 0.0)
        return jnp.sum(jnp.exp(-gamma * d2), axis=1)

    xs = jnp.asarray(x, jnp.float32)
    n = xs.shape[0]
    pad = -n % block_rows
    xp = jnp.pad(xs, ((0, pad), (0, 0)))
    sums = jnp.concatenate([tile_sums(xp[i:i + block_rows], xs, gamma)
                            for i in range(0, n + pad, block_rows)])[:n]
    m = sums / n
    return m, jnp.mean(m)


def with_means(model, m, mu):
    """``model`` with its kernel means, and the centering terms built from
    them, replaced."""
    import jax.numpy as jnp
    from repro.core.kernels_math import HIGHEST
    s = jnp.sum(model.coefs, axis=0)
    bias = mu * s - jnp.matmul(m, model.coefs, precision=HIGHEST)
    return dataclasses.replace(model, bias=bias, k_row_mean=m,
                               k_grand_mean=mu)


def mean_err(m, m_ref) -> float:
    import numpy as np
    m = np.asarray(m, np.float64)
    return float(np.max(np.abs(m - m_ref)) / np.max(m_ref))


def restart(cell, **engine):
    """A new engine on the cell's model with ``engine`` fields changed."""
    from repro.serve import KpcaEngine, KpcaServeConfig
    handle = cell.engine.handle
    cell.engine.close(drain=False)
    cfg = dict(cell.cfg["serve"]["engine"], **engine)
    cell.engine = KpcaEngine(handle, KpcaServeConfig(**cfg))
    cell.engine.start()
    cell._base = cell._counters()


def served(cell, seconds: float, prefix: str) -> dict:
    cell.window(seconds)
    out = {f"{prefix}_{n}": v for n, v, _ in cell.check()}
    out[f"{prefix}_mean_err"] = mean_err(cell.k_row_mean,
                                         cell.reference_means()[0])
    return out


def means_only(found, seed: int) -> dict:
    import jax.numpy as jnp
    from bench import reference, reference_blocks, traffic
    from repro.core import KernelSpec, oos
    cfg = found["cfg"]
    inputs = traffic.plugin(traffic.HOME, "drivers", "open_loop")
    support, coefs, _ = inputs.make_model_inputs(cfg, seed)
    model = oos.from_dual(jnp.asarray(support), jnp.asarray(coefs),
                          KernelSpec(kind=cfg["kernel"]), center=True)
    m_ref, _ = reference_blocks.kernel_means(
        support, reference.gamma_median(support))
    low, _ = bf16_kernel_means(support, model.gamma)
    return {"seed": seed, "program_mean_err": mean_err(model.k_row_mean,
                                                       m_ref),
            "control": "kernel means at one bfloat16 pass",
            "artifact_mean_err": mean_err(low, m_ref)}


def readings(found, seed: int, seconds: float) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import traffic
    cfg, mix = found["cfg"], found["mix"]
    driver = traffic.plugin(traffic.HOME, "drivers", mix["kind"]).Cell
    cell = driver(cfg, mix, seed, False, jax.profiler.TraceAnnotation)
    out = {"seed": seed}
    out.update(served(cell, seconds, "program"))
    model = cell.engine.handle.current()
    program_means = cell.k_row_mean
    low = with_means(model, *bf16_kernel_means(cell.support, model.gamma))
    cell.engine.handle.publish(low)
    cell.k_row_mean = np.asarray(low.k_row_mean, np.float64)
    out.update(served(cell, seconds, "artifact"))
    cell.engine.handle.publish(model)
    cell.k_row_mean = program_means
    restart(cell, query_dtype=jnp.bfloat16)
    out.update(served(cell, seconds, "query"))
    cell.release()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--means-only", action="store_true",
                    help="mean_err of the program and the bfloat16 means, "
                         "no window")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    found = run.resolve(bench, args.workload)
    run.require_chips(found["cell"]["chips"])
    for seed in args.seeds:
        r = means_only(found, seed) if args.means_only else \
            readings(found, seed, args.seconds)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
