"""Bring-up smoke run of the decentralized kPCA fit and serving path on TPU.

    python chip_smoke.py [--seed N]          # one chip: phases "fit", "serve"
    python chip_smoke.py --four-chips        # four chips: phase "four-chip"

Every phase drives the system through its public entry points, prints its
report line and then raises if any check failed; nothing falls back to the
CPU. The script exits non-zero unless JAX's default backend is ``tpu`` with
exactly the devices the mode needs. Each phase prints one JSON line of
bring-up facts (first-call and steady seconds and their difference as the
compile estimate, engine start-up with warmup, similarity, max error,
``device_kind``). The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Sizes follow Fig. 4 of arXiv:2211.15953 §6: a 20-node ring with two hops
each way, 300 samples of M=784 features per node. The four-chip phase runs
one node per chip (J=4, N=512, M=784, one hop: ``ring(4, 2)`` would connect
each pair twice). Data comes from ``repro.data`` with ``--seed``.

Both fits run the drivers' defaults (rho2 is the Theorem-2 constant).

References are plain float32: ``central_kpca`` on the pooled data (on the
host CPU) and ``oos.project``, both under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

FIT_SIZES = dict(nodes=20, per_node=300, features=784, hops=2, iters=30,
                 chunk=10)
FOUR_CHIP_SIZES = dict(nodes=4, per_node=512, features=784, hops=1,
                       iters=30)
REQUEST_ROWS = (1, 17, 128)
MAX_BATCH = 128

MIN_SIMILARITY = 0.99          # mean similarity to central kPCA
SERVE_REL_ERR = 1e-3           # max|scores - ref| <= this * max|ref|
CHUNKED_REL_ERR = 1e-3         # max|chunked - whole scan| <= this * max|a|


_T0 = time.perf_counter()


def _progress(step: str) -> None:
    """One line per step on stderr, so a run cut by a time limit shows
    where it was."""
    print(f"[chip_smoke {time.perf_counter() - _T0:8.1f}s] {step}",
          file=sys.stderr, flush=True)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


class _Checks:
    """A phase's checks. Failures are collected so the phase can report
    every number it measured before it raises (``_finish``)."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)


def _finish(report: dict, check: _Checks) -> dict:
    """Print the phase's report line, then raise if any check failed."""
    _emit(report)
    if check.failed:
        raise SmokeFailure(f"phase {report['phase']}: "
                           + "; ".join(check.failed))
    return report


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def _timed(fn, *args, **kwargs):
    """(result, seconds) with the result's arrays ready. Results that are
    plain dataclasses (``DkpcaSetup``, ``DkpcaResult``, ``ChunkResult``)
    are waited on field by field."""
    import dataclasses

    import jax
    _progress(getattr(fn, "__name__", "call"))
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    if dataclasses.is_dataclass(out):
        jax.block_until_ready([getattr(out, f.name)
                               for f in dataclasses.fields(out)])
    else:
        jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _first_and_steady(name: str, fn, *args, **kwargs):
    """Call ``fn`` twice. Returns (result, facts): the first call's seconds
    (compile + run), the second's (steady), and their difference as the
    compile estimate, under ``<name>_first_s`` etc."""
    _, first = _timed(fn, *args, **kwargs)
    out, steady = _timed(fn, *args, **kwargs)
    return out, {f"{name}_first_s": first, f"{name}_steady_s": steady,
                 f"{name}_compile_s": first - steady}


def _mean_similarity(alpha, x_nodes, alpha_gt, pooled, spec, gamma):
    """Mean over nodes of the paper's similarity to the central solution,
    in float32."""
    import jax
    import jax.numpy as jnp
    from repro.core import similarity

    with jax.default_matmul_precision("highest"):
        sims = jax.jit(jax.vmap(
            lambda a, x: similarity(a, x, alpha_gt, pooled, spec,
                                    gamma=gamma)))(
            jnp.asarray(alpha), jnp.asarray(x_nodes))
    return float(jnp.mean(sims))


def _reference_scores(model, xq):
    import jax
    import numpy as np
    from repro.core import oos

    with jax.default_matmul_precision("highest"):
        return np.asarray(oos.project(model, xq))


def _max_rel_err(out, ref) -> tuple:
    import numpy as np
    err = float(np.max(np.abs(np.asarray(out) - ref)))
    return err, err / max(float(np.max(np.abs(ref))), 1e-30)


def _queries(rows, features, seed):
    """Fresh draws from the synthetic generator (another seed)."""
    from repro.data import kpca_dataset
    x = kpca_dataset(sum(rows), features, seed=seed + 1)
    out, start = [], 0
    for r in rows:
        out.append(x[start:start + r])
        start += r
    return out


def phase_fit(seed: int = 0, *, nodes: int, per_node: int, features: int,
              hops: int, iters: int, chunk: int) -> dict:
    """Setup + ADMM (whole scan and chunked) + similarity to central kPCA.

    Returns the phase report; its ``"state"`` entry carries what the serve
    phase needs (node data, consensus alpha, kernel spec, gamma)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (KernelSpec, build_setup, central_kpca, run_admm,
                            run_chunked)
    from repro.core.topology import ring
    from repro.data import node_dataset

    check = _Checks()
    x_nodes, pooled = node_dataset(nodes, per_node, features, seed=seed)
    spec = KernelSpec(kind="rbf")
    xj = jnp.asarray(x_nodes)
    graph = ring(nodes, hops=hops)

    setup, t_setup = _first_and_steady("setup", build_setup, xj, graph,
                                       spec)
    res, t_admm = _first_and_steady("admm", run_admm, setup, n_iters=iters)
    alpha = np.asarray(res.alpha)
    check(bool(np.isfinite(alpha).all()), "run_admm alpha is not finite")

    def chunked():
        return list(run_chunked(setup, n_iters=iters, chunk=chunk))[-1]

    last, t_chunked = _first_and_steady("chunked", chunked)
    alpha_c = np.asarray(last.state.alpha)
    chunk_err = float(np.max(np.abs(alpha_c - alpha)))
    scale = float(np.max(np.abs(alpha)))
    check(int(last.state.t) == iters,
          f"run_chunked stopped at t={int(last.state.t)} of {iters}")
    check(chunk_err <= CHUNKED_REL_ERR * scale,
          f"chunked run differs from the whole scan: max|d|={chunk_err:.3e}"
          f" > {CHUNKED_REL_ERR} * {scale:.3e}")

    # The reference eigensolve runs on the host CPU: XLA's TPU eigh of the
    # pooled (J*N)^2 Gram takes many minutes to compile at J*N = 6000, and
    # LAPACK's answer is the plain float32 reference anyway.
    _progress("central_kpca reference on the host CPU")
    t0 = time.perf_counter()
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        alpha_gt, _, _ = central_kpca(
            jax.device_put(pooled, cpu), spec, 1,
            gamma=jax.device_put(setup.gamma, cpu))
        alpha_gt = np.asarray(alpha_gt)[:, 0]
    central_s = time.perf_counter() - t0
    _progress("similarity")
    sim = _mean_similarity(alpha, x_nodes, alpha_gt, jnp.asarray(pooled),
                           spec, setup.gamma)
    check(sim >= MIN_SIMILARITY,
          f"mean similarity {sim:.5f} < {MIN_SIMILARITY}")

    return _finish({
        "phase": "fit", "device_kind": _device_kind(),
        "nodes": nodes, "per_node": per_node, "features": features,
        "hops": hops, "iters": iters, "rho2": float(res.rho_hist[0]),
        **t_setup, **t_admm, **t_chunked,
        "chunked_max_abs_diff": chunk_err, "central_reference_s": central_s,
        "mean_similarity": sim,
        "state": {"x_nodes": x_nodes, "alpha": alpha, "spec": spec,
                  "gamma": setup.gamma},
    }, check)


def _holds_mosaic_kernel(engine) -> bool:
    """Whether the engine's compiled projection holds a Mosaic kernel."""
    return "tpu_custom_call" in engine.compiled_text()


def _serve_requests(engine, queries, refs, check: _Checks) -> dict:
    """Submit each request alone, wait for it, compare with its reference."""
    steady, worst = [], 0.0
    for xq, ref in zip(queries, refs):
        t0 = time.perf_counter()
        out = engine.submit(xq).result(timeout=120)
        steady.append(time.perf_counter() - t0)
        if out.shape != ref.shape:
            check(False, f"scores shape {out.shape} != reference "
                         f"{ref.shape}")
            continue
        err, rel = _max_rel_err(out, ref)
        check(rel <= SERVE_REL_ERR,
              f"{len(xq)}-row request: max|d|={err:.3e} is "
              f"{rel:.3e} of max|ref| > {SERVE_REL_ERR}")
        worst = max(worst, rel)
    return {"request_s": steady, "max_rel_err": worst}


def phase_serve(fit: dict, seed: int = 0, *,
                request_rows=REQUEST_ROWS, max_batch: int = MAX_BATCH
                ) -> dict:
    """Serve the fitted model through ``KpcaEngine`` on both kernel paths
    (XLA and the fused Pallas kernel) and check every answer. The Pallas
    engine's compiled program must contain the Mosaic kernel."""
    from repro.core import oos
    from repro.serve import KpcaEngine, KpcaServeConfig, ModelHandle

    st = fit["state"]
    model = oos.from_decentralized(st["x_nodes"], st["alpha"], st["spec"],
                                   gamma=st["gamma"], center=True)
    handle = ModelHandle(model)
    features = model.n_features
    queries = _queries(request_rows, features, seed)
    refs = [_reference_scores(model, xq) for xq in queries]

    check = _Checks()
    report = {"phase": "serve", "device_kind": _device_kind(),
              "support": int(model.n_support), "request_rows":
              list(request_rows)}
    for use_pallas in (False, True):
        cfg = KpcaServeConfig(max_batch=max_batch, use_pallas=use_pallas)
        name = "pallas" if use_pallas else "xla"
        _progress(f"serve {name}")
        engine = KpcaEngine(handle, cfg)
        t0 = time.perf_counter()
        with engine:
            start_s = time.perf_counter() - t0
            out = _serve_requests(engine, queries, refs, check)
            if use_pallas:
                check(_holds_mosaic_kernel(engine),
                      "the Pallas engine's program holds no tpu_custom_call")
        check(engine.stats.n_compiles == 0,
              f"{name}: {engine.stats.n_compiles} compiles after warmup")
        report[name] = dict(out, start_with_warmup_s=start_s)
    return _finish(report, check)


def phase_four_chip(seed: int = 0, *, nodes: int, per_node: int,
                    features: int, hops: int, iters: int,
                    request_rows=REQUEST_ROWS, max_batch: int = MAX_BATCH
                    ) -> dict:
    """SPMD fit on a ``nodes``-device mesh against the dense simulator,
    then sharded serving (model- and data-parallel) against
    single-device ``oos.project``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import KernelSpec, build_setup, oos, run_admm
    from repro.core.admm import initial_alpha
    from repro.core.dkpca import dkpca_distributed
    from repro.core.topology import ring
    from repro.data import node_dataset
    from repro.launch.mesh import make_mesh, make_serving_mesh
    from repro.serve import KpcaEngine, KpcaServeConfig, ModelHandle

    if len(jax.devices()) < nodes:
        raise SmokeFailure(f"{nodes} devices needed, found "
                           f"{len(jax.devices())}")
    check = _Checks()
    x_nodes, _ = node_dataset(nodes, per_node, features, seed=seed)
    spec = KernelSpec(kind="rbf")
    setup = build_setup(jnp.asarray(x_nodes), ring(nodes, hops=hops), spec)
    alpha0 = initial_alpha(setup, "local")
    sim, sim_s = _timed(run_admm, setup, n_iters=iters, alpha0=alpha0)

    mesh = make_mesh((nodes,), ("node",))

    def spmd():
        return dkpca_distributed(x_nodes, mesh, ("node",), hops=hops,
                                 spec=spec, n_iters=iters, alpha0=alpha0,
                                 gamma=setup.gamma).alpha

    a_d, t_spmd = _first_and_steady("spmd", spmd)
    a_s, a_d = np.asarray(sim.alpha), np.asarray(a_d)
    check(bool(np.isfinite(a_d).all()), "SPMD alpha is not finite")
    spmd_err = float(np.max(np.abs(a_s - a_d)))
    scale = max(float(np.max(np.abs(a_s))), 1e-6)
    # the tolerance of tests/helpers/check_dkpca_distributed.py
    check(spmd_err < 5e-3 * scale + 1e-4,
          f"SPMD fit differs from the simulator: max|d|={spmd_err:.3e}, "
          f"scale {scale:.3e}")

    model = oos.from_decentralized(x_nodes, a_d, spec, gamma=setup.gamma,
                                   center=True)
    sharded, _ = oos.shard_fitted(model, nodes)
    serving_mesh = make_serving_mesh(nodes)
    if serving_mesh is None:
        raise SmokeFailure(f"make_serving_mesh({nodes}) found too few "
                           f"devices")
    handle = ModelHandle(sharded)
    queries = _queries(request_rows, features, seed)
    refs = [_reference_scores(model, xq) for xq in queries]

    report = {"phase": "four-chip", "device_kind": _device_kind(),
              "devices": len(jax.devices()), "nodes": nodes,
              "per_node": per_node, "features": features, "hops": hops,
              "iters": iters, "rho2": float(sim.rho_hist[0]),
              "simulator_first_s": sim_s, **t_spmd,
              "spmd_max_abs_diff": spmd_err,
              "support": int(sharded.n_support)}
    for routing in ("mp", "dp"):
        for use_pallas in (False, True):
            cfg = KpcaServeConfig(max_batch=max_batch, routing=routing,
                                  use_pallas=use_pallas)
            _progress(f"serve {routing} pallas={use_pallas}")
            engine = KpcaEngine(handle, cfg, mesh=serving_mesh)
            t0 = time.perf_counter()
            with engine:
                start_s = time.perf_counter() - t0
                out = _serve_requests(engine, queries, refs, check)
                n_dev = len(engine.placed_devices(routing))
            stats = engine.stats
            routed = getattr(stats, f"n_routed_{routing}")
            name = f"{routing}_{'pallas' if use_pallas else 'xla'}"
            check(routed > 0 and stats.n_routed_single == 0,
                  f"{name}: routed {stats.routing_summary()}")
            check(n_dev == nodes,
                  f"{name}: model placed on {n_dev} devices, not {nodes}")
            report[name] = dict(out, start_with_warmup_s=start_s,
                                 placed_devices=n_dev)
    return _finish(report, check)


def _require_tpu(n_devices: int) -> None:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (default backend "
                         f"{backend!r}); nothing was run")
    found = len(jax.devices())
    if found != n_devices:
        raise SystemExit(f"chip_smoke: this mode needs {n_devices} TPU "
                         f"device(s), found {found}")


def _emit(report: dict) -> None:
    report = {k: v for k, v in report.items() if k != "state"}
    print(json.dumps(report), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (SPMD fit + "
                         "sharded serving)")
    args = ap.parse_args(argv)

    # The pre-jax environment layer (compile cache location) must run
    # before jax initializes its backend.
    from repro.launch import env
    env.apply_from_environ()
    import jax

    _require_tpu(4 if args.four_chips else 1)
    from repro.kernels import autotune
    # DEFAULT_TILES only: no tile table from outside the checkout
    autotune.set_default_table(autotune.TileTable())
    if args.four_chips:
        phase_four_chip(args.seed, **FOUR_CHIP_SIZES)
    else:
        phase_serve(phase_fit(args.seed, **FIT_SIZES), args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    main()
