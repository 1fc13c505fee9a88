"""Fit cells: repeated whole fits of a decentralized kPCA deployment.

One fit is what a user of the paper's algorithm runs: on one chip,
``build_setup`` then ``run_admm`` with the drivers' defaults; with one node
per chip (``"transport": "ring"``), one ``dkpca_distributed`` call. Each
ends when its consensus coefficients are ready. The window cycles over the
datasets made in set-up.

``correct`` compares every fit of the window with the float64 reference
(``bench.reference``): each node's similarity to central kPCA, and, on the
one-chip path, the top eigenvalues of each node's centred Gram block that
the set-up decomposed.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import reference
from bench.data import distribute, kpca_dataset, sub_seed


def make_datasets(cfg: Dict, n: int, seed: int) -> List[np.ndarray]:
    """``n`` datasets of (J, N, M) float32, each from its own stream."""
    j, per, m = cfg["nodes"], cfg["per_node"], cfg["features"]
    out = []
    for i in range(n):
        s = sub_seed(seed, 0, i)
        x = kpca_dataset(j * per, m, cfg["n_classes"], seed=s)
        out.append(distribute(x, j, seed=s + 1))
    return out


class Cell:
    kind = "fit"

    def __init__(self, cfg: Dict, mix: Dict, seed: int, tracing: bool,
                 annotate, home: str = None):
        import jax
        from repro.core import KernelSpec
        from repro.core.topology import ring

        self.cfg, self.tracing, self.annotate = cfg, tracing, annotate
        self.ring = cfg["transport"] == "ring"
        self.iters = cfg["n_iters"]
        self.spec = KernelSpec(kind=cfg["kernel"])
        self.host = make_datasets(cfg, mix["datasets"], seed)
        if self.ring:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self.mesh = Mesh(np.array(jax.devices()[:cfg["nodes"]]),
                             ("node",))
            put = NamedSharding(self.mesh, PartitionSpec("node"))
            self.message_dtype = cfg.get("message_dtype")
        else:
            self.graph = ring(cfg["nodes"], hops=cfg["hops"])
            put = jax.devices()[0]
        self.data = [jax.device_put(x, put) for x in self.host]
        jax.block_until_ready(self.data)
        self.fits: List = []
        self.setup_s: List[float] = []
        self.admm_s: List[float] = []
        self._fit(0)                      # compiles every program a fit runs
        self.fits.clear()
        self.setup_s.clear()
        self.admm_s.clear()

    def _fit(self, i: int) -> None:
        import jax
        x = self.data[i]
        if self.ring:
            from repro.core.dkpca import dkpca_distributed
            kw = {}
            if self.message_dtype:
                kw["message_dtype"] = jax.numpy.dtype(self.message_dtype)
            with self.annotate("bench.fit.spmd"):
                res = dkpca_distributed(x, self.mesh, ("node",),
                                        hops=self.cfg["hops"],
                                        spec=self.spec, n_iters=self.iters,
                                        **kw)
                alpha = jax.block_until_ready(res.alpha)
            self.fits.append((i, None, alpha))
            return
        from repro.core import build_setup, run_admm
        t0 = time.perf_counter()
        with self.annotate("bench.fit.setup"):
            setup = build_setup(x, self.graph, self.spec)
            if self.tracing:
                jax.block_until_ready([setup.kcross, setup.lam, setup.vec])
        t1 = time.perf_counter()
        with self.annotate("bench.fit.admm"):
            alpha = jax.block_until_ready(
                run_admm(setup, n_iters=self.iters).alpha)
        t2 = time.perf_counter()
        self.setup_s.append(t1 - t0)
        self.admm_s.append(t2 - t1)
        self.fits.append((i, setup.lam, alpha))

    def window(self, seconds: float) -> float:
        """Fits until ``seconds`` have passed; returns the window's wall
        time, which ends with the last fit."""
        t0 = time.perf_counter()
        while True:
            self._fit(len(self.fits) % len(self.data))
            t = time.perf_counter() - t0
            if t >= seconds:
                return t

    @property
    def attempted(self) -> int:
        return len(self.fits)

    def end_to_end(self, wall_s: float) -> Dict[str, float]:
        return {"fit_s": wall_s / len(self.fits)}

    def notes(self) -> Dict:
        return {"fits": len(self.fits),
                "sim_gap_worst_node": getattr(self, "worst_node", None)}

    def layer_inputs(self) -> Dict:
        return {"n_fits": len(self.fits), "n_iters": self.iters,
                "setup_s": list(self.setup_s), "admm_s": list(self.admm_s)}

    def release(self) -> None:
        """Bring the fits' answers to the host and drop device state."""
        self.fits = [(i, None if lam is None else np.asarray(lam),
                      np.asarray(alpha)) for i, lam, alpha in self.fits]
        self.data = []

    def check(self) -> List:
        """[(name, value, limit)]: the worst over every fit of the window.

        sim_gap: 1 - the nodes' mean similarity to central kPCA (paper
          section 6.1); the configuration states the limit.
        lam_gap: the largest gap between a node's top ``eig_k`` set-up
          eigenvalues and the reference's, over that node's largest.
        """
        lim = self.cfg["limits"]
        k = self.cfg.get("eig_k", 1)
        sim_gap, lam_gap, self.worst_node = 0.0, 0.0, 0.0
        for i in sorted({f[0] for f in self.fits}):
            ref = reference.fit(self.host[i], k)
            for _, lam, alpha in (f for f in self.fits if f[0] == i):
                sims = reference.node_similarity(ref["kc"], alpha,
                                                 ref["alpha"], ref["lam"])
                sim_gap = max(sim_gap, 1.0 - float(np.mean(sims)))
                self.worst_node = max(self.worst_node,
                                      1.0 - float(np.min(sims)))
                if lam is not None:
                    lam_gap = max(lam_gap, reference.eig_gap(lam,
                                                          ref["eigs"]))
        out = [("sim_gap", sim_gap, lim["sim_gap"])]
        if not self.ring:
            out.append(("lam_gap", lam_gap, lim["lam_gap"]))
        return out
