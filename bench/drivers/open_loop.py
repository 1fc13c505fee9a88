"""Open-loop serving cells: a fitted projection model behind
``KpcaEngine``, sent requests on a schedule whatever it does.

Set-up builds the served model from the seed (support = the configuration's
J*N pooled samples, coefficients drawn from the seed), wraps it in a
``ModelHandle`` and starts a ``KpcaEngine`` with the configuration's
``KpcaServeConfig`` fields; ``start()`` compiles every bucket. The window
sends the mix's requests (``bench.traffic``) from this thread and collects
the answers on a second one. Each request is timed from when it was due to
when its answer was in hand, so a late sender shows as latency.

``correct`` reads every answer, a sample drawn from the seed (with the
largest request in it) against the float64 reference scores, and counts the
requests that never came back.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List

import numpy as np

from bench import reference, traffic
from bench.data import kpca_dataset, sub_seed

GRACE_S = 60.0        # how long past the window an answer is waited for


def make_model_inputs(cfg: Dict, seed: int):
    """(support (L, M), coefficients (L, C), query pool (P, M)), float32."""
    srv = cfg["serve"]
    n = cfg["nodes"] * cfg["per_node"]
    m = cfg["features"]
    support = kpca_dataset(n, m, cfg["n_classes"], seed=sub_seed(seed, 10))
    coefs = np.random.default_rng(sub_seed(seed, 11)).standard_normal(
        (n, srv["n_components"])).astype(np.float32) / np.sqrt(n)
    pool = kpca_dataset(srv["query_pool_rows"], m, cfg["n_classes"],
                        seed=sub_seed(seed, 12))
    return support, coefs, pool


class Cell:
    kind = "serve"

    def __init__(self, cfg: Dict, mix: Dict, seed: int, tracing: bool,
                 annotate, home: str = traffic.HOME):
        import jax
        import jax.numpy as jnp
        from repro.core import KernelSpec, oos
        from repro.serve import KpcaEngine, KpcaServeConfig, ModelHandle

        self.cfg, self.mix, self.seed, self.home = cfg, mix, seed, home
        self.annotate = annotate
        srv = cfg["serve"]
        t0 = time.perf_counter()
        self.support, self.coefs, self.pool = make_model_inputs(cfg, seed)
        t1 = time.perf_counter()
        model = oos.from_dual(jnp.asarray(self.support),
                              jnp.asarray(self.coefs),
                              KernelSpec(kind=cfg["kernel"]), center=True)
        jax.block_until_ready(model)
        t2 = time.perf_counter()
        engine_cfg = dict(srv["engine"])
        if engine_cfg.get("query_dtype"):
            engine_cfg["query_dtype"] = jnp.dtype(engine_cfg["query_dtype"])
        self.engine = KpcaEngine(ModelHandle(model),
                                 KpcaServeConfig(**engine_cfg))
        self.engine.start()               # compiles every bucket
        t3 = time.perf_counter()
        warm = traffic.warm_rows(mix, 64, seed, len(self.pool), home)
        for r, o in zip(warm.rows, warm.offset):
            self.engine.submit(self.pool[o:o + r]).result(timeout=GRACE_S)
        self._base = self._counters()
        self.setup_phases_s = {"data": t1 - t0, "model": t2 - t1,
                               "engine_start": t3 - t2,
                               "warm_requests": time.perf_counter() - t3}

    def _counters(self) -> Dict:
        from repro.obs import metrics
        wait = metrics.histogram("serve_queue_wait_seconds")
        st = self.engine.stats
        return {"n_flushes": st.n_flushes, "n_compiles": st.n_compiles,
                "n_requests": st.n_requests,
                "queue_wait_sum_s": wait.sum, "queue_wait_count": wait.count}

    def window(self, seconds: float) -> float:
        from repro.serve import QueueFullError
        sched = traffic.open_loop(self.mix, seconds, self.seed,
                                  len(self.pool), self.home)
        self.sched = sched
        n = len(sched.rows)
        self.done = np.full(n, np.nan)
        self.due = np.empty(n)
        self.late = np.empty(n)
        self.keep = set(self._sample(n))
        self.answers: Dict[int, np.ndarray] = {}
        inbox: queue.Queue = queue.Queue()
        collector = threading.Thread(target=self._collect,
                                     args=(inbox, seconds), daemon=True)
        self.t0 = time.perf_counter()
        collector.start()
        with self.annotate("bench.serve.send"):
            for i in range(n):
                due = self.t0 + sched.arrival_s[i]
                now = time.perf_counter()
                if due > now:
                    time.sleep(due - now)
                self.due[i] = due
                self.late[i] = time.perf_counter() - due
                o, r = sched.offset[i], sched.rows[i]
                try:
                    inbox.put((i, self.engine.submit(self.pool[o:o + r])))
                except QueueFullError:  # refused: counts as unanswered
                    pass
        inbox.put(None)
        collector.join(timeout=seconds + 2 * GRACE_S)
        if collector.is_alive():
            raise RuntimeError("the answer collector did not finish")
        self.counters = {k: v - self._base[k]
                         for k, v in self._counters().items()}
        return float(np.nanmax(self.done) - self.t0) if \
            np.isfinite(self.done).any() else seconds

    def _collect(self, inbox: queue.Queue, seconds: float) -> None:
        deadline = time.perf_counter() + seconds + GRACE_S
        while True:
            item = inbox.get()
            if item is None:
                return
            i, fut = item
            try:
                out = fut.result(timeout=max(0.0, deadline
                                             - time.perf_counter()))
            except Exception:         # failed or late: unanswered
                continue
            self.done[i] = time.perf_counter()
            if i in self.keep:
                self.answers[i] = np.array(out)

    def _sample(self, n: int) -> List[int]:
        k = min(self.cfg["serve"]["check_requests"], n)
        rng = np.random.default_rng(sub_seed(self.seed, 13))
        pick = set(rng.choice(n, size=k, replace=False).tolist())
        pick.add(int(np.argmax(self.sched.rows)))
        return sorted(pick)

    @property
    def attempted(self) -> int:
        return len(self.done)

    @property
    def failed(self) -> int:
        return int(np.sum(~np.isfinite(self.done)))

    def latencies_s(self) -> np.ndarray:
        """Due-to-answer seconds; a request with no answer counts as
        answered at the end of the grace period."""
        end = self.t0 + float(np.max(self.sched.arrival_s)) + GRACE_S
        done = np.where(np.isfinite(self.done), self.done, end)
        return done - self.due

    def end_to_end(self, wall_s: float) -> Dict[str, float]:
        return {"serve_p50_ms": float(np.percentile(self.latencies_s(), 50))
                * 1e3}

    def layer_inputs(self) -> Dict:
        return dict(self.counters)

    def notes(self) -> Dict:
        return {"requests": int(len(self.done)),
                "rows": int(np.sum(self.sched.rows)),
                "p95_ms": float(np.percentile(self.latencies_s(), 95)) * 1e3,
                "p99_ms": float(np.percentile(self.latencies_s(), 99)) * 1e3,
                "sender_late_p99_ms": float(np.percentile(self.late, 99))
                * 1e3,
                "compiles_in_window": int(self.counters["n_compiles"]),
                "setup_phases_s": self.setup_phases_s}

    def release(self) -> None:
        self.engine.close(drain=False)
        self.engine = None

    def check(self) -> List:
        """[(name, value, limit)].

        score_err: max |served - reference| over the sampled requests'
          rows, as a share of the largest |reference| score among them.
        unanswered: requests that failed or never came back.
        """
        lim = self.cfg["limits"]
        idx = [i for i in sorted(self.keep) if i in self.answers]
        err = float("inf")
        if idx:
            rows = [self.pool[self.sched.offset[i]:
                              self.sched.offset[i] + self.sched.rows[i]]
                    for i in idx]
            gamma = reference.gamma_median(self.support)
            ref = reference.scores(self.support, self.coefs,
                                   np.concatenate(rows), gamma)
            got = np.concatenate([self.answers[i] for i in idx])
            if got.shape == ref.shape:
                err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        return [("score_err", err, lim["score_err"]),
                ("unanswered", float(self.failed), lim["unanswered"])]
