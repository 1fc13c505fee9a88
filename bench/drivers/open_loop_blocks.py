"""Open-loop serving cells over a support too large for the dense
reference: the ``open_loop`` driver's cell (model, engine, schedule,
latencies), checked against ``reference_blocks`` and telling the readers
about the artifact build and the projection program's calls.

``correct`` compares the sampled answers with the float64 scores, whose
support kernel means come in blocks, and counts the unanswered requests
as ``open_loop`` does. Where the configuration's ``limits`` hold a
``mean_err``, it also compares the served artifact's kernel row means
with the reference's: max |m - m_ref| / max m_ref. The scores alone can
miss an artifact built at one bfloat16 pass, whose error in the means
moves each score by a constant.
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from bench import reference, reference_blocks, traffic

HOME = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OpenLoop = traffic.plugin(HOME, "drivers", "open_loop").Cell


class Cell(OpenLoop):
    def __init__(self, cfg: Dict, mix: Dict, seed: int, tracing: bool,
                 annotate, home: str = traffic.HOME):
        import jax
        from repro.core import oos
        super().__init__(cfg, mix, seed, tracing, annotate, home=home)
        model = self.engine.handle.current()
        self.k_row_mean = np.asarray(model.k_row_mean, np.float64)
        self._means = None
        n, m = self.support.shape
        self.layer = {"artifact_ms": self.setup_phases_s["model"] * 1e3,
                      "support_rows": n, "features": m,
                      "device_kind": jax.devices()[0].device_kind}
        block_rows = getattr(oos, "kernel_mean_block_rows", None)
        if block_rows is not None:
            self.layer["kernel_mean_blocks"] = -(-n // block_rows(n))

    def _counters(self) -> Dict:
        out = super()._counters()
        st = self.engine.stats
        out.update(n_queries=st.n_queries, n_padded=st.n_padded)
        if hasattr(st, "n_slabs"):
            out["n_slabs"] = st.n_slabs
        return out

    def layer_inputs(self) -> Dict:
        return {**self.counters, **self.layer}

    def reference_means(self) -> tuple:
        """The support's float64 kernel means, computed once."""
        if self._means is None:
            self._means = reference_blocks.kernel_means(
                self.support, reference.gamma_median(self.support))
        return self._means

    def check(self) -> List:
        """[(name, value, limit)]: score_err and unanswered as
        ``open_loop`` defines them, and mean_err where the configuration
        limits it."""
        lim = self.cfg["limits"]
        idx = [i for i in sorted(self.keep) if i in self.answers]
        err = float("inf")
        if idx:
            rows = [self.pool[self.sched.offset[i]:
                              self.sched.offset[i] + self.sched.rows[i]]
                    for i in idx]
            ref = reference_blocks.scores(
                self.support, self.coefs, np.concatenate(rows),
                reference.gamma_median(self.support),
                means=self.reference_means())
            got = np.concatenate([self.answers[i] for i in idx])
            if got.shape == ref.shape:
                err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        out = [("score_err", err, lim["score_err"]),
               ("unanswered", float(self.failed), lim["unanswered"])]
        if "mean_err" in lim:
            m_ref = self.reference_means()[0]
            out.append(("mean_err", float(np.max(np.abs(
                self.k_row_mean - m_ref)) / np.max(m_ref)),
                lim["mean_err"]))
        return out
