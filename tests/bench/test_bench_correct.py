"""``correct`` at tiny sizes on the CPU: sound runs pass, the controls fail,
and so does a run with the timed path broken underneath, once per fault
each cell can have."""

import contextlib
import json
import os

import pytest

from benchtiny import SEED

from bench import control, run, traffic  # noqa: E402


@contextlib.contextmanager
def broken(obj, attr, make):
    """Replace ``obj.attr`` by ``make(original)`` for the block. Compiled
    traces are dropped on both sides, so that no program traced with the
    fault outlives it."""
    import jax
    orig = getattr(obj, attr)
    jax.clear_caches()
    setattr(obj, attr, make(orig))
    try:
        yield
    finally:
        setattr(obj, attr, orig)
        jax.clear_caches()


def _run(bench_json, home, cell, seconds=0.5):
    return run.run_cell(bench_json, cell, SEED, seconds, False, home=home)


def _found(home, config, mix):
    return {"cfg": json.load(open(os.path.join(home, "configs",
                                               config + ".json"))),
            "mix": traffic.load(os.path.join(home, "traffic",
                                             mix + ".json"))}


@pytest.mark.parametrize("cell", ["t.fit", "t.serve", "t.ring"])
def test_sound_runs_are_correct(bench_json, home, cell):
    out = _run(bench_json, home, cell)
    assert out["correct"], out["checks"]


# ---- controls: the reference, or the program's own path, one precision
# ---- below what the configuration states

def test_fit_control_fails_lam_gap(home):
    found = _found(home, "tiny-dense", "fits")
    r = control.readings(found, SEED, 0.3)
    assert r["lam_gap"] > found["cfg"]["limits"]["lam_gap"]
    assert r["start_point_sim_gap"] > found["cfg"]["limits"]["sim_gap"]


def test_serve_control_fails_score_err(home):
    found = _found(home, "tiny-dense", "poisson")
    r = control.readings(found, SEED, 0.5)
    assert r["score_err"] > found["cfg"]["limits"]["score_err"]


def test_ring_control_fails_sim_gap(home):
    found = _found(home, "tiny-ring", "fits")
    r = control.readings(found, SEED, 0.3)
    assert r["sim_gap"] > found["cfg"]["limits"]["sim_gap"]


# ---- faults planted under the timed path

def _unchanged_step(orig):
    import dataclasses

    def step(ops, comm, state, rho_slots, project="ball", slot_mask=None):
        new, res = orig(ops, comm, state, rho_slots, project, slot_mask)
        return dataclasses.replace(state, t=new.t), res
    return step


def _alter_alpha(orig):
    def fit(*args, **kwargs):
        res = orig(*args, **kwargs)
        res.alpha = res.alpha.at[0].set(res.alpha[0, ::-1])
        return res
    return fit


def _no_exchange(orig):
    import jax.numpy as jnp

    def exchange(self, cols):
        return jnp.stack([cols[0]] + [cols[r] for r in self.rev_slots])
    return exchange


def _alter_answer(orig):
    def run_slab(self, model, version, slab):
        out, dt = orig(self, model, version, slab)
        return out.at[0].add(1.0), dt
    return run_slab


def _half_batch(orig):
    import jax.numpy as jnp

    def run_slab(self, model, version, slab):
        half = slab.shape[0] // 2
        out, dt = orig(self, model, version, slab[:half])
        rest = jnp.broadcast_to(out.mean(0), (slab.shape[0] - half,)
                                + out.shape[1:])
        return jnp.concatenate([out, rest]), dt
    return run_slab


def test_fit_step_returning_its_state_unchanged(bench_json, home):
    import repro.core.admm as admm
    with broken(admm, "admm_step", _unchanged_step):
        out = _run(bench_json, home, "t.fit")
    assert not out["correct"]
    assert out["checks"]["sim_gap"]["value"] > \
        out["checks"]["sim_gap"]["limit"]


def test_fit_answer_altered_where_produced(bench_json, home):
    import repro.core as core
    with broken(core, "run_admm", _alter_alpha):
        out = _run(bench_json, home, "t.fit")
    assert not out["correct"]


def test_ring_exchange_between_chips_left_out(bench_json, home):
    from repro.core import solver
    with broken(solver.RingComm, "exchange", _no_exchange):
        out = _run(bench_json, home, "t.ring")
    assert not out["correct"]


def test_ring_step_returning_its_state_unchanged(bench_json, home):
    import repro.core.dkpca as dkpca
    with broken(dkpca, "admm_step", _unchanged_step):
        out = _run(bench_json, home, "t.ring")
    assert not out["correct"]


def test_serve_answer_altered_where_produced(bench_json, home):
    from repro.serve import kpca_engine
    with broken(kpca_engine.KpcaEngine, "_run_slab", _alter_answer):
        out = _run(bench_json, home, "t.serve", 1.0)
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > \
        out["checks"]["score_err"]["limit"]


def test_serve_half_of_each_batch_left_out(bench_json, home):
    from repro.serve import kpca_engine
    with broken(kpca_engine.KpcaEngine, "_run_slab", _half_batch):
        out = _run(bench_json, home, "t.serve", 1.0)
    assert not out["correct"]
