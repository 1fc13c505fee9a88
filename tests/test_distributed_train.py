"""Distributed-correctness subprocess tests (8 forced host devices):
- sharded (data x model) train step == single-device step
- shard_map expert-parallel MoE == dense reference
- projection-consensus compressed gradient psum ~= dense psum
- DKPCA activation probe runs over the data axis
"""

import os
import subprocess
import sys


HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "check_dp_train.py")
SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(mode, marker):
    # JAX_PLATFORMS=cpu: the child must never contend for an accelerator
    # the parent test process may hold.
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, HELPER, mode], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert marker in out.stdout


def test_dp_train_step_equivalence():
    _run("dp", "DP-EQUIV-OK")


def test_moe_sharded_matches_reference():
    _run("moe", "MOE-OK")


def test_compressed_gradient_psum():
    _run("compress", "COMPRESS-OK")
