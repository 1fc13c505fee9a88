"""SPMD correctness: shard_map + collective_permute DKPCA vs. the reference
simulator, on 8 forced host devices (subprocess — the main pytest process
keeps the default 1-device CPU config)."""

import os
import subprocess
import sys

import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "check_dkpca_distributed.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(mode):
    # JAX_PLATFORMS=cpu: the child must never contend for an accelerator
    # the parent test process may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, HELPER, mode], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout


@pytest.mark.parametrize("mode", ["exact", "pallas", "rescale"])
def test_distributed_matches_simulator(mode):
    _run(mode)
