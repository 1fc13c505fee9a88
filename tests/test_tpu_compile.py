"""Compile the main path's kernels and the four-chip SPMD fit step for a
described TPU v5e, without a chip.

Interpret mode checks a kernel's math but not Mosaic's tiling rules; these
compiles do (``interpret=False``), at the paper's width M=784 and the
serving shapes. Each test asserts the compiled program holds the kernel
(``tpu_custom_call``). The topology is described inside a module fixture —
never at import — and the tests skip only when no v5e topology can be
described here. Everything compiles in this process: the TPU library, once
loaded, belongs to it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.kernels_math import KernelSpec
from repro.kernels import (admm_local_update_op, center_op, gram_op,
                           project_op, project_partial_op)

M = 784
RBF = KernelSpec(kind="rbf")
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gram(n):
    return (lambda x, g: gram_op(RBF, x, gamma=g, interpret=False),
            [(n, M), ()])


def _center(n):
    return (lambda k: center_op(k, interpret=False), [(n, n)])


def _project(b, l):
    return (lambda q, xs, a, c, bias, g: project_op(
        RBF, q, xs, a, c, bias, gamma=g, interpret=False),
        [(b, M), (l, M), (l, 2), (2,), (2,), ()])


def _partial(b, l):
    return (lambda q, xs, a, g: project_partial_op(
        RBF, q, xs, a, gamma=g, interpret=False),
        [(b, M), (l, M), (l, 3), ()])


def _admm(j, n, s):
    return (lambda v, inv, k, b, g, r: admm_local_update_op(
        v, inv, k, b, g, r, interpret=False),
        [(j, n, n), (j, n, 1), (j, n, n), (j, n, s), (j, n, s), (j, 1, s)])


KERNEL_CASES = {
    "gram_300": _gram(300),
    "gram_8000": _gram(8000),
    "center_300": _center(300),
    "center_8000": _center(8000),
    "project_b128_l8000": _project(128, 8000),
    "project_b1_l4096": _project(1, 4096),
    "project_partial_b128_l2000": _partial(128, 2000),
    "admm_step_j20_n300": _admm(20, 300, 5),
    "admm_step_j80_n100": _admm(80, 100, 5),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = KERNEL_CASES[case]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dkpca_distributed_step_compiles_for_four_chips(topo):
    """The four-chip SPMD fit (one node per chip, J=4, N=512, M=784, one
    hop) as one program over a mesh of the described devices."""
    from repro.core.dkpca import dkpca_distributed

    j, n = 4, 512
    mesh = Mesh(np.asarray(topo.devices[:j]), ("node",))
    rows = NamedSharding(mesh, P("node"))

    def fit(x, alpha0):
        return dkpca_distributed(x, mesh, ("node",), hops=1, spec=RBF,
                                 n_iters=30, alpha0=alpha0, gamma=1e-3).alpha

    x = jax.ShapeDtypeStruct((j, n, M), jnp.float32, sharding=rows)
    a = jax.ShapeDtypeStruct((j, n), jnp.float32, sharding=rows)
    compiled = jax.jit(fit).lower(x, a).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    ma = compiled.memory_analysis()
    assert ma is not None
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes)
    assert 0 < per_device < V5E_HBM_BYTES
