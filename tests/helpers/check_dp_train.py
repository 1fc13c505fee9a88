"""Subprocess helper (8 host devices): data-parallel sharded train step must
match the single-device step bit-for-bit-ish, the sharded MoE layer must
match the dense reference, and compressed gradient psum must approximate the
dense psum."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.data.tokens import TokenStream  # noqa: E402
from repro.distributed.sharding import default_rules  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.train.loop import build_train_step  # noqa: E402


def check_dp_equivalence():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                     head_dim=16, tie_embeddings=True, remat="none",
                     param_dtype="float32", compute_dtype="float32")
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = default_rules(multi_pod=False)
    data = TokenStream(vocab=cfg.vocab, batch=8, seq=16, seed=0)
    batch = data.next_batch()

    # single-device
    model1 = build_model(cfg)
    init1, step1 = build_train_step(model1, AdamWConfig(lr=1e-2))
    s1, _ = init1(jax.random.PRNGKey(0))
    s1n, m1 = step1(s1, batch)

    # sharded
    model2 = build_model(cfg, mesh=mesh)
    init2, step2 = build_train_step(model2, AdamWConfig(lr=1e-2), mesh=mesh,
                                    rules=rules)
    s2, _ = init2(jax.random.PRNGKey(0))
    sharded_batch = {
        k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
        for k, v in batch.items()}
    s2n, m2 = step2(s2, sharded_batch)

    d_loss = abs(float(m1["loss"]) - float(m2["loss"]))
    assert d_loss < 1e-4, d_loss
    for k in s1n["params"]:
        a = np.asarray(s1n["params"][k])
        b = np.asarray(s2n["params"][k])
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=k)
    print("DP-EQUIV-OK")


def check_moe_sharded_vs_ref():
    from repro.models.moe import (init_moe, moe_forward, moe_forward_ref)
    from repro.models.common import ParamCollector
    cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=1, d_ff=64, vocab=64,
                     head_dim=16, n_experts=8, top_k=2, d_ff_expert=32,
                     param_dtype="float32", compute_dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    col = ParamCollector(jax.random.PRNGKey(0), jnp.float32)
    init_moe(col, cfg, "moe")
    p = {k[len("moe/"):]: v for k, v in col.params.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
    y_ref, aux_ref = moe_forward_ref(p, cfg, x)
    y_sh, aux_sh = moe_forward(p, cfg, x, mesh)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-4)
    print("MOE-OK")


def check_compressed_psum():
    from repro.optim import compressed_psum_grads, init_compression_state
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    # per-shard gradients: shared low-rank signal + per-worker noise
    u = rng.normal(size=(8, 16, 3)).astype(np.float32)
    v = rng.normal(size=(12, 3)).astype(np.float32)
    g_shards = jnp.asarray(np.einsum("wmr,nr->wmn", u, v))
    params = {"w": jnp.zeros((16, 12))}
    state = init_compression_state(params, rank=3)

    def body(g_loc, p_prev, err):
        st = {"w": {"p": p_prev, "err": err}}
        out, new_state = compressed_psum_grads({"w": g_loc}, st, mesh)
        return out["w"], new_state["w"]["p"], new_state["w"]["err"]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None, None), P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None), P(None, None)),
        check_vma=False))
    p_prev = jnp.asarray(state["w"]["p"])
    err = jnp.zeros((16, 12))
    approx = None
    for _ in range(4):   # a few rounds align the consensus subspace
        approx, p_prev, err = f(g_shards, p_prev, err)
    dense = np.asarray(jnp.mean(g_shards, axis=0))
    rel = np.linalg.norm(np.asarray(approx) - dense) / np.linalg.norm(dense)
    assert rel < 0.05, rel
    print("COMPRESS-OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "dp"):
        check_dp_equivalence()
    if which in ("all", "moe"):
        check_moe_sharded_vs_ref()
    if which in ("all", "compress"):
        check_compressed_psum()
    print("OK")
