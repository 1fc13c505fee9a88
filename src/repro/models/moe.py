"""Mixture-of-Experts layer (mixtral / deepseek-v2 style).

Production path (``moe_forward``): a ``shard_map`` region over the mesh —
experts are sharded along the "model" axis, activations stay replicated
across it (the dense-TP convention used throughout this repo). Each model
shard routes its data-shard's tokens, packs them into a capacity-bounded
(E, C, D) buffer (cumsum ranking + scatter — all per-shard, no cross-shard
traffic), computes ONLY its local experts' FFNs, scatters contributions back
to token order, and a single psum over "model" combines expert outputs —
the same collective a dense TP FFN needs, with active-expert FLOPs
(T * top_k * capacity_factor per token, not E *).

Reference path (``moe_forward_ref``): exact dense loop over experts (no
capacity drops) used by smoke tests to validate routing/combining math.

Capacity: C = max(1, ceil(T*k*cf/E)); when T*k <= 8*E (decode and test
shapes) we use C = T*k, which makes the layer exactly drop-free.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from .common import ParamCollector, activation
from .mlp import init_mlp, mlp_forward


def init_moe(col: ParamCollector, cfg: ArchConfig, prefix: str = "moe"):
    e, f, ne = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    col.param(f"{prefix}/router", (e, ne), ("embed_nofsdp", None),
              dtype=jnp.float32)
    col.param(f"{prefix}/w_gate", (ne, e, f), ("expert", "embed", "expert_mlp"))
    col.param(f"{prefix}/w_up", (ne, e, f), ("expert", "embed", "expert_mlp"))
    col.param(f"{prefix}/w_down", (ne, f, e), ("expert", "expert_mlp", "embed"))
    if cfg.n_shared_experts:
        init_mlp(col, cfg, f"{prefix}/shared",
                 d_ff=cfg.n_shared_experts * cfg.d_ff_expert)


def _route(p, cfg: ArchConfig, x_flat):
    """x_flat (T, E) -> (ids (T,k), weights (T,k) renormalized)."""
    logits = x_flat.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # load-balance auxiliary (Switch-style): E * mean(frac_tokens * mean_prob)
    dispatch = jnp.zeros_like(probs).at[
        jnp.arange(ids.shape[0])[:, None], ids].add(1.0)
    aux = cfg.n_experts * jnp.mean(jnp.mean(dispatch, 0) * jnp.mean(probs, 0))
    return ids, w.astype(x_flat.dtype), aux


def _capacity(t: int, cfg: ArchConfig) -> int:
    tk = t * cfg.top_k
    if tk <= 8 * cfg.n_experts:
        return tk  # exact (drop-free) — decode/smoke shapes
    return max(1, math.ceil(tk * cfg.capacity_factor / cfg.n_experts))


def _expert_ffn(w_gate, w_up, w_down, act, buf):
    """buf (E_loc, C, D) -> (E_loc, C, D)."""
    g = act(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", g * u, w_down)


def moe_forward(p, cfg: ArchConfig, x, mesh, model_axis: str = "model",
                batch_axes=None):
    """x (B, S, E) -> (y, aux_loss). shard_map over the full mesh."""
    if mesh is None:
        return moe_forward_ref(p, cfg, x)
    if batch_axes is None:
        from .common import batch_axes_of
        batch_axes = batch_axes_of(mesh)
    b, s, e = x.shape
    import numpy as _np
    dp = int(_np.prod([mesh.shape[a] for a in batch_axes]))
    if b % dp:
        # tiny-batch decode (e.g. long-context B=1): replicate tokens over
        # the data axes; every shard routes the same tokens, experts stay
        # model-sharded
        batch_axes = ()
    tp = mesh.shape[model_axis]
    ne = cfg.n_experts
    # virtual-expert splitting: when TP > n_experts (mixtral: 8e over a
    # 16-way model axis) each expert's FFN hidden dim is split across
    # repl = tp/ne shards; virtual expert v = real r * repl + replica. The
    # down-proj partial products are summed by the same psum that combines
    # experts — mathematically exact.
    repl = max(1, tp // ne)
    assert (ne * repl) % tp == 0, (ne, tp)
    assert cfg.d_ff_expert % repl == 0, (cfg.d_ff_expert, repl)

    router = p["router"]
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if repl > 1:
        f = cfg.d_ff_expert
        fr = f // repl
        wg = wg.reshape(ne, e, repl, fr).transpose(0, 2, 1, 3) \
            .reshape(ne * repl, e, fr)
        wu = wu.reshape(ne, e, repl, fr).transpose(0, 2, 1, 3) \
            .reshape(ne * repl, e, fr)
        wd = wd.reshape(ne, repl, fr, e).reshape(ne * repl, fr, e)
    ne_v = ne * repl
    all_axes = tuple(a for a in mesh.axis_names)

    def local(x_loc, router_w, wg_l, wu_l, wd_l):
        bl, sl, el = x_loc.shape
        t = bl * sl
        xf = x_loc.reshape(t, el)
        ids, w, aux = _route({"router": router_w}, cfg, xf)
        c = _capacity(t, cfg)
        ne_loc = ne_v // tp
        # rank of each (token, slot) within its REAL expert
        flat_ids = ids.reshape(-1)                          # (T*k,)
        oh = jax.nn.one_hot(flat_ids, ne, dtype=jnp.int32)  # (T*k, E)
        pos = (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(t * cfg.top_k),
                                           flat_ids]        # (T*k,)
        keep = pos < c
        # pack into this shard's owned virtual experts
        m_idx = jax.lax.axis_index(model_axis)
        real_of_local = (m_idx * ne_loc + jnp.arange(ne_loc)) // repl
        match = flat_ids[:, None] == real_of_local[None, :]  # (T*k, ne_loc)
        local_e = jnp.argmax(match, axis=1)
        mine = jnp.any(match, axis=1) & keep
        src = jnp.repeat(xf, cfg.top_k, axis=0)             # (T*k, D)
        buf = jnp.zeros((ne_loc, c, el), x_loc.dtype)
        buf = buf.at[jnp.where(mine, local_e, 0),
                     jnp.where(mine, pos, 0)].add(
            src * mine[:, None].astype(src.dtype))
        out = _expert_ffn(wg_l.astype(x_loc.dtype), wu_l.astype(x_loc.dtype),
                          wd_l.astype(x_loc.dtype), activation(cfg.act), buf)
        # gather back to (T*k, D), weight, combine over slots
        vals = out[jnp.where(mine, local_e, 0), jnp.where(mine, pos, 0)]
        vals = vals * mine[:, None].astype(vals.dtype)
        y = jnp.sum((vals * w.reshape(-1, 1)).reshape(t, cfg.top_k, el),
                    axis=1)
        y = jax.lax.psum(y, model_axis)    # combine experts + ffn splits
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(bl, sl, el), aux[None]

    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(batch_axes, None, None), P(None)),
        check_vma=False,
    )(x, router, wg, wu, wd)
    aux = aux[0]

    if cfg.n_shared_experts:
        shared = {k[len("shared/"):]: v for k, v in p.items()
                  if k.startswith("shared/")}
        y = y + mlp_forward(shared, cfg, x)
    return y, aux


def moe_forward_ref(p, cfg: ArchConfig, x):
    """Exact dense reference: loop over experts, no capacity drops."""
    b, s, e = x.shape
    xf = x.reshape(b * s, e)
    ids, w, aux = _route(p, cfg, xf)
    act = activation(cfg.act)
    y = jnp.zeros_like(xf)
    for ex in range(cfg.n_experts):
        g = act(xf @ p["w_gate"][ex].astype(xf.dtype))
        u = xf @ p["w_up"][ex].astype(xf.dtype)
        o = (g * u) @ p["w_down"][ex].astype(xf.dtype)
        gate = jnp.sum(jnp.where(ids == ex, w, 0.0), axis=-1)
        y = y + o * gate[:, None]
    y = y.reshape(b, s, e)
    if cfg.n_shared_experts:
        shared = {k[len("shared/"):]: v for k, v in p.items()
                  if k.startswith("shared/")}
        y = y + mlp_forward(shared, cfg, x)
    return y, aux
