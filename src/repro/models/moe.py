"""Mixture-of-Experts: top-k routing with two sharded execution paths.

* ``moe_local``   — pure-jnp sort-based path (single device). Oracle for the
                    sharded paths and the smoke-test implementation.
* expert-parallel — E >= model-axis size: experts sharded over 'model'.
                    Activations are replicated over 'model' between blocks
                    (Megatron convention), so each model rank selects the
                    assignments that target ITS experts into a fixed-capacity
                    buffer (sort + slice), runs a grouped matmul
                    (lax.ragged_dot — the Pallas ``gmm`` kernel is the TPU
                    twin), scatters back, and a psum over 'model' combines
                    expert outputs. An all_to_all dispatch variant is a
                    recorded perf iteration (see EXPERIMENTS.md §Perf).
* tensor-parallel — E < model-axis size (grok: 8 experts on a 16-wide axis):
                    experts replicated, expert d_ff sharded over 'model',
                    every assignment computed locally on the F shard, psum.

Aux loss: Switch-style load-balance  E * sum_e f_e * P_e.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import norm, data_axes


def _router(cfg: ModelConfig, p, x):
    """x: (T, D) -> top-k probs (T,k), indices (T,k), aux loss scalar."""
    logits = (x.astype(jnp.float32)) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load balance: fraction routed to e (top-1 proxy) x mean prob
    e = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    pbar = probs.mean(0)
    aux = e * jnp.sum(f * pbar) * cfg.router_aux_coef
    return top_p, top_i, aux


def _expert_ffn(cfg: ModelConfig, p, xs, group_sizes):
    """Grouped FFN over rows of ``xs`` sorted by expert. Weights may be the
    full (E, D, F) stacks or per-rank shards — shapes decide."""
    up = jax.lax.ragged_dot(xs, p["we_up"], group_sizes)
    if cfg.act == "silu":
        up = jax.nn.silu(jax.lax.ragged_dot(xs, p["we_gate"], group_sizes)) * up
    else:
        up = jax.nn.gelu(up)
    return jax.lax.ragged_dot(up, p["we_down"], group_sizes)


def _sorted_dispatch(cfg, x_flat, top_i, top_p):
    """Sort the T*k assignments by expert id. Returns gathered rows, gates,
    source row ids, expert ids (sorted), and the sort order."""
    t = x_flat.shape[0]
    k = cfg.top_k
    eid = top_i.reshape(t * k)
    gate = top_p.reshape(t * k)
    order = jnp.argsort(eid)
    src = order // k
    return x_flat[src], gate[order], src, eid[order]


def moe_local(cfg: ModelConfig, p, x):
    """Single-device sort-based oracle. x: (B,S,D)."""
    B, S, D = x.shape
    h = norm(cfg, p, x)
    hf = h.reshape(B * S, D)
    top_p, top_i, aux = _router(cfg, p, hf)
    xs, gates, src, eid_sorted = _sorted_dispatch(cfg, hf, top_i, top_p)
    gs = jnp.bincount(eid_sorted, length=cfg.n_experts)
    out = _expert_ffn(cfg, p, xs.astype(h.dtype), gs)
    out = out * gates[:, None].astype(out.dtype)
    y = jnp.zeros((B * S, D), out.dtype).at[src].add(out)
    return x + y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# sharded paths (shard_map)
# ---------------------------------------------------------------------------
def _expert_parallel_body(cfg: ModelConfig, e_local: int, capacity: int,
                          dp: tuple, p, x):
    """Runs per (data-rank, model-rank). x: (B_local, S, D) replicated over
    'model'; expert weights local (E/mp, D, F)."""
    B, S, D = x.shape
    h = norm(cfg, p, x)
    hf = h.reshape(B * S, D)
    top_p, top_i, aux = _router(cfg, p, hf)
    t, k = hf.shape[0], cfg.top_k

    my_rank = jax.lax.axis_index("model")
    eid = top_i.reshape(t * k)
    gate = top_p.reshape(t * k)
    local_e = eid - my_rank * e_local
    mine = (local_e >= 0) & (local_e < e_local)
    key = jnp.where(mine, local_e, e_local)          # foreign -> end
    order = jnp.argsort(key)
    sel = order[:capacity]                           # fixed-capacity buffer
    valid = mine[sel]
    xs = hf[sel // k].astype(h.dtype)
    gs = jnp.bincount(jnp.where(valid, local_e[sel], e_local),
                      length=e_local + 1)[:e_local]
    # trailing (invalid) rows are absorbed by the last group and masked out
    gs = gs.at[e_local - 1].add(capacity - gs.sum())
    out = _expert_ffn(cfg, p, xs, gs)
    out = out * (gate[sel] * valid)[:, None].astype(out.dtype)
    y = jnp.zeros((t, D), out.dtype).at[sel // k].add(out)
    y = jax.lax.psum(y, "model")
    aux = jax.lax.pmean(aux, ("model",) + tuple(dp))
    return x + y.reshape(B, S, D).astype(x.dtype), aux


def _tensor_parallel_body(cfg: ModelConfig, dp: tuple, p, x):
    """E < mp: experts replicated, F sharded. All assignments computed on the
    local F shard; down-projection gives partial sums -> psum over 'model'."""
    B, S, D = x.shape
    h = norm(cfg, p, x)
    hf = h.reshape(B * S, D)
    top_p, top_i, aux = _router(cfg, p, hf)
    xs, gates, src, eid_sorted = _sorted_dispatch(cfg, hf, top_i, top_p)
    gs = jnp.bincount(eid_sorted, length=cfg.n_experts)
    out = _expert_ffn(cfg, p, xs.astype(h.dtype), gs)   # partial over F shard
    out = out * gates[:, None].astype(out.dtype)
    y = jnp.zeros((B * S, D), out.dtype).at[src].add(out)
    y = jax.lax.psum(y, "model")
    aux = jax.lax.pmean(aux, ("model",) + dp)
    return x + y.reshape(B, S, D).astype(x.dtype), aux


def _expert_parallel_a2a_body(cfg: ModelConfig, e_local: int, mp: int,
                              capacity: int, dp: tuple, p, x):
    """all_to_all dispatch variant (perf iteration — see EXPERIMENTS.md
    §Perf). Activations arrive SEQUENCE-SHARDED over 'model'
    (x: (B_local, S/mp, D)); each rank routes its own tokens, exchanges
    them with the expert-owner ranks via all_to_all (bf16, capacity C per
    peer), computes with its local experts, and all_to_all's results back.
    Collective traffic: 2 x mp*C*D bf16 a2a (+ the surrounding layer's
    all-gather of the sequence-sharded output) instead of a full f32 psum
    of (t, D)."""
    B, S_loc, D = x.shape
    h = norm(cfg, p, x)
    hf = h.reshape(B * S_loc, D)
    top_p, top_i, aux = _router(cfg, p, hf)
    t, k = hf.shape[0], cfg.top_k
    tk = t * k

    eid = top_i.reshape(tk)
    gate = top_p.reshape(tk)
    dst = eid // e_local                                 # target model rank
    order = jnp.argsort(dst)
    counts = jnp.bincount(dst, length=mp)
    seg_start = jnp.cumsum(counts) - counts
    pos_in_seg = jnp.arange(tk) - seg_start[dst[order]]
    keep = pos_in_seg < capacity                          # overflow drops
    slot = dst[order] * capacity + pos_in_seg             # (tk,)

    # scatter into per-destination buffers; dropped rows go to a dump slot
    src_row = order // k
    slot_safe = jnp.where(keep, slot, mp * capacity)
    xs_send = jnp.zeros((mp * capacity + 1, D), h.dtype) \
        .at[slot_safe].set(hf[src_row].astype(h.dtype))[:-1]
    meta_e = jnp.full((mp * capacity + 1,), e_local, jnp.int32) \
        .at[slot_safe].set(eid[order] % e_local)[:-1]

    xs_recv = jax.lax.all_to_all(
        xs_send.reshape(mp, capacity, D), "model", 0, 0, tiled=False)
    me_recv = jax.lax.all_to_all(
        meta_e.reshape(mp, capacity), "model", 0, 0, tiled=False)

    flat_x = xs_recv.reshape(mp * capacity, D)
    flat_e = me_recv.reshape(mp * capacity)
    ord2 = jnp.argsort(flat_e)
    gs = jnp.bincount(flat_e, length=e_local + 1)[:e_local]
    gs = gs.at[e_local - 1].add(mp * capacity - gs.sum())
    out = _expert_ffn(cfg, p, flat_x[ord2], gs)
    valid = flat_e[ord2] < e_local
    out = out * valid[:, None].astype(out.dtype)
    out = jnp.zeros_like(out).at[ord2].set(out)           # unsort

    out_send = jax.lax.all_to_all(
        out.reshape(mp, capacity, D), "model", 0, 0, tiled=False)
    out_flat = out_send.reshape(mp * capacity, D)
    contrib = out_flat[jnp.where(keep, slot, 0)]         * (gate[order] * keep)[:, None].astype(out_flat.dtype)
    y = jnp.zeros((t, D), out_flat.dtype).at[src_row].add(contrib)
    aux = jax.lax.pmean(aux, ("model",) + tuple(dp))
    return x + y.reshape(B, S_loc, D).astype(x.dtype), aux


def pspecs_a2a(p):
    specs = jax.tree.map(lambda _: P(), p)
    for name in ("we_up", "we_down", "we_gate"):
        if name in p:
            specs[name] = P("model", None, None)
    return specs


def moe_block(cfg: ModelConfig, p, x, mesh=None):
    """Dispatch to the local oracle or a shard_map path based on the mesh."""
    if mesh is None or mesh.shape.get("model", 1) == 1:
        return moe_local(cfg, p, x)

    mp = mesh.shape["model"]
    dp = data_axes(mesh)
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]
    if x.shape[0] % dpsize:
        # batch does not divide the data axes (e.g. long_500k B=1):
        # replicate activations over 'data' inside the block
        dp = ()
        dpsize = 1
    xspec = P(dp if len(dp) > 1 else (dp[0] if dp else None), None, None)
    pspecs = jax.tree.map(lambda _: P(), p)
    expert_parallel = cfg.n_experts >= mp and cfg.n_experts % mp == 0
    if expert_parallel:
        for name in ("we_up", "we_down", "we_gate"):
            if name in p:
                pspecs[name] = P("model", None, None)
        e_local = cfg.n_experts // mp
        b_local = x.shape[0] // dpsize
        if getattr(cfg, "moe_impl", "psum") == "a2a" \
                and x.shape[1] % mp == 0:
            t_loc = b_local * (x.shape[1] // mp)
            capacity = max(int(t_loc * cfg.top_k / mp
                               * cfg.capacity_factor) + 1, 1)
            body = partial(_expert_parallel_a2a_body, cfg, e_local, mp,
                           capacity, dp)
            xspec_in = P(xspec[0], "model", None)
            fn = jax.shard_map(body, mesh=mesh,
                               in_specs=(pspecs_a2a(p), xspec_in),
                               out_specs=(xspec_in, P()), check_vma=False)
            return fn(p, x)
        t = b_local * x.shape[1]
        capacity = int(t * cfg.top_k / mp * cfg.capacity_factor) + 1
        body = partial(_expert_parallel_body, cfg, e_local, capacity, dp)
    else:
        for name in ("we_up", "we_gate"):
            if name in p:
                pspecs[name] = P(None, None, "model")
        pspecs["we_down"] = P(None, "model", None)
        body = partial(_tensor_parallel_body, cfg, dp)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspecs, xspec),
                       out_specs=(xspec, P()), check_vma=False)
    return fn(p, x)
