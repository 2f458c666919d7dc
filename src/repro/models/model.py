"""Model assembly: embed -> scan over super-blocks -> final norm (+ logits).

Modes:
  * 'train'   — full sequence, no cache, returns (hidden, None, aux)
  * 'prefill' — full sequence, builds cache, returns (hidden, cache, aux)
  * 'decode'  — one token against a cache, returns (hidden, cache, aux)

Layers run under ``lax.scan`` with stacked params (compile O(1) in depth);
caches carry a leading ``n_repeat`` dim and are scanned alongside params.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import schema as mschema
from repro.models.layers import (attn_block, cross_attn_block, mla_block,
                                 mlp_block, norm, shard_act,
                                 sinusoidal_positions)
from repro.models.moe import moe_block
from repro.models.ssm import mamba_block
from repro.models.xlstm import mlstm_block, slstm_block
from repro.models.schema import _pad_to, Dims


def _mixer_window(cfg: ModelConfig, mixer: str, window_override: int) -> int:
    if mixer == "attn_local":
        return cfg.window
    return window_override  # 0 = full attention


def _apply_superblock(cfg, pattern, mode, mesh, window_override, enc_out,
                      bp, csl, x, pos):
    """Apply one repetition of ``pattern``. csl: cache slice (or None)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    for i, (mixer, ffn) in enumerate(pattern):
        key = f"b{i}_{mixer}"
        p = bp[key]
        c = csl[key] if csl is not None else None
        if mixer.startswith("attn"):
            w = _mixer_window(cfg, mixer, window_override)
            x, nc = attn_block(cfg, p, x, mode=mode, pos=pos, cache=c,
                               window=w, mesh=mesh)
            if cfg.is_encdec:
                x, ncc = cross_attn_block(cfg, p, x, mode=mode,
                                          enc_out=enc_out, cache=c, mesh=mesh)
                if nc is not None and ncc is not None:
                    nc = {**nc, **ncc}
        elif mixer == "mla":
            x, nc = mla_block(cfg, p, x, mode=mode, pos=pos, cache=c,
                              window=window_override, mesh=mesh)
        elif mixer == "mamba":
            x, nc = mamba_block(cfg, p, x, mode=mode, cache=c, mesh=mesh)
        elif mixer == "mlstm":
            x, nc = mlstm_block(cfg, p, x, mode=mode, cache=c, mesh=mesh)
        elif mixer == "slstm":
            x, nc = slstm_block(cfg, p, x, mode=mode, cache=c, mesh=mesh)
        else:
            raise ValueError(mixer)
        if csl is not None:
            new_cache[key] = nc
        if ffn == "mlp":
            x = mlp_block(cfg, bp[f"b{i}_mlp"], x, mesh=mesh)
        elif ffn == "moe":
            x, a = moe_block(cfg, bp[f"b{i}_moe"], x, mesh=mesh)
            aux = aux + a
    if cfg.seq_parallel and mesh is not None and x.shape[1] \
            % (mesh.shape.get("model", 1)) == 0:
        from repro.models.layers import data_axes
        from jax.sharding import PartitionSpec as P
        dp = data_axes(mesh)
        x = shard_act(x, mesh, P(dp if len(dp) != 1 else dp[0], "model",
                                 None))
    else:
        x = shard_act(x, mesh)
    return x, (new_cache if csl is not None else None), aux


def _scan_blocks(cfg, params_stack, cache_stack, x, pos, *, mode, mesh,
                 window_override, enc_out, remat="none", unroll=False,
                 pattern=None):
    pattern = cfg.pattern if pattern is None else pattern

    def body(carry, xs):
        x, aux = carry
        bp, csl = xs
        x, nc, a = _apply_superblock(cfg, pattern, mode, mesh,
                                     window_override, enc_out, bp, csl, x,
                                     pos)
        return (x, aux + a), nc

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        # keep matmul outputs, recompute elementwise — less recompute FLOPs
        # at the cost of more saved bytes (a §Perf lever)
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    xs = (params_stack, cache_stack)
    # unroll=True inlines every repetition: required for exact cost_analysis
    # (XLA's HLO cost model counts a while-loop body ONCE, ignoring the trip
    # count) — the dry-run uses this for the roofline table.
    n = jax.tree.leaves(params_stack)[0].shape[0]
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                       xs, unroll=n if unroll else 1)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# embedding & heads
# ---------------------------------------------------------------------------
def embed_tokens(cfg: ModelConfig, params, tokens, pos=0):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    if cfg.abs_pos:
        x = x + sinusoidal_positions(tokens.shape[-1], cfg.d_model,
                                     offset=pos, dtype=x.dtype)
    return x


def logits_fn(cfg: ModelConfig, params, hidden):
    """hidden: (B, S, D) -> (B, S, V) float32 (small S only — decode)."""
    h = norm(cfg, params, hidden, prefix="final_norm")
    logits = (h @ params["unembed"]).astype(jnp.float32)
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    if logits.shape[-1] != cfg.vocab_size:  # vocab-padding mask
        logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                           logits, -1e30)
    return logits


def encode(cfg: ModelConfig, params, enc_embeds, mesh=None):
    """Whisper encoder: frame embeddings (B, enc_seq, D) -> encoder states."""
    x = enc_embeds.astype(jnp.dtype(cfg.dtype))
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model, dtype=x.dtype)

    def body(carry, bp):
        x = carry
        h, _ = attn_block(cfg, bp["b0_attn"], x, mode="train", pos=0,
                          cache=None, window=0, mesh=mesh, causal=False)
        h = mlp_block(cfg, bp["b0_mlp"], h, mesh=mesh)
        return shard_act(h, mesh), None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return norm(cfg, params, x, prefix="enc_final_norm")


# ---------------------------------------------------------------------------
# public forward
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch: dict, *, mode: str = "train",
            pos=0, cache=None, mesh=None, window_override: int = 0,
            remat: str = "none", unroll: bool = False):
    """Returns (hidden (B,S,D), new_cache, aux_loss)."""
    enc_out = None
    if cfg.is_encdec and "enc_embeds" in batch:
        enc_out = encode(cfg, params, batch["enc_embeds"], mesh=mesh)

    x = embed_tokens(cfg, params, batch["tokens"], pos=pos)
    if cfg.family == "vlm" and "image_embeds" in batch and mode != "decode":
        img = batch["image_embeds"].astype(x.dtype) @ params["img_proj"]
        x = jnp.concatenate([img, x], axis=1)
    x = shard_act(x, mesh)

    scan = partial(_scan_blocks, cfg, pos=pos, mode=mode, mesh=mesh,
                   window_override=window_override, enc_out=enc_out,
                   remat=remat, unroll=unroll)
    first_cache = None
    if cfg.first_dense:
        if cache is not None:
            cache = dict(cache)
            first_cache = cache.pop("first")
        x, first_cache, aux0 = scan(params["first"], first_cache, x,
                                    pattern=cfg.first_pattern)
    x, new_cache, aux = scan(params["dec"], cache, x)
    if cfg.first_dense:
        aux = aux + aux0
        if new_cache is not None:
            new_cache = {**new_cache, "first": first_cache}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int, *,
               window_override: int = 0, model_shards: int = 1,
               abstract: bool = False):
    """Build the (abstract or zero-filled) cache pytree for serve/prefill."""
    dims = Dims(cfg, model_shards)
    R = cfg.n_repeat
    dt = jnp.dtype(cfg.dtype)
    B = batch_size

    def mk(shape, dtype=dt, fill=0.0):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        if dtype == jnp.int32:
            return jnp.full(shape, 2 ** 30, jnp.int32)  # invalid position
        return jnp.full(shape, fill, dtype)

    def entry(mixer, R):
        if mixer.startswith("attn") or mixer == "mla":
            w = _mixer_window(cfg, mixer, window_override)
            L = min(max_seq, w) if w else max_seq
            if mixer == "mla":     # materialised per-head k and v
                h, dk, dv = cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
            else:
                h, dk, dv = dims.hkv, dims.hd, dims.hd
            ent = {"k": mk((R, B, L, h, dk)), "v": mk((R, B, L, h, dv)),
                   "kpos": mk((R, L), jnp.int32)}
            if cfg.is_encdec:
                ent["ck"] = mk((R, B, cfg.enc_seq, dims.hkv, dims.hd))
                ent["cv"] = mk((R, B, cfg.enc_seq, dims.hkv, dims.hd))
            return ent
        if mixer == "mamba":
            return {
                "conv": mk((R, B, cfg.ssm_conv - 1, cfg.ssm_d_inner)),
                "ssm": mk((R, B, cfg.ssm_d_inner, cfg.ssm_d_state),
                          jnp.float32)}
        if mixer == "mlstm":
            di = _pad_to(int(cfg.xlstm_pf_mlstm * cfg.d_model), cfg.n_heads)
            hd = di // cfg.n_heads
            H = cfg.n_heads
            return {"C": mk((R, B, H, hd, hd), jnp.float32),
                    "n": mk((R, B, H, hd), jnp.float32),
                    "m": mk((R, B, H), jnp.float32, -1e30)}
        if mixer == "slstm":
            D = cfg.d_model
            return {k: mk((R, B, D), jnp.float32,
                          -1e30 if k == "m" else 0.0) for k in "cnmh"}
        return None

    def entries(pattern, R):
        out = {}
        for i, (mixer, _) in enumerate(pattern):
            ent = entry(mixer, R)
            if ent is not None:
                out[f"b{i}_{mixer}"] = ent
        return out

    cache = entries(cfg.pattern, R)
    if cfg.first_dense:
        cache["first"] = entries(cfg.first_pattern, cfg.first_dense)
    return cache


def cache_specs(cfg: ModelConfig, long_batch_one: bool = False):
    """PartitionSpec pytree matching init_cache structure.

    KV heads shard over 'model'; batch shards over data axes. When B == 1
    (long_500k) the cache *sequence* axis shards over 'data' instead
    (sequence-parallel decode).
    """
    from jax.sharding import PartitionSpec as P
    batch = None if long_batch_one else "data"
    seq = "data" if long_batch_one else None
    specs = {}
    for i, (mixer, _) in enumerate(cfg.pattern):
        key = f"b{i}_{mixer}"
        if mixer.startswith("attn") or mixer == "mla":
            ent = {"k": P(None, batch, seq, "model", None),
                   "v": P(None, batch, seq, "model", None),
                   "kpos": P(None, seq)}
            if cfg.is_encdec:
                ent["ck"] = P(None, batch, None, "model", None)
                ent["cv"] = P(None, batch, None, "model", None)
            specs[key] = ent
        elif mixer == "mamba":
            specs[key] = {"conv": P(None, batch, None, "model"),
                          "ssm": P(None, batch, "model", None)}
        elif mixer == "mlstm":
            specs[key] = {"C": P(None, batch, None, None, None),
                          "n": P(None, batch, None, None),
                          "m": P(None, batch, None)}
        elif mixer == "slstm":
            specs[key] = {k: P(None, batch, None) for k in "cnmh"}
    if cfg.first_dense:
        key = f"b0_{cfg.pattern[0][0]}"
        specs["first"] = {key: specs[key]}
    return specs
