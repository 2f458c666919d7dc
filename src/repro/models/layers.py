"""Norms, MLPs, and the attention block (projections + KV-cache management)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.attention import chunked_attention, rope, train_attention


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def data_axes(mesh) -> tuple:
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a != "model")


def shard_act(x, mesh, spec: Optional[P] = None):
    """Activation sharding constraint: batch over data axes, rest replicated."""
    if mesh is None:
        return x
    if spec is None:
        spec = P(data_axes(mesh), *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def norm(cfg: ModelConfig, p, x, prefix: str = "norm"):
    xf = x.astype(jnp.float32) if cfg.norm_f32 else x
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        xf = xf - mu
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6) * p[f"{prefix}_scale"] \
            + p[f"{prefix}_bias"]
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6) * p[f"{prefix}_scale"]
    return out.astype(x.dtype)


def _act(cfg: ModelConfig, h):
    return jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.silu(h)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp_block(cfg: ModelConfig, p, x, mesh=None):
    h = norm(cfg, p, x)
    up = h @ p["w_up"]
    if cfg.act == "silu":
        up = jax.nn.silu(h @ p["w_gate"]) * up
    else:
        up = _act(cfg, up)
    return x + up @ p["w_down"]


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------
def _split_heads(t, hd):
    B, S, HD = t.shape
    return t.reshape(B, S, HD // hd, hd)


def attn_block(cfg: ModelConfig, p, x, *, mode: str, pos, cache,
               window: int, mesh=None, wprefix: str = "", causal: bool = True):
    """Self (or cross, wprefix='c_') attention with optional (ring) KV cache.

    mode: 'train' (no cache), 'prefill' (build cache), 'decode' (1 token).
    pos:  absolute position of x[:, 0] (python int or scalar array).
    cache: {'k','v': (B, L, HKV, hd), 'kpos': (L,) int32} or None.
    Keys are stored RoPE'd; masking uses absolute positions in 'kpos'.
    """
    w = wprefix
    hd = cfg.head_dim
    B, S, _ = x.shape
    h = norm(cfg, p, x, prefix=f"{w}norm")
    q = _split_heads(h @ p[f"{w}wq"], hd)
    k = _split_heads(h @ p[f"{w}wk"], hd)
    v = _split_heads(h @ p[f"{w}wv"], hd)

    positions = pos + jnp.arange(S)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if mode == "train":
        out = train_attention(
            q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap,
            q_offset=pos, chunk=cfg.attn_chunk, mesh=mesh)
    elif cache is None:
        out = chunked_attention(
            q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap,
            q_offset=pos, chunk=cfg.attn_chunk)
    elif mode == "prefill":
        L = cache["k"].shape[1]
        out = chunked_attention(
            q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
            q_offset=pos, chunk=cfg.attn_chunk)
        # store the last min(S, L) keys/values; ring convention: position p
        # lives at slot p % L so decode overwrites the oldest entry.
        if S >= L:
            p0 = pos + S - L
            shift = jnp.asarray(p0) % L
            ck = jnp.roll(k[:, S - L:], shift, axis=1)
            cv = jnp.roll(v[:, S - L:], shift, axis=1)
            kpos = jnp.roll(positions[S - L:], shift, axis=0)
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
            kpos = jnp.where(jnp.arange(L) < S, jnp.arange(L) + pos,
                             cache["kpos"])
        new_cache = {"k": ck.astype(cache["k"].dtype),
                     "v": cv.astype(cache["v"].dtype), "kpos": kpos}
    else:  # decode
        L = cache["k"].shape[1]
        slot = jnp.asarray(pos) % L
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        kpos = jax.lax.dynamic_update_slice_in_dim(
            cache["kpos"], jnp.asarray(pos)[None] + jnp.arange(S), slot, axis=0)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
        out = chunked_attention(
            q, ck, cv, causal=True, window=window,
            softcap=cfg.attn_softcap, q_offset=pos, kv_positions=kpos,
            chunk=cfg.attn_chunk)

    y = out.reshape(B, S, -1) @ p[f"{w}wo"]
    return x + y, new_cache


def cross_attn_block(cfg: ModelConfig, p, x, *, mode: str, enc_out=None,
                     cache=None, mesh=None):
    """Whisper-style cross attention; encoder K/V cached at prefill."""
    hd = cfg.head_dim
    B, S, _ = x.shape
    h = norm(cfg, p, x, prefix="c_norm")
    q = _split_heads(h @ p["c_wq"], hd)
    new_cache = None
    if enc_out is not None:
        k = _split_heads(enc_out @ p["c_wk"], hd)
        v = _split_heads(enc_out @ p["c_wv"], hd)
        if mode == "prefill" and cache is not None:
            new_cache = {"ck": k.astype(cache["ck"].dtype),
                         "cv": v.astype(cache["cv"].dtype)}
    else:  # decode: read cached encoder projections
        k, v = cache["ck"], cache["cv"]
        new_cache = {"ck": k, "cv": v}
    out = chunked_attention(q, k, v, causal=False, softcap=cfg.attn_softcap,
                            chunk=cfg.attn_chunk)
    return x + out.reshape(B, S, -1) @ p["c_wo"], new_cache


def sinusoidal_positions(seq: int, d: int, offset=0, dtype=jnp.float32):
    pos = offset + jnp.arange(seq)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)
