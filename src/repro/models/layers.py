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
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p[f"{prefix}_scale"] \
            + p[f"{prefix}_bias"]
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p[f"{prefix}_scale"]
    return out.astype(x.dtype)


def _act(cfg: ModelConfig, h):
    return jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.silu(h)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp(cfg: ModelConfig, p, h, prefix: str = "w"):
    """The MLP of normed ``h``: SwiGLU, or a plain MLP for gelu."""
    up = h @ p[f"{prefix}_up"]
    if cfg.act == "silu":
        up = jax.nn.silu(h @ p[f"{prefix}_gate"]) * up
    else:
        up = _act(cfg, up)
    return up @ p[f"{prefix}_down"]


def mlp_block(cfg: ModelConfig, p, x, mesh=None):
    return x + mlp(cfg, p, norm(cfg, p, x))


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------
def _split_heads(t, hd):
    B, S, HD = t.shape
    return t.reshape(B, S, HD // hd, hd)


def _fill_cache(cache, k, v, pos, positions):
    """The cache after a prefill of k, v: the last min(S, L) keys/values;
    ring convention: position p lives at slot p % L so decode overwrites
    the oldest entry."""
    S, L = k.shape[1], cache["k"].shape[1]
    if S >= L:
        p0 = pos + S - L
        shift = jnp.asarray(p0) % L
        ck = jnp.roll(k[:, S - L:], shift, axis=1)
        cv = jnp.roll(v[:, S - L:], shift, axis=1)
        kpos = jnp.roll(positions[S - L:], shift, axis=0)
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(
            cache["k"].dtype), (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(
            cache["v"].dtype), (0, 0, 0, 0))
        kpos = jnp.where(jnp.arange(L) < S, jnp.arange(L) + pos,
                         cache["kpos"])
    return {"k": ck.astype(cache["k"].dtype),
            "v": cv.astype(cache["v"].dtype), "kpos": kpos}


def _append_cache(cache, k, v, pos):
    """The cache with a decode step's k, v written at slot pos % L."""
    S, L = k.shape[1], cache["k"].shape[1]
    slot = jnp.asarray(pos) % L
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    kpos = jax.lax.dynamic_update_slice_in_dim(
        cache["kpos"], jnp.asarray(pos)[None] + jnp.arange(S), slot, axis=0)
    return {"k": ck, "v": cv, "kpos": kpos}


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
        out = chunked_attention(
            q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
            q_offset=pos, chunk=cfg.attn_chunk)
        new_cache = _fill_cache(cache, k, v, pos, positions)
    else:  # decode
        new_cache = _append_cache(cache, k, v, pos)
        out = chunked_attention(
            q, new_cache["k"], new_cache["v"], causal=True, window=window,
            softcap=cfg.attn_softcap, q_offset=pos,
            kv_positions=new_cache["kpos"], chunk=cfg.attn_chunk)

    y = out.reshape(B, S, -1) @ p[f"{w}wo"]
    return x + y, new_cache


def mla_block(cfg: ModelConfig, p, x, *, mode: str, pos, cache,
              window: int, mesh=None):
    """Latent attention (DeepSeek-V2/V3 MLA, no q latent): per token

      q = h W_q -> [q_nope, q_pe];  [c_kv, k_pe] = h W_kv_a;
      [k_nope, v] = RMSNorm(c_kv) W_kv_b;
      k = [k_nope, RoPE(k_pe)] (one RoPE head shared by every head),
      q = [q_nope, RoPE(q_pe)];  softmax scale (d_nope + d_rope) ** -0.5;
      out (H x d_v) W_o.

    The cache holds the materialised per-head k and v, stored RoPE'd, with
    the same ring convention as ``attn_block``.
    """
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    B, S, _ = x.shape
    positions = pos + jnp.arange(S)
    with jax.named_scope("mla.project"):
        h = norm(cfg, p, x)
        q = (h @ p["wq"]).reshape(B, S, H, dn + dr)
        kv_a = h @ p["wkv_a"]
        c_kv = norm(cfg, p, kv_a[..., :r], prefix="kv_norm")
        k_pe = rope(kv_a[..., None, r:], positions, cfg.rope_theta)
        kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
        v = kv[..., dn:]
    scale = (dn + dr) ** -0.5

    new_cache = None
    if mode == "train":
        out = train_attention(q, k, v, causal=True, window=window,
                              q_offset=pos, chunk=cfg.attn_chunk, mesh=mesh,
                              scale=scale)
    elif cache is None or mode == "prefill":
        out = chunked_attention(q, k, v, causal=True, window=window,
                                q_offset=pos, chunk=cfg.attn_chunk,
                                scale=scale)
        if cache is not None:
            new_cache = _fill_cache(cache, k, v, pos, positions)
    else:  # decode
        new_cache = _append_cache(cache, k, v, pos)
        out = chunked_attention(
            q, new_cache["k"], new_cache["v"], causal=True, window=window,
            q_offset=pos, kv_positions=new_cache["kpos"],
            chunk=cfg.attn_chunk, scale=scale)
    y = out.reshape(B, S, H * dv) @ p["wo"]
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
