"""Attention: GQA + RoPE + sliding window + logit softcap, chunked (flash-style).

The chunked path scans over KV blocks with an online-softmax running state so
no (Sq, Skv) score tensor ever materializes for long sequences — this is also
the pure-jnp oracle for the Pallas flash_attention kernel.

A training forward goes through ``train_attention``: on a TPU, causal MHA
self-attention whose shapes the fused Pallas kernel accepts runs that kernel
(forward and backward); everything else, and every other platform, runs
``chunked_attention``.

Decode (Sq == 1) uses a single unchunked pass: scores are (B, H, 1, Skv),
linear in cache length, and SPMD handles sequence-sharded caches via partial
max/sum reductions.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.train import (flash_attention_train,
                                                 train_block_sizes)
from repro.models import flags

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(scores, cap):
    if cap and cap > 0.0:
        return jnp.tanh(scores / cap) * cap
    return scores


# ---------------------------------------------------------------------------
# chunked (flash-style) attention
# ---------------------------------------------------------------------------
def chunked_attention(
    q: jax.Array,               # (B, Sq, Hq, hd)
    k: jax.Array,               # (B, Skv, Hkv, hd)
    v: jax.Array,               # (B, Skv, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,            # 0 = full
    softcap: float = 0.0,
    q_offset=0,                 # absolute position of q[0] (int or scalar array)
    kv_positions: Optional[jax.Array] = None,  # (Skv,) absolute, default iota
    kv_valid_len=None,          # mask k beyond this length (decode w/ prealloc)
    chunk: int = 512,
) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, hd)
    q_pos = q_offset + jnp.arange(Sq)

    if kv_positions is None:
        kv_positions = jnp.arange(Skv)

    if Skv <= chunk or Sq == 1:
        # single pass (decode or short kv)
        return _attend_block(
            qf, k, v, q_pos, kv_positions, causal, window, softcap,
            kv_valid_len).astype(q.dtype).reshape(B, Sq, Hq, hd)

    n_chunks = Skv // chunk
    rem = Skv - n_chunks * chunk
    kc = k[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, Hkv, hd)
    vc = v[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, Hkv, hd)
    pc = kv_positions[: n_chunks * chunk].reshape(n_chunks, chunk)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs
        s = jnp.einsum("bsngh,bcnh->bngsc", qf, kj.astype(jnp.float32))
        s = _softcap(s, softcap)
        mask = _make_mask(q_pos, pj, causal, window, kv_valid_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked rows keep p = 0 (avoid exp(-inf - -inf) = 1)
        p = jnp.where((m_new > NEG_INF / 2)[..., None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bngsc,bcnh->bngsh", p, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), pc),
        unroll=flags.inner_unroll(n_chunks))

    if rem:
        kr, vr, pr = k[:, -rem:], v[:, -rem:], kv_positions[-rem:]
        s = jnp.einsum("bsngh,bcnh->bngsc", qf, kr.astype(jnp.float32))
        s = _softcap(s, softcap)
        mask = _make_mask(q_pos, pr, causal, window, kv_valid_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where((m_new > NEG_INF / 2)[..., None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bngsc,bcnh->bngsh", p, vr.astype(jnp.float32))
        m = m_new

    out = acc / jnp.maximum(l, 1e-30)[..., None]                # (B,Hkv,G,Sq,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# training forward: fused kernel on TPU where the shapes allow
# ---------------------------------------------------------------------------
def fused_block_sizes(q_shape, k_shape, *, causal, window, softcap, q_offset,
                      mesh=None):
    """The fused kernel's blocks where a training call may take it, else
    None: causal self-attention (Sq == Skv, static offset 0), no softcap,
    no window narrower than the sequence, Hq == Hkv, hd <= 128 or a
    multiple of 128, a sequence some block divides, and no mesh (the
    kernel is one device's program; XLA cannot partition it)."""
    _, S, Hq, hd = q_shape
    _, Skv, Hkv, _ = k_shape
    if not (causal and S == Skv and isinstance(q_offset, int)
            and q_offset == 0 and not softcap
            and (window == 0 or window >= S) and Hq == Hkv
            and (hd <= 128 or hd % 128 == 0) and mesh is None):
        return None
    return train_block_sizes(S)


def train_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    q_offset=0, chunk=512, mesh=None):
    """Attention of a training forward, (B, S, H, hd) in and out. The
    platform is decided at lowering, so a compile for a described TPU
    takes the kernel and every other platform lowers the chunked scan."""
    def chunked(q, k, v):
        with jax.named_scope("attention.chunked"):
            return chunked_attention(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset,
                                     chunk=chunk)

    blocks = fused_block_sizes(q.shape, k.shape, causal=causal,
                               window=window, softcap=softcap,
                               q_offset=q_offset, mesh=mesh)
    if blocks is None:
        return chunked(q, k, v)

    def fused(q, k, v):
        with jax.named_scope("attention.flash"):
            return flash_attention_train(q, k, v, blocks)

    return jax.lax.platform_dependent(q, k, v, tpu=fused, default=chunked)


def _make_mask(q_pos, kv_pos, causal, window, kv_valid_len):
    """(Sq, C) bool validity mask from absolute positions."""
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= kv_pos[None, :] > q_pos[:, None] - window
    if kv_valid_len is not None:
        mask &= (kv_pos < kv_valid_len)[None, :]
    return mask


def _attend_block(qf, k, v, q_pos, kv_pos, causal, window, softcap,
                  kv_valid_len):
    s = jnp.einsum("bsngh,bcnh->bngsc", qf, k.astype(jnp.float32))
    s = _softcap(s, softcap)
    mask = _make_mask(q_pos, kv_pos, causal, window, kv_valid_len)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p * mask.any(-1).astype(p.dtype)[None, None, None, :, None]
    out = jnp.einsum("bngsc,bcnh->bngsh", p, v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4)                         # (B,Sq,Hkv,G,hd)


# ---------------------------------------------------------------------------
# reference (quadratic) oracle — small shapes only, used in tests
# ---------------------------------------------------------------------------
def reference_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                        q_offset=0, kv_valid_len=None):
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(B, Sq, Hkv, Hq // Hkv, hd)
    out = _attend_block(qf, k, v, q_offset + jnp.arange(Sq), jnp.arange(Skv),
                        causal, window, softcap, kv_valid_len)
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)
