"""Flash attention Pallas TPU kernel (causal / sliding-window / softcap, GQA).

Grid (B, Hq, nq, nk): the last axis iterates sequentially on TPU, carrying
the online-softmax state (m, l, acc) in VMEM scratch across KV blocks. Block
shapes are MXU-aligned (bq x hd, bk x hd with hd a multiple of 128 for the
assigned archs). Fully-masked KV blocks are skipped via pl.when — this is
the causal-FLOPs saving the pure-jnp chunked oracle cannot express.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, nk: int, q_offset: int, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk),
                                                          0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # block-level relevance (skip fully-masked blocks)
    first_q = q_offset + qi * bq
    last_q = first_q + bq - 1
    first_k = ki * bk
    relevant = jnp.bool_(True)
    if causal:
        relevant &= first_k <= last_q
    if window:
        relevant &= (first_k + bk - 1) > first_q - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        s = q @ k.T                                        # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        # fully-masked rows keep p = 0 (avoid exp(-inf - -inf) = 1)
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr \
            + p @ v_ref[0, 0].astype(jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=0.0,
                           q_offset=0, kv_len=None, bq=128, bk=128,
                           interpret=False):
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Skv, hd). Returns (B, Hq, Sq, hd).

    q_offset: absolute position of q[..., 0, :] (static int for the kernel).
    kv_len: number of valid KV entries (defaults to Skv).
    """
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    kv_len = Skv if kv_len is None else kv_len
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // bq
    nk = k.shape[2] // bk

    kern = functools.partial(
        _flash_kernel, scale=hd ** -0.5, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk, q_offset=q_offset,
        kv_len=kv_len)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pq:
        out = out[:, :, :Sq]
    return out
