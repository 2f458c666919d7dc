"""Fused RMSNorm Pallas TPU kernel.

Rows are tiled into VMEM blocks; the mean-square reduction, rsqrt, and the
scale multiply fuse into one pass over HBM (vs 3 for the unfused form).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
                   block_rows: int = 128, interpret: bool = False):
    """x: (..., D); scale: (D,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    rows = xf.shape[0]
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    n = xf.shape[0] // br

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        interpret=interpret,
    )(xf, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
