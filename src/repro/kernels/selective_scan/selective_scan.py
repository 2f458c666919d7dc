"""Mamba selective-scan Pallas TPU kernel.

Grid (B, nd, ns): channel blocks (bd of d_inner) x sequence blocks (bs).
The sequence axis is the LAST grid dimension, which TPU iterates
sequentially, so the SSM state h (d_state, bd) lives in VMEM scratch and is
carried across sequence blocks — HBM traffic is O(S*(bd + 2*d_state)) input
streaming instead of O(S*bd*d_state) state spill of a naive lowering. Each
step inside a block is a rank-1 VPU update; d_state stays VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref,
                 h_scr, *, bs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)          # (st, bd)

    at = at_ref[...].astype(jnp.float32)                    # (st, bd)
    st = at.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (st, st), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (st, st), 1))

    def column(row):
        # (1, st) -> (st, 1) by a masked lane reduction: Mosaic has no
        # transpose for a single row
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, h):
        # per-timestep rows are read and written through ref slices:
        # Mosaic lowers a dynamic sublane slice of a ref, not a
        # dynamic_slice of a loaded value
        row = pl.ds(t, 1)
        dt = dt_ref[0, row, :].astype(jnp.float32)          # (1, bd)
        u = u_ref[0, row, :].astype(jnp.float32)            # (1, bd)
        b = column(b_ref[0, row, :].astype(jnp.float32))    # (st, 1)
        c = column(c_ref[0, row, :].astype(jnp.float32))    # (st, 1)
        h = jnp.exp(at * dt) * h + b * (dt * u)             # (st, bd)
        y_ref[0, row, :] = jnp.sum(h * c, axis=0, keepdims=True)
        return h

    h_scr[...] = jax.lax.fori_loop(0, bs, step, h_scr[...])

    @pl.when(si == ns - 1)
    def _fin():
        hT_ref[0] = h_scr[...]


def selective_scan_pallas(u, dt, a, b, c, d_skip, h0, *, bd: int = 256,
                          bs: int = 64, interpret: bool = False):
    """u, dt: (B, S, di); a: (di, st); b, c: (B, S, st); h0: (B, di, st).
    Returns (y (B, S, di), hT (B, di, st))."""
    B, S, di = u.shape
    st = a.shape[-1]
    bd = min(bd, di)
    bs = min(bs, S)
    assert di % bd == 0 and S % bs == 0, (di, bd, S, bs)
    nd = di // bd
    ns = S // bs

    # the state is held transposed, (st, bd): channels on the 128-wide
    # lane axis, d_state on sublanes. u/dt are blocked (1, bs, bd), b/c
    # (1, bs, st); a and h0 enter transposed to match the state.
    kern = functools.partial(_scan_kernel, bs=bs, ns=ns)
    y, hT = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((B, S, di), jnp.float32),
                   jax.ShapeDtypeStruct((B, st, di), jnp.float32)),
        grid=(B, nd, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di_, si: (bi, si, di_)),
            pl.BlockSpec((1, bs, bd), lambda bi, di_, si: (bi, si, di_)),
            pl.BlockSpec((st, bd), lambda bi, di_, si: (0, di_)),
            pl.BlockSpec((1, bs, st), lambda bi, di_, si: (bi, si, 0)),
            pl.BlockSpec((1, bs, st), lambda bi, di_, si: (bi, si, 0)),
            pl.BlockSpec((1, st, bd), lambda bi, di_, si: (bi, 0, di_)),
        ],
        out_specs=(
            pl.BlockSpec((1, bs, bd), lambda bi, di_, si: (bi, si, di_)),
            pl.BlockSpec((1, st, bd), lambda bi, di_, si: (bi, 0, di_)),
        ),
        scratch_shapes=[pltpu.VMEM((st, bd), jnp.float32)],
        interpret=interpret,
    )(u, dt, a.T, b, c, h0.transpose(0, 2, 1))
    y = y + u.astype(jnp.float32) * d_skip
    return y.astype(u.dtype), hT.transpose(0, 2, 1)
