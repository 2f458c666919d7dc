"""Grouped matmul (gmm) Pallas TPU kernel — the MoE expert-FFN hot spot
(megablocks-style).

Rows of ``x`` are sorted by expert; the WRAPPER pads every group to a
multiple of the row tile so each (bt x D) tile belongs to exactly ONE
expert. The tile->expert map rides in as a scalar-prefetch operand and
drives the weight BlockSpec index_map, so each tile streams only its own
expert's (D x bn) weight panels through VMEM — no gather, no one-hot
dispatch tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(te_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...].astype(jnp.float32),
                         w_ref[0].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def gmm_pallas(x, w, tile_expert, *, bt: int = 128, bn: int = 128,
               interpret: bool = False):
    """x: (Tp, D) rows grouped by expert, Tp % bt == 0 and every tile
    single-expert; w: (E, D, F); tile_expert: (Tp//bt,) int32.
    Returns (Tp, F)."""
    Tp, D = x.shape
    E, _, F = w.shape
    bn = min(bn, F)
    assert Tp % bt == 0 and F % bn == 0
    nt, nn = Tp // bt, F // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nn),
        in_specs=[
            pl.BlockSpec((bt, D), lambda ti, ni, te: (ti, 0)),
            pl.BlockSpec((1, D, bn), lambda ti, ni, te: (te[ti], 0, ni)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda ti, ni, te: (ti, ni)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, F), x.dtype),
        interpret=interpret,
    )(tile_expert, x, w)


def pad_groups(x, group_sizes, *, bt: int = 128):
    """Re-layout rows (already sorted by group) so every group occupies a
    whole number of (bt)-row tiles. Returns (x_padded, tile_expert,
    row_index) where row_index[i] gives the padded position of source row i
    (for scattering results back)."""
    T, D = x.shape
    E = group_sizes.shape[0]
    padded_sizes = ((group_sizes + bt - 1) // bt) * bt
    starts_src = jnp.cumsum(group_sizes) - group_sizes
    starts_dst = jnp.cumsum(padded_sizes) - padded_sizes
    total = int(jnp.sum(padded_sizes))  # static only under concrete sizes
    # position of each source row within its group
    row_group = jnp.repeat(jnp.arange(E), group_sizes, total_repeat_length=T)
    within = jnp.arange(T) - starts_src[row_group]
    row_index = starts_dst[row_group] + within
    xp = jnp.zeros((total, D), x.dtype).at[row_index].set(x)
    tile_expert = jnp.repeat(jnp.arange(E), padded_sizes // bt,
                             total_repeat_length=total // bt).astype(jnp.int32)
    return xp, tile_expert, row_index
