"""Public grouped-matmul entry: ragged rows in, ragged rows out."""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.gmm.gmm import gmm_pallas, pad_groups
from repro.kernels.gmm.ref import gmm_ref


def gmm(x, w, group_sizes, use_pallas: bool = True,
        interpret: bool = False, bt: int = 128):
    """x: (T, D) rows sorted by expert; w: (E, D, F); group_sizes: (E,).

    The Pallas path requires CONCRETE group sizes (it re-tiles the rows) and
    is the TPU-target kernel; inside jitted production code the ref
    (ragged_dot) path is used on CPU.
    """
    if not use_pallas:
        return gmm_ref(x, w, group_sizes)
    bt = min(bt, max(1, x.shape[0]))
    xp, tile_expert, row_index = pad_groups(x, group_sizes, bt=bt)
    out = gmm_pallas(xp, w, tile_expert, bt=bt, interpret=interpret)
    return out[row_index]
