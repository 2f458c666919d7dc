"""jit'd public entry point for the selective-scan kernel."""
from functools import partial

import jax

from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.selective_scan.selective_scan import selective_scan_pallas


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bd", "bs"))
def selective_scan(u, dt, a, b, c, d_skip, h0, use_pallas: bool = True,
                   interpret: bool = False, bd: int = 256, bs: int = 64):
    if use_pallas:
        return selective_scan_pallas(u, dt, a, b, c, d_skip, h0, bd=bd,
                                     bs=bs, interpret=interpret)
    return selective_scan_ref(u, dt, a, b, c, d_skip, h0)
