"""jit'd public entry point for the fused RMSNorm kernel."""
from functools import partial

import jax

from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@partial(jax.jit, static_argnames=("eps", "use_pallas", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, use_pallas: bool = True,
            interpret: bool = False):
    if use_pallas:
        return rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)
    return rmsnorm_ref(x, scale, eps)
