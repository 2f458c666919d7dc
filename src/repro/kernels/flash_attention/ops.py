"""jit'd public entry point for flash attention in model layout (B,S,H,hd)."""
from functools import partial

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import chunked_attention


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "q_offset",
                                   "use_pallas", "interpret", "bq", "bk"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    use_pallas: bool = True, interpret: bool = False,
                    bq: int = 128, bk: int = 128):
    """q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd)."""
    if not use_pallas:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset)
    out = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, bq=bq, bk=bk,
        interpret=interpret)
    return out.transpose(0, 2, 1, 3)
