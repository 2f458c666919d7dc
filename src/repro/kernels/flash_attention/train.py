"""Training entry for flash attention in model layout (B, S, H, hd).

Wraps JAX's Pallas TPU flash attention
(``jax.experimental.pallas.ops.tpu.flash_attention``): a forward kernel
and a ``custom_vjp`` whose backward is two more kernels (dK/dV, dQ).
Operands keep their dtype; scores, softmax statistics and accumulators
are float32; key blocks that causality masks out entirely are skipped.

Block sizes come from the sequence length: a table of sizes swept on a
TPU v5e (attention alone, forward and backward, vmapped over 2 slots at
32 heads of 96), and otherwise the largest of 512, 256, 128 that
divides the sequence.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas.ops.tpu.flash_attention import (
    BlockSizes, flash_attention)

# seq -> ((block_q, block_k) of the forward, of dK/dV, of dQ)
_SWEPT = {
    2048: ((1024, 1024), (512, 1024), (1024, 512)),
}


def block_sizes(fwd, dkv, dq) -> BlockSizes:
    """The library's blocks from (block_q, block_k) of each kernel."""
    return BlockSizes(
        block_q=fwd[0], block_k_major=fwd[1], block_k=fwd[1], block_b=1,
        block_q_major_dkv=dkv[0], block_k_major_dkv=dkv[1],
        block_q_dkv=dkv[0], block_k_dkv=dkv[1],
        block_k_major_dq=dq[1], block_k_dq=dq[1], block_q_dq=dq[0])


def train_block_sizes(seq: int) -> Optional[BlockSizes]:
    """The kernels' blocks for a sequence of ``seq`` tokens, or None where
    no block of 128 or more divides it."""
    if seq in _SWEPT:
        return block_sizes(*_SWEPT[seq])
    for b in (512, 256, 128):
        if seq % b == 0:
            return block_sizes((b, b), (b, b), (b, b))
    return None


def flash_attention_train(q: jax.Array, k: jax.Array, v: jax.Array,
                          blocks: BlockSizes) -> jax.Array:
    """Causal self-attention, q, k, v: (B, S, H, hd) -> (B, S, H, hd),
    scaled by ``hd ** -0.5``; differentiable."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    out = flash_attention(t(q), t(k), t(v), causal=True,
                          sm_scale=q.shape[-1] ** -0.5,
                          block_sizes=blocks)
    return t(out)
