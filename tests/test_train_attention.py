"""The training forward's attention: which calls take the fused Pallas
kernel, the kernel's parity with the chunked scan (interpret mode), and
that a CPU lowering holds no kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.train import (flash_attention_train,
                                                 train_block_sizes)
from repro.models.attention import (chunked_attention, fused_attention,
                                    fused_block_sizes, kernel_width,
                                    train_attention)

MHA = dict(S=256, Skv=256, Hq=4, Hkv=4, hd=96, causal=True, window=0,
           softcap=0.0, q_offset=0, mesh=None)

# (case, overrides of MHA, takes the fused kernel)
DISPATCH = [
    ("mha_causal_train", {}, True),
    ("window_covers_seq", {"window": 256}, True),
    ("hd_128", {"hd": 128}, True),
    ("hd_256", {"hd": 256}, True),
    ("softcap", {"softcap": 50.0}, False),
    ("window_below_seq", {"window": 128}, False),
    ("gqa", {"Hkv": 2}, False),
    ("decode", {"S": 1, "q_offset": 255}, False),
    ("prefill_at_offset", {"q_offset": 128}, False),
    ("traced_offset", {"q_offset": jnp.int32(0)}, False),
    ("cross_attention", {"Skv": 512, "causal": False}, False),
    ("not_causal", {"causal": False}, False),
    ("seq_not_a_block_multiple", {"S": 320, "Skv": 320}, False),
    ("seq_below_a_block", {"S": 32, "Skv": 32}, False),
    ("hd_160", {"hd": 160}, True),
    ("under_a_mesh", {"mesh": "mesh"}, False),
]


@pytest.mark.parametrize("case,over,fused", DISPATCH,
                         ids=[c[0] for c in DISPATCH])
def test_dispatch_rule(case, over, fused):
    a = {**MHA, **over}
    q = jax.ShapeDtypeStruct((1, a["S"], a["Hq"], a["hd"]), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, a["Skv"], a["Hkv"], a["hd"]), jnp.bfloat16)
    kw = {k: a[k] for k in ("causal", "window", "softcap", "q_offset")}
    assert (fused_block_sizes(q.shape, kv.shape, mesh=a["mesh"], **kw)
            is not None) == fused
    if a["mesh"] is None:
        # the traced program holds the kernel exactly where the rule says
        jaxpr = jax.make_jaxpr(lambda q, k, v: train_attention(
            q, k, v, **kw))(q, kv, kv)
        assert ("pallas_call" in str(jaxpr)) == fused


def test_swept_blocks_divide_their_sequence():
    for seq in (256, 384, 1024, 2048, 4096):
        b = train_block_sizes(seq)
        for blk in (b.block_q, b.block_k_major, b.block_q_dkv,
                    b.block_k_dkv, b.block_q_dq, b.block_k_dq):
            assert seq % blk == 0 and blk <= seq, (seq, blk)


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_fused_entry_matches_chunked_attention_interpret():
    """Output and q/k/v gradients against the chunked scan. Two slots
    folded into the batch axis: the interpreter cannot run under vmap."""
    B, S, H, hd = 2, 256, 2, 96
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.bfloat16)
               for _ in range(3))
    ct = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    blocks = train_block_sizes(128)           # blocks of 128: a 2 x 2 grid

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       * ct)

    fused = lambda q, k, v: flash_attention_train(q, k, v, blocks)
    chunked = lambda q, k, v: chunked_attention(q, k, v, chunk=128)
    with pltpu.force_tpu_interpret_mode():
        out = fused(q, k, v)
        grads = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    ref = chunked(q, k, v)
    ref_grads = jax.grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert _rel(out, ref) < 1e-2
    for name, g, r in zip("qkv", grads, ref_grads):
        assert _rel(g, r) < 1e-2, name


@pytest.mark.parametrize("dqk,dv,width", [(96, 96, 96), (128, 128, 128),
                                          (192, 128, 256), (64, 32, 64),
                                          (160, 160, 256), (256, 256, 256)])
def test_kernel_width(dqk, dv, width):
    """One width for q, k and v, at most 128 or a multiple of 128; a
    width the kernel takes as it is is not padded (Phi-3's 96)."""
    assert kernel_width(dqk, dv) == width


def test_padded_kernel_matches_chunked_attention_at_mla_widths():
    """Latent attention's heads (q/k 192 = 128 + 64 RoPE, v 128) through
    the kernel padded to 256 with the scale 1/sqrt(192) given explicitly,
    output and gradients against the chunked scan at the native widths
    (interpret mode)."""
    B, S, H, dqk, dv = 2, 256, 2, 192, 128
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.standard_normal((B, S, H, dqk)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, S, H, dv)), jnp.bfloat16)
    ct = jnp.asarray(rng.standard_normal((B, S, H, dv)), jnp.float32)
    blocks = train_block_sizes(128)
    scale = dqk ** -0.5

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       * ct)

    fused = lambda q, k, v: fused_attention(q, k, v, blocks, scale)
    chunked = lambda q, k, v: chunked_attention(q, k, v, chunk=128,
                                                scale=scale)
    with pltpu.force_tpu_interpret_mode():
        out = fused(q, k, v)
        grads = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    ref = chunked(q, k, v)
    ref_grads = jax.grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
    assert out.shape == ref.shape == (B, S, H, dv)
    assert _rel(out, ref) < 1e-2
    for name, g, r in zip("qkv", grads, ref_grads):
        assert g.shape == r.shape
        assert _rel(g, r) < 1e-2, name


def test_cpu_lm_bucket_step_lowers_no_kernel():
    """At a sequence the fused rule accepts, the LM bucket step traces
    the kernel's branch but lowers for the CPU without it."""
    from repro.population.engine import _bucket_step
    from repro.population.objectives.lm import LMObjective

    class MHA(LMObjective):
        def __init__(self):
            super().__init__("phi3-mini-3.8b", batch=1, seq=256)
            self.cfg = dataclasses.replace(self.cfg,
                                           n_kv_heads=self.cfg.n_heads)

        def cache_key(self):
            return ("lm", self.cfg, self.batch, self.seq)

    obj = MHA()
    slot = jax.eval_shape(obj.init_slot_state, jax.random.PRNGKey(0), {})
    stack = lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype)
    learner, carry = (jax.tree.map(stack, t) for t in slot)
    hyper = [jax.ShapeDtypeStruct((2,), jnp.float32)] * 3
    args = (learner, carry, *hyper, jax.ShapeDtypeStruct((2,), jnp.bool_))
    step = _bucket_step(obj, 256, 2)
    assert "pallas_call" in str(jax.make_jaxpr(step)(*args))
    assert "tpu_custom_call" not in step.lower(*args).as_text()
