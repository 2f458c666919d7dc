"""The chunked LM head and cross-entropy (``train/steps.py:lm_loss``), whose
own backward computes each chunk's gradients in the forward scan, against
``jax.grad`` of a plain unchunked float32 loss: the value, and the
gradients with respect to the hidden states, the unembedding and the final
norm's scale."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import schema as S
from repro.models.layers import norm
from repro.train.steps import lm_loss

B, T = 2, 24


def _reference(cfg, params, hidden, labels):
    """Mean cross-entropy over all positions at once, in float32."""
    p = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    h = norm(cfg, p, hidden.astype(jnp.float32), prefix="final_norm")
    logits = jnp.matmul(h, p["unembed"], precision="highest")
    if cfg.final_softcap:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    logits = jnp.where(jnp.arange(logits.shape[-1]) < cfg.vocab_size,
                       logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def _inputs(cfg, slots, model_shards, key):
    k_p, k_h, k_l = jax.random.split(key, 3)
    params = S.init_params(cfg, k_p, model_shards=model_shards)
    # a final norm scale away from 1, so its gradient is not trivial
    params["final_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
        k_p, params["final_norm_scale"].shape)
    shape = (slots, B, T) if slots else (B, T)
    hidden = jax.random.normal(k_h, shape + (cfg.d_model,))
    labels = jax.random.randint(k_l, shape, 0, cfg.vocab_size)
    return params, hidden, labels


def _value_and_grads(loss):
    """(head, params, hidden, labels) -> (loss, (d head, d hidden)), the
    head being the unembedding and the final norm's scale."""
    def f(head, params, hidden, labels):
        return loss(dict(params, **head), hidden, labels)
    return jax.value_and_grad(f, argnums=(0, 2))


CASES = {
    "chunk_divides_seq": dict(arch="yi-9b", chunk=8),
    "chunk_is_seq": dict(arch="yi-9b", chunk=T),
    "ragged_tail": dict(arch="yi-9b", chunk=7),
    "final_softcap": dict(arch="gemma2-2b", chunk=8),
    "padded_vocab": dict(arch="yi-9b", chunk=7, vocab=509, shards=4),
    "vmap_two_slots_jit": dict(arch="gemma2-2b", chunk=7, slots=2),
    "bfloat16_weights": dict(arch="yi-9b", chunk=8, dtype=jnp.bfloat16),
    "cotangent_scaled": dict(arch="gemma2-2b", chunk=7, weight=2.5),
    "value_only": dict(arch="gemma2-2b", chunk=7, vocab=509, shards=4,
                       grads=False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_lm_loss_matches_unchunked_reference(case):
    c = CASES[case]
    cfg = get_config(c["arch"]).reduced()
    if "vocab" in c:
        cfg = dataclasses.replace(cfg, vocab_size=c["vocab"])
    params, hidden, labels = _inputs(cfg, c.get("slots", 0),
                                     c.get("shards", 1), jax.random.PRNGKey(7))
    if "dtype" in c:
        params = jax.tree.map(lambda x: x.astype(c["dtype"]), params)
        hidden = hidden.astype(c["dtype"])
    padded = params["unembed"].shape[-1] != cfg.vocab_size
    assert padded == ("vocab" in c)
    weight = c.get("weight", 1.0)  # the cotangent the backward gets
    program = lambda p, h, y: weight * lm_loss(cfg, p, h, y, c["chunk"])
    reference = lambda p, h, y: weight * _reference(cfg, p, h, y)
    head = {k: params[k] for k in ("unembed", "final_norm_scale")}

    if not c.get("grads", True):
        value = jax.jit(program)(params, hidden, labels)
        want = reference(params, hidden, labels)
        np.testing.assert_allclose(float(value), float(want), rtol=1e-5)
        got_vg, _ = jax.jit(_value_and_grads(program))(head, params, hidden,
                                                        labels)
        np.testing.assert_allclose(float(got_vg), float(value), rtol=1e-6)
        return

    got = _value_and_grads(program)
    want = _value_and_grads(reference)
    if c.get("slots"):
        got = jax.jit(jax.vmap(got, in_axes=(None, None, 0, 0)))
        want = jax.vmap(want, in_axes=(None, None, 0, 0))
    (v, (dhead, dh)) = got(head, params, hidden, labels)
    (rv, (rhead, rdh)) = want(head, params, hidden, labels)
    bf16 = "dtype" in c
    np.testing.assert_allclose(np.asarray(v, np.float32),
                               np.asarray(rv, np.float32),
                               rtol=1e-3 if bf16 else 1e-5)
    pairs = {"hidden": (dh, rdh), "unembed": (dhead["unembed"],
                                              rhead["unembed"]),
             "final_norm_scale": (dhead["final_norm_scale"],
                                  rhead["final_norm_scale"])}
    for name, (a, b) in pairs.items():
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        gap = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert gap < (1e-2 if bf16 else 1e-5), (name, gap)
