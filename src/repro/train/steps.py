"""pjit-able train / prefill / serve steps for every zoo architecture.

The LM loss chunks over the sequence so (B, S, V) logits never materialize:
per chunk, float32 logits are computed against the vocab-sharded unembedding
and reduced with a logsumexp (SPMD inserts the partial-max/sum collectives).
The head and loss have their own backward (``jax.custom_vjp``): the forward
scan computes each chunk's gradients while its logits are at hand, so no
logits cross to the backward and there is no reverse loop over the chunks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.layers import norm
from repro.models.model import forward, logits_fn
from repro.optim.optimizers import OptState, apply_updates


def _xent_terms(cfg: ModelConfig, w, h, labels):
    """One chunk's float32 logits (softcapped, vocab padding masked), the
    softcap's tanh (None without one), logsumexp, the gold mask and the
    chunk's summed cross-entropy. h: (B, C, D), labels: (B, C)."""
    logits = jnp.matmul(h, w, preferred_element_type=jnp.float32)
    t = None
    if cfg.final_softcap:
        t = jnp.tanh(logits / cfg.final_softcap)
        logits = t * cfg.final_softcap
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    if logits.shape[-1] != cfg.vocab_size:  # mask vocab-padding columns
        logits = jnp.where(cols < cfg.vocab_size, logits, -1e30)
    top = jnp.max(logits, axis=-1, keepdims=True)
    lse = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
    # the gold logit by compare, not gather: no scatter in any derivative
    gold = labels[..., None] == cols
    xent = jnp.sum(lse - jnp.sum(jnp.where(gold, logits, 0.0), axis=-1))
    return logits, t, lse, gold, xent


def _xent_grads(cfg: ModelConfig, w, h, labels, scale):
    """One chunk's summed cross-entropy and the gradients of ``scale`` times
    it: dh (B, C, D) in h's dtype, dW (D, V) in float32."""
    logits, t, lse, gold, xent = _xent_terms(cfg, w, h, labels)
    p = jnp.exp(logits - lse[..., None])
    dz = jnp.where(gold, p - 1.0, p) * scale  # 0 on padded columns
    if t is not None:
        dz = dz * (1.0 - t * t)
    dl = dz.astype(jnp.result_type(h, w))
    dh = jnp.matmul(dl, w.T, preferred_element_type=jnp.float32)
    dw = jnp.einsum("bcd,bcv->dv", h, dl, preferred_element_type=jnp.float32)
    return xent, dh.astype(h.dtype), dw


def _chunked_xent(cfg: ModelConfig, chunk: int, h, w, labels, grads: bool):
    """Mean cross-entropy of ``h @ w`` over chunks of the sequence, the
    ragged tail as one more chunk; with ``grads`` also its gradients
    with respect to h and w, computed chunk by chunk in the same scan."""
    B, S, D = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    hc = h[:, : n * chunk].reshape(B, n, chunk, D).swapaxes(0, 1)
    lc = labels[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    tail = (h[:, n * chunk:], labels[:, n * chunk:]) if S % chunk else None
    zero = jnp.zeros((), jnp.float32)
    if not grads:
        def body(tot, xs):
            return tot + _xent_terms(cfg, w, *xs)[-1], None

        tot, _ = jax.lax.scan(body, zero, (hc, lc))
        if tail:
            tot = tot + _xent_terms(cfg, w, *tail)[-1]
        return tot / (B * S)

    scale = 1.0 / (B * S)

    def body(carry, xs):
        tot, dw = carry
        xent, dh, dwc = _xent_grads(cfg, w, *xs, scale)
        return (tot + xent, (dw + dwc).astype(w.dtype)), dh

    (tot, dw), dh = jax.lax.scan(body, (zero, jnp.zeros(w.shape, w.dtype)),
                                 (hc, lc))
    dh = dh.swapaxes(0, 1).reshape(B, n * chunk, D)
    if tail:
        xent, dht, dwt = _xent_grads(cfg, w, *tail, scale)
        tot, dw = tot + xent, (dw + dwt).astype(w.dtype)
        dh = jnp.concatenate([dh, dht], axis=1)
    return tot / (B * S), dh, dw


def _linear_xent(cfg: ModelConfig, chunk: int):
    """(h, w, labels) -> mean cross-entropy of ``h @ w``, with its own
    backward: the forward scan computes each chunk's gradients while its
    logits are at hand, so only dh and dW cross to the backward, which
    scales them by the cotangent. A call that is not differentiated runs
    the loss alone."""

    @jax.custom_vjp
    def xent(h, w, labels):
        with jax.named_scope("loss.xent"):
            return _chunked_xent(cfg, chunk, h, w, labels, grads=False)

    def fwd(h, w, labels):
        with jax.named_scope("loss.xent"):
            loss, dh, dw = _chunked_xent(cfg, chunk, h, w, labels,
                                         grads=True)
        return loss, (dh, dw)

    def bwd(res, g):
        with jax.named_scope("loss.xent"):
            return tuple((x.astype(jnp.float32) * g).astype(x.dtype)
                         for x in res) + (None,)

    xent.defvjp(fwd, bwd)
    return xent


def lm_loss(cfg: ModelConfig, params, hidden, labels, chunk: int = 1024):
    """Chunked cross-entropy. hidden: (B, S, D); labels: (B, S). The final
    norm's gradient comes from autodiff through the head's dh."""
    h = norm(cfg, params, hidden, prefix="final_norm")
    return _linear_xent(cfg, chunk)(h, params["unembed"], labels)


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, tc: TrainConfig, mesh=None,
                    unroll: bool = False):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(params, batch):
        h, _, aux = forward(cfg, params, batch, mode="train", mesh=mesh,
                            remat=tc.remat, unroll=unroll)
        labels = batch["labels"]
        if cfg.family == "vlm" and "image_embeds" in batch:
            h = h[:, batch["image_embeds"].shape[1]:]  # text positions only
        loss = lm_loss(cfg, params, h, labels, tc.loss_chunk)
        return loss + aux, (loss, aux)

    def train_step(params, opt_state: OptState, batch):
        grads, (loss, aux) = jax.grad(loss_fn, has_aux=True)(params, batch)
        params, opt_state, gnorm = apply_updates(tc, params, grads, opt_state)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh=None, window_override: int = 0,
                      unroll: bool = False):
    """(params, batch, cache) -> (next_token_logits, cache)."""

    def prefill_step(params, batch, cache):
        h, cache, _ = forward(cfg, params, batch, mode="prefill", cache=cache,
                              mesh=mesh, window_override=window_override,
                              unroll=unroll)
        logits = logits_fn(cfg, params, h[:, -1:])[:, 0]
        return logits, cache

    return prefill_step


def make_serve_step(cfg: ModelConfig, mesh=None, window_override: int = 0,
                    unroll: bool = False):
    """One decode step: (params, cache, token (B,1), pos) -> (logits, cache)."""

    def serve_step(params, cache, token, pos):
        h, cache, _ = forward(cfg, params, {"tokens": token}, mode="decode",
                              pos=pos, cache=cache, mesh=mesh,
                              window_override=window_override, unroll=unroll)
        logits = logits_fn(cfg, params, h)[:, 0]
        return logits, cache

    return serve_step
