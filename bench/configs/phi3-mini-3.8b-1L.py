"""Plain reference of ``phi3-mini-3.8b-1L``: one fine-tuning trial's first
AdamW updates of a Phi-3-mini decoder cut to its first layer, from
nothing but the configuration and the seed.

The model follows the published Phi-3-mini description (arXiv:2404.14219
and its config): token embedding; per layer a pre-norm block of RMSNorm,
multi-head causal self-attention with rotary embeddings (rotate-half, the
config's theta) and an output projection, then RMSNorm and a SwiGLU MLP
(``silu(x W_gate) * (x W_up)``, then ``W_down``), each added to the
residual; a final RMSNorm and an untied LM head; mean token cross
entropy. Departures from the published model are those of the program,
stated in the configuration file: RMSNorm eps 1e-6 and full causal
attention. Weights are stored in the configuration's bfloat16, as the
program stores them; every computation here is float32 at the highest
matmul precision, and AdamW runs in float32 on the stored weights.

The inputs follow the same documented derivation from the seed as the
trial's: the trial key ``seed + hash(sorted hparams) % 10000`` split into
weight and data keys, weights drawn leaf by leaf in the sorted order of
their names (normal, scaled by 1/sqrt(fan-in), the embedding by 1, norms
at one), and each update's tokens a walk of the seeded bigram table
(``numpy default_rng(data_seed)``, 8 successors a token) from a random
start.

Variants, for the check of the check:

* ``control``: weights stored in float8 e4m3 and every matmul operand
  rounded to it in the forward pass, the step below the configuration's
  bfloat16 (gradients pass the rounding in float32);
* ``half_batch``: the loss taken over the first half of the positions.

``readings`` returns squared norms per leaf (initial weights, the first
clipped gradient, the weights' change after ``steps`` updates) and each
update's loss.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def trial_key(seed: int, hparams: dict):
    return jax.random.PRNGKey(seed + hash(str(sorted(hparams.items())))
                              % 10_000)


def leaf_shapes(cfg: dict):
    """Leaves in the sorted order of their names: (name, shape, scale);
    scale 0 means ones."""
    L, d, f = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    v = cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    fan = lambda n: 1.0 / math.sqrt(n)
    return [
        ("dec/b0_attn/norm_scale", (L, d), 0.0),
        ("dec/b0_attn/wk", (L, d, kv), fan(d)),
        ("dec/b0_attn/wo", (L, q, d), fan(q)),
        ("dec/b0_attn/wq", (L, d, q), fan(d)),
        ("dec/b0_attn/wv", (L, d, kv), fan(d)),
        ("dec/b0_mlp/norm_scale", (L, d), 0.0),
        ("dec/b0_mlp/w_down", (L, f, d), fan(f)),
        ("dec/b0_mlp/w_gate", (L, d, f), fan(d)),
        ("dec/b0_mlp/w_up", (L, d, f), fan(d)),
        ("embed", (v, d), 1.0),
        ("final_norm_scale", (d,), 0.0),
        ("unembed", (d, v), fan(d)),
    ]


def init_params(cfg: dict, key, dtype):
    leaves = leaf_shapes(cfg)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for (name, shape, scale), k in zip(leaves, keys):
        if scale == 0.0:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (scale * jax.random.normal(k, shape)).astype(dtype)
    return out


def tokens(cfg: dict, traffic: dict, key):
    """One update's (batch, seq + 1) tokens and the next data key."""
    B, S, V = int(traffic["batch"]), int(traffic["seq"]), cfg["vocab_size"]
    table = np.random.default_rng(cfg["data_seed"]).integers(
        0, V, size=(V, 8)).astype(np.int32)
    key, k_start, k_choice = jax.random.split(key, 3)
    start = np.asarray(jax.random.randint(k_start, (B,), 0, V))
    choice = np.asarray(jax.random.randint(k_choice, (S, B), 0, 8))
    chain = np.empty((B, S + 1), np.int32)
    chain[:, 0] = start
    for t in range(S):
        chain[:, t + 1] = table[chain[:, t], choice[t]]
    return chain, key


def _mm(a, b, q):
    return jnp.matmul(q(a), q(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(cfg: dict, q, used: int, p, chain):
    """Mean cross entropy of one sequence's first ``used`` positions."""
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    hd = d // H
    ids, labels = chain[:-1], chain[1:]
    x = p["embed"][ids]
    S = x.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for layer in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, p["dec/b0_attn/norm_scale"][layer], eps)
        qh = rotary(_mm(h, p["dec/b0_attn/wq"][layer], q).reshape(S, H, hd),
                    cfg["rope_theta"])
        kh = rotary(_mm(h, p["dec/b0_attn/wk"][layer], q).reshape(S, -1, hd),
                    cfg["rope_theta"])
        vh = _mm(h, p["dec/b0_attn/wv"][layer], q).reshape(S, -1, hd)
        kh = jnp.repeat(kh, H // kh.shape[1], axis=1)
        vh = jnp.repeat(vh, H // vh.shape[1], axis=1)
        scores = jnp.einsum("shd,thd->hst", q(qh), q(kh),
                            precision=HIGHEST) / math.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hst,thd->shd", q(jax.nn.softmax(scores, -1)),
                         q(vh), precision=HIGHEST)
        x = x + _mm(att.reshape(S, H * hd), p["dec/b0_attn/wo"][layer], q)
        h = rms_norm(x, p["dec/b0_mlp/norm_scale"][layer], eps)
        gate = jax.nn.silu(_mm(h, p["dec/b0_mlp/w_gate"][layer], q))
        up = _mm(h, p["dec/b0_mlp/w_up"][layer], q)
        x = x + _mm(gate * up, p["dec/b0_mlp/w_down"][layer], q)
    h = rms_norm(x, p["final_norm_scale"], eps)
    logits = _mm(h[:used], p["unembed"], q)
    gold = jnp.take_along_axis(logits, labels[:used, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def fp8(a):
    """``a`` rounded to float8 e4m3 in the forward pass; the gradient
    passes through in float32, so the backward does not underflow."""
    return a + jax.lax.stop_gradient(
        a.astype(jnp.float8_e4m3fn).astype(a.dtype) - a)


def _grad(cfg, variant, stored, chain):
    q = fp8 if variant == "control" else (lambda a: a)
    used = chain.shape[-1] - 1
    if variant == "half_batch":
        used //= 2
    p = {k: v.astype(jnp.float32) for k, v in stored.items()}

    def batch_loss(p):
        return jnp.mean(jnp.stack([loss_fn(cfg, q, used, p, c)
                                   for c in chain]))
    return jax.value_and_grad(batch_loss)(p)


def _adam(cfg, dtype, stored, m, v, grads, step, lr, clip, warmup):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    grads = {k: g * scale for k, g in grads.items()}
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    rate = lr * jnp.minimum(1.0, (step + 1) / jnp.maximum(warmup, 1.0))
    t = step + 1.0
    m = {k: b1 * m[k] + (1 - b1) * g for k, g in grads.items()}
    v = {k: b2 * v[k] + (1 - b2) * g * g for k, g in grads.items()}
    new = {k: (stored[k].astype(jnp.float32)
               - rate * (m[k] / (1 - b1 ** t))
               / (jnp.sqrt(v[k] / (1 - b2 ** t)) + cfg["adam_eps"])
               ).astype(dtype) for k in stored}
    return new, m, v, grads


def _sq(tree):
    return {k: jnp.sum(jnp.square(x.astype(jnp.float32)))
            for k, x in tree.items()}


_CACHE: dict = {}


def _programs(cfg: dict, variant: str):
    key = (cfg["name"], variant)
    if key not in _CACHE:
        dtype = jnp.float8_e4m3fn if variant == "control" \
            else jnp.dtype(cfg["torch_dtype"])
        _CACHE[key] = (
            dtype,
            jax.jit(partial(init_params, cfg), static_argnums=1),
            jax.jit(partial(_grad, cfg, variant)),
            jax.jit(partial(_adam, cfg, dtype), donate_argnums=(0, 1, 2)),
            jax.jit(_sq),
            jax.jit(lambda a, b: _sq({k: a[k].astype(jnp.float32)
                                      - b[k].astype(jnp.float32)
                                      for k in a})))
    return _CACHE[key]


def readings(config: dict, traffic: dict, seed: int, hparams: dict, *,
             steps: int = 3, variant: str = "reference") -> dict:
    dtype, init, grad, adam, sq, dsq = _programs(config, variant)
    k_params, k_data = jax.random.split(trial_key(seed, hparams))
    stored = init(k_params, dtype)
    out = {"init": sq(stored), "loss": []}
    start = {k: x.astype(jnp.float32) for k, x in stored.items()}
    m = {k: jnp.zeros(x.shape, jnp.float32) for k, x in stored.items()}
    v = {k: jnp.zeros(x.shape, jnp.float32) for k, x in stored.items()}
    lr = jnp.float32(hparams["learning_rate"])
    clip = jnp.float32(hparams.get("grad_clip", 1.0))
    warmup = jnp.float32(hparams.get("warmup_steps", 1.0))
    for step in range(steps):
        chain, k_data = tokens(config, traffic, k_data)
        loss, grads = grad(stored, jnp.asarray(chain))
        out["loss"].append(loss)
        stored, m, v, clipped = adam(stored, m, v, grads, jnp.float32(step),
                                     lr, clip, warmup)
        if step == 0:
            out["grad1"] = sq(clipped)
        del grads, clipped
    out["dparam"] = dsq(stored, start)
    out = jax.device_get(out)
    return {"init": {k: float(x) for k, x in out["init"].items()},
            "grad1": {k: float(x) for k, x in out["grad1"].items()},
            "dparam": {k: float(x) for k, x in out["dparam"].items()},
            "loss": [float(x) for x in out["loss"]]}
