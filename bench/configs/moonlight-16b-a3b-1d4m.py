"""Plain reference of ``moonlight-16b-a3b-1d4m``: one fine-tuning trial's
first AdamW updates of Moonlight-16B-A3B cut to its leading dense layer and
four MoE layers, with one chip's share of the experts and the vocabulary,
from nothing but the configuration and the seed.

The model follows the published DeepSeek-V3 description (arXiv:2412.19437)
at Moonlight's config: token embedding; per layer a pre-norm block of
RMSNorm and latent attention, then RMSNorm and a feed-forward part, each
added to the residual; a final RMSNorm and an untied LM head; mean token
cross entropy. Latent attention, with no query latent, per token:

  q = h W_q, split into q_nope (qk_nope_head_dim) and q_pe (qk_rope_head_dim)
  per head; [c_kv, k_pe] = h W_kv_a; c_kv through RMSNorm; [k_nope, v] =
  c_kv W_kv_b per head; k = [k_nope, RoPE(k_pe)] with one RoPE head shared
  by every head; q = [q_nope, RoPE(q_pe)]; softmax scale
  (qk_nope_head_dim + qk_rope_head_dim) ** -0.5; the heads' outputs
  through W_o.

It is computed here at its native widths, head by head. The first
``first_k_dense_replace`` layers have a SwiGLU MLP of
``intermediate_size``. The others are MoE layers: sigmoid scores of a
router over all ``published.n_routed_experts`` experts; the
``num_experts_per_tok`` largest of score + selection bias chosen; gates the
chosen unbiased scores, normed to sum to one, times
``routed_scaling_factor``; the output is the held experts' part, each held
expert a SwiGLU MLP of ``moe_intermediate_size`` applied to every token and
weighted by that token's gate for it (zero where it was not chosen), plus
the shared experts, one SwiGLU MLP of ``n_shared_experts`` x
``moe_intermediate_size`` that every token takes. This chip holds the
``n_routed_experts`` experts from ``expert_offset``; what the experts held
elsewhere would add is left out, as the deployment's share would leave it
out before its exchange.

Departures from the published model are those the configuration states:
RoPE in the rotate-half layout, the selection bias at its initial zero and
no auxiliary loss. Weights are stored in the configuration's bfloat16, as
the program stores them; every computation here is float32 at the highest
matmul precision, and AdamW runs in float32 on the stored weights. So that
it fits one chip: each layer is recomputed in the backward, and attention
runs one head at a time.

The inputs follow the same documented derivation from the seed as the
trial's: the trial key ``seed + hash(sorted hparams) % 10000`` split into
weight and data keys, weights drawn leaf by leaf in the sorted order of
their names (normal, scaled by 1/sqrt(fan-in), the embedding by 1, norms
at one, the selection bias at zero), and each update's tokens a walk of the
seeded bigram table (``numpy default_rng(data_seed)``, 8 successors a
token) from a random start.

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


def _widths(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def leaf_shapes(cfg: dict):
    """Leaves in the sorted order of their names: (name, shape, scale);
    scale 0 means ones, None zeros."""
    d, H, r, dn, dr, dv = _widths(cfg)
    dense = cfg["first_k_dense_replace"]
    L = cfg["num_hidden_layers"] - dense
    E, E_all = cfg["n_routed_experts"], cfg["published"]["n_routed_experts"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    v = cfg["vocab_size"]
    fan = lambda n: 1.0 / math.sqrt(n)

    def mla(pre, n):
        return [(f"{pre}/kv_norm_scale", (n, r), 0.0),
                (f"{pre}/norm_scale", (n, d), 0.0),
                (f"{pre}/wkv_a", (n, d, r + dr), fan(d)),
                (f"{pre}/wkv_b", (n, r, H * (dn + dv)), fan(r)),
                (f"{pre}/wo", (n, H * dv, d), fan(H * dv)),
                (f"{pre}/wq", (n, d, H * (dn + dr)), fan(d))]

    return (mla("dec/b0_mla", L) + [
        ("dec/b0_moe/norm_scale", (L, d), 0.0),
        ("dec/b0_moe/router", (L, d, E_all), fan(d)),
        ("dec/b0_moe/router_bias", (L, E_all), None),
        ("dec/b0_moe/we_down", (L, E, f, d), fan(f)),
        ("dec/b0_moe/we_gate", (L, E, d, f), fan(d)),
        ("dec/b0_moe/we_up", (L, E, d, f), fan(d)),
        ("dec/b0_moe/ws_down", (L, fs, d), fan(fs)),
        ("dec/b0_moe/ws_gate", (L, d, fs), fan(d)),
        ("dec/b0_moe/ws_up", (L, d, fs), fan(d)),
        ("embed", (v, d), 1.0),
        ("final_norm_scale", (d,), 0.0),
    ] + mla("first/b0_mla", dense) + [
        ("first/b0_mlp/norm_scale", (dense, d), 0.0),
        ("first/b0_mlp/w_down", (dense, fd, d), fan(fd)),
        ("first/b0_mlp/w_gate", (dense, d, fd), fan(d)),
        ("first/b0_mlp/w_up", (dense, d, fd), fan(d)),
        ("unembed", (d, v), fan(d)),
    ])


def init_params(cfg: dict, key, dtype):
    leaves = leaf_shapes(cfg)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for (name, shape, scale), k in zip(leaves, keys):
        if scale is None:
            out[name] = jnp.zeros(shape, dtype)
        elif scale == 0.0:
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
    """x (S, H, d): rotate-half rotary embedding at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(h, w_gate, w_up, w_down, q):
    return _mm(jax.nn.silu(_mm(h, w_gate, q)) * _mm(h, w_up, q), w_down, q)


def latent_attention(cfg: dict, q, w: dict, x):
    """x + latent attention of x (S, d); ``w`` one layer's leaves."""
    d, H, r, dn, dr, dv = _widths(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = x.shape[0]
    h = rms_norm(x, w["norm_scale"], eps)
    qh = _mm(h, w["wq"], q).reshape(S, H, dn + dr)
    kv_a = _mm(h, w["wkv_a"], q)
    c_kv = rms_norm(kv_a[:, :r], w["kv_norm_scale"], eps)
    k_pe = rotary(kv_a[:, None, r:], theta)[:, 0]             # (S, dr)
    kv = _mm(c_kv, w["wkv_b"], q).reshape(S, H, dn + dv)
    q_pe = rotary(qh[..., dn:], theta)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(xs):
        q_nope, q_rope, k_nope, v = xs
        qi = jnp.concatenate([q_nope, q_rope], -1)
        ki = jnp.concatenate([k_nope, k_pe], -1)
        s = _mm(qi, ki.T, q) / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return _mm(a, v, q)

    heads = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (
        qh[..., :dn], q_pe, kv[..., :dn], kv[..., dn:])))   # (H, S, dv)
    return x + _mm(heads.transpose(1, 0, 2).reshape(S, H * dv), w["wo"], q)


def moe(cfg: dict, q, w: dict, x):
    """x + the held experts' part and the shared experts, of x (S, d)."""
    h = rms_norm(x, w["norm_scale"], cfg["rms_norm_eps"])
    scores = jax.nn.sigmoid(_mm(h, w["router"], q))          # (S, E_all)
    _, ids = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, -1)
    gates = (chosen / chosen.sum(-1, keepdims=True)
             * cfg["routed_scaling_factor"])
    y = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], q)
    for e in range(cfg["n_routed_experts"]):
        g = jnp.sum(jnp.where(ids == cfg["expert_offset"] + e, gates, 0.0),
                    -1)
        y = y + g[:, None] * swiglu(h, w["we_gate"][e], w["we_up"][e],
                                    w["we_down"][e], q)
    return x + y


def dense_mlp(cfg: dict, q, w: dict, x):
    h = rms_norm(x, w["norm_scale"], cfg["rms_norm_eps"])
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], q)


def _layer(p, pre: str, i: int):
    """One layer's leaves of ``pre`` (``first/b0_mla`` ...)."""
    return {k[len(pre) + 1:]: v[i] for k, v in p.items()
            if k.startswith(pre + "/")}


def loss_fn(cfg: dict, q, used: int, p, chain):
    """Mean cross entropy of one sequence's first ``used`` positions."""
    ids, labels = chain[:-1], chain[1:]
    x = p["embed"][ids]
    blocks = [("first", "b0_mlp", dense_mlp)] * cfg["first_k_dense_replace"]
    blocks += [("dec", "b0_moe", moe)] * (cfg["num_hidden_layers"]
                                          - len(blocks))
    seen = {"first": 0, "dec": 0}
    for stack, ffn_name, ffn in blocks:
        i = seen[stack]
        seen[stack] += 1

        @jax.checkpoint
        def layer(x, attn_w, ffn_w):
            x = latent_attention(cfg, q, attn_w, x)
            return ffn(cfg, q, ffn_w, x)

        x = layer(x, _layer(p, f"{stack}/b0_mla", i),
                  _layer(p, f"{stack}/{ffn_name}", i))
    h = rms_norm(x[:used], p["final_norm_scale"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def xent(h, unembed):
        logits = _mm(h, unembed, q)
        gold = jnp.take_along_axis(logits, labels[:used, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)
    return xent(h, p["unembed"])


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


def _sq(tree):
    return {k: jnp.sum(jnp.square(x.astype(jnp.float32)))
            for k, x in tree.items()}


def _adam(cfg, dtype, stored, m, v, grads, step, lr, clip, warmup):
    """One AdamW update; returns the new weights and moments and the
    squared norms of the clipped gradient."""
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
    return new, m, v, _sq(grads)


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
    start = {k: jnp.copy(x) for k, x in stored.items()}
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
            out["grad1"] = clipped
        del grads
    out["dparam"] = dsq(stored, start)
    del stored, start, m, v
    out = jax.device_get(out)
    return {"init": {k: float(x) for k, x in out["init"].items()},
            "grad1": {k: float(x) for k, x in out["grad1"].items()},
            "dparam": {k: float(x) for k, x in out["dparam"].items()},
            "loss": [float(x) for x in out["loss"]]}
