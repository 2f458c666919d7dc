"""Moonlight-16B-A3B's mechanisms on seeded weights at a small size, each
against plain jnp written from the published equations: latent attention
(MLA), the sigmoid router with its selection bias, the held share of the
experts, the leading dense layer, and the parameter counts."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import schema as S
from repro.models.layers import mla_block, mlp, mlp_block, norm
from repro.models.model import forward
from repro.models.moe import _router, megablox_product, moe_local

HIGHEST = jax.lax.Precision.HIGHEST


def _cfg(**kw):
    r = get_config("moonlight-16b-a3b").reduced()
    return dataclasses.replace(r, **kw)


def _leaves(sch, seed):
    """A layer's weights, unstacked, seeded; norms and biases random too
    so that every term shows."""
    return {n: jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                    i), d.shape[1:])
            * (0.5 if d.init != "normal" else 1.0 / math.sqrt(d.shape[-2]))
            + (1.0 if d.init == "ones" else 0.0)
            for i, (n, d) in enumerate(sorted(sch.items()))}


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope(x, theta):
    """(S, H, d) rotate-half at positions 0..S-1."""
    S_, _, d = x.shape
    half = d // 2
    ang = jnp.arange(S_)[:, None] * theta ** (-jnp.arange(half) / half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    return jnp.concatenate([x[..., :half] * c - x[..., half:] * s,
                            x[..., half:] * c + x[..., :half] * s], -1)


def plain_mla(cfg, p, x):
    """One sequence x (S, D), head by head."""
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    S_ = x.shape[0]
    h = _rms(x, p["norm_scale"], cfg.norm_eps)
    q = _mm(h, p["wq"]).reshape(S_, H, dn + dr)
    kv_a = _mm(h, p["wkv_a"])
    c_kv = _rms(kv_a[:, :r], p["kv_norm_scale"], cfg.norm_eps)
    k_pe = _rope(kv_a[:, None, r:], cfg.rope_theta)[:, 0]
    kv = _mm(c_kv, p["wkv_b"]).reshape(S_, H, dn + dv)
    q_pe = _rope(q[..., dn:], cfg.rope_theta)
    causal = jnp.tril(jnp.ones((S_, S_), bool))
    heads = []
    for i in range(H):
        qi = jnp.concatenate([q[:, i, :dn], q_pe[:, i]], -1)
        ki = jnp.concatenate([kv[:, i, :dn], k_pe], -1)
        s = _mm(qi, ki.T) / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        heads.append(_mm(a, kv[:, i, dn:]))
    return x + _mm(jnp.concatenate(heads, -1), p["wo"])


def test_mla_forward_and_gradients_match_plain_jnp():
    cfg = _cfg(attn_chunk=8)
    p = _leaves(S.model_schema(cfg)["dec"]["b0_mla"], 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def ours(p, x):
        with jax.default_matmul_precision("highest"):
            return mla_block(cfg, p, x, mode="train", pos=0, cache=None,
                             window=0)[0]

    def plain(p, x):
        return jax.vmap(lambda xb: plain_mla(cfg, p, xb))(x)

    np.testing.assert_allclose(ours(p, x), plain(p, x), rtol=2e-5,
                               atol=2e-5)
    g1 = jax.grad(lambda p, x: jnp.sum(ours(p, x) * ct), (0, 1))(p, x)
    g2 = jax.grad(lambda p, x: jnp.sum(plain(p, x) * ct), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # every weight of the block gets a gradient
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g1[0]))


def test_sigmoid_router_selects_with_the_bias_and_gates_without():
    cfg = _cfg(n_experts=8, top_k=3, routed_scale=2.446)
    d = cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(0), (64, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, 8)) / math.sqrt(d)
    bias = jnp.zeros(8).at[5].set(10.0)        # expert 5 always selected
    gates, ids, aux = _router(cfg, {"router": w, "router_bias": bias}, x)
    scores = jax.nn.sigmoid(_mm(x, w))
    assert float(aux) == 0.0
    assert bool((ids == 5).any(-1).all())
    # the other picks are the top scores among the rest
    rest = np.argsort(-np.asarray(scores.at[:, 5].set(-1.0)), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(ids[:, 1:]), -1),
                                  np.sort(rest, -1))
    # gates: the unbiased scores of the chosen, normed, times the scale
    chosen = jnp.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(gates, 2.446 * chosen
                               / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-6)
    # the bias takes no gradient
    g = jax.grad(lambda b: _router(cfg, {"router": w, "router_bias": b},
                                   x)[0].sum())(bias)
    assert not np.asarray(g).any()


def _plain_moe(cfg, p, x, experts):
    """The routed experts in ``experts`` (ids into all n_experts), one at
    a time over every token with its gate (zero where not routed), plus
    the shared experts. ``p``'s expert weights are indexed by id."""
    h = _rms(x, p["norm_scale"], cfg.norm_eps)
    gates, ids, _ = _router(cfg, p, h)
    y = jnp.zeros_like(x)
    for e in experts:
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)[:, None]
        up = _mm(h, p["we_up"][e]) * jax.nn.silu(_mm(h, p["we_gate"][e]))
        y = y + g * _mm(up, p["we_down"][e])
    if cfg.n_shared_experts:
        up = _mm(h, p["ws_up"]) * jax.nn.silu(_mm(h, p["ws_gate"]))
        y = y + _mm(up, p["ws_down"])
    return x + y


def _share(p, cfg, offset, held):
    """What one device of an expert-parallel layout holds of the layer."""
    share = dict(p)
    for n in ("we_up", "we_gate", "we_down"):
        share[n] = p[n][offset:offset + held]
    return dataclasses.replace(cfg, experts_held=held, expert_offset=offset)\
        , share


def test_held_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 devices of 2: each share routes over all 16 and
    computes its own experts' part; the parts of all 8, with the shared
    experts (which every device computes alike) counted once, add up to
    the uncut layer, and each part to the plain reference's."""
    cfg = _cfg(n_experts=16, top_k=6)
    p = _leaves(S.model_schema(cfg)["dec"]["b0_moe"], 3)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_local(cfg, p, x)
        unshared = dataclasses.replace(cfg, n_shared_experts=0)
        parts = []
        for dev in range(8):
            c, share = _share(p, unshared, 2 * dev, 2)
            y, _ = moe_local(c, share, x)
            parts.append(y - x)
            np.testing.assert_allclose(
                y[0], _plain_moe(unshared, p, x[0], [2 * dev, 2 * dev + 1]),
                rtol=1e-5, atol=1e-5)
        shared = mlp(cfg, p, norm(cfg, p, x), prefix="ws")
    total = x + sum(parts) + shared
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole[0], _plain_moe(cfg, p, x[0], range(16)),
                               rtol=1e-5, atol=1e-5)
    # every share computed something: the router spreads over all devices
    assert all(float(jnp.abs(y).max()) > 0 for y in parts)


def test_held_share_drops_no_token_under_imbalance():
    """A bias that sends every token to the held experts: the buffer of
    T*k rows holds all of them."""
    cfg = _cfg(n_experts=16, top_k=2, n_shared_experts=0)
    p = _leaves(S.model_schema(cfg)["dec"]["b0_moe"], 6)
    p["router_bias"] = jnp.zeros(16).at[4:6].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 32, cfg.d_model))
    c, share = _share(p, cfg, 4, 2)
    with jax.default_matmul_precision("highest"):
        y, _ = moe_local(c, share, x)
    np.testing.assert_allclose(y[0], _plain_moe(cfg, p, x[0], [4, 5]),
                               rtol=1e-5, atol=1e-5)


def test_leading_dense_layer_runs_ahead_of_the_pattern():
    cfg = _cfg()
    assert cfg.first_dense == 1 and cfg.n_repeat == 1
    sch = S.model_schema(cfg)
    assert set(sch["first"]) == {"b0_mla", "b0_mlp"}
    assert sch["first"]["b0_mlp"]["w_up"].shape == (1, cfg.d_model,
                                                    cfg.d_ff)
    assert sch["dec"]["b0_moe"]["we_up"].shape == (1, cfg.n_experts,
                                                   cfg.d_model, cfg.moe_d_ff)
    params = S.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    h, _, aux = forward(cfg, params, {"tokens": tokens}, mode="train")
    layer = lambda t, i: jax.tree.map(lambda a: a[i], t)
    x = params["embed"][tokens]
    x = mla_block(cfg, layer(params["first"]["b0_mla"], 0), x, mode="train",
                  pos=0, cache=None, window=0)[0]
    x = mlp_block(cfg, layer(params["first"]["b0_mlp"], 0), x)
    x = mla_block(cfg, layer(params["dec"]["b0_mla"], 0), x, mode="train",
                  pos=0, cache=None, window=0)[0]
    x, _ = moe_local(cfg, layer(params["dec"]["b0_moe"], 0), x)
    np.testing.assert_allclose(h, x, rtol=1e-5, atol=1e-5)
    assert float(aux) == 0.0


def test_param_counts_worked_by_hand():
    """Moonlight-16B-A3B at its published sizes."""
    d, H, r, dn, dr, dv = 2048, 16, 512, 128, 64, 128
    mla = (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
           + H * dv * d + r + d)                      # + kv norm, input norm
    dense = 3 * d * 11264 + d
    experts = 64 * 3 * d * 1408
    moe = d * 64 + 64 + 3 * d * 2816 + experts + d    # router, bias, shared
    vocab = 2 * 163840 * d
    total = 27 * mla + dense + 26 * moe + vocab + d   # + final norm
    active = total - 26 * experts * (64 - 6) // 64
    cfg = get_config("moonlight-16b-a3b")
    assert S.count_params(cfg) == total
    assert S.count_params(cfg, active_only=True) == active
    assert total == pytest.approx(15.96e9, rel=1e-3)
    assert active == pytest.approx(2.91e9, rel=2e-3)


def test_tpu_grouped_product_zeroes_the_rows_no_held_expert_takes():
    """The held experts' product on a TPU (megablox ``gmm``, here in
    interpret mode) against ``lax.ragged_dot``, which the CPU runs: the
    same on the held rows, zero on the foreign rows after them, and the
    same gradients, the foreign rows' zero."""
    rng = np.random.default_rng(0)
    rows, k, n = 256, 128, 256
    sizes = jnp.asarray([40, 0, 57, 31, rows - 128], jnp.int32)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, k, n)) / 8, jnp.float32)
    ct = jnp.asarray(rng.standard_normal((rows, n)), jnp.float32)
    held = np.arange(rows) < 128

    def mega(x, w):
        return megablox_product(x, w, sizes, interpret=True)

    def ragged(x, w):
        return jax.lax.ragged_dot(x, w, sizes[:-1])

    out, ref = mega(x, w), ragged(x, w)
    np.testing.assert_allclose(out[held], ref[held], rtol=1e-4, atol=1e-4)
    assert not np.asarray(out[~held]).any()
    grads = [jax.grad(lambda x, w, f=f: jnp.sum(f(x, w) * ct), (0, 1))(x, w)
             for f in (mega, ragged)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    assert not np.asarray(grads[0][0][~held]).any()
