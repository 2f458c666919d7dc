"""Per-architecture smoke tests (REQUIRED): a reduced variant of each
assigned family runs one forward/train step on CPU with shape + no-NaN
asserts, plus prefill->decode consistency against the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config, list_archs
from repro.models import schema as S
from repro.models.model import forward, init_cache, logits_fn
from repro.optim.optimizers import init_opt_state
from repro.train.steps import make_train_step

ARCHS = [a for a in list_archs() if a != "a3c-atari"]


def _batch(r, B, T, seed=0):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, T), 0, r.vocab_size)}
    if r.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            ks[1], (B, r.n_image_tokens, r.d_model))
    if r.is_encdec:
        batch["enc_embeds"] = jax.random.normal(ks[2], (B, r.enc_seq,
                                                        r.d_model))
    return batch


def test_all_ten_archs_registered():
    assert len(ARCHS) == 11
    families = {get_config(a).family for a in ARCHS}
    assert families == {"dense", "moe", "ssm", "hybrid", "encdec", "vlm"}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward(arch):
    cfg = get_config(arch)
    r = cfg.reduced()
    assert r.n_layers <= 8 and r.d_model <= 512 and r.n_experts <= 4
    params = S.init_params(r, jax.random.PRNGKey(0))
    B, T = 2, 16
    h, _, aux = forward(r, params, _batch(r, B, T), mode="train")
    img = r.n_image_tokens if r.family == "vlm" else 0
    assert h.shape == (B, T + img, r.d_model)
    assert np.isfinite(np.asarray(h, np.float32)).all()
    logits = logits_fn(r, params, h[:, -1:])
    assert logits.shape == (B, 1, S.Dims(r, 1).v)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    r = get_config(arch).reduced()
    params = S.init_params(r, jax.random.PRNGKey(0))
    tc = TrainConfig(learning_rate=1e-3, optimizer="rmsprop", loss_chunk=8)
    opt = init_opt_state(tc, params)
    batch = _batch(r, 2, 16)
    batch["labels"] = batch["tokens"]
    step = jax.jit(make_train_step(r, tc))
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    r = get_config(arch).reduced()
    params = S.init_params(r, jax.random.PRNGKey(1))
    B, T, Tp = 2, 16, 12
    batch = _batch(r, B, T, seed=2)
    full, _, _ = forward(r, params, batch, mode="train")
    img = r.n_image_tokens if r.family == "vlm" else 0

    cache = init_cache(r, B, T + img)
    pre = {**batch, "tokens": batch["tokens"][:, :Tp]}
    hp, cache, _ = forward(r, params, pre, mode="prefill", cache=cache)
    hs = [hp]
    pos = Tp + img
    for t in range(Tp, T):
        hd, cache, _ = forward(r, params,
                               {"tokens": batch["tokens"][:, t:t + 1]},
                               mode="decode", pos=pos, cache=cache)
        hs.append(hd)
        pos += 1
    inc = jnp.concatenate(hs, axis=1)
    err = float(jnp.max(jnp.abs(inc - full)))
    assert err < 2e-3, f"{arch}: decode/forward divergence {err}"


@pytest.mark.parametrize("arch", ["gemma2-2b", "yi-9b", "jamba-v0.1-52b",
                                  "xlstm-1.3b"])
def test_windowed_decode_long_context_variant(arch):
    """Ring-buffer (windowed) decode: agreement with full attention on the
    positions inside the window."""
    r = get_config(arch).reduced()
    if not (r.supports_long_context() or r.subquadratic):
        pytest.skip("no long-context path")
    params = S.init_params(r, jax.random.PRNGKey(3))
    B, T = 1, 24
    win = 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, T), 0, r.vocab_size)
    cache = init_cache(r, B, T, window_override=win)
    hp, cache, _ = forward(r, params, {"tokens": toks[:, :8]}, mode="prefill",
                           cache=cache, window_override=win)
    pos = 8
    for t in range(8, T):
        hd, cache, _ = forward(r, params, {"tokens": toks[:, t:t + 1]},
                               mode="decode", pos=pos, cache=cache,
                               window_override=win)
        pos += 1
    assert np.isfinite(np.asarray(hd, np.float32)).all()


def test_param_counts_match_names():
    expect = {"yi-9b": (8.8e9, 0.1), "grok-1-314b": (316e9, 0.05),
              "kimi-k2-1t-a32b": (1.04e12, 0.05),
              "jamba-v0.1-52b": (52e9, 0.05),
              "llava-next-34b": (34e9, 0.05),
              "phi3-mini-3.8b": (3.8e9, 0.05),
              "starcoder2-3b": (3.2e9, 0.05)}
    for arch, (n, tol) in expect.items():
        got = S.count_params(get_config(arch))
        assert abs(got - n) / n < max(tol, 0.07), f"{arch}: {got/1e9:.2f}B"
    # MoE active counts
    assert S.count_params(get_config("kimi-k2-1t-a32b"), active_only=True) \
        == pytest.approx(32e9, rel=0.08)


def test_vocab_padding_masked():
    """Padded vocab columns (model_shards > vocab divisor) never win."""
    r = get_config("whisper-large-v3").reduced()
    import dataclasses
    r = dataclasses.replace(r, vocab_size=510)  # 510 % 4 != 0
    params = S.init_params(r, jax.random.PRNGKey(0), model_shards=4)
    assert params["embed"].shape[0] == 512
    batch = _batch(r, 1, 8)
    h, _, _ = forward(r, params, batch, mode="train")
    logits = logits_fn(r, params, h[:, -1:])
    assert logits.shape[-1] == 512
    assert float(logits[..., 510:].max()) <= -1e29
