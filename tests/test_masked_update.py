"""The eviction mask inside the optimizer: a step that declares ``active``
freezes a masked slot's learner itself, and the engine masks only its
carry. Its bucket step must give, bit for bit, what the same step gives
through the engine's own select of every output, which a step without
``active`` still gets."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.optim.optimizers import (OptState, apply_updates, init_opt_state,
                                    learning_rate)
from repro.population.engine import _bucket_step


def _ga3c():
    from repro.population.objectives.ga3c import GA3CObjective
    return GA3CObjective("pong", n_envs=2), 4, {"t_max": 4}


def _lm():
    from repro.population.objectives.lm import LMObjective
    return LMObjective("yi-9b", batch=1, seq=16), 16, {"loss_chunk": 16}


def _hiding_active(objective):
    """The same objective, its step wrapped so that it declares no
    ``active``: the engine then selects its whole output, as it did before
    steps could freeze their own learner."""
    hidden = copy.copy(objective)
    key, make_step = objective.cache_key(), objective.make_step
    hidden.cache_key = lambda: (key, "hides active")

    def make(structural, capacity):
        one = make_step(structural, capacity)
        return lambda learner, carry, *hyper: one(learner, carry, *hyper)
    hidden.make_step = make
    return hidden


def _stacked_state(objective, hparams, capacity):
    slots = [objective.init_slot_state(jax.random.PRNGKey(i), hparams)
             for i in range(capacity)]
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *slots)


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("make_objective", [_lm, _ga3c], ids=["lm", "ga3c"])
@pytest.mark.parametrize("active", [[True, False], [True], [False]],
                         ids=["cap2", "cap1_active", "cap1_masked"])
def test_step_freezes_masked_slots_as_the_engine_select_did(make_objective,
                                                            active):
    """The masked slot's traced hyperparameters are NaN, so every value
    its update computes (clipped gradient, moments, parameters) is NaN;
    it must come back bit for bit as it went in, and every slot must match
    the engine's outer select of the same step."""
    objective, key, hparams = make_objective()
    capacity = len(active)
    learner, carry = _stacked_state(objective, hparams, capacity)
    values = objective.traced_values(
        dict(objective.hparam_spec().defaults, learning_rate=1e-3,
             gamma=0.99, **hparams))
    mask = np.asarray(active)
    hyper = [np.where(mask, v, np.nan).astype(np.float32) for v in values]

    def run(obj):
        step = _bucket_step(obj, key, capacity)
        # fresh device copies: the step donates its state
        return step(*jax.tree.map(jnp.asarray, (learner, carry)),
                    *map(jnp.asarray, hyper), jnp.asarray(mask))

    new = run(objective)
    old = run(_hiding_active(objective))
    assert _bits(new) == _bits(old)
    for i, on in enumerate(active):
        got = _bits(jax.tree.map(lambda x: x[i], new))
        was = _bits(jax.tree.map(lambda x: x[i], (learner, carry)))
        if on:
            assert got != was
        else:
            assert got == was


def _update_as_written(tc, params, grads, state, lr, grad_clip, warmup):
    """AdamW and RMSProp as ``apply_updates`` computes them without a
    mask: the reference its ``active=None`` path must match bit for bit."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)
    lr = learning_rate(tc, state.step, base=lr, warmup=warmup)
    if tc.optimizer == "rmsprop":
        d = tc.rmsprop_decay
        acc1 = jax.tree.map(lambda a, g: d * a + (1 - d) * g * g,
                            state.acc1, grads)
        new = jax.tree.map(
            lambda p, g, a: (p.astype(jnp.float32) - lr * g
                             / jnp.sqrt(a + tc.rmsprop_eps)).astype(p.dtype),
            params, grads, acc1)
        return new, OptState(state.step + 1, acc1, None), gn
    b1, b2 = tc.adam_b1, tc.adam_b2
    t = state.step + 1
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, state.acc1, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, state.acc2,
                     grads)
    bc1 = 1 - b1 ** t.astype(jnp.float32)
    bc2 = 1 - b2 ** t.astype(jnp.float32)

    def upd(p, m_, v_):
        step_ = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8)
        pf = p.astype(jnp.float32)
        if tc.weight_decay:
            step_ = step_ + lr * tc.weight_decay * pf
        return (pf - step_).astype(p.dtype)
    return jax.tree.map(upd, params, m, v), OptState(t, m, v), gn


@pytest.mark.parametrize("optimizer", ["adamw", "rmsprop"])
def test_unmasked_update_is_unchanged(optimizer):
    """Without ``active`` the update is the arithmetic it always was (no
    select in its program); with ``active`` true it gives the same bits."""
    tc = TrainConfig(optimizer=optimizer, weight_decay=0.1)
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {"w": jax.random.normal(k[0], (64, 48), jnp.bfloat16),
              "b": jax.random.normal(k[1], (48,), jnp.float32)}
    grads = {"w": jax.random.normal(k[2], (64, 48), jnp.bfloat16),
             "b": jax.random.normal(k[3], (48,), jnp.float32)}
    state = init_opt_state(tc, params)
    hyper = (jnp.float32(3e-3), jnp.float32(0.5), jnp.float32(4.0))

    def ours(p, g, s, lr, clip, warmup, *active):
        return apply_updates(tc, p, g, s, lr=lr, grad_clip=clip,
                             warmup_steps=warmup,
                             active=active[0] if active else None)
    ref = jax.jit(lambda *a: _update_as_written(tc, *a))
    for _ in range(3):
        want = ref(params, grads, state, *hyper)
        assert _bits(jax.jit(ours)(params, grads, state, *hyper)) \
            == _bits(want)
        assert _bits(jax.jit(ours)(params, grads, state, *hyper,
                                   jnp.bool_(True))) == _bits(want)
        params, state = want[0], want[1]
    assert "select_n" not in str(
        jax.make_jaxpr(ours)(params, grads, state, *hyper))
