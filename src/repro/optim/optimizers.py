"""Pure-JAX optimizers.

* ``rmsprop`` — non-centered RMSProp (Tieleman & Hinton 2012), exactly the
  optimizer A3C/GA3C uses in the paper (shared statistics variant): one
  accumulator, no momentum, no centering.
* ``adamw`` — for the LM-training objectives.

State is a pytree mirroring params; ``zero_sharded_opt`` reshards the
accumulators over the 'data' axis (ZeRO-1 style) on the largest divisible dim.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import TrainConfig


class OptState(NamedTuple):
    step: jax.Array
    acc1: Any            # rmsprop: sq-avg; adam: m
    acc2: Any            # adam: v; rmsprop: unused (None)


def init_opt_state(tc: TrainConfig, params) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    if tc.optimizer == "rmsprop":
        return OptState(jnp.zeros((), jnp.int32), zeros, None)
    return OptState(jnp.zeros((), jnp.int32), zeros,
                    jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                                 params))


def learning_rate(tc: TrainConfig, step, base=None, warmup=None) -> jax.Array:
    """``base`` overrides ``tc.learning_rate`` — it may be a traced scalar,
    which is how the population engine vmaps one train step over per-trial
    learning rates (the config value is a python float baked into the jit).
    ``warmup`` likewise overrides ``tc.warmup_steps`` with a (possibly
    traced) horizon; values <= 1 mean no warmup, matching the config
    semantics without a data-dependent branch."""
    lr = jnp.asarray(tc.learning_rate if base is None else base, jnp.float32)
    if warmup is not None:
        w = jnp.maximum(jnp.asarray(warmup, jnp.float32), 1.0)
        return lr * jnp.minimum(1.0, (step + 1) / w)
    if tc.warmup_steps:
        lr = lr * jnp.minimum(1.0, (step + 1) / tc.warmup_steps)
    return lr


def _clip_by_global_norm(grads, max_norm):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    # a concrete 0/None disables clipping at trace time (the historical
    # contract); a traced max_norm always takes the clip branch — per-slot
    # searches that want "no clip" pass a large norm instead
    no_clip = max_norm is None or (isinstance(max_norm, (int, float))
                                   and not max_norm)
    scale = (jnp.float32(1.0) if no_clip
             else jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9)))
    return jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads), gn


def apply_updates(tc: TrainConfig, params, grads, state: OptState, lr=None,
                  grad_clip=None, warmup_steps=None, active=None):
    """Returns (new_params, new_state, grad_norm). ``lr``, ``grad_clip``,
    and ``warmup_steps`` (optional traced scalars) override their config
    twins — how the population engine vmaps one train step over per-trial
    hyperparameters (config values are python floats baked into the jit).

    ``active`` (optional traced boolean scalar) freezes the update where
    it is false: every accumulator, parameter and the step counter come
    back as they went in, bit for bit, whatever the gradient holds (a
    ``where``, never a multiply). The select sits inside each leaf's
    update, so XLA's TPU compiler writes a leaf's parameter and
    accumulators in one fusion, one HBM pass; a mask applied to the
    outputs afterwards splits that pass in two (the accumulators, then the
    parameter with them recomputed). The parameter is selected in float32,
    before its cast back: a select of the bfloat16 value splits the pass
    too. The cast back is exact, except that a NaN parameter may come back
    as another NaN."""
    grads, gnorm = _clip_by_global_norm(
        grads, tc.grad_clip if grad_clip is None else grad_clip)
    lr = learning_rate(tc, state.step, base=lr, warmup=warmup_steps)
    if active is None:
        keep = lambda new, old: new
    else:
        keep = lambda new, old: jnp.where(active, new, old)
    with jax.named_scope("optim.update"):
        if tc.optimizer == "rmsprop":
            # non-centered RMSProp: g2 <- d*g2 + (1-d)*g^2 ;
            # p -= lr*g/sqrt(g2+eps)
            d = tc.rmsprop_decay
            acc1 = jax.tree.map(lambda a, g: keep(d * a + (1 - d) * g * g, a),
                                state.acc1, grads)

            def upd(p, g, a):
                pf = p.astype(jnp.float32)
                return keep(pf - lr * g / jnp.sqrt(a + tc.rmsprop_eps),
                            pf).astype(p.dtype)
            new_params = jax.tree.map(upd, params, grads, acc1)
            return (new_params,
                    OptState(keep(state.step + 1, state.step), acc1, None),
                    gnorm)

        # adamw
        b1, b2 = tc.adam_b1, tc.adam_b2
        t = state.step + 1
        m = jax.tree.map(lambda a, g: keep(b1 * a + (1 - b1) * g, a),
                         state.acc1, grads)
        v = jax.tree.map(lambda a, g: keep(b2 * a + (1 - b2) * g * g, a),
                         state.acc2, grads)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def upd(p, m_, v_):
            step_ = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8)
            pf = p.astype(jnp.float32)
            if tc.weight_decay:
                step_ = step_ + lr * tc.weight_decay * pf
            return keep(pf - step_, pf).astype(p.dtype)

        return (jax.tree.map(upd, params, m, v),
                OptState(keep(t, state.step), m, v), gnorm)


# ---------------------------------------------------------------------------
# ZeRO-1: shard accumulators over 'data' on the largest divisible dim
# ---------------------------------------------------------------------------
def zero_spec(shape: tuple, spec: P, data_size: int) -> P:
    dims = list(spec) + [None] * (len(shape) - len(spec))
    best, best_size = -1, 0
    for i, (n, s) in enumerate(zip(shape, dims)):
        if s is None and n % data_size == 0 and n > best_size:
            best, best_size = i, n
    if best >= 0:
        dims[best] = "data"
    return P(*dims)


def opt_state_specs(tc: TrainConfig, pspecs, abstract_params,
                    data_size: int = 1) -> OptState:
    def one():
        if not tc.zero_sharded_opt or data_size <= 1:
            return pspecs
        return jax.tree.map(
            lambda sp, sh: zero_spec(sh.shape, sp, data_size),
            pspecs, abstract_params)
    acc2 = one() if tc.optimizer != "rmsprop" else None
    return OptState(P(), one(), acc2)
