"""LM fine-tuning trials as a ``PopulationObjective``.

The second workload the engine serves end-to-end: per-trial learning
rate, gradient-clip norm, and warmup schedule ride the slot axis as
traced scalars into one vmapped ``train/steps.py`` update over a tiny
``configs.registry`` model (``reduced()`` smoke dims), so a whole LM
hyperparameter search trains inside one compiled step — the same
mechanism (bucketing, eviction masks, device-side clones, ``shard_map``)
that serves GA3C.

* traced:      ``learning_rate``, ``grad_clip``, ``warmup_steps`` — the
  clip norm and warmup horizon enter ``optim.apply_updates`` as traced
  overrides, the traced twins of ``TrainConfig.grad_clip`` /
  ``warmup_steps``;
* structural:  ``loss_chunk`` — the sequence-chunking of the vocab xent
  changes the scan structure of the loss, i.e. the XLA program, so it
  buckets (the key is the *effective* chunk ``min(loss_chunk, seq)``:
  chunk sizes the sequence truncates to the same program share one
  compile);
* learner:     ``(params, opt_state)`` (adamw);
* carry:       per-slot data rng + update counter + summed ``-loss`` —
  the phase metric is mean ``-loss`` over the phase's updates (higher is
  better, the service's convention, matching
  ``train.trainer.make_lm_objective``);
* cost:        ``batch * seq`` tokens per update per slot.

Data is the same seeded bigram chain as ``data.synthetic.BigramStream``,
regenerated *on device* (the host pipeline is numpy and cannot live
inside a vmapped step): the transition table is a baked constant shared
by every slot, and each slot draws its own chains from its carry rng —
per-trial data order, one compile.
"""
from __future__ import annotations

from typing import Any, Dict, Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.models import schema as mschema
from repro.models.model import forward
from repro.optim.optimizers import apply_updates, init_opt_state
from repro.population.objectives import (LM_SPEC, HparamSpec,
                                         PopulationObjective)
from repro.train.steps import lm_loss


def _bigram_chain(table, k_start, k_choice, batch: int, seq: int):
    """(batch, seq+1) tokens from the seeded bigram table — the on-device
    twin of ``BigramStream.sample``."""
    start = jax.random.randint(k_start, (batch,), 0, table.shape[0])
    choice = jax.random.randint(k_choice, (seq, batch), 0, table.shape[1])

    def body(tok, ch):
        nxt = table[tok, ch]
        return nxt, nxt

    _, rest = jax.lax.scan(body, start, choice)
    return jnp.concatenate([start[None], rest]).T


class LMObjective(PopulationObjective):
    name = "lm"

    def __init__(self, arch: str = "yi-9b", batch: int = 2, seq: int = 32,
                 data_seed: int = 0):
        from repro.configs.registry import get_config
        self.arch = arch
        self.batch = batch
        self.seq = seq
        self.data_seed = data_seed
        self.cfg = get_config(arch).reduced()
        # lr/clip/warmup are overridden per-slot inside the step; the
        # config values are only the (unused) defaults
        self.tc = TrainConfig(optimizer="adamw")
        rng = np.random.default_rng(data_seed)
        self.table = jnp.asarray(
            rng.integers(0, self.cfg.vocab_size,
                         size=(self.cfg.vocab_size, 8)).astype(np.int32))

    @classmethod
    def hparam_spec(cls) -> HparamSpec:
        return LM_SPEC

    def bucket_key(self, hparams: Dict[str, Any]) -> int:
        return min(int(hparams.get("loss_chunk", 1024)), self.seq)

    def cache_key(self) -> Hashable:
        return ("lm", self.arch, self.batch, self.seq, self.data_seed)

    def init_slot_state(self, rng, hparams: Dict[str, Any]):
        k_params, k_data = jax.random.split(rng)
        params = mschema.init_params(self.cfg, k_params)
        opt_state = init_opt_state(self.tc, params)
        carry = {"rng": k_data,
                 "n": jnp.zeros((), jnp.float32),
                 "loss_sum": jnp.zeros((), jnp.float32)}
        return (params, opt_state), carry

    def make_step(self, structural: Hashable, local_capacity: int):
        cfg, tc, table = self.cfg, self.tc, self.table
        batch_size, seq, chunk = self.batch, self.seq, int(structural)

        def one(learner, carry, lr, grad_clip, warmup_steps, active=None):
            params, opt_state = learner
            rng, k_start, k_choice = jax.random.split(carry["rng"], 3)
            chain = _bigram_chain(table, k_start, k_choice, batch_size, seq)
            batch = {"tokens": chain[:, :-1], "labels": chain[:, 1:]}

            def loss_fn(p):
                h, _, aux = forward(cfg, p, batch, mode="train")
                loss = lm_loss(cfg, p, h, batch["labels"], chunk)
                return loss + aux, loss

            grads, loss = jax.grad(loss_fn, has_aux=True)(params)
            params, opt_state, _ = apply_updates(
                tc, params, grads, opt_state, lr=lr,
                grad_clip=grad_clip, warmup_steps=warmup_steps,
                active=active)
            carry = {"rng": rng, "n": carry["n"] + 1.0,
                     "loss_sum": carry["loss_sum"] - loss}
            return (params, opt_state), carry

        return one

    def progress(self, carry):
        return carry["n"], carry["loss_sum"]

    def update_cost(self, structural: Hashable) -> int:
        return self.batch * self.seq
