"""GA3C as a ``PopulationObjective`` — the engine's default workload.

This is the paper's workload, re-registered behind the generic protocol
with **bit-identical numerics**: the step body below is exactly the
pre-refactor engine's (itself exactly the ``GA3CTrainer`` train step with
the continuous hyperparameters as traced scalars), the slot-init path
reproduces the same rng splits, and the unroll heuristic is unchanged —
tests/test_population.py asserts ``==`` on params against the thread
backend, and tests/test_population_sharded.py does the same under
``shard_map``.

* traced:      ``learning_rate``, ``gamma``, ``beta`` — per-slot scalars
  into one compiled step;
* structural:  ``t_max`` — the rollout scan length, hence the bucket key;
* learner:     ``(params, opt_state)`` (what a PBT clone copies);
* carry:       the ``LoopState`` (env state + episode counters — a clone
  keeps exploring its own environments);
* cost:        ``t_max * n_envs`` env transitions per update per slot.
"""
from __future__ import annotations

from typing import Any, Dict, Hashable

import jax

from repro.optim.optimizers import apply_updates, init_opt_state
from repro.population.objectives import (GA3C_SPEC, HparamSpec,
                                         PopulationObjective)
from repro.rl.a3c import a3c_loss, init_loop_state, rollout
from repro.rl.envs.minigames import make_env
from repro.rl.ga3c import ga3c_train_config
from repro.rl.network import A3CNetConfig, apply_net, init_net

# full-unroll ceiling: XLA:CPU won't parallelize inside while loops, so
# unrolling ~2x-halves the step time of a multi-slot bucket — but compile
# time grows with t_max * capacity, so large-t_max buckets keep the loop
# (partial unrolls measure no faster than unroll=1 here; only full pays)
UNROLL_T_MAX = 16


class GA3CObjective(PopulationObjective):
    name = "ga3c"

    def __init__(self, game: str = "pong", n_envs: int = 16):
        self.game = game
        self.n_envs = n_envs
        self.env = make_env(game)
        self.net_cfg = A3CNetConfig(grid=self.env.spec.grid,
                                    n_actions=self.env.spec.n_actions)
        # lr is overridden per-slot inside the step; the config value is
        # only the (unused) default
        self.tc = ga3c_train_config(3e-4)

    @classmethod
    def hparam_spec(cls) -> HparamSpec:
        return GA3C_SPEC

    def bucket_key(self, hparams: Dict[str, Any]) -> int:
        return int(hparams.get("t_max", 8))

    def cache_key(self) -> Hashable:
        return ("ga3c", self.game, self.n_envs)

    def init_slot_state(self, rng, hparams: Dict[str, Any]):
        k_net, k_env = jax.random.split(rng)
        params = init_net(self.net_cfg, k_net)
        opt_state = init_opt_state(self.tc, params)
        loop = init_loop_state(self.env, self.n_envs, k_env)
        return (params, opt_state), loop

    def make_step(self, structural: Hashable, local_capacity: int):
        env, tc = self.env, self.tc
        t_max = int(structural)
        unroll = (t_max if (local_capacity > 1 and t_max <= UNROLL_T_MAX)
                  else 1)

        def one(learner, loop, lr, gamma, beta, active=None):
            params, opt_state = learner
            traj, new_loop = rollout(env, params, loop, t_max, unroll=unroll)
            _, v_boot = apply_net(params, new_loop.obs_stack)
            v_boot = v_boot * (1.0 - traj.dones[-1])
            grads, _ = jax.grad(
                lambda p: a3c_loss(p, traj, v_boot, gamma=gamma, beta=beta),
                has_aux=True)(params)
            params, opt_state, _ = apply_updates(tc, params, grads,
                                                 opt_state, lr=lr,
                                                 active=active)
            return (params, opt_state), new_loop

        return one

    def progress(self, carry):
        return carry.finished_n, carry.finished_sum

    def update_cost(self, structural: Hashable) -> int:
        return int(structural) * self.n_envs
