"""Population-engine throughput: the on-device vectorized backend against
the thread and process backends on identical searches.

Work is made deterministic so throughput is comparable across backends:
``episodes_per_phase`` is unreachable and ``max_updates`` is fixed, so every
phase is exactly ``max_updates`` GA3C updates of ``t_max * n_envs`` env
transitions, and total env-steps follow from the phase-report count alone.
``t_max`` is pinned so all trials share one bucket — the single-bucket case
isolates the vectorization win (bucketing itself is exercised by the tests
and the tune CLI, where t_max is searched over).

Compilation accounting: the vectorized backend is measured WARM (a
throwaway search first populates the module-level bucket-step cache),
because its compile is a one-time cost per bucket shape — hyperparameters
are traced inputs, so one compilation serves every configuration for the
rest of the process. The thread/process backends are measured cold because
their compiles are *recurring*: each trial bakes its hyperparameters into
its own jit, so every new configuration recompiles by construction. The
cold vectorized wall time is reported in ``derived`` so nothing is hidden.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.executor import (PopulationCluster, ProcessCluster,
                                 ThreadCluster)
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import Categorical, LogUniform, SearchSpace

T_MAX = 8
N_ENVS = 16
MAX_UPDATES = 25
N_PHASES = 2


def _space() -> SearchSpace:
    return SearchSpace({
        "learning_rate": LogUniform(1e-4, 1e-3),
        "gamma": Categorical((0.99, 0.995)),
        "t_max": Categorical((T_MAX,)),
    })


def _policy(w0: int) -> RandomSearchPolicy:
    return RandomSearchPolicy(_space(), w0, N_PHASES, seed=0)


def _throughput(res) -> tuple:
    """(env_steps/s, trials_completed/s): env-steps from the report count —
    every phase is exactly MAX_UPDATES updates on every backend."""
    env_steps = len(res.records) * MAX_UPDATES * T_MAX * N_ENVS
    done = sum(1 for t in res.service.db.trials.values()
               if t.status.value == "completed")
    return env_steps / res.wall_time, done / res.wall_time


def bench_population_throughput():
    """vectorized vs thread vs process at W0 in {4, 8, 16}. The acceptance
    bar for the population engine is >= 3x env-steps/sec over thread at
    W0 = 8 on CPU."""
    from repro.rl.ga3c import make_rl_objective
    rows = []
    w0s = (4, 8, 16)
    # process: tune.py's default node count, OS-process workers over TCP.
    # These run first, before this process touches jax: a chip belongs to
    # one process at a time, and each worker needs one
    spec = {"kind": "rl", "game": "pong",
            "episodes_per_phase": 10 ** 9, "seed": 0,
            "max_updates": MAX_UPDATES}
    procs = {w0: ProcessCluster(4, spec, lease_ttl=30.0,
                                heartbeat_interval=1.0).run(_policy(w0))
             for w0 in w0s}
    for w0 in w0s:
        per = {"process": procs[w0]}
        # thread: same node count
        objective = make_rl_objective("pong", episodes_per_phase=10 ** 9,
                                      n_envs=N_ENVS, seed=0,
                                      max_updates=MAX_UPDATES)
        per["thread"] = ThreadCluster(4, objective).run(_policy(w0))
        # vectorized: the whole population in one vmapped jitted step.
        # A 1-update throwaway search first pays the one-per-bucket-shape
        # compile; the measured search reuses the cached compiled step.
        warm = PopulationCluster(w0, game="pong",
                                 episodes_per_phase=10 ** 9, n_envs=N_ENVS,
                                 max_updates=1, seed=0).run(
            RandomSearchPolicy(_space(), w0, 1, seed=0))
        per["vectorized"] = PopulationCluster(
            w0, game="pong", episodes_per_phase=10 ** 9, n_envs=N_ENVS,
            max_updates=MAX_UPDATES, seed=0).run(_policy(w0))

        eps = {k: _throughput(r) for k, r in per.items()}
        for name in ("thread", "process", "vectorized"):
            sps, tps = eps[name]
            extra = (f" compile~{warm.wall_time:.1f}s"
                     if name == "vectorized" else "")
            rows.append((f"population/w{w0}/{name}/env_steps_per_s",
                         float(sps),
                         f"trials_per_s={tps:.3f} "
                         f"wall={per[name].wall_time:.1f}s{extra}"))
        rows.append((f"population/w{w0}/vectorized_over_thread",
                     float(eps["vectorized"][0] / max(eps["thread"][0],
                                                      1e-9)),
                     f"t_max={T_MAX} n_envs={N_ENVS} "
                     f"updates/phase={MAX_UPDATES}"))
    return rows


# ---------------------------------------------------------------------------
# the LM workload (PopulationObjective protocol)
# ---------------------------------------------------------------------------
LM_ARCH = "yi-9b"
LM_BATCH = 4
LM_SEQ = 32
LM_STEPS = 20


def _lm_space() -> SearchSpace:
    # loss_chunk pinned: one bucket, one compile for the whole population
    return SearchSpace({
        "learning_rate": LogUniform(1e-4, 1e-3),
        "loss_chunk": Categorical((LM_SEQ,)),
        "grad_clip": Categorical((1.0,)),
        "warmup_steps": Categorical((1,)),
    })


def bench_population_lm():
    """LM fine-tuning trials on the engine vs the thread backend at W0 in
    {2, 8}: same reduced model, same batch/seq, ``LM_STEPS`` updates per
    phase on both, so tokens/s follows from the report count alone. Warm
    accounting matches bench_population_throughput: the vectorized
    engine's one-per-bucket compile is paid by a throwaway search, the
    thread backend recompiles per trial by construction."""
    from repro.train.trainer import make_lm_objective
    rows = []
    for w0 in (2, 8):
        # policies are stateful: each backend drains its own fresh copy
        def policy():
            return RandomSearchPolicy(_lm_space(), w0, N_PHASES, seed=0)
        objective = make_lm_objective(LM_ARCH, steps_per_phase=LM_STEPS,
                                      batch=LM_BATCH, seq=LM_SEQ, seed=0)
        thread = ThreadCluster(4, objective).run(policy())

        spec = {"kind": "lm", "arch": LM_ARCH, "batch": LM_BATCH,
                "seq": LM_SEQ, "data_seed": 0}
        warm = PopulationCluster(w0, objective=spec, episodes_per_phase=1,
                                 seed=0).run(
            RandomSearchPolicy(_lm_space(), w0, 1, seed=0))
        vect = PopulationCluster(w0, objective=spec,
                                 episodes_per_phase=LM_STEPS, seed=0
                                 ).run(policy())

        tok = LM_BATCH * LM_SEQ * LM_STEPS
        tps = {"thread": len(thread.records) * tok / thread.wall_time,
               "vectorized": len(vect.records) * tok / vect.wall_time}
        walls = {"thread": thread.wall_time, "vectorized": vect.wall_time}
        for name in ("thread", "vectorized"):
            extra = (f" compile~{warm.wall_time:.1f}s"
                     if name == "vectorized" else "")
            rows.append((f"population_lm/w{w0}/{name}/tokens_per_s",
                         float(tps[name]),
                         f"wall={walls[name]:.1f}s{extra}"))
        rows.append((f"population_lm/w{w0}/vectorized_over_thread",
                     float(tps["vectorized"] / max(tps["thread"], 1e-9)),
                     f"arch={LM_ARCH} batch={LM_BATCH} seq={LM_SEQ} "
                     f"updates/phase={LM_STEPS}"))
    return rows
