"""Dedicated coverage for ``core.evolution.EvolutionaryHyperTrick``,
exercised through the Scheduler pipeline (the service uses it as its
scheduler and every decision flows as a ``Verdict``)."""
import numpy as np

from repro.core.evolution import EvolutionaryHyperTrick
from repro.core.scheduler import VerdictKind
from repro.core.search_space import (Categorical, LogUniform, QLogUniform,
                                     SearchSpace)
from repro.core.service import Decision, OptimizationService, TrialStatus

SPACE = SearchSpace({"lr": LogUniform(1e-5, 1e-1),
                     "t": QLogUniform(2, 64, 1),
                     "g": Categorical((0.9, 0.99, 0.999))})


def test_warmup_spawns_are_fresh_samples():
    """The first ``warmup`` configurations are independent draws — the
    exploit path must not engage before any evidence exists."""
    policy = EvolutionaryHyperTrick(SPACE, w0=8, n_phases=2,
                                    eviction_rate=0.25, seed=0,
                                    warmup_frac=0.5, mutate_prob=1.0)
    twin = np.random.default_rng(0)
    svc = OptimizationService(policy)
    assert svc.scheduler is policy
    for _ in range(policy.warmup):
        rec = svc.acquire_trial()
        assert rec.hparams == SPACE.sample(twin)  # same seed, same draws


def test_post_warmup_spawns_mutate_a_top_quartile_parent():
    """After warmup (mutate_prob=1) every spawn derives from a top-quartile
    reported trial: each hyperparameter is within one mutation step of the
    parent's value."""
    policy = EvolutionaryHyperTrick(SPACE, w0=9, n_phases=2,
                                    eviction_rate=0.25, seed=3,
                                    warmup_frac=1 / 3, mutate_prob=1.0)
    svc = OptimizationService(policy)
    warm = [svc.acquire_trial() for _ in range(policy.warmup)]
    for i, rec in enumerate(warm):
        assert svc.report(rec.trial_id, 0, float(i)) is Decision.CONTINUE
    # top quartile of 3 reported trials = max(1, 3 // 4) = the single best
    parent = warm[-1]
    child = svc.acquire_trial()
    assert child.hparams["lr"] / parent.hparams["lr"] in \
        (0.5, 0.8, 1.0, 1.25, 2.0) or child.hparams["lr"] in (1e-5, 1e-1)
    gs = list(SPACE.params["g"].values)
    assert abs(gs.index(child.hparams["g"]) - gs.index(parent.hparams["g"])) \
        <= 1
    assert 2 <= child.hparams["t"] <= 64


def test_budget_and_eviction_through_the_verdict_pipeline():
    """The full lifecycle over the service: w0 spawns total (mutants
    included), DCM/WSM evictions arrive as STOP verdicts, and the budget
    exhausts to None."""
    policy = EvolutionaryHyperTrick(SPACE, w0=12, n_phases=3,
                                    eviction_rate=0.4, seed=1,
                                    warmup_frac=0.5, mutate_prob=0.8)
    svc = OptimizationService(policy)
    rng = np.random.default_rng(7)
    live, spawned, kinds = [], 0, set()
    while True:
        rec = svc.acquire_trial()
        if rec is None:
            break
        spawned += 1
        metric = float(rng.normal())
        for phase in range(policy.n_phases):
            v = svc.report_verdict(rec.trial_id, phase, metric)
            kinds.add(v.kind)
            if v.kind is VerdictKind.STOP:
                break
        live.append(rec)
    assert spawned == 12 and svc.acquire_trial() is None
    statuses = [t.status for t in svc.db.trials.values()]
    assert statuses.count(TrialStatus.KILLED) > 0      # WSM evicted some
    assert statuses.count(TrialStatus.COMPLETED) > 0   # others finished
    assert TrialStatus.RUNNING not in statuses
    assert kinds <= {VerdictKind.CONTINUE, VerdictKind.STOP}


def test_mutation_falls_back_to_fresh_sample_without_reports():
    """Post-warmup with an empty knowledge DB (nothing reported yet) the
    exploit path degrades to fresh sampling instead of crashing."""
    policy = EvolutionaryHyperTrick(SPACE, w0=4, n_phases=2,
                                    eviction_rate=0.25, seed=5,
                                    warmup_frac=0.25, mutate_prob=1.0)
    svc = OptimizationService(policy)
    recs = [svc.acquire_trial() for _ in range(4)]    # nobody reported
    assert all(r is not None for r in recs)
    for r in recs:
        for k, p in SPACE.params.items():
            v = r.hparams[k]
            assert (v in p.values) if isinstance(p, Categorical) \
                else p.lo <= v <= p.hi
