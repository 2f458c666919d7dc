"""Golden parity for every metaoptimizer behind ``OptimizationService``.

One fixed, scripted acquire/report stream (3 slots, a metric that is a
fixed function of trial id and phase, fixed seeds) is driven through the
service for each scheduler. The granted ``hparams`` in grant order, every
report's decision and verdict (with PBT's clone payload), the rung log and
the final statuses must match ``fixtures/scheduler_golden.json`` exactly:
the same configurations in the same order, and the same verdicts.

Re-record (only when a behaviour change is intended), saying in the
header where it was recorded:

    PYTHONPATH=src python tests/test_scheduler_golden.py --record "HEADER"
"""
import json
import os
import sys

import pytest

from repro.core.asha import ASHA
from repro.core.evolution import EvolutionaryHyperTrick
from repro.core.hypertrick import HyperTrick, RandomSearchPolicy
from repro.core.scheduler import (BracketScheduler, HyperbandScheduler,
                                  PBTScheduler, VerdictKind)
from repro.core.search_space import (Categorical, LogUniform, QLogUniform,
                                     SearchSpace, Uniform)
from repro.core.service import Decision, OptimizationService

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "scheduler_golden.json")
SLOTS = 3
MAX_ROUNDS = 10_000

SPACE = SearchSpace({"learning_rate": LogUniform(1e-5, 1e-2),
                     "t_max": QLogUniform(2, 100, 1),
                     "gamma": Categorical((0.9, 0.99, 0.999)),
                     "x": Uniform(0.0, 1.0)})

# case -> (scheduler factory, bracket_eta)
CASES = {
    "hypertrick": (lambda: HyperTrick(SPACE, 12, 3, 0.3, seed=1), None),
    "random_configs": (lambda: RandomSearchPolicy(
        SPACE, 5, 2, seed=2, configs=SPACE.sample_n(5, seed=7)), None),
    "asha": (lambda: ASHA(SPACE, n_trials=12, n_phases=9, eta=3, seed=3),
             None),
    "evolution": (lambda: EvolutionaryHyperTrick(
        SPACE, w0=12, n_phases=3, eviction_rate=0.3, seed=4,
        warmup_frac=0.25, mutate_prob=0.8), None),
    "random_bracket": (lambda: RandomSearchPolicy(SPACE, 9, 4, seed=5), 3),
    "hyperband": (lambda: HyperbandScheduler(SPACE, n_phases=4, eta=2,
                                             seed=6), None),
    "pbt": (lambda: PBTScheduler(SPACE, population=6, n_phases=4, seed=7,
                                 min_reports=2), None),
}


def _metric(trial_id: int, phase: int) -> float:
    """A fixed, scrambled function of (trial, phase) that rises with
    phase, so quantile rules see ties-free, reproducible stats."""
    return ((trial_id * 7919 + phase * 104729) % 1009) / 1009.0 + 0.25 * phase


def drive(case: str) -> dict:
    """Run the scripted stream for ``case``: fill every free slot (with the
    rung hint when the service has a barrier), then report each occupied
    slot once per round, in slot order. A parked report is re-sent (the
    poll) the next round until its cohort resolves."""
    factory, bracket_eta = CASES[case]
    svc = OptimizationService(factory(), clock=lambda: 0.0,
                              bracket_eta=bracket_eta)
    rung = 0 if svc.barrier is not None else None
    slots = [None] * SLOTS                  # [trial_id, phase] or None
    events = []
    exhausted = False
    for _ in range(MAX_ROUNDS):
        for s in range(SLOTS):
            if slots[s] is None and not exhausted:
                rec = svc.acquire_trial(node=s, rung=rung)
                if rec is None:
                    exhausted = True
                    continue
                events.append(["grant", rec.trial_id, rec.bracket_id,
                               rec.hparams])
                slots[s] = [rec.trial_id, 0]
        if all(x is None for x in slots):
            break
        for s in range(SLOTS):
            if slots[s] is None:
                continue
            tid, phase = slots[s]
            v = svc.report_verdict(tid, phase, _metric(tid, phase))
            ev = ["report", tid, phase, v.decision.value, v.kind.value]
            if v.kind is VerdictKind.CLONE:
                ev += [v.clone_from, v.perturb]
            events.append(ev)
            if v.decision is Decision.CONTINUE:
                slots[s][1] += 1
            elif v.decision is Decision.STOP:
                slots[s] = None
    else:
        raise AssertionError(f"{case}: stream did not drain")
    out = {"events": events,
           "rung_log": svc.barrier.rung_log if svc.barrier else None,
           "statuses": [svc.db.trials[t].status.value
                        for t in sorted(svc.db.trials)]}
    return json.loads(json.dumps(out))      # the fixture's own types


@pytest.fixture(scope="module")
def golden():
    with open(FIXTURE) as f:
        return json.load(f)["cases"]


@pytest.mark.parametrize("case", list(CASES))
def test_scheduler_matches_golden_stream(golden, case):
    got = drive(case)
    want = golden[case]
    grants = [e[3] for e in got["events"] if e[0] == "grant"]
    assert grants == [e[3] for e in want["events"] if e[0] == "grant"]
    assert got["events"] == want["events"]
    assert got["rung_log"] == want["rung_log"]
    assert got["statuses"] == want["statuses"]


def test_bracket_mode_wraps_any_scheduler():
    """Bracket mode is a wrapper over the scheduler passed in, so a
    scheduler with its own stopping rule keeps it behind the barrier."""
    policy = HyperTrick(SPACE, 6, 4, 0.3, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    assert isinstance(svc.scheduler, BracketScheduler)
    assert svc.scheduler.inner is policy
    assert svc.scheduler.n_phases == policy.n_phases
    assert svc.barrier.brackets == {0: (0, 2)}
    assert policy.db is svc.db                  # bind reached the inner
    with pytest.raises(AssertionError):         # brackets of its own
        OptimizationService(HyperbandScheduler(SPACE, 4, eta=2),
                            bracket_eta=3)


if __name__ == "__main__":
    assert len(sys.argv) == 3 and sys.argv[1] == "--record", __doc__
    with open(FIXTURE, "w") as f:
        json.dump({"header": sys.argv[2],
                   "cases": {c: drive(c) for c in CASES}}, f, indent=1)
        f.write("\n")
