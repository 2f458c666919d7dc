"""HyperTrick (paper §3.2, Algorithm 1).

Each trial runs N_p phases. Per phase p the scheduler starts in Data
Collection Mode: the first W_p^DCM = W0 (1-sqrt(r)) (1-r)^p reporters
continue unconditionally. After that it is in Worker Selection Mode: a
reporter whose metric falls in the lower sqrt(r) quantile of the metrics
reported for that phase is terminated. Under a stationary metric process
this yields E[W_p] = W0 (1-r)^p (Eq. 1; proof by induction in the paper —
mirrored by a hypothesis test in tests/test_hypertrick_math.py).

No synchronization, no preemption: a worker that is stopped frees its node,
which immediately acquires a fresh configuration. Both schedulers here are
``SampledScheduler``s: a budget of W0 configurations, sampled or given.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from repro.core.scheduler import SampledScheduler, Verdict
from repro.core.search_space import SearchSpace


def expected_workers(w0: int, r: float, p: int) -> float:
    """Eq. (1): E[W_p] = W0 (1-r)^p."""
    return w0 * (1 - r) ** p


def dcm_threshold(w0: int, r: float, p: int) -> float:
    """Eq. (2): W_p^DCM = W0 (1-sqrt(r)) (1-r)^p."""
    return w0 * (1 - math.sqrt(r)) * (1 - r) ** p


class HyperTrick(SampledScheduler):
    def __init__(self, space: SearchSpace, w0: int, n_phases: int,
                 eviction_rate: float, seed: int = 0,
                 configs: Optional[list] = None):
        assert 0 < eviction_rate < 1
        super().__init__(space, w0, n_phases, seed=seed, configs=configs)
        self.w0 = w0
        self.r = eviction_rate

    def on_report(self, trial_id: int, phase: int, metric: float,
                  prior_reports: int) -> Verdict:
        if prior_reports < dcm_threshold(self.w0, self.r, phase):
            return Verdict.CONTINUE           # Data Collection Mode
        # Worker Selection Mode: lower sqrt(r) quantile of this phase's stats
        stats = self.db.metrics_for_phase(phase)
        cut = float(np.quantile(np.asarray(stats), math.sqrt(self.r)))
        return Verdict.STOP if metric < cut else Verdict.CONTINUE


class RandomSearchPolicy(SampledScheduler):
    """Parallel random search, no early stopping (alpha = 100%)."""

    def on_report(self, trial_id, phase, metric, prior_reports) -> Verdict:
        return Verdict.CONTINUE
