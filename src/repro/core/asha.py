"""ASHA — asynchronous Successive Halving (Li et al. 2018), the partial
mitigation the paper cites for SH's synchronization problem (§2). Included
as a beyond-paper baseline: like HyperTrick it never blocks, but it uses
rung-based promotion (top 1/eta of the reports at each rung so far,
continuation variant) instead of the DCM/WSM early-worker rule. The rung
placement is ``core.scheduler.rung_phases``, shared with the bracket
barrier.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.scheduler import SampledScheduler, Verdict, rung_phases
from repro.core.search_space import SearchSpace


class ASHA(SampledScheduler):
    def __init__(self, space: SearchSpace, n_trials: int, n_phases: int,
                 eta: int = 3, seed: int = 0, configs: Optional[list] = None):
        super().__init__(space, n_trials, n_phases, seed=seed,
                         configs=configs)
        self.eta = eta
        # report counts gate promotion at each rung
        self.rungs = rung_phases(n_phases, eta)

    def on_report(self, trial_id, phase, metric, prior_reports) -> Verdict:
        if phase not in self.rungs or phase >= self.n_phases - 1:
            return Verdict.CONTINUE
        stats = self.db.metrics_for_phase(phase)
        if len(stats) < self.eta:            # not enough evidence yet
            return Verdict.CONTINUE
        cut = float(np.quantile(np.asarray(stats), 1.0 - 1.0 / self.eta))
        return Verdict.CONTINUE if metric >= cut else Verdict.STOP
