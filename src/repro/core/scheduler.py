"""The trial-lifecycle Scheduler: the one interface every metaoptimizer
in the repo implements, and the one verdict pipeline behind it.

The paper frames HyperTrick as one point in a family of population
metaoptimizers that trade exploration for compute efficiency on a
distributed system. A ``Scheduler`` owns the whole lifecycle of a
family member's trials:

* ``spawn()``                 -> the next configuration (plus which
                                 *bracket* it joins and that bracket's
                                 rung schedule);
* ``on_report(...)``          -> a ``Verdict``: continue / stop / demote /
                                 clone_from+perturb (parking is produced
                                 by the service's barrier for enrolled
                                 trials at their declared rungs);
* ``resolve_cohort(...)``     -> which members of a complete rung cohort
                                 are demoted (barrier schedulers only).

``SampledScheduler`` is the base of the budgeted samplers: HyperTrick,
random search, ASHA and evolutionary HyperTrick (``core.hypertrick``,
``core.asha``, ``core.evolution``) and ``PBTScheduler`` below
(exploit/explore via CLONE verdicts, executed device-side by the
population engine — compare Elfwing et al. (1702.07490) and SEARL
(2009.01555) for why copy-and-perturb populations matter for deep RL).
``BracketScheduler`` puts any scheduler behind one successive-halving
bracket; ``HyperbandScheduler`` runs multiple concurrent brackets, cohorts
keyed by ``(bracket_id, rung)``.

Imports point one way: ``search_space`` and ``completion``, then this
module and the samplers built on it, then ``core.service`` (which
dispatches on verdicts for every transport: thread cluster, TCP server,
on-device population engine), then the executors, server and engine
drivers.
"""
from __future__ import annotations

import enum
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.completion import hyperband_brackets
from repro.core.search_space import SearchSpace, perturb_hparams


class Decision(enum.Enum):
    """The transport-level decision a worker receives for a report (the
    wire's ``report_ok.decision``). ``Verdict`` is the richer scheduler-
    level value; ``Verdict.decision`` maps onto this."""
    CONTINUE = "continue"
    STOP = "stop"
    # rung barrier (bracket mode): the report is withheld server-side until
    # the trial's rung cohort is complete — keep the slot parked, keep the
    # lease alive, and poll by re-sending the identical report
    PARKED = "parked"


class TrialStatus(enum.Enum):
    RUNNING = "running"
    COMPLETED = "completed"     # ran all phases
    KILLED = "killed"           # evicted by the scheduler
    CRASHED = "crashed"         # worker failure (local effect only, §3.2)


class VerdictKind(enum.Enum):
    CONTINUE = "continue"
    STOP = "stop"          # scheduler eviction / terminal phase
    PARK = "park"          # withheld at the rung barrier (poll to resolve)
    DEMOTE = "demote"      # killed by a rung cohort's ranking
    CLONE = "clone"        # PBT exploit/explore: copy a parent, perturb


@dataclass(frozen=True)
class Verdict:
    """What happens to a trial after a report. ``CLONE`` carries the parent
    trial to copy learner state from (``clone_from``) and the perturbed
    hyperparameters the trial continues with (``perturb``)."""
    kind: VerdictKind
    clone_from: Optional[int] = None
    perturb: Optional[Dict[str, Any]] = None

    @property
    def decision(self) -> Decision:
        """The wire decision: CLONE rides a ``"continue"`` (plus the
        ``clone_from``/``perturb`` response fields); DEMOTE is a
        ``"stop"`` like any other kill."""
        return _DECISION_OF[self.kind]


_DECISION_OF = {
    VerdictKind.CONTINUE: Decision.CONTINUE,
    VerdictKind.CLONE: Decision.CONTINUE,
    VerdictKind.PARK: Decision.PARKED,
    VerdictKind.STOP: Decision.STOP,
    VerdictKind.DEMOTE: Decision.STOP,
}

# the four argument-less verdicts are singletons
Verdict.CONTINUE = Verdict(VerdictKind.CONTINUE)
Verdict.STOP = Verdict(VerdictKind.STOP)
Verdict.PARK = Verdict(VerdictKind.PARK)
Verdict.DEMOTE = Verdict(VerdictKind.DEMOTE)


class ReportReply(str):
    """A report decision as the worker-side string (``"continue"`` /
    ``"stop"`` / ``"parked"`` — compares equal to plain strings, so every
    pre-verdict driver keeps working) carrying the optional CLONE payload
    as attributes. Built by ``ServiceClient.report`` from the wire fields
    and by ``LocalDriver`` from the in-process ``Verdict``."""
    clone_from: Optional[int]
    perturb: Optional[Dict[str, Any]]

    def __new__(cls, decision: str, clone_from: Optional[int] = None,
                perturb: Optional[Dict[str, Any]] = None):
        self = super().__new__(cls, decision)
        self.clone_from = clone_from
        self.perturb = perturb
        return self


@dataclass(frozen=True)
class SpawnSpec:
    """One spawned trial: its configuration and the bracket it joins.
    ``bracket_id`` keys the service barrier's cohorts — two trials park
    together only when their ``(bracket_id, rung)`` match."""
    hparams: Dict[str, Any]
    bracket_id: int = 0


def rung_phases(n_phases: int, eta: int) -> list:
    """Rung placement shared by ASHA and the bracket barrier's
    successive-halving mode: rungs at phase indices eta^0-1, eta^1-1, ...
    (the final phase completes unconditionally and is never a rung)."""
    return sorted({min(eta ** i, n_phases) - 1
                   for i in range(0, 1 + max(1, int(
                       math.log(max(n_phases, 1), eta)) + 1))})


def rung_demotions(n: int, eta: int) -> int:
    """How many of an ``n``-trial rung cohort are demoted: the bottom
    ``n // eta``, EXCEPT that a cohort smaller than eta carries too little
    evidence to demote anyone (ASHA's "not enough evidence" rule, made
    explicit — ``n // eta`` happens to be 0 there too, but relying on the
    floor silently was how small-cohort demotion degraded to a no-op).
    Shared by the service-side ``RungBarrier``, so single-host and
    multi-host brackets agree by construction."""
    assert eta >= 2, eta
    if n < eta:
        return 0
    return n // eta


def bottom_indices(metrics: list, k: int) -> set:
    """Indices (into ``metrics``'s order — the cohort's park order) of the
    bottom ``k`` members: ONE stable ascending argsort over float32
    metrics (matching the on-device ranking dtype), ties broken by
    position. The single ranking rule every rung scheduler shares — the
    bottom-1/eta barrier and Hyperband's keep-top-1/eta both slice it."""
    order = np.argsort(np.asarray(metrics, np.float32), kind="stable")
    return set(order[:max(0, k)].tolist())


def demote_indices(metrics: list, eta: int) -> set:
    """The members a bottom-1/eta rung barrier demotes: the bottom
    ``rung_demotions`` of the stable ranking."""
    return bottom_indices(metrics, rung_demotions(len(metrics), eta))


class Scheduler:
    """Owns the whole trial lifecycle. ``brackets`` maps each bracket_id to
    its tuple of rung phases; an empty mapping means the scheduler never
    parks anything (purely asynchronous search). Subclasses implement
    ``spawn`` and ``on_report``; barrier schedulers also implement
    ``resolve_cohort``."""

    n_phases: int = 1
    # bracket_id -> tuple of rung phase indices (ascending, final phase
    # excluded). The service builds its RungBarrier from this.
    brackets: Dict[int, Tuple[int, ...]] = {}

    def bind(self, db) -> None:
        self.db = db

    def spawn(self) -> Optional[SpawnSpec]:
        """The next configuration to explore, or None when the budget is
        spent."""
        raise NotImplementedError

    def on_report(self, trial_id: int, phase: int, metric: float,
                  prior_reports: int) -> Verdict:
        raise NotImplementedError

    def resolve_cohort(self, bracket_id: int, rung: int,
                       metrics: List[float]) -> Set[int]:
        """Indices (into the cohort's park order) of the members demoted at
        this rung. Only called for brackets declared in ``brackets``."""
        return set()

    def split_entry_capacity(self, capacity: int) -> Dict[int, int]:
        """How many entrants each bracket's ENTRY cohort should wait for,
        given ``capacity`` total worker slots. Single-bracket schedulers
        put all of it on bracket 0; Hyperband splits it in fill order."""
        return {b: capacity for b in list(self.brackets)[:1]}

    def attribute_refill(self, freed: int) -> Dict[int, int]:
        """``freed`` slots just opened at a rung resolution: which
        brackets' entry cohorts should wait for the refills the freed
        capacity will acquire next?"""
        return {b: freed for b in list(self.brackets)[:1]}

    def note_replayed_trial(self, hparams: Dict[str, Any],
                            requeued: bool = False) -> None:
        """A trial issued by a previous incarnation of the service (journal
        replay). Budget-accounting subclasses override this."""


class SampledScheduler(Scheduler):
    """A budget of ``n_trials`` configurations that never park: drawn from
    ``space`` with ``rng``, or taken in order from pre-drawn ``configs``
    (e.g. to compare against Hyperband on the *same* configurations, paper
    §5.2.4). Subclasses implement ``on_report`` and may override
    ``_draw``."""

    def __init__(self, space: SearchSpace, n_trials: int, n_phases: int,
                 seed: int = 0, configs: Optional[list] = None):
        self.space = space
        self.n_trials = n_trials
        self.n_phases = n_phases
        self.rng = np.random.default_rng(seed)
        self._configs = list(configs) if configs is not None else None
        if self._configs is not None:
            assert len(self._configs) == n_trials, (
                f"got {len(self._configs)} configs for {n_trials} trials — "
                "same-configs comparisons (§5.2.4) require exactly one "
                "config per trial")
        self._launched = 0

    def spawn(self) -> Optional[SpawnSpec]:
        if self._launched >= self.n_trials:
            return None
        self._launched += 1
        return SpawnSpec(self._draw())

    def _draw(self) -> Dict[str, Any]:
        """The configuration of the ``_launched``-th spawn."""
        if self._configs is not None:
            return self._configs[self._launched - 1]
        return self.space.sample(self.rng)

    def note_replayed_trial(self, hparams, requeued: bool = False) -> None:
        if not requeued:
            self._launched += 1


class BracketScheduler(Scheduler):
    """``inner`` behind ONE successive-halving bracket (id 0): its rung
    phases park at the service barrier and demote the bottom ``n // eta``
    of each pooled cohort (ASHA's small-cohort rule included). ``inner``
    spawns the configurations and may still stop trials between rungs."""

    def __init__(self, inner: Scheduler, eta: int):
        assert eta >= 2, eta
        self.inner = inner
        self.eta = eta
        self.n_phases = inner.n_phases
        self.brackets = {0: tuple(p for p in rung_phases(inner.n_phases, eta)
                                  if p < inner.n_phases - 1)}

    def bind(self, db) -> None:
        self.db = db
        self.inner.bind(db)

    def spawn(self) -> Optional[SpawnSpec]:
        return self.inner.spawn()

    def on_report(self, trial_id, phase, metric, prior_reports) -> Verdict:
        return self.inner.on_report(trial_id, phase, metric, prior_reports)

    def resolve_cohort(self, bracket_id, rung, metrics) -> Set[int]:
        return demote_indices(metrics, self.eta)

    def note_replayed_trial(self, hparams, requeued: bool = False) -> None:
        self.inner.note_replayed_trial(hparams, requeued)


class HyperbandScheduler(Scheduler):
    """Full Hyperband (Li et al. 2016) as one Scheduler: every bracket of
    the ``(eta, R)`` construction runs CONCURRENTLY against the shared
    worker pool. Bracket ``s`` spawns its ``n0_s`` configurations (fill
    order: most-aggressive bracket first), runs rungs at phase indices
    ``r_i - 1``, and the service barrier keys each cohort by
    ``(bracket_id, rung)`` — so two brackets' cohorts at the same phase
    resolve independently. Demotion is classic SH: keep the top
    ``max(1, n // eta)`` of each cohort (ranking rule shared with the
    single-bracket barrier via ``bottom_indices``)."""

    def __init__(self, space: SearchSpace, n_phases: int, eta: int = 3,
                 seed: int = 0, plan=None):
        assert eta >= 2, eta
        self.space = space
        self.n_phases = n_phases                 # R, in phases
        self.eta = eta
        self.rng = np.random.default_rng(seed)
        self.plan = list(plan) if plan is not None \
            else hyperband_brackets(eta, n_phases)
        self.brackets = {}
        self._quota: List[int] = []              # configs per bracket
        for b, br in enumerate(self.plan):
            rungs = tuple(sorted({r - 1 for r in br.r[:-1]
                                  if 0 < r < n_phases}))
            if rungs:
                self.brackets[b] = rungs
            self._quota.append(br.n[0])
        self.n_trials = sum(self._quota)         # budget, for capacity math
        self._spawned = [0] * len(self.plan)

    def spawn(self) -> Optional[SpawnSpec]:
        for b, quota in enumerate(self._quota):
            if self._spawned[b] < quota:
                self._spawned[b] += 1
                return SpawnSpec(self.space.sample(self.rng), bracket_id=b)
        return None

    def on_report(self, trial_id, phase, metric, prior_reports) -> Verdict:
        return Verdict.CONTINUE                  # all decisions are rungs'

    def resolve_cohort(self, bracket_id, rung, metrics) -> Set[int]:
        keep = max(1, len(metrics) // self.eta)
        return bottom_indices(metrics, len(metrics) - keep)

    def split_entry_capacity(self, capacity: int) -> Dict[int, int]:
        # sequential fill: bracket b's entrants start arriving only after
        # the earlier brackets' quotas are granted
        out, offset = {}, 0
        for b, quota in enumerate(self._quota):
            share = max(0, min(quota, capacity - offset))
            offset += quota
            if b in self.brackets and share:
                out[b] = share
        return out

    def attribute_refill(self, freed: int) -> Dict[int, int]:
        # freed capacity acquires the next unspawned configurations, which
        # belong to whichever brackets still have quota in fill order —
        # rungless brackets consume their share of the freed capacity too
        # (their spawns have no entry cohort to wait for)
        out: Dict[int, int] = {}
        for b, quota in enumerate(self._quota):
            if freed <= 0:
                break
            take = min(max(0, quota - self._spawned[b]), freed)
            if take and b in self.brackets:
                out[b] = take
            freed -= take
        return out

    def note_replayed_trial(self, hparams, requeued: bool = False) -> None:
        if requeued:
            return
        for b, quota in enumerate(self._quota):
            if self._spawned[b] < quota:
                self._spawned[b] += 1
                return


class PBTScheduler(SampledScheduler):
    """Population Based Training as a Scheduler: a fixed population runs
    every phase; a member whose phase metric falls in the bottom
    ``exploit_frac`` quantile of that phase's reports receives a CLONE
    verdict — copy the learner state of a uniformly-drawn top
    ``top_frac`` member and continue with a perturbed copy of its
    hyperparameters (``search_space.perturb_hparams``). On the on-device
    population engine the copy is a device-side slot-to-slot transfer
    (weights never leave the device); scalar workers adopt the perturbed
    hyperparameters and keep their own learner state (weights never cross
    hosts). ``frozen`` hyperparameters (structural: ``t_max``) are never
    perturbed, so a cloned trial stays in its engine bucket.

    Purely asynchronous — no barrier, no parking: the exploit decision
    uses whatever metrics have been reported for the phase so far, the
    same knowledge-DB-quantile shape as HyperTrick's WSM rule.
    """

    def __init__(self, space: SearchSpace, population: int, n_phases: int,
                 seed: int = 0, exploit_frac: float = 0.25,
                 top_frac: float = 0.25, min_reports: Optional[int] = None,
                 frozen: Sequence[str] = ("t_max",)):
        assert 0 < exploit_frac < 1 and 0 < top_frac <= 1
        super().__init__(space, population, n_phases, seed=seed)
        self.population = population
        self.exploit_frac = exploit_frac
        self.top_frac = top_frac
        self.min_reports = (min_reports if min_reports is not None
                            else max(2, population // 2))
        self.frozen = tuple(frozen)
        # (trial_id, clone_from, phase) per CLONE verdict issued
        self.clone_log: List[Tuple[int, int, int]] = []

    def on_report(self, trial_id, phase, metric, prior_reports) -> Verdict:
        if phase >= self.n_phases - 1:
            return Verdict.CONTINUE              # final phase: completes
        stats = self.db.metrics_for_phase(phase)
        if len(stats) < self.min_reports:
            return Verdict.CONTINUE
        cut = float(np.quantile(np.asarray(stats, np.float32),
                                self.exploit_frac))
        if metric > cut:
            return Verdict.CONTINUE
        parent = self._pick_parent(trial_id, phase)
        if parent is None:
            return Verdict.CONTINUE
        child = self.db.trials[trial_id]
        hp = perturb_hparams(self.space, parent.hparams, self.rng,
                             frozen=self.frozen)
        for name in self.frozen:                 # child keeps its structure
            if name in child.hparams:
                hp[name] = child.hparams[name]
        self.clone_log.append((trial_id, parent.trial_id, phase))
        return Verdict(VerdictKind.CLONE, clone_from=parent.trial_id,
                       perturb=hp)

    def _pick_parent(self, trial_id: int, phase: int):
        """A uniform draw from the top ``top_frac`` of the members that
        have reported this phase (crashed trials excluded — their learner
        state is gone)."""
        peers = [t for t in self.db.trials.values()
                 if t.trial_id != trial_id and t.phases_completed > phase
                 and t.status is not TrialStatus.CRASHED]
        if not peers:
            return None
        peers.sort(key=lambda t: -t.reports[phase][0])
        top = peers[: max(1, int(math.ceil(self.top_frac * len(peers))))]
        return top[int(self.rng.integers(len(top)))]
