"""The unified trial-lifecycle Scheduler: one verdict pipeline for
HyperTrick, full Hyperband (multiple concurrent brackets keyed by
(bracket_id, rung)), and PBT exploit/explore — plus the speculative
rung-0 refill ordering and the clone_from/perturb wire extension."""
import json

import numpy as np
import pytest

from repro.core.executor import ProcessCluster
from repro.core.hypertrick import HyperTrick, RandomSearchPolicy
from repro.core.scheduler import (BracketScheduler, HyperbandScheduler,
                                  PBTScheduler, ReportReply, SpawnSpec,
                                  Verdict, VerdictKind)
from repro.core.search_space import (Categorical, LogUniform, SearchSpace,
                                     perturb_hparams)
from repro.core.service import (Decision, OptimizationService, TrialStatus)
from repro.distributed import protocol as proto
from repro.distributed.client import ServiceClient
from repro.distributed.server import MetaoptServer


def _space():
    return SearchSpace({"x": LogUniform(0.01, 100.0)})


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# the verdict vocabulary
# ---------------------------------------------------------------------------
def test_verdict_decision_mapping():
    assert Verdict.CONTINUE.decision is Decision.CONTINUE
    assert Verdict.STOP.decision is Decision.STOP
    assert Verdict.DEMOTE.decision is Decision.STOP
    assert Verdict.PARK.decision is Decision.PARKED
    clone = Verdict(VerdictKind.CLONE, clone_from=3, perturb={"x": 1.0})
    assert clone.decision is Decision.CONTINUE   # rides a continue + fields


def test_report_reply_is_a_decision_string_with_payload():
    r = ReportReply("continue", clone_from=7, perturb={"x": 2.0})
    assert r == "continue" and r != "stop"
    assert r.clone_from == 7 and r.perturb == {"x": 2.0}
    assert ReportReply("parked").clone_from is None


def test_policy_scheduler_wraps_classic_policies():
    policy = HyperTrick(_space(), w0=3, n_phases=2, eviction_rate=0.25)
    svc = OptimizationService(policy)
    assert svc.scheduler is policy
    assert svc.barrier is None                   # async: nothing ever parks
    recs = [svc.acquire_trial() for _ in range(3)]
    assert svc.acquire_trial() is None
    assert svc.report(recs[0].trial_id, 0, 1.0) is Decision.CONTINUE


def test_bracket_scheduler_reproduces_single_bracket():
    policy = RandomSearchPolicy(_space(), 4, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    assert isinstance(svc.scheduler, BracketScheduler)
    assert svc.scheduler.inner is policy
    assert svc.barrier.brackets == {0: tuple(svc.barrier.rungs)}
    assert svc.scheduler.resolve_cohort(0, 0, [3.0, 1.0, 2.0]) == {1}


# ---------------------------------------------------------------------------
# full Hyperband: concurrent brackets, per-bracket cohorts
# ---------------------------------------------------------------------------
def test_hyperband_plan_and_bracket_rungs():
    hb = HyperbandScheduler(_space(), n_phases=4, eta=2, seed=0)
    # (eta=2, R=4): s=2 -> n0=4, rungs at phases 0,1; s=1 -> n0=3, rung at
    # phase 1; s=0 -> n0=3, no rungs (runs to completion)
    assert hb.brackets == {0: (0, 1), 1: (1,)}
    assert hb._quota == [4, 3, 3] and hb.n_trials == 10
    got = [hb.spawn() for _ in range(10)]
    assert [s.bracket_id for s in got] == [0] * 4 + [1] * 3 + [2] * 3
    assert hb.spawn() is None
    # classic SH demotion: keep top max(1, n // eta)
    assert hb.resolve_cohort(0, 0, [3.0, 1.0, 2.0, 4.0]) == {1, 2}
    assert hb.resolve_cohort(1, 1, [1.0]) == set()
    # entry capacity splits in fill order, rungless brackets excluded
    assert hb.split_entry_capacity(10) == {0: 4, 1: 3}
    assert hb.split_entry_capacity(5) == {0: 4, 1: 1}
    assert hb.split_entry_capacity(3) == {0: 3}


def test_hyperband_cohorts_resolve_independently_in_process():
    hb = HyperbandScheduler(_space(), n_phases=4, eta=2, seed=0)
    svc = OptimizationService(hb)
    svc.configure_bracket(expect_entrants=hb.n_trials)
    recs = [svc.acquire_trial(rung=0) for _ in range(hb.n_trials)]
    by_b = {}
    for r in recs:
        by_b.setdefault(r.bracket_id, []).append(r)
    # bracket 1's trials pass phase 0 freely (their first rung is phase 1)
    for r in by_b[1]:
        assert svc.report(r.trial_id, 0, 5.0) is Decision.CONTINUE
    # bracket 0 parks at phase 0; resolving it must not touch bracket 1
    for i, r in enumerate(by_b[0]):
        assert svc.report(r.trial_id, 0, float(i)) is Decision.PARKED
    entry = svc.barrier.rung_log[0]
    assert entry["bracket"] == 0 and entry["phase"] == 0 and entry["n"] == 4
    assert len(entry["demoted"]) == 2            # keep top 4 // 2
    # both brackets park at phase 1 — SEPARATE cohorts at the same phase
    b0_live = [r for r in by_b[0]
               if svc.db.trials[r.trial_id].status is TrialStatus.RUNNING]
    for r in b0_live:                            # poll verdicts, then phase 1
        assert svc.report(r.trial_id, 0, 0.0) is Decision.CONTINUE
    for i, r in enumerate(b0_live):
        assert svc.report(r.trial_id, 1, float(i)) is Decision.PARKED
    # bracket 0's phase-1 cohort resolved alone (n=2), bracket 1 untouched
    entry = svc.barrier.rung_log[1]
    assert entry["bracket"] == 0 and entry["phase"] == 1 and entry["n"] == 2
    for i, r in enumerate(by_b[1]):
        assert svc.report(r.trial_id, 1, float(i)) is Decision.PARKED
    entry = svc.barrier.rung_log[2]
    assert entry["bracket"] == 1 and entry["phase"] == 1 and entry["n"] == 3
    assert len(entry["demoted"]) == 2            # keep top max(1, 3 // 2)
    # rungless bracket 2 runs every phase unbarriered
    r = by_b[2][0]
    for p in range(4):
        d = svc.report(r.trial_id, p, 1.0)
    assert d is Decision.STOP
    assert svc.db.trials[r.trial_id].status is TrialStatus.COMPLETED


def test_hyperband_two_concurrent_brackets_over_process_backend():
    """The acceptance scenario: one Hyperband run, >= 2 concurrent
    brackets, OS-process scalar workers over TCP, per-bracket cohorts
    resolving independently at the server-side barrier."""
    hb = HyperbandScheduler(_space(), n_phases=4, eta=2, seed=0)
    cluster = ProcessCluster(hb.n_trials, {"kind": "synthetic",
                                           "sleep": 0.01},
                             lease_ttl=15.0, heartbeat_interval=0.2)
    res = cluster.run(hb)
    s = res.summary()
    assert s["n_trials"] == 10
    by_b = {}
    for e in s["rungs"]:
        by_b.setdefault(e["bracket"], []).append(e)
    assert set(by_b) == {0, 1}                   # two brackets ran cohorts
    b0 = sorted(by_b[0], key=lambda e: e["phase"])
    assert [(e["phase"], e["n"], len(e["demoted"])) for e in b0] \
        == [(0, 4, 2), (1, 2, 1)]
    assert [(e["phase"], e["n"], len(e["demoted"])) for e in by_b[1]] \
        == [(1, 3, 2)]
    # survivors: 1 from bracket 0, 1 from bracket 1, all 3 of rungless s=0
    assert s["by_status"] == {"killed": 5, "completed": 5}


def test_hyperband_requeue_rejoins_its_bracket():
    hb = HyperbandScheduler(_space(), n_phases=4, eta=2, seed=0)
    svc = OptimizationService(hb)
    recs = [svc.acquire_trial(rung=0) for _ in range(5)]
    dead = recs[4]                               # a bracket-1 trial dies
    assert dead.bracket_id == 1
    svc.crash(dead.trial_id)
    svc.requeue(dead.hparams, dead.bracket_id)
    rest = [svc.acquire_trial(rung=0) for _ in range(6)]
    refill = rest[0]                             # requeues precede fresh draws
    assert refill.hparams == dead.hparams and refill.bracket_id == 1


# ---------------------------------------------------------------------------
# PBT: clone verdicts through the service and over the wire
# ---------------------------------------------------------------------------
def test_pbt_clone_verdict_and_hparam_swap():
    pbt = PBTScheduler(_space(), population=3, n_phases=3, seed=0,
                       exploit_frac=0.5, top_frac=0.25, min_reports=2)
    svc = OptimizationService(pbt)
    t0, t1, t2 = (svc.acquire_trial() for _ in range(3))
    assert svc.report_verdict(t0.trial_id, 0, 3.0).kind \
        is VerdictKind.CONTINUE                  # below min_reports
    assert svc.report_verdict(t1.trial_id, 0, 5.0).kind \
        is VerdictKind.CONTINUE                  # above the cut
    orig = dict(t2.hparams)
    v = svc.report_verdict(t2.trial_id, 0, 1.0)
    assert v.kind is VerdictKind.CLONE
    assert v.clone_from == t1.trial_id           # the top peer
    assert v.perturb is not None and v.perturb != orig
    # the live record now carries the perturbed configuration
    assert svc.db.trials[t2.trial_id].hparams == v.perturb
    assert pbt.clone_log == [(t2.trial_id, t1.trial_id, 0)]
    # PBT never kills: every member completes its final phase
    assert svc.report(t2.trial_id, 1, 1.0) is Decision.CONTINUE
    assert svc.report(t2.trial_id, 2, 1.0) is Decision.STOP
    assert svc.db.trials[t2.trial_id].status is TrialStatus.COMPLETED


def test_pbt_clone_rides_report_response_over_tcp():
    pbt = PBTScheduler(_space(), population=3, n_phases=2, seed=0,
                       exploit_frac=0.5, min_reports=2)
    svc = OptimizationService(pbt)
    with MetaoptServer(svc) as server:
        with ServiceClient(server.host, server.port) as c:
            t0, t1, t2 = c.acquire(), c.acquire(), c.acquire()
            assert c.report(t0.trial_id, 0, 3.0) == "continue"
            assert c.report(t1.trial_id, 0, 5.0) == "continue"
            reply = c.report(t2.trial_id, 0, 1.0)
            assert reply == "continue"           # a clone IS a continue
            assert reply.clone_from == t1.trial_id
            assert isinstance(reply.perturb, dict)
    assert svc.db.trials[t2.trial_id].hparams == reply.perturb


def test_pbt_frozen_hparams_keep_child_structure():
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "t_max": Categorical((4, 8))})
    pbt = PBTScheduler(space, population=8, n_phases=2, seed=0,
                       exploit_frac=0.9, min_reports=2, frozen=("t_max",))
    svc = OptimizationService(pbt)
    recs = [svc.acquire_trial() for _ in range(8)]
    clones = 0
    for i, r in enumerate(recs):
        v = svc.report_verdict(r.trial_id, 0, float(i % 3))
        if v.kind is VerdictKind.CLONE:
            clones += 1
            assert v.perturb["t_max"] == r.hparams["t_max"]
    assert clones >= 1


def test_pbt_frozen_from_objective_spec_ga3c():
    """Wiring ``frozen=spec_for("rl").structural`` freezes exactly the
    objective-declared structural keys: a CLONE verdict's perturb keeps the
    child's ``t_max`` while the traced keys move."""
    from repro.population.objectives import spec_for
    assert spec_for("rl").structural == ("t_max",)
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "gamma": Categorical((0.99, 0.995)),
                         "t_max": Categorical((4, 8))})
    pbt = PBTScheduler(space, population=8, n_phases=2, seed=0,
                       exploit_frac=0.9, min_reports=2,
                       frozen=spec_for("rl").structural)
    svc = OptimizationService(pbt)
    recs = [svc.acquire_trial() for _ in range(8)]
    clones = 0
    for i, r in enumerate(recs):
        orig = dict(r.hparams)          # the record mutates on CLONE
        v = svc.report_verdict(r.trial_id, 0, float(i % 3))
        if v.kind is VerdictKind.CLONE:
            clones += 1
            assert set(v.perturb) == set(orig)
            # structural: the child keeps its compiled bucket
            assert v.perturb["t_max"] == orig["t_max"]
            # traced: genuinely explored (parent's lr, perturbed)
            assert v.perturb["learning_rate"] != orig["learning_rate"]
    assert clones >= 1


def test_pbt_frozen_from_objective_spec_lm():
    """The same rule for the LM workload: ``loss_chunk`` (its declared
    structural key) survives CLONE perturbation unchanged."""
    from repro.population.objectives import spec_for
    assert spec_for("lm").structural == ("loss_chunk",)
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "loss_chunk": Categorical((256, 1024))})
    pbt = PBTScheduler(space, population=8, n_phases=2, seed=0,
                       exploit_frac=0.9, min_reports=2,
                       frozen=spec_for("lm").structural)
    svc = OptimizationService(pbt)
    recs = [svc.acquire_trial() for _ in range(8)]
    clones = 0
    for i, r in enumerate(recs):
        orig = dict(r.hparams)
        v = svc.report_verdict(r.trial_id, 0, float(i % 3))
        if v.kind is VerdictKind.CLONE:
            clones += 1
            assert v.perturb["loss_chunk"] == orig["loss_chunk"]
            assert v.perturb["learning_rate"] != orig["learning_rate"]
    assert clones >= 1


def test_perturb_hparams_respects_frozen_and_bounds():
    space = SearchSpace({"lr": LogUniform(1e-5, 1e-1),
                         "g": Categorical((0.9, 0.99, 0.999))})
    rng = np.random.default_rng(0)
    hp = {"lr": 1e-5, "g": 0.9}
    for _ in range(50):
        m = perturb_hparams(space, hp, rng, frozen=("g",))
        assert 1e-5 <= m["lr"] <= 1e-1
        assert m["g"] == 0.9                     # frozen: copied through
        hp = m


# ---------------------------------------------------------------------------
# speculative rung-0 refill: the acquire-ordering tweak
# ---------------------------------------------------------------------------
def test_hinted_acquire_resolves_ready_cohort_before_enrolling():
    """A speculative entrant (acquired while a fully-parked entry cohort
    is only waiting out its patience window) must land in the NEXT
    generation: the ready cohort resolves first, then the grant enrolls."""
    clock = _Clock()
    policy = RandomSearchPolicy(_space(), 4, 3, seed=0)
    svc = OptimizationService(policy, clock=clock, bracket_eta=2)
    svc.barrier.expect_entrants(3)               # one entrant never arrives
    svc.barrier.entrant_patience = 5.0
    a = svc.acquire_trial(rung=0)
    b = svc.acquire_trial(rung=0)
    assert svc.report(a.trial_id, 0, 1.0) is Decision.PARKED
    assert svc.report(b.trial_id, 0, 2.0) is Decision.PARKED
    assert not svc.barrier.rung_log              # waiting on the 3rd entrant
    clock.t = 6.0                                # patience expires silently
    c = svc.acquire_trial(rung=0)                # the speculative refill
    # ordering: the gen-1 cohort resolved BEFORE c enrolled — n stayed 2
    entry = svc.barrier.rung_log[0]
    assert entry["n"] == 2 and entry["demoted"] == [a.trial_id]
    # ... and c heads a fresh generation at the entry rung
    assert svc.barrier.heading_rung(c.trial_id) == 0
    assert not svc.barrier.is_parked(c.trial_id)


# ---------------------------------------------------------------------------
# the on-device clone path (PBT on the population engine)
# ---------------------------------------------------------------------------
def test_on_device_clone_is_bit_identical():
    """A CLONE verdict executed by the engine is a device-side slot-to-slot
    copy: the child's params and optimizer state become bit-identical to
    the parent's, the env/loop state stays the child's own, and the
    perturbed hyperparameters are installed."""
    import jax
    from repro.population.engine import PopulationEngine, TrialLease
    engine = PopulationEngine("pong", max_slots=2, n_envs=2,
                              episodes_per_phase=10 ** 9,
                              max_updates=10 ** 9, seed=0)
    hp0 = {"learning_rate": 1e-3, "t_max": 4, "gamma": 0.99}
    hp1 = {"learning_rate": 4e-4, "t_max": 4, "gamma": 0.995}
    engine.admit(TrialLease(0, dict(hp0)))
    engine.admit(TrialLease(1, dict(hp1)))
    bucket = engine.buckets[4]
    # different trial seeds -> different initial params
    assert any(not np.array_equal(np.asarray(a)[0], np.asarray(a)[1])
               for a in jax.tree.leaves(bucket.params))
    loop_before = [np.asarray(a).copy()
                   for a in jax.tree.leaves(bucket.loop)]
    perturb = {"learning_rate": 5e-4, "t_max": 4, "gamma": 0.99}
    reply = ReportReply("continue", clone_from=0, perturb=perturb)
    engine._exploit(bucket, 1, bucket.meta[1], reply)
    assert engine.clones == 1
    for a in jax.tree.leaves(bucket.params):
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(a)[0])
    for a in jax.tree.leaves(bucket.opt_state):
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(a)[0])
    # the env/loop state was NOT copied: the clone explores its own envs
    for before, after in zip(loop_before, jax.tree.leaves(bucket.loop)):
        np.testing.assert_array_equal(np.asarray(after), before)
    assert bucket.meta[1].hparams == perturb
    assert bucket.lr[1] == np.float32(5e-4)
    # an absent parent degrades to hparam adoption (no copy, no crash)
    reply = ReportReply("continue", clone_from=99,
                        perturb=dict(perturb, learning_rate=2e-4))
    engine._exploit(bucket, 1, bucket.meta[1], reply)
    assert engine.clones == 1                    # no device copy happened
    assert bucket.lr[1] == np.float32(2e-4)


def test_pbt_on_vectorized_backend_clones_end_to_end():
    """The acceptance scenario: a PBT run on the on-device population
    engine performs at least one device-side slot clone+perturb, and the
    whole population completes (PBT never kills)."""
    from repro.core.executor import PopulationCluster
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "t_max": Categorical((4,)),
                         "gamma": Categorical((0.99,))})
    pbt = PBTScheduler(space, population=4, n_phases=3, seed=0,
                       exploit_frac=0.9, min_reports=2)
    res = PopulationCluster(4, game="pong", episodes_per_phase=2, n_envs=2,
                            max_updates=5, seed=0).run(pbt)
    s = res.summary()
    assert s["n_trials"] == 4
    assert s["by_status"] == {"completed": 4}
    assert s["clones"] == len(pbt.clone_log) >= 1
    assert s["clones_on_device"] >= 1


def test_engine_speculative_refill_overlaps_barrier_wait():
    """Speculative rung-0 refill: once every local slot is parked at the
    barrier, the engine acquires the entrants its demotions will make
    room for BEFORE the verdict polls deliver — the acquire must be
    observed while the cohort is still parked."""
    from repro.population.engine import PopulationEngine, TrialLease

    class ScriptedDriver:
        """3-slot bracket, eta=3: parks trials 0-2 at phase 0, withholds
        verdicts until the engine has acquired the speculative entrant,
        then demotes trial 0."""

        def __init__(self):
            self.granted = 0
            self.parked = set()
            self.speculative_acquires = 0
            self.resolved = False

        def acquire_many(self, k, rung=None):
            assert rung == 0                     # bracket participants hint
            if len(self.parked) == 3 and not self.resolved:
                self.speculative_acquires += 1
            leases = []
            for _ in range(min(k, 4 - self.granted)):
                leases.append(TrialLease(
                    self.granted, {"learning_rate": 1e-3, "t_max": 4,
                                   "gamma": 0.99}, 2))
                self.granted += 1
            return leases, None

        def report(self, tid, phase, metric, ts, te, env_steps=None):
            if phase == 0 and tid < 3:
                self.parked.add(tid)
                if self.speculative_acquires:    # entrant already granted
                    self.resolved = True
                    return "stop" if tid == 0 else "continue"
                return "parked"
            return "stop" if phase >= 1 else "continue"

        def poll_lost(self):
            return set()

    engine = PopulationEngine("pong", max_slots=3, n_envs=2,
                              episodes_per_phase=1, max_updates=1, seed=0,
                              bracket_eta=3)
    engine.park_poll_interval = 0.0
    driver = ScriptedDriver()
    engine.run(driver)
    assert driver.speculative_acquires >= 1      # acquired while parked
    assert engine.speculated == 1                # exactly n // eta = 1
    assert driver.granted == 4                   # 3 initial + 1 speculative


# ---------------------------------------------------------------------------
# protocol evolution: clone payload + bracket ids on the wire
# ---------------------------------------------------------------------------
def test_report_response_clone_fields_wire_compat():
    # a plain report_ok carries NO clone fields at all (rule 3)
    wire = proto.encode(proto.ReportResponse(decision="continue"))[4:]
    body = json.loads(wire.decode())
    assert "clone_from" not in body and "perturb" not in body
    # an old peer's frame without them still decodes
    msg = proto.decode(json.dumps({"type": "report_ok",
                                   "decision": "stop"}).encode())
    assert msg.clone_from is None and msg.perturb is None
    # and a clone frame round-trips
    msg = proto.decode(proto.encode(proto.ReportResponse(
        decision="continue", clone_from=4, perturb={"x": 2.0}))[4:])
    assert msg.clone_from == 4 and msg.perturb == {"x": 2.0}


def test_acquire_response_bracket_id_wire_compat():
    wire = proto.encode(proto.AcquireResponse(0, {"x": 1.0}, 2))[4:]
    assert "bracket_id" not in json.loads(wire.decode())
    msg = proto.decode(proto.encode(proto.AcquireResponse(
        0, {"x": 1.0}, 2, bracket_id=3))[4:])
    assert msg.bracket_id == 3


def test_server_sends_bracket_id_for_hyperband_leases():
    hb = HyperbandScheduler(_space(), n_phases=4, eta=2, seed=0)
    svc = OptimizationService(hb)
    with MetaoptServer(svc) as server:
        with ServiceClient(server.host, server.port) as c:
            resp = c._call(proto.AcquireRequest(slots=hb.n_trials, rung=0))
    # the primary lease is bracket 0: the field is omitted (back-compat);
    # batch entries carry "bracket_id" exactly when nonzero
    assert resp.bracket_id is None
    bids = [e.get("bracket_id", 0) for e in resp.batch]
    assert bids == [0, 0, 0, 1, 1, 1, 2, 2, 2]
    recs = {r.trial_id: r.bracket_id for r in svc.db.trials.values()}
    assert sorted(recs.values()) == [0] * 4 + [1] * 3 + [2] * 3
