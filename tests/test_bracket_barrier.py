"""The service-side rung barrier (multi-host successive-halving brackets):
cohorts pooled across connections, the parked/poll decision protocol,
reaper-shrink resolution, small-cohort demotion rules, and the
ProcessCluster distributed-correctness fixes that ride along."""
import json
import socket
import subprocess
import sys
import time
import warnings

import pytest

from repro.core.executor import ProcessCluster
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.scheduler import demote_indices, rung_demotions
from repro.core.search_space import LogUniform, SearchSpace
from repro.core.service import (Decision, OptimizationService, TrialStatus)
from repro.distributed import protocol as proto
from repro.distributed.client import ServiceClient
from repro.distributed.server import MetaoptServer


def _space():
    return SearchSpace({"x": LogUniform(0.01, 100.0)})


def _wait_until(cond, deadline=10.0, step=0.02):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


# ---------------------------------------------------------------------------
# the demotion rule (shared single-host / multi-host)
# ---------------------------------------------------------------------------
def test_rung_demotions_small_cohort_rule():
    """Cohorts smaller than eta demote NOBODY (ASHA's not-enough-evidence
    rule, now explicit): regression for the silent ``n // eta == 0``
    degradation with cohorts of 1 and of eta-1."""
    assert rung_demotions(1, 3) == 0
    assert rung_demotions(2, 3) == 0            # eta - 1
    assert rung_demotions(3, 3) == 1
    assert rung_demotions(6, 3) == 2
    assert rung_demotions(1, 2) == 0
    assert rung_demotions(7, 2) == 3
    assert demote_indices([5.0], 3) == set()
    assert demote_indices([5.0, 1.0], 3) == set()
    # stable: ties break by position (park order)
    assert demote_indices([1.0, 1.0, 2.0], 3) == {0}
    assert demote_indices([3.0, 1.0, 2.0, 0.5, 4.0, 5.0], 3) == {3, 1}


def test_service_barrier_small_cohorts_promote_everyone():
    for n in (1, 2):                            # 1 and eta-1
        policy = RandomSearchPolicy(_space(), n, 4, seed=0)
        svc = OptimizationService(policy, bracket_eta=3)
        recs = [svc.acquire_trial(rung=0) for _ in range(n)]
        for i, rec in enumerate(recs):
            assert svc.report(rec.trial_id, 0, float(i)) is Decision.PARKED
        entry = svc.barrier.rung_log[-1]
        assert entry == {"phase": 0, "n": n, "demoted": [],
                         "promoted": [r.trial_id for r in recs]}
        for rec in recs:                        # verdict polls: all promoted
            assert svc.report(rec.trial_id, 0, 0.0) is Decision.CONTINUE


def test_service_barrier_parks_and_resolves_bottom_n_over_eta():
    policy = RandomSearchPolicy(_space(), 6, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    recs = [svc.acquire_trial(rung=0) for _ in range(6)]
    metrics = [3.0, 0.5, 2.0, 1.0, 4.0, 5.0]
    for rec, m in zip(recs, metrics):
        assert svc.report(rec.trial_id, 0, m) is Decision.PARKED
        # the withheld report is NOT in the DB until resolution
    entry = svc.barrier.rung_log[0]
    assert entry["n"] == 6
    assert set(entry["demoted"]) == {recs[1].trial_id, recs[3].trial_id}
    # resolution recorded every withheld report, in rank order
    for rec, m in zip(recs, metrics):
        assert [mm for mm, _ in svc.db.trials[rec.trial_id].reports] == [m]
    # verdicts ride the next poll; demoted trials are KILLED
    assert svc.report(recs[1].trial_id, 0, 0.5) is Decision.STOP
    assert svc.db.trials[recs[1].trial_id].status is TrialStatus.KILLED
    assert svc.report(recs[0].trial_id, 0, 3.0) is Decision.CONTINUE
    assert svc.db.trials[recs[0].trial_id].status is TrialStatus.RUNNING


def test_unhinted_trials_never_park():
    """A trial acquired without the rung hint (bracket-unaware worker
    sharing the server) reports straight through rung phases."""
    policy = RandomSearchPolicy(_space(), 2, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    plain = svc.acquire_trial()                 # no hint
    assert svc.report(plain.trial_id, 0, 1.0) is Decision.CONTINUE
    enrolled = svc.acquire_trial(rung=0)
    assert svc.report(enrolled.trial_id, 0, 1.0) is Decision.PARKED


# ---------------------------------------------------------------------------
# the barrier over TCP: cohorts pool across connections
# ---------------------------------------------------------------------------
@pytest.mark.timeout(120)
def test_bracket_cohort_pools_across_two_clients():
    """Two hosts, 2 trials each, eta=3: each host alone is below eta (no
    demotion possible), the POOLED cohort of 4 demotes exactly 4 // 3 = 1 —
    the bottom metric, wherever it ran."""
    policy = RandomSearchPolicy(_space(), 4, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    with MetaoptServer(svc, lease_ttl=10.0) as server:
        a = ServiceClient(server.host, server.port)
        b = ServiceClient(server.host, server.port)
        ta = a.acquire_batch(node=0, slots=2, rung=0)
        tb = b.acquire_batch(node=1, slots=2, rung=0)
        assert len(ta) == 2 and len(tb) == 2
        # host A parks both of its trials: cohort still filling
        assert a.report(ta[0].trial_id, 0, 3.0, node=0) == "parked"
        assert a.report(ta[1].trial_id, 0, 1.0, node=0) == "parked"
        # a poll while waiting is still parked, and renews the lease
        assert a.report(ta[1].trial_id, 0, 1.0, node=0) == "parked"
        assert a.heartbeat(ta[1].trial_id)
        # host B completes the cohort
        assert b.report(tb[0].trial_id, 0, 2.0, node=1) == "parked"
        assert b.report(tb[1].trial_id, 0, 4.0, node=1) == "parked"
        # pooled ranking: bottom 1 of 4 = A's 1.0 trial
        assert a.report(ta[0].trial_id, 0, 3.0, node=0) == "continue"
        assert a.report(ta[1].trial_id, 0, 1.0, node=0) == "stop"
        assert b.report(tb[0].trial_id, 0, 2.0, node=1) == "continue"
        assert b.report(tb[1].trial_id, 0, 4.0, node=1) == "continue"
        a.close()
        b.close()
    entry = svc.barrier.rung_log[0]
    assert entry["n"] == 4 and entry["demoted"] == [ta[1].trial_id]
    assert svc.db.trials[ta[1].trial_id].status is TrialStatus.KILLED
    # every withheld report was logged at resolution — exactly ONCE each
    # (the cohort-completing park must not also log via the normal path)
    logged = [tid for tid, *_ in server.report_log]
    assert sorted(logged) == sorted(t.trial_id for t in ta + tb)
    # ... and the DB agrees: one report per trial
    for t in ta + tb:
        assert len(svc.db.trials[t.trial_id].reports) == 1


@pytest.mark.timeout(120)
def test_reaper_shrink_resolves_barrier_and_requeues():
    """A worker that dies mid-rung (lease expires) cannot wedge the
    barrier: the cohort shrinks, resolves on the survivors, and the dead
    trial's configuration is requeued by the reaper."""
    policy = RandomSearchPolicy(_space(), 3, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=2)
    with MetaoptServer(svc, lease_ttl=0.3) as server:
        live = ServiceClient(server.host, server.port)
        dead = ServiceClient(server.host, server.port)
        mine = live.acquire_batch(node=0, slots=2, rung=0)
        other = dead.acquire(node=1, rung=0)
        dead.close()                            # dies: no heartbeat, ever
        assert live.report(mine[0].trial_id, 0, 2.0) == "parked"
        assert live.report(mine[1].trial_id, 0, 1.0) == "parked"
        # cohort is 3 with one member dead -> wedged until the reaper
        # reclaims it; keep the parked leases alive meanwhile
        def resolved():
            for t in mine:
                live.heartbeat(t.trial_id)
            return bool(svc.barrier.rung_log)
        assert _wait_until(resolved, deadline=15.0, step=0.05)
        entry = svc.barrier.rung_log[0]
        # shrunken cohort of 2, eta=2 -> bottom 1 demoted
        assert entry["n"] == 2
        assert entry["demoted"] == [mine[1].trial_id]
        assert svc.db.trials[other.trial_id].status is TrialStatus.CRASHED
        # the dead trial's withheld report was dropped entirely
        assert svc.db.trials[other.trial_id].reports == []
        # ... and its config is re-issued without consuming fresh budget
        refill = live.acquire(node=0, rung=0)
        assert refill.hparams == other.hparams
        live.close()


@pytest.mark.timeout(120)
def test_parked_member_death_shrinks_cohort():
    """Lease loss of a PARKED trial during the barrier: its withheld
    report is dropped and the remaining cohort resolves."""
    policy = RandomSearchPolicy(_space(), 3, 4, seed=0)
    svc = OptimizationService(policy, bracket_eta=2)
    with MetaoptServer(svc, lease_ttl=0.3) as server:
        live = ServiceClient(server.host, server.port)
        dead = ServiceClient(server.host, server.port)
        mine = live.acquire_batch(node=0, slots=2, rung=0)
        parked_dead = dead.acquire(node=1, rung=0)
        # the doomed worker parks FIRST (best metric!), then dies
        assert dead.report(parked_dead.trial_id, 0, 99.0) == "parked"
        dead.close()
        assert live.report(mine[0].trial_id, 0, 2.0) == "parked"
        assert not svc.barrier.rung_log         # cohort still has 3 members

        def dead_reaped():
            for t in mine:                      # keep OUR leases alive
                live.heartbeat(t.trial_id)
            return (svc.db.trials[parked_dead.trial_id].status
                    is TrialStatus.CRASHED)
        assert _wait_until(dead_reaped, deadline=15.0, step=0.05)
        # the last live member parks the now-2-member cohort: resolves
        assert live.report(mine[1].trial_id, 0, 1.0) == "parked"
        entry = svc.barrier.rung_log[0]
        assert entry["n"] == 2                  # dead member shrunk away
        assert set(entry["demoted"]) == {mine[1].trial_id}
        # dropped, not recorded: the 99.0 never reached the DB
        assert svc.db.trials[parked_dead.trial_id].reports == []
        assert (svc.db.trials[parked_dead.trial_id].status
                is TrialStatus.CRASHED)
        live.close()


@pytest.mark.timeout(300)
def test_bracket_search_completes_with_scalar_workers():
    """End-to-end: ProcessCluster(bracket_eta=...) runs one shared bracket
    over OS-process scalar workers (numpy-only objective) — the same wire
    path the CI quickstart smoke exercises. Entry cohorts are sized to the
    cluster's real capacity (4 workers x 1 slot), so the first rung pools
    all four trials even though each worker acquired sequentially."""
    policy = RandomSearchPolicy(_space(), 4, 3, seed=0)
    cluster = ProcessCluster(4, {"kind": "synthetic", "sleep": 0.01},
                             lease_ttl=10.0, heartbeat_interval=0.2,
                             bracket_eta=3)
    res = cluster.run(policy)
    s = res.summary()
    assert s["n_trials"] == 4
    rungs = s["rungs"]
    assert rungs and rungs[0]["n"] == 4         # one pooled cohort
    assert len(rungs[0]["demoted"]) == 4 // 3
    killed = sum(len(r["demoted"]) for r in rungs)
    assert s["by_status"].get("killed", 0) == killed
    assert (s["by_status"].get("completed", 0)
            == 4 - killed)


# ---------------------------------------------------------------------------
# ProcessCluster distributed-correctness fixes
# ---------------------------------------------------------------------------
class _OneBadWorkerCluster(ProcessCluster):
    """Node 0 exits nonzero immediately; other nodes run normally."""

    def _worker_cmd(self, port, node):
        if node == 0:
            return [sys.executable, "-c", "import sys; sys.exit(3)"]
        return super()._worker_cmd(port, node)


@pytest.mark.timeout(300)
def test_partial_worker_failure_is_surfaced_not_silent():
    policy = RandomSearchPolicy(_space(), 3, 2, seed=0)
    cluster = _OneBadWorkerCluster(2, {"kind": "synthetic", "sleep": 0.01},
                                   lease_ttl=10.0, heartbeat_interval=0.2)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = cluster.run(policy)
    assert res.extra["worker_exit_codes"] == [3, 0]
    assert any("exited nonzero" in str(w.message) for w in caught)
    # the search itself still completed on the surviving worker
    assert res.summary()["by_status"] == {"completed": 3}
    # ... and the partial failure shows in the summary via extra
    assert res.summary()["worker_exit_codes"] == [3, 0]


class _OneHungWorkerCluster(ProcessCluster):
    """Node 0 hangs forever without ever touching the service."""

    def _worker_cmd(self, port, node):
        if node == 0:
            return [sys.executable, "-c", "import time; time.sleep(600)"]
        return super()._worker_cmd(port, node)


@pytest.mark.timeout(300)
def test_hung_worker_cannot_stall_launcher_after_drain():
    policy = RandomSearchPolicy(_space(), 2, 2, seed=0)
    cluster = _OneHungWorkerCluster(2, {"kind": "synthetic", "sleep": 0.01},
                                    lease_ttl=10.0, heartbeat_interval=0.2,
                                    worker_grace=1.0)
    t0 = time.monotonic()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = cluster.run(policy)
    assert time.monotonic() - t0 < 60.0         # bounded, not p.wait() forever
    assert any("presumed hung" in str(w.message) for w in caught)
    assert res.extra["worker_exit_codes"][0] != 0   # the killed straggler
    assert res.summary()["by_status"] == {"completed": 2}


# ---------------------------------------------------------------------------
# the acceptance scenarios: real worker processes sharing one bracket
# ---------------------------------------------------------------------------
def _spawn_worker(port: int, node: int, spec: dict,
                  heartbeat: float = 0.1) -> subprocess.Popen:
    import repro
    import os
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.Popen(
        [sys.executable, "-m", "repro.distributed.worker",
         "--host", "127.0.0.1", "--port", str(port),
         "--spec", json.dumps(spec), "--node", str(node),
         "--heartbeat-interval", str(heartbeat), "--bracket"],
        env=env)


@pytest.mark.timeout(300)
def test_killing_worker_mid_rung_resolves_via_reaper_shrink():
    """One worker process parks at rung 0; the other hangs inside its
    objective (its enrolled, unparked trial gates the cohort) and is
    KILLED mid-rung. The barrier must resolve via the reaper-shrink path
    instead of wedging, and the dead trial's config must be requeued and
    completed by the survivor."""
    policy = RandomSearchPolicy(_space(), 2, 2, seed=0)
    svc = OptimizationService(policy, bracket_eta=3)
    svc.barrier.expect_entrants(2)
    with MetaoptServer(svc, lease_ttl=0.5) as server:
        # node 0 sleeps 600 s inside every phase: acquires + enrolls, then
        # hangs forever before its first report
        hung = _spawn_worker(server.port, 0,
                             {"kind": "synthetic", "sleep": 600.0})
        try:
            assert _wait_until(lambda: len(svc.db.trials) >= 1)
            live = _spawn_worker(server.port, 1,
                                 {"kind": "synthetic", "sleep": 0.01})
            # the live worker parks; the cohort of 2 cannot resolve while
            # the hung worker's heartbeats keep its lease alive
            assert _wait_until(
                lambda: svc.barrier is not None
                and len(svc.barrier._parked) == 1, deadline=20.0)
            time.sleep(1.5)                     # several TTLs: still parked
            assert not svc.barrier.rung_log
            hung.kill()                         # mid-rung worker death
            hung.wait(timeout=30)               # bounded: it was SIGKILL'd
            # lease expires -> cohort shrinks to the parked survivor ->
            # resolves -> survivor promoted, dead config requeued + rerun
            assert _wait_until(lambda: bool(svc.barrier.rung_log),
                               deadline=20.0)
            assert live.wait(timeout=30) == 0
        finally:
            for p in (hung, live):
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)          # bounded: SIGKILL'd already
    first = svc.barrier.rung_log[0]
    assert first["n"] == 1 and not first["demoted"]     # shrink, then
    statuses = [t.status for t in svc.db.trials.values()]
    assert statuses.count(TrialStatus.CRASHED) == 1     # the killed trial
    # the requeued config ran to completion on the survivor
    completed = [t for t in svc.db.trials.values()
                 if t.status is TrialStatus.COMPLETED]
    assert len(completed) == 2
    assert any(t.requeued for t in completed)


@pytest.mark.timeout(900)
def test_two_population_workers_share_one_bracket():
    """The tentpole acceptance: 2 population-worker PROCESSES (one device
    each, 2 slots each) over TCP share ONE bracket. eta=3: either host
    alone (cohort 2 < eta) could demote nobody — the pooled cohort of 4
    demotes exactly 4 // 3 = 1, the bottom metric across both hosts."""
    from repro.core.search_space import Categorical
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "t_max": Categorical((4,)),
                         "gamma": Categorical((0.99,))})
    policy = RandomSearchPolicy(space, 4, 2, seed=0)
    cluster = ProcessCluster(
        2, {"kind": "rl", "game": "pong", "episodes_per_phase": 2,
            "max_updates": 3, "seed": 0},
        lease_ttl=30.0, heartbeat_interval=1.0, slots=2, bracket_eta=3)
    res = cluster.run(policy)
    s = res.summary()
    assert s["n_trials"] == 4
    rungs = s["rungs"]
    assert rungs and rungs[0]["phase"] == 0
    assert rungs[0]["n"] == 4                   # pooled across both hosts
    assert len(rungs[0]["demoted"]) == 4 // 3   # exactly bottom n // eta
    # the demoted trial is the pooled cohort's bottom metric
    by_trial = {r.trial_id: r.metric for r in res.records if r.phase == 0}
    assert len(by_trial) == 4                   # every withheld report logged
    demoted = rungs[0]["demoted"][0]
    assert by_trial[demoted] == min(by_trial.values())
    # cohort membership really did span both hosts
    nodes = {r.node for r in res.records}
    assert nodes == {0, 1}
    assert s["by_status"] == {"killed": 1, "completed": 3}


def test_engine_abandons_parked_slot_and_drops_pending_report():
    """Lease loss while a slot is PARKED at a rung (the server reaped us
    mid-barrier): ``_abandon`` must free the slot and drop the withheld
    ``pending`` report — it is never delivered as a record — and the freed
    slot is immediately admittable again."""
    from repro.population.engine import PopulationEngine, TrialLease
    engine = PopulationEngine("pong", max_slots=2, n_envs=2,
                              episodes_per_phase=10 ** 9,
                              max_updates=10 ** 9, seed=0, bracket_eta=3)
    hp = {"learning_rate": 1e-3, "t_max": 4, "gamma": 0.99}
    engine.admit(TrialLease(0, dict(hp)))
    engine.admit(TrialLease(1, dict(hp)))
    bucket = engine.buckets[4]
    # trial 0 parks at its rung (the service answered "parked")
    bucket.meta[0].pending = (1.5, 0.0, 1.0)
    bucket.park(0)
    assert engine._any_parked() and engine.n_occupied == 2
    engine._abandon({0})                        # heartbeat said lease lost
    assert not engine._any_parked()
    assert engine.n_occupied == 1               # slot freed for admission
    assert bucket.meta[0] is None               # pending died with the meta
    assert engine.records == []                 # the report was DROPPED
    engine.admit(TrialLease(2, dict(hp)))       # hot-swap works again
    assert bucket.meta[0].trial_id == 2 and bucket.n_active == 2


# ---------------------------------------------------------------------------
# protocol evolution: the rung hint on the wire
# ---------------------------------------------------------------------------
def test_acquire_rung_hint_wire_compat():
    # hint-less acquire frames carry NO rung field at all (rule 3)
    wire = proto.encode(proto.AcquireRequest(node=1, slots=2))[4:]
    assert "rung" not in json.loads(wire.decode())
    # an old peer's frame without it still decodes
    msg = proto.decode(json.dumps({"type": "acquire", "node": 1}).encode())
    assert msg.rung is None
    # and a hinted frame round-trips
    msg = proto.decode(proto.encode(proto.AcquireRequest(rung=0))[4:])
    assert msg.rung == 0
