"""Load smoke tier (``distributed.loadgen``): real sockets, hundreds of
synthetic workers, seconds of wall clock.

Three ground truths ride here:

* the batched verbs actually pay: at 64 slots/host one ``report_batch``
  frame replaces 64 round-trips, so reports/sec must be a multiple of
  the per-trial verb's (a CPU wall-clock bar of 3x, not measured on a
  chip);
* the sim tier scales: 200 replay_trace hosts against the real service
  finish in seconds with every report accounted for;
* tenants are isolated end to end: two searches on one server journal
  independently and each journal replays to exactly its own trials.
"""
import pytest

from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import LogUniform, SearchSpace
from repro.core.service import OptimizationService
from repro.distributed.journal import Journal, replay_journal
from repro.distributed.loadgen import run_load, run_sim_load
from repro.distributed.server import MetaoptServer


def _space():
    return SearchSpace({"x": LogUniform(0.01, 100.0)})


def _socket_run(hosts, slots, phases, batched, server_kwargs=None):
    svc = OptimizationService(
        RandomSearchPolicy(_space(), hosts * slots, phases, seed=0))
    with MetaoptServer(svc, lease_ttl=60.0,
                       **(server_kwargs or {})) as server:
        return run_load(server.host, server.port, hosts=hosts, slots=slots,
                        phases=phases, batched=batched)


@pytest.mark.timeout(120)
def test_batched_verbs_beat_per_trial_reports():
    hosts, slots, phases = 2, 64, 3
    per = _socket_run(hosts, slots, phases, batched=False)
    bat = _socket_run(hosts, slots, phases, batched=True)
    want = hosts * slots * phases
    assert per.errors == 0 and bat.errors == 0
    assert per.reports == want and bat.reports == want
    assert per.acquired == bat.acquired == hosts * slots
    assert bat.reports_per_s >= 3.0 * per.reports_per_s, (
        f"batched {bat.reports_per_s:.0f}/s vs per-trial "
        f"{per.reports_per_s:.0f}/s — the batch verb stopped paying")
    assert bat.p99_ms is not None and per.p99_ms is not None


@pytest.mark.timeout(120)
def test_load_smoke_200_workers_over_sockets():
    """The CI load-smoke shape: 200 worker threads, one slot each, real
    sockets — nonzero throughput, every report lands, no errors."""
    stats = _socket_run(200, 1, 2, batched=True)
    assert stats.errors == 0
    assert stats.acquired == 200
    assert stats.reports == 400
    assert stats.reports_per_s > 0
    assert stats.p99_ms is not None and stats.p99_ms < 5000


@pytest.mark.timeout(120)
def test_sim_tier_200_hosts_accounts_for_every_report():
    stats = run_sim_load(n_hosts=200, n_trials=400, n_phases=4)
    assert stats.reports == 400 * 4              # no failures configured
    assert stats.acquired == 400
    assert stats.reports_per_s > 0
    assert stats.p99_ms is not None


@pytest.mark.timeout(120)
def test_two_tenants_journal_and_replay_independently(tmp_path):
    phases = 2
    shape = {"alpha": (2, 8), "beta": (3, 4)}    # hosts, slots
    paths = {t: str(tmp_path / f"{t}.jsonl") for t in shape}
    default_svc = OptimizationService(
        RandomSearchPolicy(_space(), 1, phases, seed=0))
    with MetaoptServer(default_svc, lease_ttl=60.0) as server:
        for t, (h, s) in shape.items():
            server.add_search(
                t, OptimizationService(
                    RandomSearchPolicy(_space(), h * s, phases, seed=0)),
                journal=Journal(paths[t]))
        stats = {t: run_load(server.host, server.port, hosts=h, slots=s,
                             phases=phases, batched=True, search=t)
                 for t, (h, s) in shape.items()}
    for t, (h, s) in shape.items():
        assert stats[t].errors == 0
        assert stats[t].reports == h * s * phases
        fresh = OptimizationService(
            RandomSearchPolicy(_space(), h * s, phases, seed=0))
        replay_journal(paths[t], fresh)
        # exactly this tenant's trials — nothing leaked across journals
        assert len(fresh.db.trials) == h * s
        assert all(len(r.reports) == phases
                   for r in fresh.db.trials.values())
