"""The engine host loop's profiler spans: each one's registry histogram
counts exactly the loop events it wraps, nothing of it reaches the
journal, and the telemetry package still imports without JAX."""
import subprocess
import sys

import pytest

from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import SearchSpace
from repro.core.service import OptimizationService
from repro.population import engine as engine_mod
from repro.population.engine import LocalDriver, PopulationEngine
from repro.telemetry import MetricsRegistry, SpanRecorder
from repro.telemetry.spans import PROFILER_SPANS, profiler_span


class CountingDriver:
    """``LocalDriver`` with a count of each call the engine makes. The
    engine calls ``poll_lost`` once a loop pass."""

    def __init__(self, service):
        self.inner = LocalDriver(service)
        self.acquires = self.leases = self.reports = self.passes = 0

    def acquire_many(self, k, rung=None):
        self.acquires += 1
        leases, retry = self.inner.acquire_many(k, rung=rung)
        self.leases += len(leases)
        return leases, retry

    def report_many(self, reports):
        self.reports += 1
        return self.inner.report_many(reports)

    def poll_lost(self):
        self.passes += 1
        return self.inner.poll_lost()


def _count(reg, span):
    return reg.snapshot()["histograms"].get(span + "_s", {}).get("count", 0)


def test_span_histograms_count_the_loop(monkeypatch):
    """Two bucket keys over two slots: the t_max 2 and 4 buckets are
    created and stepped side by side, then both trials end in one pass
    and two t_max 2 trials take their slots, which grows that bucket."""
    configs = [{"learning_rate": 1e-3, "t_max": t, "gamma": 0.99}
               for t in (2, 4, 2, 2)]
    svc = OptimizationService(RandomSearchPolicy(SearchSpace({}), 4, 2,
                                                 configs=configs))
    reg, journal = MetricsRegistry(), []
    engine = PopulationEngine("pong", max_slots=2, n_envs=2,
                              episodes_per_phase=2, max_updates=3, seed=0,
                              metrics=reg, spans=SpanRecorder(journal))
    made = {"buckets": 0, "grows": 0, "steps": 0, "polls": 0,
            "active_buckets": 0}
    bucket_init, grow, step = (engine_mod.Bucket.__init__,
                               engine_mod.Bucket.grow,
                               engine_mod.Bucket.step)

    def counting(key, fn):
        def wrapped(*a, **kw):
            made[key] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(engine_mod.Bucket, "__init__",
                        counting("buckets", bucket_init))
    monkeypatch.setattr(engine_mod.Bucket, "grow", counting("grows", grow))
    monkeypatch.setattr(engine_mod.Bucket, "step", counting("steps", step))
    poll_phases = engine._poll_phases

    def poll(driver, t0):
        made["polls"] += 1
        made["active_buckets"] += sum(1 for b in engine.buckets.values()
                                      if b.n_active)
        return poll_phases(driver, t0)

    engine._poll_phases = poll
    driver = CountingDriver(svc)
    engine.run(driver)

    assert driver.leases == 4 and made["grows"] == 1
    assert _count(reg, "engine.iteration") == driver.passes
    assert _count(reg, "engine.acquire") == driver.acquires
    assert _count(reg, "engine.grow") == made["buckets"] + made["grows"]
    for span in ("engine.admit", "engine.init_slot", "engine.write_slot"):
        assert _count(reg, span) == driver.leases, span
    assert _count(reg, "engine.dispatch") == made["steps"]
    assert _count(reg, "engine.poll") == made["polls"]
    # one progress read per active bucket per pass (no barrier here)
    assert _count(reg, "engine.sync") == made["active_buckets"]
    assert _count(reg, "engine.report") == driver.reports
    counters = reg.snapshot()["counters"]
    assert counters["engine.updates"] == engine.total_updates
    # the per-pass spans write nothing to the journal
    assert {e["name"] for e in journal} == {"engine.compile",
                                            "engine.phase"}


def test_profiler_span_observes_its_histogram():
    reg = MetricsRegistry()
    for span in PROFILER_SPANS:
        with profiler_span(span, reg):
            pass
    with profiler_span("engine.iteration", reg, step_num=7):
        pass
    hists = reg.snapshot()["histograms"]
    assert {n: h["count"] for n, h in hists.items()} == dict(
        {s + "_s": 1 for s in PROFILER_SPANS}, **{"engine.iteration_s": 2})
    with pytest.raises(KeyError):
        profiler_span("engine.unknown", reg)


def test_telemetry_imports_no_jax():
    code = ("import sys, repro.telemetry; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
