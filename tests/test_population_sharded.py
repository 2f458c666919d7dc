"""Multi-device population engine: sharded == single-device bit-for-bit
parity (run in a subprocess so the device-count env var is set before jax
initializes), on-device successive-halving rungs, and the REPORT verb's
``demote`` extension."""
import json
import os
import subprocess
import sys

import numpy as np

from repro.core.executor import PopulationCluster
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import (Categorical, LogUniform, SearchSpace,
                                     paper_rl_space)
from repro.core.service import OptimizationService, TrialStatus

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from repro.core.hypertrick import RandomSearchPolicy
from repro.core.search_space import SearchSpace
from repro.core.service import OptimizationService
from repro.launch.mesh import make_population_mesh
from repro.population.engine import LocalDriver, PopulationEngine

assert jax.device_count() == 2
CFGS = [{"learning_rate": 1e-3, "gamma": 0.99, "t_max": 4},
        {"learning_rate": 4e-4, "gamma": 0.995, "t_max": 4}]
KW = dict(n_envs=4, episodes_per_phase=4, max_updates=40, seed=0)

def run(max_slots, configs, mesh):
    policy = RandomSearchPolicy(SearchSpace({}), len(configs), 2,
                                configs=[dict(c) for c in configs])
    engine = PopulationEngine("pong", max_slots=max_slots, mesh=mesh, **KW)
    engine.run(LocalDriver(OptimizationService(policy)))
    return engine

# the sharded engine: 2 slots over 2 devices (local capacity 1 per shard)
mesh = make_population_mesh(2, 1)
sharded = run(2, CFGS, mesh)
bucket = sharded.buckets[4]
assert bucket.capacity == 2
by_trial = {}
for tid, slot, phase, t0, t1, m in sharded.records:
    by_trial.setdefault(tid, []).append((phase, m))

# the single-device engine, one run per configuration, same seeds
for lane, cfg in enumerate(CFGS):
    ref = run(1, [cfg], None)
    ref_metrics = sorted((phase, m) for _, _, phase, _, _, m in ref.records)
    assert sorted(by_trial[lane]) == ref_metrics, (
        lane, by_trial[lane], ref_metrics)          # metrics: exact ==
    ref_bucket = ref.buckets[4]
    for a, b in zip(jax.tree.leaves(bucket.params),
                    jax.tree.leaves(ref_bucket.params)):
        np.testing.assert_array_equal(np.asarray(a)[lane],
                                      np.asarray(b)[0])  # params: bitwise
print("SHARDED_PARITY_OK")
"""


def _run_sub(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sharded_population_bitwise_parity():
    """A 2-virtual-device population produces bit-identical params and
    phase metrics to the single-device engine for the same seeds: the
    shard-local program at local capacity c is the same XLA program as an
    unsharded capacity-c bucket."""
    out = _run_sub(_PARITY)
    assert "SHARDED_PARITY_OK" in out


_WRITE_SLOT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from repro.launch.mesh import make_population_mesh
from repro.population.engine import Bucket, PopulationEngine, SlotMeta

assert jax.device_count() == 2
HP = {"learning_rate": 1e-3, "gamma": 0.99, "t_max": 4}
engine = PopulationEngine("pong", max_slots=4, n_envs=2, seed=0,
                          mesh=make_population_mesh(2, 1))
bucket = Bucket(engine, 4, 4, HP)
obj = engine.objective
for i in (0, 3, 1):
    old = jax.tree.leaves((bucket.learner, bucket.carry))
    before = [np.asarray(x) for x in old]
    learner, carry = obj.init_slot_state(jax.random.PRNGKey(i), HP)
    bucket.write_slot(i, SlotMeta(i, HP, bucket.slot_ids[i]), learner,
                      carry, obj.traced_values(HP))
    new = jax.tree.leaves((bucket.learner, bucket.carry))
    assert all(x.is_deleted() for x in old)
    for a, b, v in zip(new, before, jax.tree.leaves((learner, carry))):
        assert a.sharding == engine._sharding, a.sharding
        expect = b.copy()
        expect[i] = np.asarray(v)
        np.testing.assert_array_equal(np.asarray(a), expect)
print("SHARDED_WRITE_SLOT_OK")
"""


def test_sharded_write_slot_keeps_the_slot_sharding():
    """The donated in-place slot write under a 2-device population mesh:
    bit-identical to an indexed set, every other slot unchanged, the old
    stack donated, and the output still committed to the slot sharding
    (an uncommitted stack would make every sharded step reshard)."""
    out = _run_sub(_WRITE_SLOT)
    assert "SHARDED_WRITE_SLOT_OK" in out


def _tiny_space(t_max=4):
    return SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                        "t_max": Categorical((t_max,)),
                        "gamma": Categorical((0.99,))})


def test_rung_demotion_frees_exactly_bottom_one_over_eta():
    """At a rung barrier the engine demotes exactly ``n // eta`` slots, and
    they are the cohort's bottom metrics; freed slots are hot-swapped with
    the remaining budget."""
    policy = RandomSearchPolicy(_tiny_space(), 8, 2, seed=0)
    res = PopulationCluster(6, game="pong", episodes_per_phase=2, n_envs=2,
                            max_updates=5, seed=0, bracket_eta=3).run(policy)
    s = res.summary()
    rungs = s["rungs"]
    first = rungs[0]
    assert first["phase"] == 0 and first["n"] == 6
    assert len(first["demoted"]) == 6 // 3          # exactly bottom 1/eta
    # the demoted trials are the lowest metrics of the rung-0 cohort
    # (stable ranking: ties break by admission order)
    cohort = [(r.metric, r.trial_id) for r in res.records
              if r.phase == 0 and r.trial_id in
              set(first["demoted"]) | set(first["promoted"])]
    # stable sort by metric = the engine's on-device stable argsort
    ranked = [tid for _, tid in sorted(cohort, key=lambda p: p[0])]
    assert set(first["demoted"]) == set(ranked[:2])
    # demoted -> KILLED in the knowledge DB; budget refills freed slots
    for tid in first["demoted"]:
        assert res.service.db.trials[tid].status is TrialStatus.KILLED
    assert s["n_trials"] == 8                       # 6 initial + 2 refills
    assert s["bracket"]["n"][0] == 6
    assert 0 < s["bracket_alpha"] <= 1


def test_bracket_end_to_end_summary():
    """A --bracket-style search over the real RL space completes and the
    summary carries the rung log (promotions visible)."""
    policy = RandomSearchPolicy(paper_rl_space(), 4, 3, seed=0)
    res = PopulationCluster(4, game="pong", episodes_per_phase=2, n_envs=4,
                            max_updates=8, seed=0, bracket_eta=3).run(policy)
    s = res.summary()
    assert s["n_trials"] == 4
    assert s["rungs"] and s["rungs"][0]["promoted"]
    assert s["by_status"].get("killed", 0) == sum(
        len(r["demoted"]) for r in s["rungs"])
    assert s["best_metric"] is not None


def test_sharded_engine_and_bracket_compose():
    """`tune.py --backend vectorized --devices 2 --bracket`: the
    shard_map-sharded population engine and the service-side rung barrier
    compose in one run — rung cohorts resolve over slots that live on two
    (virtual) devices, driven end-to-end from the launcher CLI."""
    import tempfile
    out = tempfile.NamedTemporaryFile(suffix=".json", delete=False).name
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.tune",
         "--backend", "vectorized", "--devices", "2", "--bracket",
         "--eta", "2", "--objective", "rl", "--game", "pong",
         "--workers", "4", "--phases", "2", "--episodes-per-phase", "2",
         "--n-envs", "2", "--seed", "0", "--out", out],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    s = json.load(open(out))
    os.remove(out)
    assert s["devices"] == 2
    rungs = s["rungs"]
    assert rungs and rungs[0]["phase"] == 0
    assert len(rungs[0]["demoted"]) == rungs[0]["n"] // 2
    assert s["by_status"].get("killed", 0) == sum(
        len(r["demoted"]) for r in rungs)
    assert s["n_trials"] == 4


# ---------------------------------------------------------------------------
# the REPORT ``demote`` extension
# ---------------------------------------------------------------------------
def test_report_demote_wire_compat_and_kill():
    from repro.distributed import protocol as proto
    from repro.distributed.client import ServiceClient
    from repro.distributed.server import MetaoptServer

    # a classic report frame has no demote field at all
    wire = proto.encode(proto.ReportRequest(0, 0, 1.0))[4:]
    assert "demote" not in json.loads(wire.decode())
    # ... and an old peer's frame without it still decodes
    msg = proto.decode(json.dumps(
        {"type": "report", "trial_id": 0, "phase": 0,
         "metric": 1.0}).encode())
    assert msg.demote is None

    policy = RandomSearchPolicy(_tiny_space(), 2, 3, seed=0)
    svc = OptimizationService(policy)
    with MetaoptServer(svc) as server:
        with ServiceClient(server.host, server.port) as client:
            t0 = client.acquire()
            t1 = client.acquire()
            # a demoting report records the metric AND kills the trial
            assert client.report(t0.trial_id, 0, 0.1, demote=True) == "stop"
            # a plain report still follows the policy (continue)
            assert client.report(t1.trial_id, 0, 0.9) == "continue"
    assert svc.db.trials[t0.trial_id].status is TrialStatus.KILLED
    assert svc.db.trials[t0.trial_id].reports[0][0] == 0.1
    assert svc.db.trials[t1.trial_id].status is TrialStatus.RUNNING
