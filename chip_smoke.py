#!/usr/bin/env python3
"""Bring-up smoke test: drive the HyperTrick population search once on TPU
chips through the entry points a user calls, and check what comes out.

  python chip_smoke.py               # one chip: phases (a), (b), (c)
  python chip_smoke.py --four-chips  # four chips: phase (d) only

(a) socket: ``ProcessCluster`` with the metaopt server in this process and
    one population-worker process on the chip, 8 slots (what ``tune.py
    --backend process --slots 8`` runs). It runs before this process
    touches JAX: a chip belongs to one process at a time.
(b) ga3c: ``PopulationCluster`` (``tune.py --backend vectorized``), 32
    slots of the A3C network at the one width the engine supports.
(c) lm: the LM population objective, yi-9b at its ``reduced()`` widths.
(d) sharded: the GA3C slot axis ``shard_map``-ed over four chips, against
    the same trials run on one chip at the same local capacity.

Weights and data come from ``--seed``. Lines before the last are bring-up
observations, not benchmark numbers. The last line, printed only when
every phase passed, is one JSON object naming the device. Any failed
check, and a host where JAX finds no TPU, exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.executor import PopulationCluster, ProcessCluster  # noqa: E402
from repro.core.hypertrick import HyperTrick, RandomSearchPolicy  # noqa: E402
from repro.core.search_space import (Categorical,  # noqa: E402
                                     SearchSpace, lm_space, paper_rl_space)
from repro.launch.runtime import use_compile_cache  # noqa: E402

PLATFORM = "tpu"          # every phase must run here, never on the CPU
PHASES = 3                # HyperTrick phases N_p
EVICTION = 0.25
EPISODES = 2              # GA3C phase length (finished episodes) ...
MAX_UPDATES = 25          # ... bounded by updates per phase
N_ENVS = 16
GA3C_SLOTS = 32
SOCKET_SLOTS = 8
LM_SLOTS = 4
LM_STEPS = 3              # LM phase length (updates)
SHARD_SLOTS = 32          # phase (d): 8 local slots on each of 4 chips
SHARD_T_MAX = 8           # one bucket, so shard k holds trials 8k..8k+7


def rl_space() -> SearchSpace:
    """The paper's GA3C space with ``t_max`` held to three values: each
    distinct ``t_max`` is a program of its own to compile. 32 lies beyond
    the full-unroll limit, so both rollout forms compile."""
    return SearchSpace(dict(paper_rl_space().params,
                            t_max=Categorical((4, 8, 32))))


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileWatch:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events (a cache read counts as compile time)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.compile_s, self.compiles, self.hits, self.misses

    def since(self, mark: tuple) -> str:
        s, n, h, m = (a - b for a, b in zip(self.mark(), mark))
        return (f"backend compiles {n} taking {s:.2f}s, persistent cache "
                f"hits {h} misses {m}")


def check_search(res, budget: int, name: str) -> dict:
    """The search spent its budget, every trial ended completed or killed,
    and every report is finite."""
    s = res.summary()
    by = s["by_status"]
    check(s["n_trials"] == budget,
          f"{name}: {s['n_trials']} trials, budget {budget}")
    check(sum(by.values()) == budget and set(by) <= {"completed", "killed"},
          f"{name}: trial statuses {by}")
    reported = {r.trial_id for r in res.records}
    check(reported == set(res.service.db.trials),
          f"{name}: trials without a report")
    check(all(math.isfinite(r.metric) for r in res.records),
          f"{name}: non-finite metric")
    return s


def socket_phase(seed: int) -> None:
    spec = {"kind": "rl", "game": "pong", "episodes_per_phase": EPISODES,
            "max_updates": MAX_UPDATES, "seed": seed}
    policy = HyperTrick(rl_space(), SOCKET_SLOTS, PHASES, EVICTION,
                        seed=seed)
    t0 = time.monotonic()
    # a long lease: the worker compiles on the chip while holding leases
    res = ProcessCluster(1, spec, slots=SOCKET_SLOTS,
                         lease_ttl=120.0).run(policy)
    codes = (res.extra or {}).get("worker_exit_codes")
    check(codes is None, f"socket: worker exit codes {codes}")
    s = check_search(res, SOCKET_SLOTS, "socket")
    say(f"# (a) socket: ok, 1 worker process x {SOCKET_SLOTS} slots, "
        f"by_status {s['by_status']}, {len(res.records)} reports, wall "
        f"{time.monotonic() - t0:.2f}s (worker start-up and compiles "
        "included)")


def _on_device(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def _slot_of(engine, res) -> dict:
    """trial id -> (bucket, slot index), from the trial's last report."""
    where = {}
    for r in res.records:
        for bucket in engine.buckets.values():
            if r.node in bucket.slot_ids:
                where[r.trial_id] = (bucket, bucket.slot_ids.index(r.node))
    return where


def check_trained(engine, res, seed: int, name: str) -> None:
    """Each trial's params left its initial values."""
    import jax
    import numpy as np
    from repro.rl.ga3c import trial_seed
    obj = engine.objective
    for tid, (bucket, i) in _slot_of(engine, res).items():
        hp = res.service.db.trials[tid].hparams
        init = obj.init_slot_state(jax.random.PRNGKey(trial_seed(seed, hp)),
                                   hp)[0][0]
        now = jax.tree.map(lambda x: np.asarray(x[i]), bucket.params)
        moved = any(not np.array_equal(a, np.asarray(b)) for a, b in
                    zip(jax.tree.leaves(now), jax.tree.leaves(init)))
        check(moved, f"{name}: trial {tid} params never changed")


def population_phase(name: str, policy, budget: int, seed: int, watch,
                     **cluster_kw):
    import jax
    device = jax.devices()[0]
    cluster = PopulationCluster(budget, seed=seed, **cluster_kw)
    mark = watch.mark()
    t0 = time.monotonic()
    res = cluster.run(policy)
    wall = time.monotonic() - t0
    s = check_search(res, budget, name)
    engine = cluster.engine
    for bucket in engine.buckets.values():
        check(_on_device(bucket.learner, device),
              f"{name}: bucket {bucket.key} learner not on {device}")
    check_trained(engine, res, seed, name)
    say(f"# {name}: ok, by_status {s['by_status']}, {len(res.records)} "
        f"reports, {len(engine.buckets)} buckets, {engine.total_updates} "
        f"slot-updates, {engine.total_env_steps} work units, wall "
        f"{wall:.2f}s; {watch.since(mark)}")
    return res, engine


def ga3c_phase(seed: int, watch) -> None:
    policy = HyperTrick(rl_space(), GA3C_SLOTS, PHASES, EVICTION,
                        seed=seed)
    population_phase("(b) ga3c", policy, GA3C_SLOTS, seed, watch,
                     game="pong", episodes_per_phase=EPISODES,
                     n_envs=N_ENVS, max_updates=MAX_UPDATES)


def lm_phase(seed: int, watch) -> None:
    policy = HyperTrick(lm_space(), LM_SLOTS, 2, EVICTION, seed=seed)
    _, engine = population_phase(
        "(c) lm", policy, LM_SLOTS, seed, watch,
        objective={"kind": "lm", "arch": "yi-9b", "data_seed": seed},
        episodes_per_phase=LM_STEPS)
    obj = engine.objective
    cfg = obj.cfg
    say(f"# (c) lm: {obj.arch} runs at its reduced() widths, not the "
        f"published ones: d_model {cfg.d_model}, {cfg.n_layers} layers, "
        f"{cfg.n_heads} heads, vocab {cfg.vocab_size}, batch {obj.batch} x "
        f"seq {obj.seq}")


def sharded_phase(seed: int, watch) -> None:
    """Four chips: one search with the slot axis sharded over them, then
    the same trials on one chip, one run per shard at the same local
    capacity. Per-trial metrics and params must agree bit for bit: the
    shard's program is the one-chip program of its local capacity."""
    import jax
    import numpy as np
    devices = jax.devices()
    check(len(devices) == 4, f"(d) sharded: {len(devices)} devices, not 4")
    rng = np.random.default_rng(seed)
    configs = [dict(paper_rl_space().sample(rng), t_max=SHARD_T_MAX)
               for _ in range(SHARD_SLOTS)]
    kw = dict(game="pong", episodes_per_phase=EPISODES, n_envs=N_ENVS,
              max_updates=MAX_UPDATES, seed=seed)

    def search(trials, devices_):
        policy = RandomSearchPolicy(SearchSpace({}), len(trials), PHASES,
                                    configs=[dict(c) for c in trials])
        cluster = PopulationCluster(len(trials), devices=devices_, **kw)
        mark = watch.mark()
        t0 = time.monotonic()
        res = cluster.run(policy)
        wall = time.monotonic() - t0
        check_search(res, len(trials), "(d) sharded")
        # one bucket; trials fill its slots in acquisition order
        (bucket,) = cluster.engine.buckets.values()
        metrics = {}
        for r in res.records:
            metrics.setdefault(r.trial_id, []).append((r.phase, r.metric))
        return bucket, metrics, wall, watch.since(mark)

    sharded, m4, wall4, comp4 = search(configs, 4)
    local = SHARD_SLOTS // 4
    for leaf in jax.tree.leaves(sharded.learner):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == 4
              and all(s.data.shape[0] == local for s in shards),
              "(d) sharded: learner not split over 4 distinct devices")
    say(f"# (d) sharded: {SHARD_SLOTS} slots over 4 chips, {local} per "
        f"chip, wall {wall4:.2f}s; {comp4}")
    params4 = [np.asarray(x) for x in jax.tree.leaves(sharded.params)]

    worst = 0.0
    bit_equal = True
    one_wall = 0.0
    for k in range(4):
        part = configs[k * local:(k + 1) * local]
        bucket, m1, wall1, comp1 = search(part, 1)
        one_wall += wall1
        check(_on_device(bucket.learner, devices[0]),
              "(d) one chip: learner not on device 0")
        for j in range(local):
            a, b = sorted(m4[k * local + j]), sorted(m1[j])
            check([p for p, _ in a] == [p for p, _ in b],
                  f"(d) trial {k * local + j}: phases {a} vs {b}")
            bit_equal &= a == b
            worst = max([worst] + [abs(x - y) for (_, x), (_, y)
                                   in zip(a, b)])
        for p4, p1 in zip(params4, jax.tree.leaves(bucket.params)):
            p1 = np.asarray(p1)
            p4k = p4[k * local:(k + 1) * local]
            bit_equal &= np.array_equal(p4k, p1)
            worst = max(worst, float(np.max(np.abs(p4k - p1))))
        say(f"# (d) one chip, shard {k}'s {local} trials: wall "
            f"{wall1:.2f}s; {comp1}")
    say(f"# (d) sharded vs one chip: per-trial metrics and params "
        f"bit-equal: {bit_equal}, max abs difference {worst!r}; one-chip "
        f"wall {one_wall:.2f}s in 4 runs")
    check(bit_equal, "(d) sharded and one-chip trials differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded search and the "
                         "one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # JAX and every worker process it starts must find the chip or fail
    os.environ["JAX_PLATFORMS"] = PLATFORM
    say(f"# compile cache: {use_compile_cache()}")
    t0 = time.monotonic()
    if not args.four_chips:
        socket_phase(args.seed)
    import jax
    watch = CompileWatch()
    dev = jax.devices()[0]
    check(dev.platform == PLATFORM, f"JAX runs on {dev.platform}")
    say(f"# device: {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    if args.four_chips:
        sharded_phase(args.seed, watch)
    else:
        ga3c_phase(args.seed, watch)
        lm_phase(args.seed, watch)
    say(f"# all phases ok in {time.monotonic() - t0:.2f}s; "
        f"{watch.since((0.0, 0, 0, 0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
