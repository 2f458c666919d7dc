"""The on-device population engine: every live HyperTrick trial trains
simultaneously inside vmapped, jitted train steps.

The engine is pure *mechanism*, generic over a ``PopulationObjective``
(``population.objectives``): the objective supplies one trial's device
state as a ``(learner, carry)`` pair, a jittable single-slot step over
traced per-slot hyperparameters, and the traced-vs-structural hparam
split. The engine supplies everything else: per-trial state stacked
along a leading *slot* axis, the step vmapped over the traced
hyperparameters (ONE compile serves every configuration), trials
bucketed by the objective-declared structural key (each bucket is
exactly one jitted step with donated buffers), device-side eviction
masks, hot-swap admission, park/poll rung barriers, device-side PBT
clones, and ``shard_map`` sharding. Eviction is device-side masking — a
stopped slot's state is frozen via ``jnp.where`` and the slot is
immediately hot-swapped with the next configuration from the service —
which is the paper's §3.2 "the stopped worker's node immediately
acquires a fresh configuration", at slot granularity on one device.

Objectives shipped: GA3C (``objectives/ga3c.py``, the paper's workload
and the default — bit-identical to the pre-refactor engine) and LM
fine-tuning (``objectives/lm.py``: per-trial lr/clip/warmup over a tiny
``configs.registry`` model). A plain game string still constructs the
GA3C objective, so every pre-refactor call site works unchanged.

The engine is driven through a small *driver* interface so the same loop
serves two deployments:

* ``LocalDriver``    — wraps an in-process ``OptimizationService``
  (``core.executor.PopulationCluster``, ``launch/tune.py --backend
  vectorized``);
* ``RemoteDriver``   — wraps the PR-1 TCP ``ServiceClient``, leasing up to
  ``slots`` trials per ACQUIRE so one GPU node serves an entire search
  (``population.worker``).

Two orthogonal extensions ride on the slot axis:

* **Multi-device sharding** — give the engine a mesh from
  ``launch.mesh.make_population_mesh(slots, data)`` and each bucket's slot
  axis is split across the ``slots`` mesh axis with ``shard_map``: every
  device trains its local slice of the population, eviction masks and
  hot-swaps stay device-side per shard, and no collective is ever needed
  (trials are independent). Numerics are a function of the *local* (per-
  shard) slot count only: a sharded run with local capacity c bit-matches
  an unsharded run of the same trials at capacity c (see
  tests/test_population_sharded.py).
* **Successive-halving rungs** (``bracket``) — the generation barrier
  lives in the SERVICE (``core.service.RungBarrier``), not here: a report
  at a rung phase is answered ``"parked"``, the engine masks the slot
  (params/opt/env state frozen on device) and keeps polling by re-sending
  the identical report, and promote/demote come back as plain
  continue/stop decisions once the rung cohort — which may span any
  number of hosts — is complete. The engine never ranks a cohort itself;
  it only tells ACQUIRE (via the ``rung`` hint) that freed capacity is
  refilling the bracket, so the service sizes rung-0 cohorts to the
  capacity actually freed across every host.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Any, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.population.objectives import (PopulationObjective,
                                         objective_from_spec)
from repro.population.objectives.ga3c import UNROLL_T_MAX  # noqa: F401
from repro.rl.ga3c import trial_seed
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.spans import NULL_RECORDER, profiler_span


@dataclass(frozen=True)
class TrialLease:
    trial_id: int
    hparams: Dict[str, Any]
    n_phases: Optional[int] = None    # search length, when the driver knows it


# ---------------------------------------------------------------------------
# drivers: how the engine talks to the metaoptimization service
# ---------------------------------------------------------------------------
class LocalDriver:
    """In-process service — the engine IS the whole cluster. Speaks the
    same park/resolve interface as the TCP path (the barrier lives in the
    service either way), so the single-host fast path and a multi-host
    bracket run the identical decision protocol."""

    def __init__(self, service):
        self.service = service

    def acquire_many(self, k: int, rung: Optional[int] = None,
                     ) -> Tuple[List[TrialLease], Optional[float]]:
        """Up to ``k`` fresh leases. ``(leases, retry)``: ``retry`` is None
        when an empty result is final (budget spent), else seconds to wait
        before polling again. ``rung`` is the bracket-refill hint."""
        n_phases = getattr(self.service.policy, "n_phases", None)
        leases = []
        for slot in range(k):
            rec = self.service.acquire_trial(rung=rung)
            if rec is None:
                break
            leases.append(TrialLease(rec.trial_id, rec.hparams, n_phases))
        return leases, None

    def report(self, trial_id: int, phase: int, metric: float,
               t_start: float, t_end: float,
               env_steps: Optional[int] = None) -> "ReportReply":
        from repro.core.scheduler import ReportReply
        verdict = self.service.report_verdict(trial_id, phase, metric,
                                              t_start=t_start, t_end=t_end,
                                              env_steps=env_steps)
        return ReportReply(verdict.decision.value,
                           clone_from=verdict.clone_from,
                           perturb=verdict.perturb)

    def report_many(self, reports: List[dict]) -> List["ReportReply"]:
        """Batched reports (one engine generation). In-process there is no
        round-trip to save, so this simply loops — but the engine speaks
        one interface either way."""
        return [self.report(r["trial_id"], r["phase"], r["metric"],
                            r["t_start"], r["t_end"],
                            env_steps=r.get("env_steps")) for r in reports]

    def poll_lost(self) -> set:
        """Trials whose lease was revoked out from under us (remote only)."""
        return set()


class RemoteDriver:
    """The PR-1 TCP client — one process leases a whole population. A lease
    lost to the server's reaper (reported by the worker's heartbeat thread
    via ``mark_lost``) is abandoned without a report, exactly like a worker
    death with strictly local effect."""

    def __init__(self, client, node: Optional[int] = None):
        self.client = client
        self.node = node
        self._lost: set = set()
        self._t0 = time.monotonic()

    def set_timebase(self, t0: float) -> None:
        """Adopt the engine's run clock (``time.monotonic()`` at run
        start) so the trace ``t`` this driver sends matches the
        t_start/t_end timebase of the engine's reports exactly."""
        self._t0 = t0

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def acquire_many(self, k: int, rung: Optional[int] = None,
                     ) -> Tuple[List[TrialLease], Optional[float]]:
        from repro.distributed.client import Pending
        got = self.client.acquire_batch(node=self.node, slots=k, rung=rung,
                                        trace_t=self._now())
        if got is None:
            return [], None
        if isinstance(got, Pending):
            return [], got.retry_after
        return [TrialLease(t.trial_id, t.hparams, t.n_phases)
                for t in got], None

    def report(self, trial_id: int, phase: int, metric: float,
               t_start: float, t_end: float,
               env_steps: Optional[int] = None) -> str:
        from repro.distributed.client import ServiceError
        try:
            return self.client.report(trial_id, phase, metric,
                                      t_start=t_start, t_end=t_end,
                                      node=self.node, env_steps=env_steps,
                                      trace_t=self._now())
        except ServiceError:
            # stale trial (server restarted / lease reaped between our
            # heartbeat and this report): strictly local effect — drop the
            # one slot, keep the rest of the population training
            return "stop"

    def report_many(self, reports: List[dict]) -> List:
        """A whole generation's reports in ONE ``report_batch`` frame —
        the round-trip count per generation drops from slots to 1 (the
        load harness's batched-vs-per-trial headline). A server-rejected
        entry comes back ``"stop"`` (the client maps entry errors), and a
        transport-level failure stops every slot in the batch — the same
        strictly-local abandonment the per-trial path produces."""
        from repro.distributed.client import ServiceError
        entries = []
        for r in reports:
            e = {"trial_id": r["trial_id"], "phase": r["phase"],
                 "metric": r["metric"], "t_start": r["t_start"],
                 "t_end": r["t_end"]}
            if r.get("env_steps") is not None:
                e["env_steps"] = r["env_steps"]
            entries.append(e)
        try:
            return self.client.report_batch(entries, node=self.node,
                                            trace_t=self._now())
        except ServiceError:
            return ["stop"] * len(reports)

    def mark_lost(self, trial_id: int) -> None:
        self._lost.add(trial_id)

    def poll_lost(self) -> set:
        lost, self._lost = self._lost, set()
        return lost


# ---------------------------------------------------------------------------
# slots and buckets
# ---------------------------------------------------------------------------
@dataclass
class SlotMeta:
    """Host-side bookkeeping for one live trial in a bucket slot."""
    trial_id: int
    hparams: Dict[str, Any]
    slot_id: int                      # stable global slot number ("node")
    phase: int = 0
    updates_in_phase: int = 0
    phase_t0: float = 0.0
    start_sum: float = 0.0
    start_n: float = 0.0
    # bracket mode: (metric, t_start, t_end, env_steps) of a rung-phase
    # report the service answered "parked" — re-sent verbatim as the
    # barrier poll until the cohort resolves and a continue/stop verdict
    # comes back
    pending: Optional[Tuple[float, float, float, int]] = None
    # telemetry: wall time (perf_counter) the slot parked, for the
    # park-stall histogram; None while training
    parked_at: Optional[float] = None


class Bucket:
    """All slots sharing one structural bucket key (GA3C: ``t_max``):
    stacked pytrees with a leading axis of ``capacity``, one compiled
    train step. Under a mesh the capacity is always a multiple of the
    ``slots`` axis size and the slot axis is sharded across it (padding
    slots are just inactive masks)."""

    def __init__(self, engine: "PopulationEngine", key: Hashable,
                 capacity: int, template_hparams: Dict[str, Any]):
        self.engine = engine
        self.key = key
        obj = engine.objective
        self.traced_names = obj.hparam_spec().traced
        # work units (env transitions / tokens) one update of one slot
        # performs — the engine's throughput accounting
        self.update_cost = int(obj.update_cost(key))
        capacity = engine._round_capacity(capacity)
        self.capacity = capacity
        # template state fixes the stacked shapes/dtypes only (zeros;
        # real state is written per-slot at admission): traced, never built
        tmpl = jax.eval_shape(
            lambda k: obj.init_slot_state(k, template_hparams),
            jax.random.PRNGKey(0))
        zeros = lambda x: jnp.zeros((capacity,) + x.shape, x.dtype)
        self.learner, self.carry = (
            engine._place(jax.tree.map(zeros, t)) for t in tmpl)
        self.hyper = {n: np.zeros(capacity, np.float32)
                      for n in self.traced_names}
        self.active = np.zeros(capacity, bool)
        self._hyper_dev = None          # device mirror, refreshed on change
        self.meta: List[Optional[SlotMeta]] = [None] * capacity
        self.slot_ids = [engine._new_slot_id() for _ in range(capacity)]
        self._stepped = False           # telemetry: first step = compile
        self._step = _bucket_step(obj, key, capacity, engine.mesh)

    # -- GA3C-vocabulary views (the pre-refactor attribute surface) ---------
    @property
    def t_max(self):
        return self.key

    @property
    def params(self):
        return self.learner[0]

    @params.setter
    def params(self, v):
        self.learner = (v,) + tuple(self.learner[1:])

    @property
    def opt_state(self):
        return self.learner[1]

    @opt_state.setter
    def opt_state(self, v):
        self.learner = (self.learner[0], v) + tuple(self.learner[2:])

    @property
    def loop(self):
        return self.carry

    @property
    def lr(self):
        return self.hyper["learning_rate"]

    @property
    def gamma(self):
        return self.hyper["gamma"]

    @property
    def beta(self):
        return self.hyper["beta"]

    # -- slot management ----------------------------------------------------
    def free_index(self) -> Optional[int]:
        for i in range(self.capacity):
            if not self.active[i] and self.meta[i] is None:
                return i
        return None

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_occupied(self) -> int:
        """Active + parked slots (a parked trial still owns its slot)."""
        return sum(1 for m in self.meta if m is not None)

    def grow(self, new_capacity: int) -> None:
        new_capacity = self.engine._round_capacity(new_capacity)
        pad = new_capacity - self.capacity
        assert pad > 0
        padz = lambda x: jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        self.learner, self.carry = (
            self.engine._place(jax.tree.map(padz, t))
            for t in (self.learner, self.carry))
        self.hyper = {n: np.concatenate([a, np.zeros(pad, np.float32)])
                      for n, a in self.hyper.items()}
        self.active = np.concatenate([self.active, np.zeros(pad, bool)])
        self._hyper_dev = None
        self.meta += [None] * pad
        self.slot_ids += [self.engine._new_slot_id() for _ in range(pad)]
        self.capacity = new_capacity
        self._stepped = False           # new shape: next step compiles again
        self._step = _bucket_step(self.engine.objective, self.key,
                                  new_capacity, self.engine.mesh)

    def write_slot(self, i: int, meta: SlotMeta, learner, carry,
                   traced: Sequence[float]) -> None:
        """Hot-swap a fresh configuration into slot ``i``. ``traced`` are
        the per-slot hyperparameter scalars in ``hparam_spec().traced``
        order (``PopulationObjective.traced_values``)."""
        self.learner, self.carry = self.engine._write_slot(
            (self.learner, self.carry), (learner, carry), i)
        for n, v in zip(self.traced_names, traced):
            self.hyper[n][i] = v
        self.active[i] = True
        self.meta[i] = meta
        self._hyper_dev = None

    def clone_slot(self, dst: int, src_bucket: "Bucket", src: int,
                   traced: Sequence[float]) -> None:
        """PBT exploit: copy ``src_bucket``'s slot ``src`` learner state
        (params + optimizer state — NOT the carry: the clone keeps
        exploring its own environments / data stream) into slot ``dst``,
        entirely device-side (one jitted slot-copy executable, weights
        never materialize on the host), and install the perturbed traced
        hyperparameters. Learner shapes are independent of the structural
        key, so the source may live in a different bucket of the same
        engine."""
        place = self.engine._place
        self.learner = place(
            _clone_slot_step(self.learner, src_bucket.learner, src, dst))
        for n, v in zip(self.traced_names, traced):
            self.hyper[n][dst] = v
        self._hyper_dev = None

    def release(self, i: int) -> None:
        """Device-side eviction: mask the slot; its params stop updating
        (frozen by the step's ``where``) until a fresh config is swapped in."""
        self.active[i] = False
        self.meta[i] = None
        self._hyper_dev = None

    def park(self, i: int) -> None:
        """Rung barrier: mask the slot but keep the trial — params, opt
        state, and env state stay frozen on device until the generation
        resolves and the survivor is unparked (promoted)."""
        self.active[i] = False
        self._hyper_dev = None

    def unpark(self, i: int) -> None:
        self.active[i] = True
        self._hyper_dev = None

    # -- the one jitted step ------------------------------------------------
    def step(self) -> None:
        if self._hyper_dev is None:
            arrays = tuple(self.hyper[n] for n in self.traced_names)
            self._hyper_dev = tuple(
                self.engine._place(jnp.asarray(a))
                for a in arrays + (self.active,))
        self.learner, self.carry = self._step(
            self.learner, self.carry, *self._hyper_dev)


def _write_slot(stacked, slot, i):
    """The hot-swap write as ONE program (jitted by the engine, the
    stacked state donated): slot ``i`` of every leaf of the stacked
    (learner, carry) overwritten in place, so a swap holds one stacked
    state and the fresh slot, not two stacked states. ``i`` is traced:
    one compile per tree and capacity serves every slot."""
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(
            a, v.astype(a.dtype), i, 0), stacked, slot)


@jax.jit
def _clone_slot_step(dst_state, src_state, src: int, dst: int):
    """The whole PBT slot copy as ONE jitted executable: every leaf of the
    destination learner state gets the source slot's row. ``src``/``dst``
    are traced scalars, so one compilation (per tree structure) serves
    every clone the search ever performs. (No donation: for a same-bucket
    clone the destination leaves ARE the source leaves, and donating an
    aliased input just trades the copy for an XLA warning.)"""
    return jax.tree.map(
        lambda d, s: jax.lax.dynamic_update_index_in_dim(
            d, jax.lax.dynamic_index_in_dim(s, src, 0, keepdims=False),
            dst, 0),
        dst_state, src_state)


# module-level compile cache: keyed by the OBJECTIVE's cache_key (not the
# instance), so two engines over equivalent objectives share executables —
# benches warm a search with a throwaway engine and keep the compiles
_STEP_CACHE: Dict[tuple, Any] = {}
_STEP_CACHE_MAX = 64


def _bucket_step(objective: PopulationObjective, structural: Hashable,
                 capacity: int, mesh=None):
    """One jitted, buffer-donating train step for a whole bucket, cached at
    module level: hyperparameters are traced inputs, so ONE compilation
    serves every configuration that ever occupies the bucket — per-trial
    backends cannot reuse compiles because each trial's hyperparameters are
    burned into its jit as constants.

    The per-slot body comes from ``objective.make_step``; the engine wraps
    it in vmap over the slot axis, the eviction mask, donation, and (under
    a mesh) ``shard_map``. A local capacity of 1 skips vmap and squeezes
    the slot axis instead, so a single-trial population runs the
    objective's own compact program — for GA3C that is the same XLA
    program as the thread backend (bit-for-bit parity).

    With a ``mesh`` (from ``make_population_mesh``) the step body runs
    under ``shard_map`` with the slot axis split over the mesh's ``slots``
    axis: each device owns ``capacity // n_shards`` slots and runs the
    identical per-shard program — vmap, the objective's local-capacity
    choice, and the eviction mask all act on the *local* slice, and since
    trials are independent no collective appears anywhere. Numerics
    therefore depend only on the local capacity: D devices at local
    capacity c bit-match one device at capacity c."""
    key = (objective.cache_key(), structural, capacity, mesh)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    n_shards = int(mesh.shape["slots"]) if mesh is not None else 1
    assert capacity % n_shards == 0, (capacity, n_shards)
    local_cap = capacity // n_shards
    n_traced = len(objective.hparam_spec().traced)
    one = objective.make_step(structural, local_cap)

    if local_cap == 1:
        def batched(learner, carry, *hyper):
            squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
            out = one(squeeze(learner), squeeze(carry),
                      *(h[0] for h in hyper))
            return tuple(jax.tree.map(lambda x: x[None], t) for t in out)
    else:
        batched = jax.vmap(one)

    def step(learner, carry, *rest):
        hyper, active = rest[:-1], rest[-1]
        new = batched(learner, carry, *hyper)
        def keep_active(n, o):
            mask = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
            return jnp.where(mask, n, o)
        return tuple(jax.tree.map(keep_active, n, o)
                     for n, o in zip(new, (learner, carry)))

    if mesh is not None:
        from jax.sharding import PartitionSpec
        spec = PartitionSpec("slots")
        step = jax.shard_map(step, mesh=mesh,
                             in_specs=(spec,) * (n_traced + 3),
                             out_specs=(spec,) * 2, check_vma=False)

    fn = jax.jit(step, donate_argnums=(0, 1))
    if len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    _STEP_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class PopulationEngine:
    """Runs a whole asynchronous search on one device.

    The loop: fill free slots from the driver (service), run every bucket's
    jitted step once, poll the episode counters, report finished phases,
    mask evicted slots and hot-swap fresh configurations into them. Phase
    semantics match ``GA3CTrainer.run_episodes`` exactly: a phase ends after
    the update in which ``episodes_per_phase`` episodes have finished, or at
    ``max_updates`` updates."""

    def __init__(self, objective, *, max_slots: int, n_envs: int = 16,
                 episodes_per_phase: int = 60, max_updates: int = 2000,
                 seed: int = 0, mesh=None, bracket_eta: Optional[int] = None,
                 metrics=None, spans=None):
        # the workload: a PopulationObjective instance, a spec dict
        # ({"kind": "lm", ...}), or — the pre-refactor surface — a plain
        # game string, which constructs the default GA3C objective
        if isinstance(objective, str):
            from repro.population.objectives.ga3c import GA3CObjective
            objective = GA3CObjective(objective, n_envs=n_envs)
        elif isinstance(objective, dict):
            objective = objective_from_spec(objective)
        self.objective = objective
        self.game = getattr(objective, "game", objective.name)
        # telemetry (engine.* metrics — see telemetry.METRIC_SCHEMA);
        # pass NULL_REGISTRY for a zero-overhead run (the bench baseline)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # distributed tracing (engine.* spans — telemetry.SPAN_SCHEMA):
        # a SpanRecorder sinking to a journal, or the default no-op twin
        # (span emission sites are per-phase / per-compile, never per-step)
        self.spans = spans if spans is not None else NULL_RECORDER
        self.max_slots = max_slots
        self.n_envs = n_envs
        self.episodes_per_phase = episodes_per_phase
        self.max_updates = max_updates
        self.seed = seed
        # multi-device: slot axes sharded over mesh.shape["slots"] devices.
        # Stacked state is COMMITTED to the slot sharding (device_put at
        # creation / growth / hot-swap): feeding uncommitted arrays into
        # the sharded step makes XLA reshard the whole state every call —
        # measured ~10x slower than committed inputs on CPU.
        self.mesh = mesh
        self.n_shards = int(mesh.shape["slots"]) if mesh is not None else 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._sharding = NamedSharding(mesh, PartitionSpec("slots"))
        else:
            self._sharding = None
        # the hot-swap's slot write: the output keeps the slot sharding
        self._write_slot = jax.jit(
            _write_slot, donate_argnums=(0,),
            **({} if mesh is None else {"out_shardings": self._sharding}))
        # bracket mode: the rung barrier itself lives in the SERVICE (the
        # driver answers "parked" at rung phases); the engine only needs to
        # know it is a bracket participant so ACQUIRE carries the rung-0
        # refill hint. eta is enforced service-side — the value here is a
        # participation flag kept for API continuity.
        assert bracket_eta is None or bracket_eta >= 2, bracket_eta
        self.bracket_eta = bracket_eta
        self._rung_hint = 0 if bracket_eta is not None else None
        # seconds between barrier polls of parked slots while other slots
        # still train (an idle host polls continuously instead)
        self.park_poll_interval = 0.2
        # speculative rung-0 refill: once every local slot is parked at a
        # rung barrier, the bottom 1/eta of them WILL be demoted when the
        # cohort resolves — acquire (and start training) that many fresh
        # entrants immediately instead of idling them across the verdict
        # poll's round-trip. Exact on a single host; on a multi-host
        # bracket it is the local fair share (the pooled demotions may
        # land elsewhere, in which case occupancy transiently exceeds
        # max_slots and the admission gate self-corrects).
        self.speculative_refill = True
        self.buckets: Dict[Hashable, Bucket] = {}
        self.total_env_steps = 0       # active-lane env transitions
        self.total_updates = 0
        self.clones = 0                # on-device PBT slot copies executed
        self.speculated = 0            # leases acquired by speculative refill
        self._slot_counter = 0
        self.records: List[Tuple] = []  # (trial_id, slot, phase, t0, t1, m)

    def _place(self, tree):
        """Commit a stacked pytree to the slot sharding (no-op unsharded or
        when already correctly placed)."""
        if self._sharding is None:
            return tree
        return jax.device_put(tree, self._sharding)

    def _round_capacity(self, capacity: int) -> int:
        """Smallest multiple of the shard count >= capacity, so the slot
        axis always splits evenly across the mesh (pad slots stay masked)."""
        s = self.n_shards
        return -(-capacity // s) * s

    def _new_slot_id(self) -> int:
        self._slot_counter += 1
        return self._slot_counter - 1

    @property
    def n_active(self) -> int:
        return sum(b.n_active for b in self.buckets.values())

    @property
    def n_occupied(self) -> int:
        """Active + parked: slots that cannot take a fresh configuration."""
        return sum(b.n_occupied for b in self.buckets.values())

    def active_trial_ids(self) -> List[int]:
        """Snapshot of live trial ids (parked trials included — they still
        hold leases that heartbeats must renew). Called from the worker's
        heartbeat thread while the engine mutates buckets: every container
        is copied in one C-level call (atomic under the GIL) before
        iterating."""
        out = []
        for b in list(self.buckets.values()):
            for m in list(b.meta):
                if m is not None:
                    out.append(m.trial_id)
        return out

    # -- admission ----------------------------------------------------------
    def admit(self, lease: TrialLease, now: float = 0.0) -> None:
        with profiler_span("engine.admit", self.metrics):
            hp = lease.hparams
            obj = self.objective
            key = obj.bucket_key(hp)
            bucket = self.buckets.get(key)
            if bucket is None:
                with profiler_span("engine.grow", self.metrics):
                    bucket = self.buckets[key] = Bucket(self, key, 1, hp)
            i = bucket.free_index()
            if i is None:
                i = bucket.capacity
                with profiler_span("engine.grow", self.metrics):
                    bucket.grow(bucket.capacity + 1)
            rng = jax.random.PRNGKey(trial_seed(self.seed, hp))
            with profiler_span("engine.init_slot", self.metrics):
                learner, carry = obj.init_slot_state(rng, hp)
            meta = SlotMeta(lease.trial_id, hp, bucket.slot_ids[i],
                            phase_t0=now)
            with profiler_span("engine.write_slot", self.metrics):
                bucket.write_slot(i, meta, learner, carry,
                                  obj.traced_values(hp))

    def _admit_grouped(self, leases: Sequence[TrialLease],
                       now: float) -> None:
        """Group by bucket key and pre-size buckets so an initial
        population of k same-bucket trials compiles ONE step, not k."""
        by_key: Dict[Hashable, List[TrialLease]] = {}
        for lease in leases:
            by_key.setdefault(self.objective.bucket_key(lease.hparams),
                              []).append(lease)
        for key, group in by_key.items():
            bucket = self.buckets.get(key)
            free = (bucket.capacity - bucket.n_occupied) if bucket else 0
            need = len(group) - free
            if bucket is None:
                with profiler_span("engine.grow", self.metrics):
                    self.buckets[key] = Bucket(self, key, len(group),
                                               group[0].hparams)
            elif need > 0:
                with profiler_span("engine.grow", self.metrics):
                    bucket.grow(bucket.capacity + need)
            for lease in group:
                self.admit(lease, now)

    # -- the loop -----------------------------------------------------------
    def run(self, driver) -> List[Tuple]:
        t0 = time.monotonic()
        set_tb = getattr(driver, "set_timebase", None)
        if set_tb is not None:
            # remote tracing: the driver's trace `t` must share this run's
            # t_start/t_end timebase, or the server's clock offset is off
            # by the construction-to-run gap
            set_tb(t0)
        exhausted = False
        retry_at = 0.0
        poll_at = 0.0
        for it in itertools.count():
            with profiler_span("engine.iteration", self.metrics,
                               step_num=it):
                now = time.monotonic()
                want = 0
                if not exhausted and now >= retry_at:
                    if self.n_occupied < self.max_slots:
                        want = self.max_slots - self.n_occupied
                    elif (self.speculative_refill and self.bracket_eta
                          and self.n_active == 0 and self._any_parked()):
                        # speculative rung-0 refill: the local cohort is
                        # fully parked; acquire the entrants its demotions
                        # will make room for BEFORE the verdict polls
                        # return, so freed slots never idle across the
                        # barrier round-trip (the service resolves any
                        # ready cohort before enrolling them, so they land
                        # in the next generation)
                        from repro.core.asha import rung_demotions
                        want = (self.max_slots
                                + rung_demotions(self._n_parked(),
                                                 self.bracket_eta)
                                - self.n_occupied)
                if want > 0:
                    with profiler_span("engine.acquire", self.metrics):
                        leases, retry = driver.acquire_many(
                            want, rung=self._rung_hint)
                    if self.n_occupied >= self.max_slots:
                        self.speculated += len(leases)
                        self.metrics.counter(
                            "engine.speculative_leases").inc(len(leases))
                    if leases:
                        self._admit_grouped(leases, now - t0)
                    elif retry is None:
                        exhausted = True
                    else:
                        retry_at = now + retry
                lost = driver.poll_lost()
                if lost:
                    self._abandon(lost)
                if self._any_parked() and (self.n_active == 0
                                           or now >= poll_at):
                    # barrier poll: every parked slot re-sends its withheld
                    # report; the service answers "parked" until the rung
                    # cohort (possibly spanning other hosts) is complete,
                    # then promote/demote come back as continue/stop
                    self._poll_parked(driver, t0)
                    poll_at = now + self.park_poll_interval
                if self.n_active == 0:
                    if self._any_parked():
                        # the cohort is waiting on another host — keep
                        # leases warm and poll again shortly
                        time.sleep(min(self.park_poll_interval, 0.05))
                        continue
                    if exhausted:
                        break
                    time.sleep(min(max(retry_at - time.monotonic(), 0.01),
                                   0.5))
                    continue
                for bucket in self.buckets.values():
                    if bucket.n_active:
                        self._step_bucket(bucket)
                with profiler_span("engine.poll", self.metrics):
                    self._poll_phases(driver, t0)
        return self.records

    def _step_bucket(self, bucket: "Bucket") -> None:
        """Dispatch one bucket's step and charge its active slots."""
        step_t0 = time.perf_counter()
        with profiler_span("engine.dispatch", self.metrics):
            bucket.step()
        if not bucket._stepped:
            # first call of this executable shape: dominated by
            # trace+compile (dispatch is async, compile is not)
            bucket._stepped = True
            compile_s = time.perf_counter() - step_t0
            self.metrics.histogram("engine.compile_s").observe(compile_s)
            # the compile serves every trial stacked in the bucket —
            # critical_path splits it across them
            self.spans.end("engine.compile", compile_s, cat="engine",
                           bucket=bucket.key,
                           trials=[m.trial_id for m in bucket.meta
                                   if m is not None])
        stepped = bucket.n_active
        self.total_updates += stepped
        self.total_env_steps += stepped * bucket.update_cost
        self.metrics.counter("engine.updates").inc(stepped)
        self.metrics.counter("engine.env_steps").inc(
            stepped * bucket.update_cost)

    def _report_many(self, driver, reports: List[dict]) -> List:
        """Send a generation's reports through the driver — one
        ``report_many`` call when the driver has it (RemoteDriver: one
        wire frame), a per-report loop otherwise (scripted test
        drivers)."""
        with profiler_span("engine.report", self.metrics):
            many = getattr(driver, "report_many", None)
            if many is not None:
                return many(reports)
            return [driver.report(r["trial_id"], r["phase"], r["metric"],
                                  r["t_start"], r["t_end"],
                                  env_steps=r.get("env_steps"))
                    for r in reports]

    def _poll_phases(self, driver, t0: float) -> None:
        # two passes so every slot that finished its phase this iteration
        # reports in ONE driver call (one wire round-trip per generation,
        # not per slot): first collect the finished slots, then apply the
        # index-aligned decisions
        ready: List[tuple] = []
        for bucket in self.buckets.values():
            if not bucket.n_active:
                continue
            with profiler_span("engine.sync", self.metrics):
                counts, sums = self.objective.progress(bucket.carry)
                fin_n = np.asarray(counts)
                fin_sum = np.asarray(sums)
            for i in range(bucket.capacity):
                meta = bucket.meta[i]
                if meta is None or not bucket.active[i]:
                    continue
                meta.updates_in_phase += 1
                n = float(fin_n[i]) - meta.start_n
                if (n < self.episodes_per_phase
                        and meta.updates_in_phase < self.max_updates):
                    continue
                score = (float(fin_sum[i]) - meta.start_sum) / max(n, 1.0)
                t_now = time.monotonic() - t0
                phase_steps = meta.updates_in_phase * bucket.update_cost
                phase_s = t_now - meta.phase_t0
                self.spans.end("engine.phase", phase_s, cat="engine",
                               trial_id=meta.trial_id, phase=meta.phase,
                               slot=meta.slot_id)
                ready.append((bucket, fin_n, fin_sum, i, meta, score,
                              t_now, phase_steps))
        if not ready:
            return
        decisions = self._report_many(driver, [
            {"trial_id": m.trial_id, "phase": m.phase, "metric": score,
             "t_start": m.phase_t0, "t_end": t_now,
             "env_steps": phase_steps}
            for (_, _, _, _, m, score, t_now, phase_steps) in ready])
        for ((bucket, fin_n, fin_sum, i, meta, score, t_now,
              phase_steps), decision) in zip(ready, decisions):
            if decision == "parked":
                # rung phase: the service withheld the report at the
                # barrier — mask the slot (state frozen on device) and
                # keep the exact report for the barrier polls
                meta.pending = (score, meta.phase_t0, t_now, phase_steps)
                meta.parked_at = time.perf_counter()
                bucket.park(i)
                continue
            self.records.append((meta.trial_id, meta.slot_id, meta.phase,
                                 meta.phase_t0, t_now, score))
            if decision == "stop":
                bucket.release(i)
            else:
                if getattr(decision, "clone_from", None) is not None:
                    # PBT exploit/explore: the verdict rode the report
                    # reply — execute the copy device-side and adopt
                    # the perturbed hyperparameters before continuing
                    self._exploit(bucket, i, meta, decision)
                meta.phase += 1
                meta.updates_in_phase = 0
                meta.start_n = float(fin_n[i])
                meta.start_sum = float(fin_sum[i])
                meta.phase_t0 = t_now

    # -- PBT exploit/explore (CLONE verdicts) -------------------------------
    def _find_slot(self, trial_id: int
                   ) -> Optional[Tuple["Bucket", int]]:
        for bucket in self.buckets.values():
            for i, meta in enumerate(bucket.meta):
                if meta is not None and meta.trial_id == trial_id:
                    return bucket, i
        return None

    def _exploit(self, bucket: "Bucket", i: int, meta: SlotMeta,
                 reply) -> None:
        """Execute a CLONE verdict: the trial continues as a copy of
        ``reply.clone_from``'s learner state under ``reply.perturb``.
        When the parent occupies a slot of THIS engine the copy is a
        device-side slot-to-slot transfer (learner state only; weights
        never leave the device). A parent on another host — or one that
        finished and left its slot — cannot ship its weights, so the
        trial keeps its own learner state and only adopts the perturbed
        hyperparameters (documented degradation of remote clones)."""
        hp = dict(reply.perturb) if reply.perturb else dict(meta.hparams)
        traced = self.objective.traced_values(hp, fallback=meta.hparams)
        src = self._find_slot(reply.clone_from)
        if src is not None and src != (bucket, i):
            src_bucket, j = src
            clone_t0 = time.perf_counter()
            bucket.clone_slot(i, src_bucket, j, traced)
            self.clones += 1
            self.metrics.counter("engine.clones").inc()
            self.spans.end("engine.clone",
                           time.perf_counter() - clone_t0, cat="engine",
                           trial_id=meta.trial_id,
                           clone_from=reply.clone_from)
        else:
            for n, v in zip(bucket.traced_names, traced):
                bucket.hyper[n][i] = v
            bucket._hyper_dev = None
        meta.hparams = hp

    # -- rung barriers (service-side successive halving) --------------------
    def _any_parked(self) -> bool:
        return any(m is not None and not b.active[i]
                   for b in self.buckets.values()
                   for i, m in enumerate(b.meta))

    def _n_parked(self) -> int:
        return sum(1 for b in self.buckets.values()
                   for i, m in enumerate(b.meta)
                   if m is not None and not b.active[i])

    def _poll_parked(self, driver, t0: float) -> None:
        """The thin-client side of the service's rung barrier: re-send each
        parked slot's withheld report. ``"parked"`` → the cohort (possibly
        spanning other hosts) is still filling, keep waiting; ``"continue"``
        → promoted, unpark into the next phase; ``"stop"`` → demoted (or
        the lease is gone), free the slot for the admission path to
        hot-swap a fresh configuration."""
        polls: List[tuple] = []
        for bucket in self.buckets.values():
            for i in range(bucket.capacity):
                meta = bucket.meta[i]
                if meta is None or bucket.active[i] or meta.pending is None:
                    continue
                polls.append((bucket, i, meta))
        if not polls:
            return
        self.metrics.counter("engine.park_polls").inc(len(polls))
        decisions = self._report_many(driver, [
            {"trial_id": m.trial_id, "phase": m.phase,
             "metric": m.pending[0], "t_start": m.pending[1],
             "t_end": m.pending[2], "env_steps": m.pending[3]}
            for (_, _, m) in polls])
        # lazily materialize each bucket's episode counters only when one
        # of its slots actually unparks
        counters: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for (bucket, i, meta), decision in zip(polls, decisions):
            if decision == "parked":
                continue
            score, ts, te, phase_steps = meta.pending
            self.records.append((meta.trial_id, meta.slot_id, meta.phase,
                                 ts, te, score))
            meta.pending = None
            if meta.parked_at is not None:
                stall_s = time.perf_counter() - meta.parked_at
                self.metrics.histogram("engine.park_stall_s").observe(
                    stall_s)
                self.spans.end("engine.park_stall", stall_s,
                               cat="engine", trial_id=meta.trial_id,
                               phase=meta.phase, slot=meta.slot_id)
                meta.parked_at = None
            if decision == "stop":
                bucket.release(i)
                continue
            key = id(bucket)
            if key not in counters:
                with profiler_span("engine.sync", self.metrics):
                    counts, sums = self.objective.progress(bucket.carry)
                    counters[key] = (np.asarray(counts), np.asarray(sums))
            fin_n, fin_sum = counters[key]
            meta.phase += 1
            meta.updates_in_phase = 0
            meta.start_n = float(fin_n[i])
            meta.start_sum = float(fin_sum[i])
            meta.phase_t0 = time.monotonic() - t0
            bucket.unpark(i)

    def _abandon(self, trial_ids: set) -> None:
        for bucket in self.buckets.values():
            for i in range(bucket.capacity):
                meta = bucket.meta[i]
                if meta is not None and meta.trial_id in trial_ids:
                    bucket.release(i)
