"""One run of one cell: build the search from the seed, warm up, measure a
window, check the result against the plain reference.

Everything particular to a cell lives in files found by name:

* ``bench/workloads/<cell>.json``  its configuration, traffic, chips,
  sampled slots and the limits of its comparisons;
* ``bench/configs/<config>.json``  the configuration as it is run, with
  its plain reference beside it in ``bench/configs/<config>.py``;
* ``bench/traffic/<traffic>.json`` the search: space, slots per chip,
  phases, eviction, phase length and budget;
* ``bench/kinds/<kind>.py``        how the program's objective of that
  model kind is built and where its state keeps what is compared;
* ``bench/flops/<kind>.py``        the operations and bytes of that kind's
  work, reckoned from the configuration's shapes;
* ``bench/metrics/<metric>.py``    one reader per metric named in
  ``BENCHMARK.json``.

A kind module provides ``build_objective(config, traffic)`` (the
program's objective for the configuration file and traffic mix),
``engine_kwargs(config, traffic)`` (further arguments of
``PopulationEngine``), ``params(learner)`` (the weights in a bucket's
stacked learner), ``grad_moment(learner, config)`` (an optimizer state
leaf tree and the factor that turn it into the squared first gradient
after one step), ``counter(learner)`` (each slot's optimizer step
count) and ``loss_sum(carry)`` (each slot's summed ``-loss``, or None).
A kind of the LM family reuses ``kinds/lm.py``: it builds the program's
``ModelConfig`` itself and hands it to that module's ``objective(cfg,
config, traffic)``, and takes the other functions from it, loading it
with ``load_module(os.path.join(os.path.dirname(__file__), "lm.py"),
"bench_kind_lm")``, which gives the module object the harness holds.

A flops module provides, for the configuration file ``cfg``:
``flops_per_token(cfg, seq)`` (training operations a token, nothing
recomputed), ``update_bytes(cfg)`` (the least bytes one slot's update
moves) and ``attention_work(cfg, batch, seq)`` (operations and least
bytes of the attention, forward and backward, over ``batch`` sequences).
A metric's readers use what they need of it, and a metric reaches only
the cells its ``workloads`` list in ``BENCHMARK.json`` names.

The run drives ``PopulationEngine.run`` through the program's own
``LocalDriver``, wrapped so that the harness sees each engine iteration
(the driver's ``poll_lost`` is called once per iteration, after
admission and before the steps), each acquire and each report.
Iterations 1 to 4 hold the correctness snapshots: the sampled slots'
state as admitted and after the first three steps. The window opens at
the first iteration from ``WINDOW_ITER`` on at which no trial of the
initial fill holds a slot any more: every program the window runs
(step, hot-swap, report) has run, and the synchronized start of the
fill is over. It closes at the first iteration after ``--seconds``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_STEPS = 3             # steps of each sampled slot the reference follows
WINDOW_ITER = REF_STEPS + 3
TRACE_S = 3.0             # length of the profiler trace in the window
HOST_SPANS = ("acquire", "admit", "report")
SAMPLE_SEED = 0x5EED


class NoAccelerator(RuntimeError):
    pass


class WindowClosed(Exception):
    pass


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------
def read_json(root: str, rel: str):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


_MODULES: Dict[str, object] = {}


def load_module(path: str, name: str):
    """The module at ``path``, loaded once a process."""
    if path in _MODULES:
        return _MODULES[path]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


class Cell:
    """A workload entry and the files it names."""

    def __init__(self, name: str, root: str = ROOT):
        self.name = name
        self.root = root
        self.workload = read_json(root, f"bench/workloads/{name}.json")
        self.config = read_json(
            root, f"bench/configs/{self.workload['config']}.json")
        self.traffic = read_json(
            root, f"bench/traffic/{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])
        self.kind = self.config["kind"]
        self.limits = self.workload["limits"]

    def _bench(self, *parts) -> str:
        return os.path.join(self.root, "bench", *parts)

    def kind_module(self):
        return load_module(self._bench("kinds", f"{self.kind}.py"),
                           f"bench_kind_{self.kind}")

    def reference(self):
        name = self.workload["config"]
        return load_module(self._bench("configs", f"{name}.py"),
                           "bench_ref_" + name.replace("-", "_")
                           .replace(".", "_"))

    def flops(self):
        return load_module(self._bench("flops", f"{self.kind}.py"),
                           f"bench_flops_{self.kind}")

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics ``BENCHMARK.json`` names for this cell: end-to-end
        ones without a trace, per-layer ones with it."""
        bm = read_json(self.root, "BENCHMARK.json")
        group = bm["per_layer" if trace else "end_to_end"]
        return [m for m in group if self.name in m.get("workloads",
                                                       [self.name])]

    def reader(self, metric: str):
        return load_module(self._bench("metrics", f"{metric}.py"),
                           "bench_metric_" + metric.replace(".", "_"))


def peaks(root: str, device_kind: str) -> dict:
    table = read_json(root, "bench/peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def search_space(spec: dict):
    """The program's ``SearchSpace`` from the traffic file's data."""
    from repro.core import search_space as ss
    kinds = {"log_uniform": ss.LogUniform, "q_log_uniform": ss.QLogUniform,
             "uniform": ss.Uniform}
    params = {}
    for name, (kind, *args) in spec.items():
        if kind == "categorical":
            params[name] = ss.Categorical(tuple(args[0]))
        else:
            params[name] = kinds[kind](*args)
    return ss.SearchSpace(params)


def devices_for(chips: int, platform: str):
    """The first ``chips`` devices, which must be of ``platform``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX finds no devices: {e}") from None
    if not devs or devs[0].platform != platform:
        raise NoAccelerator(
            f"this cell runs on {platform}; JAX finds "
            f"{devs[0].platform if devs else 'nothing'}")
    if len(devs) < chips:
        raise NoAccelerator(f"this cell needs {chips} {platform} devices; "
                            f"JAX finds {len(devs)}")
    return devs[:chips]


class CompileWatch:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (copied from the bring-up smoke test)."""

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


# ---------------------------------------------------------------------------
# device-side readings of the sampled slots
# ---------------------------------------------------------------------------
def _leaf_names(tree) -> List[str]:
    import jax
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in paths]


def _named(tree, values) -> Dict[str, np.ndarray]:
    import jax
    return dict(zip(_leaf_names(tree),
                    (np.asarray(v, np.float64)
                     for v in jax.tree.leaves(values))))


def _jits():
    import jax
    import jax.numpy as jnp

    def rows(x):
        return tuple(range(1, x.ndim))

    take = jax.jit(lambda t, idx: jax.tree.map(
        lambda x: jnp.take(x, idx, axis=0), t))
    sq = jax.jit(lambda t, idx: jax.tree.map(
        lambda x: jnp.sum(jnp.square(
            jnp.take(x, idx, axis=0).astype(jnp.float32)), axis=rows(x)), t))
    total = jax.jit(lambda t, idx: jax.tree.map(
        lambda x: jnp.sum(jnp.take(x, idx, axis=0).astype(jnp.float32),
                          axis=rows(x)), t))
    dsq = jax.jit(lambda t, t0, idx: jax.tree.map(
        lambda x, y: jnp.sum(jnp.square(
            jnp.take(x, idx, axis=0).astype(jnp.float32)
            - y.astype(jnp.float32)), axis=rows(x)), t, t0))
    return take, sq, total, dsq


# ---------------------------------------------------------------------------
# the driver wrapper and the run's state
# ---------------------------------------------------------------------------
class BenchDriver:
    """The program's driver, seen by the harness: spans around each call
    (read from the trace), the reports and their answers, and the engine
    iteration hook."""

    def __init__(self, inner, run: "Run"):
        self.inner = inner
        self.run = run
        self._admit = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def acquire_many(self, k, rung=None):
        import jax
        with jax.profiler.TraceAnnotation("acquire"):
            leases, retry = self.inner.acquire_many(k, rung=rung)
        if leases:
            self._admit = jax.profiler.TraceAnnotation("admit")
            self._admit.__enter__()
        return leases, retry

    def report_many(self, reports):
        import jax
        with jax.profiler.TraceAnnotation("report"):
            replies = self.inner.report_many(reports)
        self.run.on_reports(reports, replies)
        return replies

    def poll_lost(self):
        if self._admit is not None:
            self._admit.__exit__(None, None, None)
            self._admit = None
        self.run.on_iteration()
        return self.inner.poll_lost()


class Run:
    def __init__(self, cell: Cell, kind, engine, seed: int,
                 seconds: Optional[float], trace_dir: Optional[str],
                 watch: CompileWatch):
        import jax
        self.cell = cell
        self.kind = kind
        self.engine = engine
        self.seed = seed
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.watch = watch
        self.it = 0
        self.take, self.sq, self.total, self.dsq = _jits()
        self.sample = None                # (bucket, slot indices)
        self.prog: Dict[str, object] = {"loss_sum": []}
        self.p0 = None
        self.win = None                   # perf_counter bounds
        self.win_mono = None
        self.work = [0, 0]
        self.trace_state = None
        self.answered = 0
        self.unmatched = 0
        self.stops = 0
        self.trial_work: Dict[int, int] = defaultdict(int)
        self.seen = set()
        self.duplicates = 0
        self.initial = None               # trial ids of the initial fill
        self.compiles0 = 0
        self._jax = jax

    # -- hooks ---------------------------------------------------------------
    def on_reports(self, reports, replies) -> None:
        if len(replies) != len(reports):
            self.unmatched += abs(len(reports) - len(replies))
        for r, reply in zip(reports, replies):
            key = (r["trial_id"], r["phase"])
            if key in self.seen:
                self.duplicates += 1
            self.seen.add(key)
            self.trial_work[r["trial_id"]] += int(r.get("env_steps") or 0)
            if str(reply) == "stop":
                self.stops += 1
        if self.win_mono is not None and self.win_mono[1] is None:
            self.answered += len(reports)

    def on_iteration(self) -> None:
        self.it += 1
        if self.it <= REF_STEPS + 1:
            self._snapshot()
        elif self.seconds is None:
            raise WindowClosed            # set-up only: no window
        if self.win is None:
            if self.it >= WINDOW_ITER and not (self.initial
                                               & self._holding()):
                self._open_window()
            return
        now = time.perf_counter()
        if self.trace_dir is not None and self.trace_state is None \
                and now >= self.win[0] + self.seconds / 2:
            self._start_trace()
        elif self.trace_state is not None and self.trace_state[0] == "on" \
                and now >= self.trace_state[1] + TRACE_S:
            self._stop_trace(now)
        if now >= self.win[0] + self.seconds:
            self._close_window(now)
            raise WindowClosed

    def _holding(self) -> set:
        """Trial ids that hold a slot."""
        return {m.trial_id for b in self.engine.buckets.values()
                for m in b.meta if m is not None}

    # -- snapshots of the sampled slots ---------------------------------------
    def _snapshot(self) -> None:
        kind, take, sq, dsq = self.kind, self.take, self.sq, self.dsq
        total = self.total
        if self.sample is None:
            self.initial = self._holding()
            (bucket,) = self.engine.buckets.values()
            idx = sample_slots(bucket.capacity,
                               self.cell.workload["sample_slots"], self.seed)
            self.sample = (bucket, np.asarray(idx, np.int32))
            self.prog["hparams"] = [dict(bucket.meta[i].hparams)
                                    for i in idx]
        bucket, idx = self.sample
        params = kind.params(bucket.learner)
        step = self.it - 1
        if step == 0:
            self.p0 = take(params, idx)
            self.prog["init"] = _named(params, sq(params, idx))
        if step == 1:
            moment, coef = kind.grad_moment(bucket.learner,
                                             self.cell.config)
            self.prog["grad1"] = {k: v * coef for k, v in
                                  _named(params, total(moment, idx)).items()}
        if step == REF_STEPS:
            self.prog["dparam"] = _named(params, dsq(params, self.p0, idx))
            self.p0 = None
        loss_sum = kind.loss_sum(bucket.carry)
        if loss_sum is not None:
            self.prog["loss_sum"].append(
                np.asarray(self._jax.device_get(loss_sum))[idx])

    # -- the window -----------------------------------------------------------
    def _work(self) -> int:
        return int(self.engine.total_env_steps)

    def _open_window(self) -> None:
        self.compiles0 = self.watch.compiles
        self.win = [time.perf_counter(), None]
        self.win_mono = [time.monotonic(), None]
        self.work[0] = self._work()

    def _start_trace(self) -> None:
        """Trace ``TRACE_S`` seconds from the middle of the window, past
        the transient that follows the initial fill."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        self.trace_state = ["on", time.perf_counter(), span, self._work()]

    def _stop_trace(self, now: float) -> None:
        import jax
        _, t_on, span, work_on = self.trace_state
        span.__exit__(None, None, None)
        self.trace_state = ["done", now - t_on, self._work() - work_on]
        jax.profiler.stop_trace()

    def _close_window(self, now: float) -> None:
        self.win[1] = now
        self.win_mono[1] = time.monotonic()
        self.work[1] = self._work()
        if self.trace_state is not None and self.trace_state[0] == "on":
            self._stop_trace(now)
        self.compiles_in_window = self.watch.compiles - self.compiles0


def sample_slots(capacity: int, k: int, seed: int) -> List[int]:
    """``k`` slot indices, drawn from the seed."""
    rng = np.random.default_rng([SAMPLE_SEED, seed])
    return sorted(int(i) for i in rng.choice(capacity, size=min(k, capacity),
                                             replace=False))


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------
def leaf_gaps(prog: Dict[str, np.ndarray], ref: List[Dict[str, float]],
              keep: List[Dict[str, bool]]) -> List[List[float]]:
    """Per sampled slot, per kept leaf: the gap between the program's
    norm of the leaf and the reference's, over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    out = []
    for k, ref_k in enumerate(ref):
        norms = {n: math.sqrt(max(v, 0.0)) for n, v in ref_k.items()}
        median = statistics.median(norms.values())
        out.append([abs(math.sqrt(max(float(prog[n][k]), 0.0)) - r)
                    / max(r, median, 1e-30)
                    for n, r in norms.items() if keep[k][n]])
    return out


def worst_leaf_gap(prog, ref, keep) -> float:
    """The largest leaf gap over the sampled slots."""
    return max(max(g, default=0.0) for g in leaf_gaps(prog, ref, keep))


def kept_leaves(grad1: Dict[str, float]) -> Dict[str, bool]:
    """Leaves whose first gradient in the reference is not nought to
    rounding: at least a thousandth of the median leaf's."""
    norms = {n: math.sqrt(max(v, 0.0)) for n, v in grad1.items()}
    median = statistics.median(norms.values())
    return {n: v >= 1e-3 * median for n, v in norms.items()}


def compare(prog: dict, refs: List[dict]) -> Dict[str, float]:
    """The numbers the reference decides, one value each."""
    keep = [kept_leaves(r["grad1"]) for r in refs]
    every = [{n: True for n in r["init"]} for r in refs]
    out = {
        "init_gap": worst_leaf_gap(prog["init"], [r["init"] for r in refs],
                                   every),
        "grad1_gap": worst_leaf_gap(prog["grad1"],
                                    [r["grad1"] for r in refs], keep),
        "dparam_gap": worst_leaf_gap(prog["dparam"],
                                     [r["dparam"] for r in refs], keep),
    }
    if refs and refs[0].get("loss") is not None and prog["loss_sum"]:
        sums = np.stack(prog["loss_sum"])            # (REF_STEPS + 1, K)
        losses = -(sums[1:] - sums[:-1])             # per step, per slot
        gap = 0.0
        for k, r in enumerate(refs):
            for s, lr in enumerate(r["loss"]):
                gap = max(gap, abs(float(losses[s, k]) - lr) / abs(lr))
        out["loss_gap"] = gap
    return out


def reference_readings(cell: Cell, seed: int, hparams: List[dict],
                       variant: str = "reference") -> List[dict]:
    ref = cell.reference()
    return [ref.readings(cell.config, cell.traffic, seed, hp,
                         steps=REF_STEPS, variant=variant)
            for hp in hparams]


# ---------------------------------------------------------------------------
# building the search
# ---------------------------------------------------------------------------
def policy(cell: Cell, seed: int):
    """The program's HyperTrick over the traffic's space and budget."""
    from repro.core.hypertrick import HyperTrick
    tr = cell.traffic
    return HyperTrick(search_space(tr["space"]), int(tr["trials"]),
                      int(tr["phases"]), float(tr["eviction"]), seed=seed)


class Search:
    """``PopulationEngine.run`` driven by ``LocalDriver`` over an
    in-process ``OptimizationService``: what ``PopulationCluster`` (``tune.py
    --backend vectorized``) builds. ``objective`` replaces the program's
    objective (the tests plant faults there)."""

    def __init__(self, cell: Cell, seed: int, objective=None):
        from repro.core.service import OptimizationService
        from repro.population.engine import LocalDriver, PopulationEngine
        if cell.chips != 1:
            raise ValueError(f"{cell.name}: cells on {cell.chips} chips "
                             "are not supported; a cell takes one chip")
        kind = cell.kind_module()
        tr = cell.traffic
        obj = objective if objective is not None else \
            kind.build_objective(cell.config, tr)
        self.service = OptimizationService(policy(cell, seed))
        self.engine = PopulationEngine(
            obj, metrics=self.service.metrics,
            max_slots=int(tr["slots_per_chip"]),
            episodes_per_phase=int(tr["episodes_per_phase"]),
            max_updates=int(tr["max_updates"]), seed=seed,
            **kind.engine_kwargs(cell.config, tr))
        self.driver = LocalDriver(self.service)

    def status_counts(self) -> Dict[str, int]:
        return self.service.db.summary()["by_status"]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, platform: str = "tpu", objective=None,
             log=None) -> dict:
    """One run; returns the result line's object. ``log`` receives the
    comparison lines (standard error by default)."""
    t_process = float(os.environ.get("BENCH_PROCESS_T0", time.monotonic()))
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = Cell(name, root)
    devices = devices_for(cell.chips, platform)
    watch = CompileWatch()
    kind = cell.kind_module()
    search = Search(cell, seed, objective)
    engine = search.engine
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell, kind, engine, seed, seconds, trace_dir, watch)
    try:
        engine.run(BenchDriver(search.driver, run))
        raise RuntimeError("the search ended before the window closed; "
                           "raise the traffic's trial budget")
    except WindowClosed:
        pass
    setup_s = run.win_mono[0] - t_process
    window_s = run.win[1] - run.win[0]
    work_rate = (run.work[1] - run.work[0]) / window_s
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    counters = check_bookkeeping(engine, search.status_counts(), run, kind)
    hparams = run.prog["hparams"]
    # free the program's state before the reference runs on the chip
    engine.buckets.clear()
    del engine, search
    run.engine = None
    run.sample = None
    gc.collect()
    refs = reference_readings(cell, seed, hparams)
    values = compare(run.prog, refs)
    values.update(counters)
    compared = {k: {"value": float(values[k]),
                    "limit": float(cell.limits[k])} for k in cell.limits}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    ctx = {
        "cell": cell, "seed": seed, "devices": devices, "chips": cell.chips,
        "peaks": peaks(root, devices[0].device_kind), "flops": cell.flops(),
        "setup_s": setup_s, "window_s": window_s, "work_per_s": work_rate,
        "peak_bytes": peak, "trace": None,
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": run.answered,
              "failed": run.unmatched}
    breakdown = None
    if trace:
        ctx["trace"] = read_trace(trace_dir)
        ctx["trace_work_per_s"] = run.trace_state[2] / run.trace_state[1]
        shutil.rmtree(os.path.join(root, ".bench_trace"),
                      ignore_errors=True)
        red = ctx["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [list(x) for x in red["ops"][:10]],
                     "idle_gaps": [list(x) for x in red["gaps"][:10]]}
    metrics, notes = {}, {}
    for m in cell.metrics(trace):
        reader = cell.reader(m["name"])
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if hasattr(reader, "note"):
                notes[m["name"]] = reader.note(ctx)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["notes"] = dict(notes, compiles_in_window=run.compiles_in_window,
                           compiles=watch.compiles, compile_s=watch.compile_s,
                           cache_hits=watch.hits, cache_misses=watch.misses)
    result["compared"] = compared
    for k, c in compared.items():
        log(f"compared {k} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {correct}")
    return result


def first_steps(name: str, seed: int, *, root: str = ROOT,
                platform: str = "tpu", objective=None):
    """Only the set-up of a run: build the search from the seed and drive
    it through the steps the reference follows. Returns the program's
    readings of the sampled slots and their hyperparameters, with the
    program's state freed."""
    cell = Cell(name, root)
    devices_for(cell.chips, platform)
    search = Search(cell, seed, objective)
    run = Run(cell, cell.kind_module(), search.engine, seed, None, None,
              CompileWatch())
    try:
        search.engine.run(BenchDriver(search.driver, run))
    except WindowClosed:
        pass
    search.engine.buckets.clear()
    del search
    run.engine = run.sample = None
    gc.collect()
    return run.prog, run.prog["hparams"]


def as_program(readings: List[dict]) -> dict:
    """Reference readings in the form of the program's snapshots, so that
    a variant of the reference can stand in the program's place."""
    names = readings[0]["init"]
    out = {k: {n: np.asarray([r[k][n] for r in readings]) for n in names}
           for k in ("init", "grad1", "dparam")}
    out["loss_sum"] = []
    if readings[0].get("loss") is not None:
        losses = np.asarray([r["loss"] for r in readings])   # (K, steps)
        sums = -np.concatenate([np.zeros((len(readings), 1)),
                                np.cumsum(losses, axis=1)], axis=1)
        out["loss_sum"] = list(sums.T)
    return out


def read_trace(trace_dir: str) -> dict:
    import glob
    from bench import trace as tr
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return tr.reduce_trace(tr.load(max(files, key=os.path.getmtime)),
                           host_spans=HOST_SPANS)


def check_bookkeeping(engine, statuses: Dict[str, int], run: Run,
                      kind) -> dict:
    """Exact counts: every occupied slot's update counter equals the
    updates its trial was charged (a hot-swapped slot starts from zero, a
    masked one does not move), every report got one answer, and the
    service's trial statuses add up with what the engine holds and was
    told to stop."""
    import jax
    mismatched = 0
    occupied = 0
    for bucket in engine.buckets.values():
        steps = np.asarray(jax.device_get(kind.counter(bucket.learner)))
        for i, meta in enumerate(bucket.meta):
            if meta is None:
                continue
            occupied += 1
            done = run.trial_work[meta.trial_id] // bucket.update_cost
            if int(steps[i]) != done + meta.updates_in_phase:
                mismatched += 1
    ended = statuses.get("completed", 0) + statuses.get("killed", 0)
    status_mismatch = (abs(statuses.get("running", 0) - occupied)
                       + abs(ended - run.stops) + statuses.get("crashed", 0))
    return {"slot_update_mismatch": mismatched,
            "report_mismatch": run.unmatched + run.duplicates,
            "status_mismatch": status_mismatch}
