"""Per-trial distributed tracing: span recording, wire-protocol trace
context (and its byte-level back-compat), journal -> Chrome trace export,
critical-path attribution, and the dashboard/tailer satellites."""
import dataclasses
import json
import os
import time

import pytest

from repro.core.hypertrick import HyperTrick, RandomSearchPolicy
from repro.core.search_space import LogUniform, SearchSpace, Uniform
from repro.core.service import OptimizationService
from repro.distributed import protocol as proto
from repro.distributed.client import ServiceClient
from repro.distributed.journal import Journal, read_events
from repro.distributed.server import MetaoptServer
from repro.distributed.worker import WorkerAgent, make_synthetic_objective
from repro.telemetry.critical_path import (BUCKETS, aggregate, attribute,
                                           critical_path_report)
from repro.telemetry.export import (build_trace, export_journal,
                                    validate_chrome_trace)
from repro.telemetry.export import main as export_main
from repro.telemetry.spans import (NULL_RECORDER, PROFILER_SPANS,
                                   SPAN_SCHEMA, Span, SpanRecorder,
                                   derive_spans)


def _space():
    return SearchSpace({"x": LogUniform(0.01, 100.0)})


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------
def test_span_recorder_records_complete_spans():
    sink = []
    rec = SpanRecorder(sink, clock=lambda: 100.0)
    rec.record("trial.phase", 10.0, 2.5, trial_id=7, phase=1, node=None)
    rec.end("rpc.report", 0.25, trial_id=7)
    assert sink[0] == {"ev": "span", "name": "trial.phase", "ts": 10.0,
                       "dur": 2.5, "trial_id": 7, "phase": 1}
    assert "node" not in sink[0]          # None args are dropped
    assert sink[1]["ts"] == pytest.approx(99.75)   # end: start = clock - dur
    rec.record("x", 5.0, -1.0)            # negative duration: dropped
    assert len(sink) == 2
    assert rec.enabled


def test_null_recorder_is_inert():
    NULL_RECORDER.record("a", 0.0, 1.0, trial_id=1)
    NULL_RECORDER.end("b", 1.0)
    assert not NULL_RECORDER.enabled


def test_span_event_roundtrip():
    s = Span("engine.clone", 12.5, 0.125, cat="engine",
             args={"trial_id": 3, "clone_from": 1})
    assert Span.from_event(s.to_event()) == s


# ---------------------------------------------------------------------------
# wire protocol: trace context back-compat (satellite 4)
# ---------------------------------------------------------------------------
def test_untraced_frames_are_byte_identical_to_pre_trace_wire():
    """A client that never sets a trace context emits frames with NO trace
    key at all — byte-identical to what the previous protocol emitted."""
    for msg in (proto.AcquireRequest(node=3),
                proto.AcquireRequest(node=3, rung=1, slots=4),
                proto.ReportRequest(7, 2, -1.25, t_start=0.1, t_end=0.9,
                                    node=3)):
        frame = proto.encode(msg)
        assert b"trace" not in frame
        assert proto.decode(frame[4:]) == msg


def test_old_client_frames_decode_on_new_server():
    """A frame hand-built without the trace field (what an old client
    sends) decodes cleanly; the server sees trace=None."""
    payload = {"type": "acquire", "node": 5, "slots": 1, "batch": None}
    msg = proto.decode(json.dumps(payload).encode())
    assert msg.node == 5 and msg.trace is None
    payload = {"type": "report", "trial_id": 2, "phase": 0, "metric": 1.0,
               "t_start": 0.0, "t_end": 1.0, "node": 5}
    assert proto.decode(json.dumps(payload).encode()).trace is None


def test_traced_frames_survive_an_old_server():
    """The decode rule drops unknown fields, so an old server (no trace
    field on its dataclasses) accepts a new traced frame. Simulated by
    filtering to the pre-trace field set before construction."""
    msg = proto.AcquireRequest(node=1, trace={"ctx": "w1-abc", "t": 3.25})
    obj = json.loads(proto.encode(msg)[4:].decode())
    assert obj["trace"] == {"ctx": "w1-abc", "t": 3.25}
    obj.pop("type")
    old_fields = {f.name for f in dataclasses.fields(proto.AcquireRequest)}
    old_fields.discard("trace")           # the old dataclass never had it
    old_msg = proto.AcquireRequest(
        **{k: v for k, v in obj.items() if k in old_fields})
    assert old_msg.node == 1 and old_msg.trace is None


def test_client_trace_context_attached_only_when_set():
    c = ServiceClient.__new__(ServiceClient)   # no socket needed
    c.trace_ctx = None
    assert c._trace(1.5) is None
    c.trace_ctx = "w0-abc123"
    assert c._trace(1.5) == {"ctx": "w0-abc123", "t": 1.5}
    # no clock sample: the context still rides along (no "t" key)
    assert c._trace(None) == {"ctx": "w0-abc123"}


# ---------------------------------------------------------------------------
# live server: rpc + stitched phase spans in the journal
# ---------------------------------------------------------------------------
def test_server_journals_rpc_and_phase_spans(tmp_path):
    objective = make_synthetic_objective(sleep=0.001, seed=1)
    policy = HyperTrick(_space(), w0=6, n_phases=3, eviction_rate=0.3,
                        seed=0)
    jpath = str(tmp_path / "journal.jsonl")
    t_lo = time.time() - 5.0
    with Journal(jpath) as journal:
        svc = OptimizationService(policy)
        with MetaoptServer(svc, lease_ttl=10.0, journal=journal) as server:
            with ServiceClient(server.host, server.port) as c:
                agent = WorkerAgent(c, objective, heartbeat_interval=0.1,
                                    node=0)
                ctx = c.trace_ctx
                agent.run()
    assert ctx and ctx.startswith("w0-")  # tracing is on by default
    events = list(read_events(jpath))
    spans = [e for e in events if e.get("ev") == "span"]
    names = {e["name"] for e in spans}
    # the agent batches reports by default: one rpc.report_batch span per
    # generation replaces the per-trial rpc.report spans
    assert "rpc.acquire" in names and "rpc.report_batch" in names
    phases = [e for e in spans if e["name"] == "trial.phase"]
    assert phases, "reports must produce stitched trial.phase spans"
    t_hi = time.time() + 5.0
    for ph in phases:
        assert ph["ctx"] == ctx           # stitched to the worker's context
        assert ph["dur"] >= 0.0
        # stitched onto the server's epoch clock: span ends in the run's
        # wall-clock window, not on the worker's relative clock near zero
        assert t_lo <= ph["ts"] + ph["dur"] <= t_hi
    # acquire events carry the worker context too
    acquires = [e for e in events if e.get("ev") == "acquire"]
    assert acquires and all(e.get("ctx") == ctx for e in acquires)
    # every trial gets a closed lifecycle span from derivation
    life = [s for s in derive_spans(events) if s.name == "trial.lifecycle"]
    assert len(life) == 6
    assert {s.args["status"] for s in life} <= {"completed", "killed"}


def test_untraced_worker_still_gets_phase_spans(tmp_path):
    """A client with trace_ctx explicitly cleared sends no trace field;
    the server still spans the phase (anchored at arrival) without ctx."""
    objective = make_synthetic_objective(sleep=0.001, seed=2)
    policy = RandomSearchPolicy(_space(), 3, 2, seed=0)
    jpath = str(tmp_path / "journal.jsonl")
    with Journal(jpath) as journal:
        svc = OptimizationService(policy)
        with MetaoptServer(svc, lease_ttl=10.0, journal=journal) as server:
            with ServiceClient(server.host, server.port) as c:
                agent = WorkerAgent(c, objective, heartbeat_interval=0.1,
                                    node=1)
                c.trace_ctx = None        # opt out after the agent set one
                agent.run()
    phases = [e for e in read_events(jpath)
              if e.get("ev") == "span" and e["name"] == "trial.phase"]
    assert phases
    assert all("ctx" not in e for e in phases)


# ---------------------------------------------------------------------------
# derive_spans on a synthetic stream
# ---------------------------------------------------------------------------
def _sim_events():
    return [
        {"ev": "acquire", "trial_id": 0, "node": 4, "bracket": 0, "ts": 10.0,
         "ctx": "h4"},
        {"ev": "acquire", "trial_id": 1, "node": 5, "bracket": 0, "ts": 10.5},
        {"ev": "park", "trial_id": 0, "phase": 0, "ts": 12.0},
        {"ev": "park", "trial_id": 1, "phase": 0, "ts": 13.0},
        {"ev": "report", "trial_id": 0, "phase": 0, "metric": 1.0,
         "ts": 14.0},
        {"ev": "report", "trial_id": 1, "phase": 0, "metric": 2.0,
         "ts": 14.0},
        {"ev": "status", "trial_id": 0, "status": "killed", "ts": 14.1},
        {"ev": "span", "name": "trial.phase", "ts": 10.6, "dur": 2.3,
         "trial_id": 1, "phase": 0},
    ]


def test_derive_spans_lifecycle_park_cohort():
    spans = derive_spans(_sim_events())
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    life = {s.args["trial_id"]: s for s in by_name["trial.lifecycle"]}
    assert life[0].ts == 10.0 and life[0].dur == pytest.approx(4.1)
    assert life[0].args["status"] == "killed"
    assert life[0].args["ctx"] == "h4"
    # trial 1 never reached a terminal status: open-ended to its last event
    assert life[1].args["status"] == "running"
    assert life[1].dur == pytest.approx(14.0 - 10.5)
    parks = {s.args["trial_id"]: s for s in by_name["trial.park"]}
    assert parks[0].dur == pytest.approx(2.0)
    assert parks[1].dur == pytest.approx(1.0)
    (cohort,) = by_name["cohort.rung"]
    assert cohort.args == {"bracket": 0, "rung": 0, "members": 2}
    assert cohort.ts == 12.0 and cohort.dur == pytest.approx(2.0)
    # the recorded span passes through verbatim
    assert by_name["trial.phase"][0].dur == pytest.approx(2.3)


# ---------------------------------------------------------------------------
# export + critical path on a simulated 200-host search
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def replay_journal(tmp_path_factory):
    from repro.core.simulator import ToyWorkload
    from repro.telemetry.trace import replay_trace, synthetic_trace
    policy = HyperTrick(SearchSpace({"x": Uniform(0.0, 1.0)}), w0=200,
                        n_phases=4, eviction_rate=0.3, seed=0)
    hosts = synthetic_trace(200, seed=7, fail_frac=0.02, fail_horizon=20.0)
    jpath = str(tmp_path_factory.mktemp("replay") / "journal.jsonl")
    with Journal(jpath) as journal:
        replay_trace(policy, ToyWorkload(seed=0), hosts, bracket_eta=3,
                     lease_ttl=10.0, seed=0, journal=journal)
    return jpath


def test_replay_journal_exports_valid_chrome_trace(replay_journal, tmp_path):
    out = str(tmp_path / "trace.json")
    counts = export_journal(replay_journal, out)
    # one track per trial; crashed-host requeues mint fresh trial ids, so
    # the count can exceed w0
    assert counts["trial_tracks"] >= 200
    assert counts["cohort_tracks"] >= 1
    assert counts["complete_events"] > 400    # lifecycle+phases at least
    with open(out, encoding="utf-8") as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == counts
    # metadata names for Perfetto's track labels
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {"trials", "cohorts"} <= {
        e["args"]["name"] for e in meta if e["name"] == "process_name"}
    # all complete events are rebased to a non-negative microsecond clock
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert min(e["ts"] for e in xs) == pytest.approx(0.0)


def test_critical_path_buckets_sum_to_wall_clock(replay_journal):
    events = list(read_events(replay_journal))
    per_trial = attribute(events)
    assert len(per_trial) >= 200
    for tid, rec in per_trial.items():
        assert rec["wall"] > 0
        total = sum(rec[b] for b in BUCKETS)
        assert total == pytest.approx(rec["wall"], rel=0.01), \
            f"trial {tid}: buckets {total} vs wall {rec['wall']}"
    agg = aggregate(per_trial)
    assert sum(a["trials"] for a in agg.values()) == len(per_trial)
    table = critical_path_report(events)
    assert table.startswith("where did time go (per bracket):")
    assert "park_wait%" in table


def test_export_cli_require_trials(replay_journal, tmp_path, capsys):
    out = str(tmp_path / "t.json")
    assert export_main(["--journal", replay_journal, "--out", out,
                       "--require-trials", "1"]) == 0
    assert export_main(["--journal", replay_journal, "--out", out,
                       "--require-trials", "100000"]) == 1
    assert os.path.exists(out)


# ---------------------------------------------------------------------------
# engine-side spans (device phases, compile)
# ---------------------------------------------------------------------------
def test_engine_emits_compile_and_phase_spans():
    from repro.core.search_space import Categorical
    from repro.population.engine import LocalDriver, PopulationEngine
    space = SearchSpace({"learning_rate": LogUniform(1e-4, 1e-3),
                         "gamma": Categorical((0.99,)),
                         "t_max": Categorical((4,))})
    policy = RandomSearchPolicy(space, 2, 2, seed=0)
    svc = OptimizationService(policy)
    sink = []
    engine = PopulationEngine("pong", max_slots=2, n_envs=2,
                              episodes_per_phase=2, max_updates=10, seed=0,
                              spans=SpanRecorder(sink))
    engine.run(LocalDriver(svc))
    names = {}
    for ev in sink:
        names.setdefault(ev["name"], []).append(ev)
    assert "engine.compile" in names
    comp = names["engine.compile"][0]
    assert comp["dur"] > 0 and comp["trials"]   # cost split across these
    phases = names["engine.phase"]
    assert {p["trial_id"] for p in phases} == {0, 1}
    assert all(p["dur"] >= 0 for p in phases)


# ---------------------------------------------------------------------------
# satellite 1: bounded tailer polls
# ---------------------------------------------------------------------------
def test_tailer_poll_is_bounded_but_complete(tmp_path):
    from repro.telemetry.tailer import JournalTailer
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        for i in range(500):
            f.write(json.dumps({"ev": "report", "trial_id": i}) + "\n")
    tailer = JournalTailer(path, max_bytes=1024)
    polls, got = 0, []
    while True:
        batch = tailer.poll()
        if not batch:
            break
        # newline-boundary semantics under the budget: whole events only
        assert all("trial_id" in e for e in batch)
        assert len(batch) <= 1024 // 20 + 1
        got.extend(batch)
        polls += 1
    assert [e["trial_id"] for e in got] == list(range(500))
    assert polls > 10                     # the budget actually bounded reads
    assert tailer.skipped == 0


def test_tailer_oversized_single_line_does_not_wedge(tmp_path):
    from repro.telemetry.tailer import JournalTailer
    path = str(tmp_path / "j.jsonl")
    big = {"ev": "report", "trial_id": 0, "blob": "x" * 5000}
    with open(path, "w") as f:
        f.write(json.dumps(big) + "\n")
        f.write(json.dumps({"ev": "report", "trial_id": 1}) + "\n")
    tailer = JournalTailer(path, max_bytes=256)
    first = tailer.poll()
    assert any(e.get("trial_id") == 0 for e in first)
    rest = first + tailer.poll()
    assert [e["trial_id"] for e in rest] == [0, 1]


def test_tailer_leaves_torn_line_for_next_poll(tmp_path):
    from repro.telemetry.tailer import JournalTailer
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        f.write('{"ev": "report", "trial_id": 0}\n{"ev": "rep')
    tailer = JournalTailer(path, max_bytes=1024)
    assert [e["trial_id"] for e in tailer.poll()] == [0]
    with open(path, "a") as f:
        f.write('ort", "trial_id": 1}\n')
    assert [e["trial_id"] for e in tailer.poll()] == [1]
    assert tailer.skipped == 0


# ---------------------------------------------------------------------------
# satellite 2 + 3: dashboard skew warning, skipped count, monotonic rates
# ---------------------------------------------------------------------------
def test_dashboard_warns_on_regressing_timestamps():
    from repro.telemetry.dashboard import SearchView
    view = SearchView()
    view.apply({"ev": "acquire", "trial_id": 0, "ts": 100.0})
    view.apply({"ev": "report", "trial_id": 0, "phase": 0, "metric": 1.0,
                "env_steps": 10, "ts": 101.0})
    view.apply({"ev": "report", "trial_id": 0, "phase": 1, "metric": 2.0,
                "env_steps": 10, "ts": 99.0})      # 2s backwards: skew
    assert view.ts_regressions == 1
    assert view.max_regression_s == pytest.approx(2.0)
    out = view.render("j")
    assert "WARNING: 1 events with regressing ts" in out
    assert "undecodable skipped" in view.render("j", skipped=3)
    # the clamp keeps the event clock monotone
    assert view.t_last == 101.0


def test_dashboard_spans_do_not_count_as_skew():
    from repro.telemetry.dashboard import SearchView
    view = SearchView()
    view.apply({"ev": "report", "trial_id": 0, "phase": 0, "metric": 1.0,
                "ts": 100.0})
    # a parked phase span lands late but is stamped in the past
    view.apply({"ev": "span", "name": "trial.phase", "ts": 90.0, "dur": 3.0,
                "trial_id": 0})
    assert view.ts_regressions == 0
    assert "WARNING" not in view.render("j")


def test_dashboard_small_jitter_is_tolerated():
    from repro.telemetry.dashboard import SearchView
    view = SearchView(skew_tolerance_s=0.05)
    view.apply({"ev": "report", "trial_id": 0, "phase": 0, "metric": 1.0,
                "ts": 100.0})
    view.apply({"ev": "report", "trial_id": 1, "phase": 0, "metric": 1.0,
                "ts": 99.99})              # stamp-then-lock writer jitter
    assert view.ts_regressions == 0


def test_dashboard_follow_rates_use_monotonic_arrival():
    from repro.telemetry.dashboard import SearchView
    view = SearchView(window_s=30.0)
    mono = time.monotonic()
    for i in range(5):
        view.apply({"ev": "report", "trial_id": i, "phase": 0, "metric": 1.0,
                    "env_steps": 100, "ts": 1e9 + i}, mono=mono)
    span, rps, eps = view._window_rates()
    assert span <= 30.0 and rps > 0 and eps > 0


def test_metrics_snapshot_has_uptime():
    from repro.telemetry import MetricsRegistry, NULL_REGISTRY
    snap = MetricsRegistry().snapshot()
    assert snap["uptime_s"] >= 0.0
    assert NULL_REGISTRY.snapshot()["uptime_s"] == 0.0


def test_dashboard_once_appends_critical_path_table(replay_journal, capsys):
    from repro.telemetry.dashboard import main as dash_main
    assert dash_main(["--journal", replay_journal, "--once"]) == 0
    out = capsys.readouterr().out
    assert "undecodable skipped" in out
    assert "where did time go (per bracket):" in out
    assert "WARNING" not in out           # simulated clocks never regress


# ---------------------------------------------------------------------------
# schema hygiene
# ---------------------------------------------------------------------------
def test_span_schema_covers_recorded_and_derived_names():
    assert {"rpc.<verb>", "trial.phase", "engine.compile", "engine.phase",
            "engine.clone", "engine.park_stall", "trial.lifecycle",
            "trial.park", "cohort.rung"} | set(PROFILER_SPANS) \
        == set(SPAN_SCHEMA)
    assert all(isinstance(v, str) and v for v in SPAN_SCHEMA.values())
