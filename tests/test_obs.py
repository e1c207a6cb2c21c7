"""The unified telemetry layer (tpu_bfs/obs, ISSUE 6).

- recorder: record shape, ring bound, cross-thread span pairing, query
  chains, flight dumps (window, header, budget, unwritable-dir safety);
- ZERO-OVERHEAD-WHEN-DISABLED: spy counters prove the disarmed packed
  dispatch/fetch and the serve hot loop make no obs-layer calls and
  allocate no obs objects (the <2% serve_p50_ms acceptance bar's guard,
  mirroring the faults determinism tests);
- exporters: golden-file tests for the Perfetto trace-event JSON and the
  Prometheus text (tests/golden/obs_trace.json, obs_metricz.txt);
- mergeable log2-bucket histograms: single-sample exactness, bounded
  estimate error, merge == union, JSON round-trip, and the p50/p99
  snapshot keys keeping their shape;
- engine traces: dist/packed assembly from loop-carry recordings and
  the trace_summary verdict keys;
- armed serve integration: every query id's span chain is complete and
  the engine's per-level trace materializes.
"""

import argparse
import json
import os
import threading

import numpy as np
import pytest

from tpu_bfs import obs
from tpu_bfs.obs import engine_trace as et
from tpu_bfs.obs.exporters import (
    prometheus_text,
    trace_events,
    write_metricz,
    write_perfetto,
)
from tpu_bfs.obs.recorder import Recorder
from tpu_bfs.serve.frontend import BfsService, resolve_statsz_interval
from tpu_bfs.serve.metrics import Log2Histogram, ServeMetrics
from tpu_bfs.serve.registry import EngineRegistry
from tpu_bfs.graph.generate import random_graph

pytestmark = pytest.mark.obs

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with no recorder armed — the module
    global is process-wide state (same discipline as test_faults)."""
    obs.disarm()
    yield
    obs.disarm()


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Recorder


def test_record_shape_and_sequencing():
    clock = FakeClock()
    r = Recorder(now=clock)
    ev = r.event("warm", cat="serve.registry", width=32)
    assert ev["seq"] == 1 and ev["t"] == 100.0 and ev["ph"] == "i"
    assert ev["name"] == "warm" and ev["cat"] == "serve.registry"
    assert ev["id"] is None and ev["args"] == {"width": 32}
    assert ev["tid"] == threading.current_thread().name
    clock.t = 101.0
    b = r.begin("query", "q1", cat="serve.query", query=1)
    e = r.end("query", "q1", cat="serve.query", status="ok")
    assert (b["seq"], e["seq"]) == (2, 3)
    assert b["ph"] == "b" and e["ph"] == "e" and b["id"] == e["id"] == "q1"


def test_ring_capacity_drops_oldest():
    r = Recorder(capacity=4)
    for i in range(6):
        r.event("e", i=i)
    snap = r.snapshot()
    assert len(snap) == 4 and r.dropped == 2
    assert [ev["args"]["i"] for ev in snap] == [2, 3, 4, 5]


def test_span_context_manager_pairs():
    r = Recorder()
    with r.span("build", "w64", cat="serve.registry", width=64):
        r.event("inner")
    names = [(ev["ph"], ev["name"]) for ev in r.snapshot()]
    assert names == [("b", "build"), ("i", "inner"), ("e", "build")]


def test_query_chain_follows_batch_events():
    r = Recorder()
    r.begin("query", "q7", cat="serve.query", query=7)
    r.event("coalesce", cat="serve.batch", queries=[7, 8], width=32)
    r.event("dispatch_done", cat="serve.batch", batch=3)  # not q7's
    r.end("query", "q7", cat="serve.query", query=7, status="ok")
    chain = r.query_chain(7)
    assert [ev["name"] for ev in chain] == ["query", "coalesce", "query"]
    assert r.counts_by_name() == {
        "query": 2, "coalesce": 1, "dispatch_done": 1,
    }


def test_flight_dump_window_header_and_trigger_event(tmp_path):
    clock = FakeClock(50.0)
    r = Recorder(window_s=10.0, dump_dir=str(tmp_path), now=clock)
    r.event("ancient", i=0)
    clock.t = 100.0
    r.event("recent", i=1)
    path = r.flight_dump("watchdog_trip")
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    assert r.dumps == [path]
    lines = [json.loads(l) for l in open(path)]
    header, events = lines[0], lines[1:]
    assert header["flight_recorder"] == "watchdog_trip"
    assert header["window_s"] == 10.0 and header["events"] == len(events)
    names = [ev["name"] for ev in events]
    assert "ancient" not in names  # outside the window
    assert names == ["recent", "flight_dump"]  # the trigger records itself
    assert events[-1]["args"]["reason"] == "watchdog_trip"


def test_flight_dump_budget_is_bounded(tmp_path):
    r = Recorder(dump_dir=str(tmp_path), max_dumps=2)
    r.event("x")
    assert r.flight_dump("a") and r.flight_dump("b")
    assert r.flight_dump("c") is None  # budget spent: disk is protected
    assert len(r.dumps) == 2


def test_flight_dump_unwritable_dir_never_raises(tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file, not dir")
    r = Recorder(dump_dir=str(blocker))
    r.event("x")
    assert r.flight_dump("trip") is None  # reported, never raised
    assert "flight_dump_failed" in r.counts_by_name()


# ---------------------------------------------------------------------------
# Arming: spec grammar and precedence


def test_spec_defaults_and_kv_grammar():
    r = obs.arm_from_spec("1")
    assert r is obs.ACTIVE and r._events.maxlen == 65536
    r = obs.arm_from_spec("capacity=8,window=2.5,dump_dir=/tmp/fr,max_dumps=3")
    assert r._events.maxlen == 8 and r.window_s == 2.5
    assert r.dump_dir == "/tmp/fr" and r.max_dumps == 3


@pytest.mark.parametrize("bad", [
    "capacity=x", "nonsense=1", "capacity", "window=", "max_dumps=1.5",
])
def test_bad_specs_raise(bad):
    with pytest.raises(ValueError):
        obs.arm_from_spec(bad)


def test_falsy_specs_disarm_instead_of_crashing(monkeypatch):
    """TPU_BFS_OBS=0 is a fleet-standard disable, not a parse error —
    the never-die-on-an-env-knob rule (bench._env_bool) applies; an
    explicit --obs 0 also overrides a fleet-set env var."""
    for v in ("0", "false", "off", "no"):
        assert obs.arm_from_spec(v) is None
    assert obs.ACTIVE is None
    monkeypatch.setenv(obs.ENV_VAR, "0")
    assert obs.arm_from_spec_or_env(None) is None
    monkeypatch.setenv(obs.ENV_VAR, "capacity=100")
    assert obs.arm_from_spec_or_env("0") is None  # explicit off wins
    assert obs.ACTIVE is None


def test_arm_precedence_spec_wins_over_env(monkeypatch):
    monkeypatch.setenv(obs.ENV_VAR, "capacity=100")
    r = obs.arm_from_spec_or_env("capacity=8")
    assert r._events.maxlen == 8  # explicit spec wins
    obs.disarm()
    r = obs.arm_from_spec_or_env(None)
    assert r._events.maxlen == 100  # env fallback
    obs.disarm()
    monkeypatch.delenv(obs.ENV_VAR)
    assert obs.arm_from_spec_or_env(None) is None
    assert obs.ACTIVE is None  # neither set: stays disarmed


# ---------------------------------------------------------------------------
# Mergeable log2-bucket histograms


def test_single_sample_is_exact():
    h = Log2Histogram()
    h.add(3.7)
    assert h.percentile(50) == 3.7 and h.percentile(99) == 3.7


def test_percentile_estimate_error_is_bounded():
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=1.5, sigma=1.0, size=4000)
    h = Log2Histogram()
    h.add_many(vals)
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        est = h.percentile(q)
        # One sub-bucket of one octave: <= 1/SUB relative error.
        assert abs(est - exact) / exact <= 1.0 / Log2Histogram.SUB


def test_merge_equals_union():
    rng = np.random.default_rng(11)
    a, b = rng.exponential(5.0, 300), rng.exponential(50.0, 500)
    ha, hb, hall = Log2Histogram(), Log2Histogram(), Log2Histogram()
    ha.add_many(a)
    hb.add_many(b)
    hall.add_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert ha.counts == hall.counts and ha.count == hall.count
    assert ha.total == pytest.approx(hall.total)
    assert ha.percentile(99) == pytest.approx(hall.percentile(99))


def test_state_dict_round_trip_is_exact():
    h = Log2Histogram()
    h.add_many([0.0, 0.5, 3.0, 1e7])  # underflow, normal x2, overflow
    h2 = Log2Histogram.from_state(json.loads(json.dumps(h.state_dict())))
    assert h2.counts == h.counts and h2.count == h.count
    assert (h2.vmin, h2.vmax) == (h.vmin, h.vmax)
    empty = Log2Histogram.from_state(Log2Histogram().state_dict())
    assert empty.count == 0 and empty.percentile(50) is None


def test_cumulative_buckets_are_monotone_and_total():
    h = Log2Histogram()
    h.add_many([0.0, 0.25, 1.5, 1.6, 900.0, 1e8])
    buckets = h.cumulative_buckets()
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)  # cumulative
    assert buckets[-1] == (None, h.count)  # +Inf covers everything


def test_percentile_keys_age_out_old_samples():
    """The deque's recency invariant, kept by time: a slow cold batch
    must not haunt p99 forever; the EXPORTED histograms stay all-time
    (Prometheus counters — scrapers difference them)."""
    from tpu_bfs.serve.metrics import RECENT_WINDOW_S

    clock = FakeClock(0.0)
    m = ServeMetrics(now=clock)
    m.record_batch(1, 32, [30000.0])  # cold-start straggler
    assert m.snapshot()["p99_ms"] == pytest.approx(30000.0)
    clock.t = 3 * RECENT_WINDOW_S  # several windows later
    m.record_batch(2, 32, [2.0, 3.0])
    snap = m.snapshot()
    assert snap["p99_ms"] <= 3.0 + 1e-9  # the straggler aged out
    assert m.histograms()["latency_ms"].count == 3  # all-time keeps all
    clock.t = 10 * RECENT_WINDOW_S
    assert m.snapshot()["p50_ms"] is None  # long idle: aged to None


def test_snapshot_percentile_keys_keep_their_shape():
    m = ServeMetrics(now=FakeClock())
    snap = m.snapshot()
    assert snap["p50_ms"] is None and snap["extract_p50_ms"] is None
    m.record_batch(2, 32, [1.0, 9.0], extract_ms=0.5)
    snap = m.snapshot()
    assert isinstance(snap["p50_ms"], float)
    assert isinstance(snap["p99_ms"], float)
    assert 1.0 <= snap["p50_ms"] <= 9.0 <= snap["p99_ms"] <= 9.0 + 1e-9


# ---------------------------------------------------------------------------
# Exporters: golden files


GOLDEN_EVENTS = [
    {"seq": 1, "t": 100.0, "ph": "b", "name": "query", "cat": "serve.query",
     "id": "q1", "tid": "client-0", "args": {"query": 1, "source": 5}},
    {"seq": 2, "t": 100.0005, "ph": "i", "name": "enqueue",
     "cat": "serve.queue", "id": None, "tid": "client-0",
     "args": {"query": 1, "depth": 1}},
    {"seq": 3, "t": 100.001, "ph": "b", "name": "dispatch",
     "cat": "serve.batch", "id": "b1", "tid": "scheduler",
     "args": {"batch": 1, "width": 32}},
    {"seq": 4, "t": 100.003, "ph": "e", "name": "dispatch",
     "cat": "serve.batch", "id": "b1", "tid": "scheduler",
     "args": {"attempt": 0}},
    {"seq": 5, "t": 100.004, "ph": "e", "name": "query", "cat": "serve.query",
     "id": "q1", "tid": "worker", "args": {"status": "ok", "batch": 1}},
]

GOLDEN_LEVELS = [
    {"level": 0, "frontier": 1, "direction": "push", "gated_tiles": None,
     "exchange": None, "wire_bytes": None},
    {"level": 1, "frontier": 30, "direction": "pull-gated", "gated_tiles": 2,
     "exchange": "dense", "wire_bytes": 4096.0},
]


def test_perfetto_export_matches_golden(tmp_path):
    path = str(tmp_path / "trace.json")
    write_perfetto(
        GOLDEN_EVENTS, path, t0=100.0,
        level_traces=[("hybrid/w32", GOLDEN_LEVELS)],
        meta={"tool": "test", "graph": "golden"},
    )
    got = json.load(open(path))
    want = json.load(open(os.path.join(GOLDEN_DIR, "obs_trace.json")))
    assert got == want


def test_trace_events_span_encoding_invariants():
    evs = trace_events(GOLDEN_EVENTS, t0=100.0)
    # One thread_name metadata record per distinct recording thread.
    meta = [e for e in evs if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == [
        "client-0", "scheduler", "worker",
    ]
    # Span begin/end pairs keep the async correlation id; instants are
    # thread-scoped; timestamps are relative microseconds.
    q = [e for e in evs if e.get("id") == "q1"]
    assert [e["ph"] for e in q] == ["b", "e"]
    assert q[0]["ts"] == 0.0 and q[1]["ts"] == 4000.0
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["ts"] == 500.0


def _golden_metrics() -> ServeMetrics:
    clock = FakeClock(0.0)
    m = ServeMetrics(now=clock)
    clock.t = 12.5
    m.record_batch(3, 32, [1.0, 2.0, 4.0], extract_ms=1.5)
    m.record_retry()
    m.record_rejected()
    return m


def test_prometheus_export_matches_golden():
    m = _golden_metrics()
    text = m.prometheus_text(queue_depth=2, lanes=32)
    want = open(os.path.join(GOLDEN_DIR, "obs_metricz.txt")).read()
    assert text == want


def test_prometheus_text_counts_every_completion():
    m = _golden_metrics()
    text = prometheus_text(m.snapshot(), histograms=m.histograms())
    assert "# TYPE tpu_bfs_serve_completed counter" in text
    assert "tpu_bfs_serve_completed 3" in text
    assert 'tpu_bfs_serve_latency_ms_bucket{le="+Inf"} 3' in text
    assert 'tpu_bfs_serve_routing{width="32"} 1' in text
    assert "tpu_bfs_serve_latency_ms_sum 7" in text


def test_histograms_are_consistent_copies():
    m = ServeMetrics()
    m.record_batch(1, 32, [2.0])
    h = m.histograms()["latency_ms"]
    m.record_batch(1, 32, [4.0])
    assert h.count == 1  # a copy: later records cannot tear a render
    assert m.histograms()["latency_ms"].count == 2


def test_periodic_emission_shares_one_snapshot():
    """The statsz line and the /metricz text render the SAME snapshot
    dict — a second snapshot microseconds later would read an already-
    consumed interval window and export garbage interval_qps."""
    clock = FakeClock(0.0)
    m = ServeMetrics(now=clock)
    clock.t = 10.0
    m.record_batch(3, 32, [1.0, 2.0, 3.0])
    snap = m.snapshot(mark_interval=True)
    assert snap["interval_qps"] == pytest.approx(0.3)
    assert json.loads(m.statsz_line(snapshot=snap)[len("statsz "):]) == snap
    text = m.prometheus_text(snapshot=snap)
    assert f"tpu_bfs_serve_interval_qps {snap['interval_qps']:g}" in text


def test_write_metricz_replaces_atomically(tmp_path):
    path = str(tmp_path / "metricz.txt")
    write_metricz("a 1\n", path)
    write_metricz("a 2\n", path)
    assert open(path).read() == "a 2\n"
    assert os.listdir(tmp_path) == ["metricz.txt"]  # no tmp litter


# ---------------------------------------------------------------------------
# Engine traces


class FakeDistEngine:
    def __init__(self, per_level, mode="sparse", caps=(4, 8)):
        self._per_level = per_level
        self._exchange = mode
        self.sparse_caps = caps

    def wire_bytes_per_level(self):
        return self._per_level


def test_assemble_dist_trace_sparse_ladder():
    eng = FakeDistEngine([100.0, 200.0, 300.0])
    front = np.zeros(et.TRACE_LEVELS, np.int32)
    branch = np.full(et.TRACE_LEVELS, -1, np.int32)
    front[:3] = (5, 40, 9)
    branch[:3] = (0, 2, 1)
    rows = et.assemble_dist_trace(eng, 3, front, branch, direction="push",
                                  level0=10)
    assert [r["level"] for r in rows] == [10, 11, 12]
    assert [r["frontier"] for r in rows] == [5, 40, 9]
    assert [r["exchange"] for r in rows] == ["sparse[4]", "dense", "sparse[8]"]
    assert [r["wire_bytes"] for r in rows] == [100.0, 300.0, 200.0]
    assert all(r["direction"] == "push" for r in rows)


def test_assemble_dist_trace_single_branch_uses_impl_label():
    eng = FakeDistEngine([512.0], mode="ring", caps=(4, 8))
    front = np.zeros(et.TRACE_LEVELS, np.int32)
    branch = np.full(et.TRACE_LEVELS, -1, np.int32)
    front[:2] = (1, 17)
    branch[:2] = 0
    rows = et.assemble_dist_trace(eng, 2, front, branch, direction="push")
    # One-branch exchanges label by impl, not the (still-populated) caps.
    assert [r["exchange"] for r in rows] == ["ring", "ring"]
    assert [r["wire_bytes"] for r in rows] == [512.0, 512.0]


def test_assemble_dist_trace_clamps_deep_traversals():
    eng = FakeDistEngine([64.0], mode="ring", caps=())
    front = np.zeros(et.TRACE_LEVELS, np.int32)
    branch = np.zeros(et.TRACE_LEVELS, np.int32)
    rows = et.assemble_dist_trace(eng, et.TRACE_LEVELS + 9, front, branch,
                                  direction="push")
    assert len(rows) == et.TRACE_LEVELS
    assert rows[-1]["truncated_levels"] == 10  # the clamped tail, marked


class FakePackedEngine:
    pull_gate = True
    sparse_caps = (16, 64)

    def __init__(self):
        self.last_gate_level_counts = np.array([0, 3, 7])
        self.last_exchange_level_counts = np.array([0, 2, 0])

    def wire_bytes_per_level(self):
        return [10.0, 20.0, 30.0]


def test_assemble_packed_trace_single_branch_and_gates():
    rows = et.assemble_packed_trace(FakePackedEngine(), 3)
    assert [r["gated_tiles"] for r in rows] == [0, 3, 7]
    assert all(r["direction"] == "pull-gated" for r in rows)
    assert all(r["frontier"] is None for r in rows)  # packed loops don't count
    assert all(r["exchange"] == "sparse[64]" for r in rows)
    assert all(r["wire_bytes"] == 20.0 for r in rows)


def test_assemble_packed_trace_mixed_branches():
    eng = FakePackedEngine()
    eng.last_exchange_level_counts = np.array([1, 2, 0])
    rows = et.assemble_packed_trace(eng, 3)
    assert all(r["exchange"] == "mixed" for r in rows)
    assert all(r["wire_bytes"] is None for r in rows)  # split is in summary


def test_dist_trace_clamp_slot_aggregates_frontier():
    """A deeper-than-TRACE_LEVELS traversal: the clamp row's frontier is
    the exact SUM over the clamped tail (the loop carry accumulates with
    .add), so frontier_total never undercounts."""
    from tpu_bfs.graph import io as gio
    from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

    n = 90  # path 0-1-...-89: n expansion levels (the last claims none)
    u = np.arange(n - 1)
    g = gio.from_edges(u, u + 1, num_vertices=n)
    eng = DistBfsEngine(g, make_mesh(2))
    eng.run(0)
    trace = eng.last_run_trace
    assert len(trace) == et.TRACE_LEVELS
    assert trace[-1]["truncated_levels"] == n - et.TRACE_LEVELS + 1
    assert sum(r["frontier"] for r in trace) == n - 1  # every vertex claimed
    assert et.trace_summary(trace, eng)["frontier_total"] == n - 1


def test_trace_summary_verdict_keys():
    eng = FakePackedEngine()
    trace = [
        {"level": 0, "frontier": 1, "direction": "push", "gated_tiles": None,
         "exchange": None, "wire_bytes": None},
        {"level": 1, "frontier": 40, "direction": "pull-gated",
         "gated_tiles": 3, "exchange": "dense", "wire_bytes": 100.0},
        {"level": 2, "frontier": 8, "direction": "pull-gated",
         "gated_tiles": 9, "exchange": "dense", "wire_bytes": 100.0},
    ]
    s = et.trace_summary(trace, eng)
    assert s["levels"] == 3
    assert s["frontier_total"] == 49 and s["frontier_peak"] == 40
    assert s["directions"] == ["pull-gated", "push"]
    assert s["gated_tiles_total"] == 12
    assert s["exchange_levels"] == {"dense": 2}
    assert s["exchange_branch_counts"] == [0, 2, 0]
    assert s["wire_bytes_total"] == 200.0
    eng.last_exchange_bytes = 512.0
    assert et.trace_summary(trace, eng)["wire_bytes_total"] == 512.0
    assert et.trace_summary(None) == {"levels": 0}


# ---------------------------------------------------------------------------
# Statsz interval precedence (ISSUE 6 satellite)


def _ns(**kw):
    kw.setdefault("statsz_interval_s", None)
    kw.setdefault("statsz_every", None)
    return argparse.Namespace(**kw)


def test_statsz_interval_precedence():
    assert resolve_statsz_interval(_ns(), env="") == 10.0
    assert resolve_statsz_interval(_ns(), env="2.5") == 2.5
    assert resolve_statsz_interval(_ns(statsz_every=0.0), env="2.5") == 0.0
    assert resolve_statsz_interval(
        _ns(statsz_interval_s=7.0, statsz_every=3.0), env="2.5"
    ) == 7.0
    assert resolve_statsz_interval(_ns(), env="typo") == 10.0


# ---------------------------------------------------------------------------
# The serve path: zero-overhead disarmed, complete span chains armed.


@pytest.fixture(scope="module")
def obs_graph():
    return random_graph(128, 768, seed=11)


@pytest.fixture(scope="module")
def obs_registry(obs_graph):
    """ONE warmed engine for the module (builds cost seconds; the
    registry exists to amortize exactly this)."""
    reg = EngineRegistry(capacity=4)
    reg.add_graph("obs-graph", obs_graph)
    return reg


def _svc(reg, **kw):
    kw.setdefault("lanes", 32)
    kw.setdefault("linger_ms", 2.0)
    return BfsService("obs-graph", registry=reg, **kw)


@pytest.fixture
def obs_spy(monkeypatch):
    """Counts every obs-layer call AND every Recorder allocation: the
    disarmed guarantee is 'one attribute read per site', so any entry
    into the obs layer at all is a regression."""
    calls = []

    def counted(name, orig):
        def spy(self, *a, **kw):
            calls.append(name)
            return orig(self, *a, **kw)
        return spy

    for meth in ("__init__", "_push", "flight_dump"):
        monkeypatch.setattr(
            Recorder, meth, counted(meth, getattr(Recorder, meth))
        )
    # The packed fetch's trace assembly is its own obs entry point
    # (lazy-imported under the guard in _packed_common.fetch_packed_batch).
    monkeypatch.setattr(
        et, "record_packed_run",
        lambda *a, **kw: calls.append("record_packed_run"),
    )
    # The per-batch host spans: with no profiler and no recorder, each is
    # the shared no-op span and never computes its stats.
    host_span = obs.host_span

    def spied_span(name, stats=None, **kw):
        def counted_stats():
            calls.append(f"stats:{name}")
            return stats()

        span = host_span(name, None if stats is None else counted_stats,
                         **kw)
        if span is not obs._NULL_SPAN:
            calls.append(f"span_on:{name}")
        return span

    monkeypatch.setattr(obs, "host_span", spied_span)
    return calls


def test_disarmed_serve_hot_loop_makes_zero_obs_calls(obs_registry, obs_spy):
    assert obs.ACTIVE is None
    with _svc(obs_registry) as svc:
        for s in (0, 3, 5, 9):
            r = svc.query(s, timeout=60)
            assert r.ok, (r.status, r.error)
    assert obs_spy == []  # the hot loop never entered the obs layer


def test_disarmed_dispatch_fetch_make_zero_obs_calls(obs_registry, obs_spy):
    svc = _svc(obs_registry, autostart=False)
    engine = svc._registry.get(svc._spec())
    pend = engine.dispatch(np.zeros(engine.lanes, dtype=np.int64))
    res = engine.fetch(pend)
    assert int(res.reached[0]) > 0
    assert obs_spy == []


def test_disarmed_host_spans_are_per_batch_not_per_query(obs_registry,
                                                       monkeypatch):
    """One serve batch and one engine.run with no profiler and no
    recorder: the host spans are one call per batch step whatever the
    batch's size, and none computes its stats."""
    from tpu_bfs.serve.executor import BatchExecutor
    from tpu_bfs.serve.scheduler import AdmissionQueue, PendingQuery

    calls = []
    host_span = obs.host_span

    def spied_span(name, stats=None, **kw):
        calls.append(name)
        return host_span(
            name, None if stats is None
            else (lambda: calls.append(f"stats:{name}") or stats()), **kw)

    monkeypatch.setattr(obs, "host_span", spied_span)
    svc = _svc(obs_registry, autostart=False)
    engine = svc._registry.get(svc._spec())
    executor = BatchExecutor(ServeMetrics())
    per_batch = []
    for sources in ((0,), (0, 3, 5, 9, 17, 33, 65)):
        calls.clear()
        queue = AdmissionQueue(64)
        for s in sources:
            queue.offer(PendingQuery(s, want_distances=False))
        queries = queue.next_batch(engine.lanes, 0.0)
        executor.run_batch(engine, queries)
        assert all(q.result(0).ok for q in queries)
        per_batch.append(sorted(calls))
    assert per_batch[0] == per_batch[1] == sorted([
        "tpu_bfs.serve.coalesce", "tpu_bfs.serve.dispatch",
        "tpu_bfs.engine.dispatch", "tpu_bfs.serve.fetch",
        "tpu_bfs.engine.fetch", "tpu_bfs.serve.extract",
        "tpu_bfs.serve.write",
    ])
    calls.clear()
    engine.run(np.zeros(engine.lanes, dtype=np.int64))
    assert calls == ["tpu_bfs.engine.dispatch", "tpu_bfs.engine.fetch"]


def test_armed_serve_records_complete_span_chains(obs_registry, tmp_path):
    rec = obs.arm(dump_dir=str(tmp_path))
    with _svc(obs_registry) as svc:
        results = {s: svc.query(s, timeout=60) for s in (0, 3, 5)}
    assert all(r.ok for r in results.values())
    for s, r in results.items():
        chain = rec.query_chain(r.id)
        names = {ev["name"] for ev in chain}
        # admission -> queue -> coalesce -> batch; dispatch/fetch/extract
        # ride the batch correlation id the query span closes with.
        assert {"query", "enqueue", "coalesce", "batch"} <= names, (s, names)
        done = next(ev for ev in chain
                    if ev["name"] == "query" and ev["ph"] == "e")
        assert done["args"]["status"] == "ok"
        bid = done["args"]["batch"]
        assert bid is not None
        batch_events = [ev for ev in rec.snapshot()
                        if ev["id"] == f"b{bid}"]
        stages = {ev["name"] for ev in batch_events}
        assert {"batch", "dispatch", "fetch", "extract"} <= stages
    # The armed fetch assembled the engine's per-level trace.
    engine = svc._registry.get(svc._spec())
    trace = engine.last_run_trace
    assert trace and {"level", "frontier", "direction", "gated_tiles",
                      "exchange", "wire_bytes"} <= set(trace[0])
    assert et.trace_summary(trace, engine)["levels"] == len(trace)
    assert "engine.run_trace" in rec.counts_by_name()
    assert not rec.dumps  # healthy run: no flight dumps


def test_oom_closes_open_spans_and_rebatches_query(tmp_path):
    """An OOM'd dispatch must not leave a dangling dispatch/fetch begin
    in the trace, and a requeued query's span must close naming the
    batch that actually SERVED it, not the aborted one."""
    from tpu_bfs.serve.executor import BatchExecutor, OomRequeue
    from tpu_bfs.serve.scheduler import PendingQuery

    rec = obs.arm(dump_dir=str(tmp_path))

    class OomOnceEngine:
        lanes = 4
        num_vertices = 8

        def __init__(self):
            self.calls = 0

        def dispatch(self, padded):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: injected oom")
            return np.asarray(padded)

        def fetch(self, handle):
            class R:
                reached = np.ones(4, np.int64)
                ecc = np.zeros((4, 32), np.int32)

                @staticmethod
                def distances_int32(i):
                    return np.zeros(8, np.int32)

            return R()

    ex = BatchExecutor(ServeMetrics())
    q = PendingQuery(0, id=1)
    eng = OomOnceEngine()
    with pytest.raises(OomRequeue):
        ex.dispatch_batch(eng, [q])
    pending = ex.dispatch_batch(eng, [q])  # the service's re-admission
    ex.finish_batch(pending)
    assert q.result().ok
    # Every span begin has its end — nothing dangles for Perfetto.
    open_spans = {}
    for ev in rec.snapshot():
        if ev["ph"] == "b":
            open_spans[(ev["name"], ev["id"])] = open_spans.get(
                (ev["name"], ev["id"]), 0) + 1
        elif ev["ph"] == "e":
            open_spans[(ev["name"], ev["id"])] -= 1
    assert all(v == 0 for v in open_spans.values()), open_spans
    # The query span names the serving batch, and the aborted batch's
    # span closed with the oom marker.
    done = next(ev for ev in rec.snapshot()
                if ev["name"] == "query" and ev["ph"] == "e")
    assert done["args"]["batch"] == pending.bid
    oom_end = next(ev for ev in rec.snapshot()
                   if ev["name"] == "batch" and ev["ph"] == "e"
                   and ev["args"].get("oom"))
    assert oom_end["args"]["batch"] != pending.bid


def test_extraction_failure_closes_batch_spans(tmp_path):
    """An exception during result extraction must close the open
    extract/batch spans before it propagates to the service's
    flight-dumping catch-all — the dump exists to debug exactly this."""
    from tpu_bfs.serve.executor import BatchExecutor
    from tpu_bfs.serve.scheduler import PendingQuery

    rec = obs.arm(dump_dir=str(tmp_path))

    class BadResult:
        reached = np.ones(4, np.int64)
        ecc = np.zeros((4, 32), np.int32)

        @staticmethod
        def distances_int32(i):
            raise RuntimeError("host transfer exploded")

    class Eng:
        lanes = 4
        num_vertices = 8

        def dispatch(self, padded):
            return np.asarray(padded)

        def fetch(self, handle):
            return BadResult()

    ex = BatchExecutor(ServeMetrics())
    pending = ex.dispatch_batch(Eng(), [PendingQuery(0, id=1)])
    with pytest.raises(RuntimeError, match="host transfer exploded"):
        ex.finish_batch(pending)
    # Every batch-stage span begin has its end (the query span stays
    # open here by design — the SERVICE resolves it as an error).
    opens = {}
    for ev in rec.snapshot():
        if ev["cat"] != "serve.batch":
            continue
        if ev["ph"] == "b":
            opens[(ev["name"], ev["id"])] = opens.get(
                (ev["name"], ev["id"]), 0) + 1
        elif ev["ph"] == "e":
            opens[(ev["name"], ev["id"])] -= 1
    assert opens and all(v == 0 for v in opens.values()), opens


def test_armed_service_metricz_agrees_with_statsz(obs_registry):
    obs.arm()
    with _svc(obs_registry) as svc:
        assert svc.query(4, timeout=60).ok
        snap = svc.statsz()
        text = svc.metricz()
    assert f"tpu_bfs_serve_completed {snap['completed']}" in text
    assert ('tpu_bfs_serve_latency_ms_bucket{le="+Inf"} '
            f"{snap['completed']}") in text
