"""Decoding the program's own names from a profiler trace
(``benchmark/program_trace.py``) and the per-layer metrics that read them.

``small.xplane.pb`` (``record_small_trace.py``) is a TPU trace of a
program with no scopes: it checks the wire decoder against JAX's own
reader and that every new reader reads nothing there, as on a program
without the names. ``scoped.xplane.pb`` (``record_scoped_trace.py``) is a
TPU trace of the serve tier's batch path on a 64-lane engine: it holds the
level loop's scopes and the per-batch host spans with their stats.
"""

from __future__ import annotations

import os
import shutil
import types

import pytest

import bench_helpers  # noqa: F401  (puts the checkout root on the path)

from benchmark import harness
from benchmark import program_trace as pt
from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")
SCOPED = os.path.join(HERE, "data", "scoped.xplane.pb")
READERS = ("level_gather_ms", "level_claim_ms", "serve_queue_ms",
           "serve_narrow_share")


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH_DIR, "metrics", f"{name}.py"),
        f"test_reader_{name}")


def _ctx(tmp_path, path, **counters):
    """A reader's context for the trace at ``path``, placed where
    ``program_trace.find`` looks (a copy under ``tmp_path``)."""
    d = tmp_path / "trace" / "cell"
    d.mkdir(parents=True)
    shutil.copy(path, d / "t.xplane.pb")
    summary = tr.reduce(tr.load_events(path))
    return types.SimpleNamespace(trace=summary, counters=counters,
                                 device_kind="TPU v5 lite")


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path / "trace"))
    pt._CACHE.clear()
    yield tmp_path
    pt._CACHE.clear()


@pytest.mark.parametrize("tf_op,scope,lanes", [
    ("jit(core)/lanes64/level.claim/while/body/level.gather/or:", "level.gather", 64),
    ("jit(core)/lanes256/level.claim/while/body/level.claim/and", "level.claim", 256),
    ("jit(core)/lanes8192/level.tiles/tile_spmm:", "level.tiles", 8192),
    ("jit(lane_stats)/batch.stats/reduce_sum:", "batch.stats", None),
    ("jit(core)/dot_general:", None, None),
    ("", None, None),
])
def test_scope_and_width_of_an_op_name(tf_op, scope, lanes):
    assert pt.scope_of(tf_op) == scope
    assert pt.lanes_of(tf_op) == lanes


def test_decoder_agrees_with_jax_on_the_small_trace():
    got = pt.decode(SMALL)
    events = tr.load_events(SMALL)
    summary = tr.reduce(events)
    assert got.window_s == pytest.approx(summary.window_s, abs=1e-9)
    ops = sorted((e.start_ns, e.dur_ns) for e in events
                 if e.line == tr.OPS_LINE and e.end_ns > got.lo
                 and e.start_ns < got.hi)
    mine = sorted((o.start_ns, o.dur_ns) for o in got.ops)
    assert len(mine) == len(ops) > 0
    # JAX rounds to whole nanoseconds; the decoder keeps the picoseconds.
    for (s0, d0), (s1, d1) in zip(ops, mine):
        assert abs(s0 - s1) < 1.0 and abs(d0 - d1) < 1.0
    assert "jit(core)/dot_general:" in {o.tf_op for o in got.ops}
    assert {sp.name for sp in got.spans} == {"bench.window", "bench.batch"}


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_names(name, trace_root):
    ctx = _ctx(trace_root, SMALL, levels_run=3)
    assert _reader(name).read(ctx) is None


def test_a_trace_of_another_window_is_not_read(trace_root):
    ctx = _ctx(trace_root, SCOPED)
    assert pt.find(ctx.trace.window_s + 1e-3) is None
    assert pt.find(ctx.trace.window_s) is not None
    assert pt.of(types.SimpleNamespace(trace=None)) is None


def test_scoped_trace_holds_the_level_loop_scopes():
    got = pt.decode(SCOPED)
    by_scope = got.scope_s()
    assert by_scope.get("level.gather", 0) > 0
    assert by_scope.get("level.claim", 0) > 0
    assert set(got.lanes_s()) >= {64}
    every = {pt.scope_of(o.tf_op) for o in got.ops}
    assert "batch.stats" in every


def test_scoped_trace_holds_the_batch_spans_with_stats():
    got = pt.decode(SCOPED)
    dispatch = got.in_window("tpu_bfs.serve.dispatch")
    assert len(dispatch) == 2
    for sp in dispatch:
        assert sp.stats["width"] == 64 and sp.stats["n"] == 9
        assert sp.stats["wait_ms_sum"] > 0
    for sp in got.in_window("tpu_bfs.serve.coalesce"):
        assert sp.stats["pending"] == sp.stats["taken"] == 9
    names = {sp.name for sp in got.spans}
    assert {"tpu_bfs.engine.dispatch", "tpu_bfs.engine.fetch",
            "tpu_bfs.serve.fetch", "tpu_bfs.serve.extract",
            "tpu_bfs.serve.write"} <= names


def test_the_readers_on_the_scoped_trace(trace_root):
    ctx = _ctx(trace_root, SCOPED, levels_run=10)
    got = pt.find(ctx.trace.window_s)
    gather = _reader("level_gather_ms").read(ctx)
    claim = _reader("level_claim_ms").read(ctx)
    core_ms = 1e3 * sum(s for _, s in got.self_times(core_only=True)) / 10
    assert 0 < gather and 0 < claim and gather + claim <= core_ms + 1e-9
    dispatch = got.in_window("tpu_bfs.serve.dispatch")
    assert _reader("serve_queue_ms").read(ctx) == pytest.approx(
        sum(sp.stats["wait_ms_sum"] for sp in dispatch) / 18)
    narrow = _reader("serve_narrow_share").read(ctx)
    assert 0 < narrow <= 100
    by_lanes = got.lanes_s()
    assert narrow == pytest.approx(100 * by_lanes[64]
                                   / sum(by_lanes.values()))
