"""The profiler-side names of tpu_bfs/obs: ``obs.scope`` in the compiled
level loops and ``obs.host_span`` on the host.

- coverage: every op a compiled core's own code made (its ``op_name``
  metadata under ``jit(core)/``) names a ``level.*`` / ``batch.*`` phase
  and the ``lanes<W>`` of its engine's width, the 1D-sharded engine's
  exchange and termination sum among them;
- identity: the optimized HLO, metadata and stack-frame tables stripped,
  is the same with ``obs.scope`` replaced by a null context: the names
  change nothing the device runs;
- ``obs.scope`` as a decorator opens a fresh named scope per call, so a
  function traced by two threads at once keeps each thread's names;
- host_span: off (no profiler, no recorder) it is one shared no-op span
  that never calls its stats; on a profiler it lands in the trace with its
  stats; with a recorder armed it is the recorder's begin/end pair, for
  the per-batch steps and the CLI's set-up steps alike.
"""

from __future__ import annotations

import contextlib
import glob
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_bfs import obs
from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
from tpu_bfs.algorithms.msbfs_packed import PackedMsBfsEngine
from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine
from tpu_bfs.graph.generate import random_graph, rmat_graph
from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

#: Instructions that run nothing themselves.
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
#: The sharded core's one op with no phase: the distance planes' zero
#: fill, which XLA lifts out of the ``shard_map`` body under the name of
#: its own instruction (``broadcast.421``). Filled outside the body it
#: loses even the ``jit(core)`` prefix; JAX names every op it emits by its
#: primitive, with no such suffix.
PLANE_FILL = re.compile(r"^jit\(core\)/shard_map/broadcast\.\d+$")

ENGINES = {
    "packed64": lambda: PackedMsBfsEngine(rmat_graph(10, 8, seed=3),
                                          lanes=64, kcap=8),
    "wide64": lambda: WidePackedMsBfsEngine(random_graph(500, 2000,
                                                         seed=12345),
                                            lanes=64),
    "wide256": lambda: WidePackedMsBfsEngine(rmat_graph(10, 8, seed=3),
                                             lanes=256, kcap=8),
    "hybrid_tiles": lambda: HybridMsBfsEngine(rmat_graph(10, 8, seed=3),
                                              tile_thr=8, kcap=4),
    # The 1D-sharded hybrid at the four-chip cell's width, on 4 of the
    # suite's virtual devices.
    "mesh4_hybrid8192": lambda: DistHybridMsBfsEngine(
        rmat_graph(10, 8, seed=3), make_mesh(4), lanes=8192, tile_thr=8,
        kcap=4),
}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """JAX keys its persistent compile cache on the program without its
    op metadata, so an entry compiled under other names (the identity
    test's unnamed cores, or an older checkout's) would hand these tests
    its op names: compile afresh here, and write nothing."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _core_hlo(engine) -> str:
    """The optimized HLO of the engine's level-loop core."""
    sources = np.arange(engine.lanes) % engine.num_vertices
    if isinstance(engine, PackedMsBfsEngine):
        fw0 = jnp.asarray(engine._seed(sources))
        lowered = engine._core.lower(engine.arrs, fw0, fw0[:-1],
                                     jnp.int32(8))
    elif isinstance(engine, DistHybridMsBfsEngine):
        fw0 = engine._seed_dev(sources)
        lowered = engine._dist_core.lower(engine.arrs, fw0, jnp.int32(8))
    else:
        fw0 = engine._seed_dev(sources)
        lowered = engine._core.lower(engine.arrs, fw0, jnp.int32(8))
    return lowered.compile().as_text()


def _running_instructions(hlo: str):
    """(opcode, op_name or None) of each instruction of the computations
    that run (every one but the bodies of fusions)."""
    fused = set()
    for line in hlo.splitlines():
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%\S+ = (.*)$", line)
        if not m or comp is None or comp in fused:
            continue
        op = re.search(r"[\]\}\)] ([a-z][\w-]*)\(", m.group(1))
        name = re.search(r'op_name="([^"]*)"', line)
        yield (op.group(1) if op else "?"), (name.group(1) if name else None)


def _strip(hlo: str) -> str:
    """The HLO text without op metadata and the stack-frame tables."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    return "\n".join(out)


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_every_core_op_names_its_phase(name):
    engine = ENGINES[name]()
    scopes = set()
    unnamed = []
    fills = set()
    for opcode, op_name in _running_instructions(_core_hlo(engine)):
        if opcode in NO_WORK or op_name is None:
            continue  # XLA's own copies and constant fills carry no name
        if name == "mesh4_hybrid8192" and PLANE_FILL.match(op_name):
            fills.add(op_name)
            continue
        if not op_name.startswith("jit(core)/"):
            continue  # argument names
        parts = op_name.split("/")
        phase = [p for p in parts if p.startswith(("level.", "batch."))]
        if not phase or f"lanes{engine.lanes}" not in parts:
            unnamed.append((opcode, op_name))
        else:
            scopes.add(phase[-1])
    assert unnamed == []
    assert len(fills) <= 1
    assert {"level.gather", "level.claim"} <= scopes
    if name in ("hybrid_tiles", "mesh4_hybrid8192"):
        assert "level.tiles" in scopes
    if name == "mesh4_hybrid8192":
        assert {"level.exchange", "level.sync"} <= scopes
    if name in ("packed64", "wide256"):
        assert "level.heavy" in scopes


class _NoScope(contextlib.ContextDecorator):
    """``obs.scope`` with no name: a null ``with`` block and decorator."""

    def __init__(self, _name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_scopes_leave_the_compiled_core_unchanged(name, monkeypatch):
    scoped = _strip(_core_hlo(ENGINES[name]()))
    monkeypatch.setattr(obs, "scope", _NoScope)
    assert _strip(_core_hlo(ENGINES[name]())) == scoped


def test_scope_decorator_is_a_fresh_scope_per_call():
    """One decorated function traced by two threads at once, each inside
    its own outer scope: each thread's later ops keep its own names."""
    inside = threading.Barrier(2, timeout=30)

    @obs.scope("level.gather")
    def gather(x):
        inside.wait()  # both threads inside the scope at once
        return x + 1

    texts = {}

    def trace(outer):
        def f(x):
            with jax.named_scope(outer):
                y = gather(x)
                return y * 3

        texts[outer] = jax.jit(f).lower(jnp.ones(4)).as_text(
            debug_info=True)

    threads = [threading.Thread(target=trace, args=(o,))
               for o in ("outerA", "outerB")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for outer, other in (("outerA", "outerB"), ("outerB", "outerA")):
        names = re.findall(r'loc\("(jit\(f\)/[^"]*)"', texts[outer])
        assert f"jit(f)/{outer}/level.gather/add" in names
        assert f"jit(f)/{outer}/mul" in names
        assert not any(other in n for n in names)


def test_host_span_off_is_the_shared_noop():
    obs.disarm()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    called = []
    span = obs.host_span("tpu_bfs.test.step", lambda: called.append(1) or {},
                         span_id="b1", batch=1)
    assert span is obs.host_span("tpu_bfs.test.other")
    with span as s:
        s.set(attempt=0)
    assert called == []


def test_host_span_records_its_pair_where_armed(tmp_path):
    rec = obs.arm(dump_dir=str(tmp_path))
    try:
        with obs.host_span("tpu_bfs.serve.dispatch", span_id="b7",
                           cat="serve.batch", batch=7) as span:
            span.set(attempt=1)
        with pytest.raises(RuntimeError):
            with obs.host_span("tpu_bfs.serve.fetch", span_id="b7",
                               cat="serve.batch", batch=7):
                raise RuntimeError("boom")
        # A span without an id is the profiler's alone.
        with obs.host_span("tpu_bfs.serve.write"):
            pass
    finally:
        obs.disarm()
    evs = [(e["ph"], e["name"], e["id"], e["args"]) for e in rec.snapshot()]
    assert evs == [
        ("b", "dispatch", "b7", {"batch": 7}),
        ("e", "dispatch", "b7", {"attempt": 1}),
        ("b", "fetch", "b7", {"batch": 7}),
        ("e", "fetch", "b7", {"failed": True}),
    ]


def test_host_span_lands_in_the_profiler_trace_with_its_stats(tmp_path):
    obs.disarm()
    seen = {}

    def stats():
        seen["n"] = seen.get("n", 0) + 1
        return {"width": 64, "n": 3, "wait_ms_sum": 1.5}

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.host_span("tpu_bfs.serve.dispatch", stats):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert seen == {"n": 1}
    from jax.profiler import ProfileData

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "tpu_bfs.serve.dispatch"]
    assert found == [{"width": 64, "n": 3, "wait_ms_sum": 1.5}]


def test_setup_spans_reach_the_recorder_where_armed(tmp_path):
    """The CLI's set-up steps are host spans too: with ``--trace-out`` the
    recorder holds their begin/end pairs under the names it always had."""
    from tpu_bfs import cli

    out = tmp_path / "trace.json"
    try:
        rc = cli.main(["0", "random:n=100,m=300,seed=1", "--multi-source",
                       "4", "--trace-out", str(out)])
    finally:
        obs.disarm()
    assert rc == 0
    with open(out) as f:
        evs = [(e["ph"], e["name"]) for e in json.load(f)["traceEvents"]
               if e.get("cat") == "cli"]
    assert evs[:4] == [("b", "graph_load"), ("e", "graph_load"),
                       ("b", "engine_build"), ("e", "engine_build")]
