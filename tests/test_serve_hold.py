"""Device-paced dispatch: a partial batch waits while a core is in flight.

A fake engine hands out one completion gate per dispatch, which the test
opens; its handle's ``levels.block_until_ready()`` blocks on the gate, as
a real dispatch's level count blocks on its level loop. Pinned:

- a partial batch waits while a dispatched core runs, taking arrivals,
  and is dispatched the moment that core completes;
- a full batch is dispatched while another core runs;
- a deadlined query ends a wait at once and is answered ``ok``;
- ``pipeline=False`` never waits;
- ``batches_held`` / ``held_ms_total`` and the ``tpu_bfs.serve.coalesce``
  span's ``held_ms`` / ``released_by``.
"""

import contextlib
import sys
import threading
import time

import numpy as np
import pytest

from tpu_bfs import obs
from tpu_bfs.graph.generate import random_graph
from tpu_bfs.serve import BfsService, EngineRegistry
from tpu_bfs.serve.executor import PendingBatch
from tpu_bfs.serve.scheduler import AdmissionQueue, PendingQuery

pytestmark = pytest.mark.serve

WAIT_S = 30.0


class _Gate:
    """A dispatch's ``levels``: ready once the test opens it."""

    def __init__(self):
        self.opened = threading.Event()

    def block_until_ready(self):
        assert self.opened.wait(WAIT_S), "core never completed"
        return self


class _Handle:
    def __init__(self, padded, gate):
        self.padded = padded
        self.levels = gate


class _Result:
    def __init__(self, sources):
        n = len(sources)
        self.reached = np.ones(n, np.int64)
        self.ecc = np.zeros(n, np.int32)


class GatedEngine:
    """dispatch/fetch double whose every core completes when the test
    opens its gate (``gates[i]`` for the i-th dispatch)."""

    def __init__(self, lanes, v):
        self.lanes = lanes
        self.num_vertices = v
        self.gates: list = []
        self.dispatched_at: list = []
        self._cond = threading.Condition()

    def dispatch(self, padded):
        gate = _Gate()
        with self._cond:
            self.gates.append(gate)
            self.dispatched_at.append(time.monotonic())
            self._cond.notify_all()
        return _Handle(np.asarray(padded), gate)

    def fetch(self, handle):
        handle.levels.block_until_ready()
        return _Result(handle.padded)

    def wait_dispatches(self, n: int) -> None:
        with self._cond:
            assert self._cond.wait_for(lambda: len(self.gates) >= n,
                                       WAIT_S), len(self.gates)

    def dispatches(self) -> int:
        with self._cond:
            return len(self.gates)

    def open_all(self) -> None:
        with self._cond:
            gates = list(self.gates)
        for g in gates:
            g.opened.set()


@pytest.fixture
def graph():
    return random_graph(64, 300, seed=5)


def _service(graph, monkeypatch, eng, **kw):
    reg = EngineRegistry(capacity=4, warm=False)
    reg.add_graph("fake", graph)
    monkeypatch.setattr(reg, "get", lambda spec: eng)
    kw.setdefault("linger_ms", 0.0)
    return BfsService("fake", registry=reg, lanes=eng.lanes,
                      width_ladder="off", distances=False, **kw)


@pytest.fixture
def coalesce_spans(monkeypatch):
    """The stats of every ``tpu_bfs.serve.coalesce`` span, read as each
    span ends (the profiler would record them; none runs here)."""
    spans = []
    host_span = obs.host_span

    @contextlib.contextmanager
    def spied(name, stats=None, **kw):
        with host_span(name, stats, **kw) as span:
            yield span
        if name == "tpu_bfs.serve.coalesce":
            spans.append(stats())

    monkeypatch.setattr(obs, "host_span", spied)
    return spans


def test_partial_batch_waits_for_core_then_goes_at_once(
        graph, monkeypatch, coalesce_spans):
    eng = GatedEngine(32, graph.num_vertices)
    svc = _service(graph, monkeypatch, eng)
    try:
        first = svc.submit(0)
        eng.wait_dispatches(1)
        held = [svc.submit(s) for s in (1, 2)]
        time.sleep(0.2)
        held.append(svc.submit(3))
        time.sleep(0.2)
        # A core is in flight: the partial batch waits and keeps taking
        # arrivals.
        assert eng.dispatches() == 1
        t_open = time.monotonic()
        eng.gates[0].opened.set()
        eng.wait_dispatches(2)
        # Released by the core's completion (observed, not polled).
        assert eng.dispatched_at[1] - t_open < 5.0
        eng.open_all()
        assert first.result(WAIT_S).ok
        rs = [q.result(WAIT_S) for q in held]
        assert all(r.ok for r in rs), [r.status for r in rs]
        assert [r.batch_lanes for r in rs] == [3, 3, 3]
        snap = svc.statsz()
        assert snap["batches"] == 2
        assert snap["batches_held"] == 1
        assert snap["held_ms_total"] >= 350.0
    finally:
        eng.open_all()
        svc.close()
    spans = coalesce_spans[:2]
    assert [s["released_by"] for s in spans] == ["linger", "device"]
    assert [s["taken"] for s in spans] == [1, 3]
    assert spans[0]["held_ms"] == 0.0
    assert spans[1]["held_ms"] >= 350.0
    assert abs(spans[1]["held_ms"] - snap["held_ms_total"]) < 1e-3


def test_full_batch_dispatches_while_core_in_flight(
        graph, monkeypatch, coalesce_spans):
    eng = GatedEngine(32, graph.num_vertices)
    svc = _service(graph, monkeypatch, eng)
    try:
        first = svc.submit(0)
        eng.wait_dispatches(1)
        full = [svc.submit(s) for s in range(1, 33)]
        # Dispatched with the first core still running.
        eng.wait_dispatches(2)
        assert not eng.gates[0].opened.is_set()
        eng.open_all()
        assert first.result(WAIT_S).ok
        rs = [q.result(WAIT_S) for q in full]
        assert {r.batch_lanes for r in rs} == {32}
    finally:
        eng.open_all()
        svc.close()
    assert coalesce_spans[1]["released_by"] == "full"
    assert coalesce_spans[1]["taken"] == 32


def test_deadline_releases_a_held_query_answered_ok(
        graph, monkeypatch, coalesce_spans):
    """A deadlined query is never held on the device: it ends a hold at
    once, and a batch it heads goes as on an idle device. So it keeps
    nearly its whole deadline for the dispatch path, a slow engine
    acquisition included, and is answered ``ok``."""
    eng = GatedEngine(32, graph.num_vertices)
    svc = _service(graph, monkeypatch, eng)
    acquire_s = 0.5
    get = svc._registry.get

    def slow_get(spec):
        if eng.dispatches():
            time.sleep(acquire_s)
        return get(spec)

    monkeypatch.setattr(svc._registry, "get", slow_get)
    try:
        first = svc.submit(0)
        eng.wait_dispatches(1)
        held = svc.submit(1)
        time.sleep(0.2)
        assert eng.dispatches() == 1
        t0 = time.monotonic()
        q = svc.submit(2, deadline_ms=3000)
        # The first core never completes on its own.
        eng.wait_dispatches(2)
        assert eng.dispatched_at[1] - t0 < 3.0 - acquire_s
        alone = svc.submit(3, deadline_ms=3000)
        eng.wait_dispatches(3)
        assert not eng.gates[0].opened.is_set()
        eng.open_all()
        for p in (q, alone, held, first):
            r = p.result(WAIT_S)
            assert r.ok, (r.status, r.error)
        snap = svc.statsz()
        assert snap["expired"] == 0 and snap["batches_held"] == 1
    finally:
        eng.open_all()
        svc.close()
    assert [s["taken"] for s in coalesce_spans[1:3]] == [2, 1]
    assert [s["released_by"] for s in coalesce_spans[1:3]] == [
        "deadline", "linger"]
    assert coalesce_spans[2]["held_ms"] == 0.0


def test_unpipelined_service_never_holds(graph, monkeypatch,
                                         coalesce_spans):
    """Without the pipeline a batch is fetched before the next one forms,
    so nothing is ever in flight to wait on."""

    class OpenEngine(GatedEngine):
        def dispatch(self, padded):
            handle = super().dispatch(padded)
            handle.levels.opened.set()
            return handle

    eng = OpenEngine(32, graph.num_vertices)
    svc = _service(graph, monkeypatch, eng, pipeline=False)
    try:
        staged = [svc.submit(s) for s in range(3)]
        assert all(q.result(WAIT_S).ok for q in staged)
        assert svc.query(5, timeout=WAIT_S).ok
        assert svc.statsz()["batches_held"] == 0
    finally:
        svc.close()
    assert coalesce_spans
    assert all(s["held_ms"] == 0.0 for s in coalesce_spans)
    assert {s["released_by"] for s in coalesce_spans} <= {"linger", "full"}


def test_handle_without_device_value_counts_as_done(graph, monkeypatch):
    """A handle with no ``levels`` to wait on (host values, most test
    doubles) never engages the wait."""

    class HostEngine(GatedEngine):
        def dispatch(self, padded):
            super().dispatch(padded)
            return np.asarray(padded)

        def fetch(self, handle):
            return _Result(handle)

    eng = HostEngine(32, graph.num_vertices)
    svc = _service(graph, monkeypatch, eng)
    try:
        for s in range(3):
            assert svc.query(s, timeout=WAIT_S).ok
        assert svc.statsz()["batches_held"] == 0
    finally:
        svc.close()


def test_queue_hold_releases_on_core_finished_and_stop():
    """The admission queue on its own: held while a core is counted,
    released by ``core_finished``; ``stop`` ends a hold too."""
    queue = AdmissionQueue(16)
    queue.core_started()
    queue.offer(PendingQuery(1))
    held = []
    out = []
    t = threading.Thread(target=lambda: out.append(queue.next_batch(
        8, 0.0, on_held=held.append)))
    t.start()
    time.sleep(0.1)
    queue.offer(PendingQuery(2))
    time.sleep(0.1)
    assert not out
    queue.core_finished()
    t.join(WAIT_S)
    assert not t.is_alive()
    assert [q.source for q in out[0]] == [1, 2]
    assert len(held) == 1 and held[0] >= 150.0
    queue.core_started()
    queue.offer(PendingQuery(3))
    t = threading.Thread(target=lambda: out.append(queue.next_batch(
        8, 0.0)))
    t.start()
    time.sleep(0.05)
    queue.stop()
    t.join(WAIT_S)
    assert not t.is_alive()
    assert [q.source for q in out[1]] == [3]


def test_core_accounting_survives_racing_completions():
    """Each counted core is released exactly once although its waiter and
    its fetch half race to mark it: many batches, more threads than cores,
    a short switch interval. A lost or doubled release would leave the
    count off zero."""

    eng = GatedEngine(32, 64)
    queue = AdmissionQueue(16)
    fired = []
    lock = threading.Lock()

    def finished():
        with lock:
            fired.append(1)
        queue.core_finished()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        batches = []
        for i in range(64):
            pb = PendingBatch(eng, [PendingQuery(i)], 1,
                              np.full(32, i, np.int64))
            pb.handle = eng.dispatch(pb.padded)
            queue.core_started()
            pb.watch_core(finished)
            batches.append(pb)
        threads = [threading.Thread(target=eng.open_all)]
        threads += [threading.Thread(target=pb.mark_core_ready)
                    for pb in batches[::2]]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
            assert not t.is_alive()
        for pb in batches:
            assert pb.core_ready.wait(WAIT_S)
    finally:
        sys.setswitchinterval(old)
    deadline = time.monotonic() + WAIT_S
    while len(fired) < 64 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(fired) == 64
    queue.offer(PendingQuery(99))
    t0 = time.monotonic()
    assert [q.source for q in queue.next_batch(8, 0.0)] == [99]
    assert time.monotonic() - t0 < 5.0


def test_linger_still_bounds_a_hold_that_ends_early(coalesce_spans):
    """A core that finishes inside the linger does not cut the linger
    short: the batch goes when both have passed, released by the
    linger, and the wait on the device is counted up to the core's end."""
    queue = AdmissionQueue(16)
    queue.core_started()
    queue.offer(PendingQuery(1))
    held = []
    out = []
    t0 = time.monotonic()
    t = threading.Thread(target=lambda: out.append(queue.next_batch(
        8, 0.4, on_held=held.append)))
    t.start()
    time.sleep(0.1)
    queue.core_finished()
    t.join(WAIT_S)
    assert not t.is_alive()
    assert time.monotonic() - t0 >= 0.4
    assert [q.source for q in out[0]] == [1]
    assert len(held) == 1 and 50.0 <= held[0] < 350.0
    assert coalesce_spans[-1]["released_by"] == "linger"
    assert coalesce_spans[-1]["held_ms"] == held[0]


def test_watch_core_fires_at_once_for_a_finished_core():
    """A core whose value is ready already, or that its fetch half has
    marked, is reported at once, on the caller's thread."""

    class ReadyGate(_Gate):
        def is_ready(self):
            return True

    eng = GatedEngine(32, 64)
    fired = []
    pb = PendingBatch(eng, [PendingQuery(0)], 1, np.zeros(32, np.int64))
    pb.handle = _Handle(pb.padded, ReadyGate())
    pb.watch_core(lambda: fired.append(threading.current_thread()))
    assert fired == [threading.current_thread()]
    pb = PendingBatch(eng, [PendingQuery(0)], 1, np.zeros(32, np.int64))
    pb.handle = _Handle(pb.padded, _Gate())
    pb.mark_core_ready()
    pb.watch_core(lambda: fired.append(threading.current_thread()))
    assert len(fired) == 2 and pb.core_ready.is_set()
