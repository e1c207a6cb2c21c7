"""The serving front-end: in-process ``BfsService`` + stdin/stdout JSONL.

``BfsService`` is the API tests and the bench drive; the JSONL loop
(``tpu-bfs-serve`` / ``python -m tpu_bfs.serve``) is the same service
behind a line protocol:

    request   {"id": 7, "source": 12345}
              (+ "deadline_ms", + "want_distances": false)
    response  {"id": 7, "source": 12345, "status": "ok", "levels": 6,
               "reached": 104857, "latency_ms": 18.4, "batch_lanes": 31,
               "dispatched_lanes": 32, "distances_npy": "<base64 .npy>"}

With ``--mutations`` (ISSUE 19) the wire also takes edge updates:

    request   {"id": 9, "op": "mutate", "add": [[1, 2], [3, 4, 7]],
               "remove": [[5, 6]]}
    response  {"id": 9, "op": "mutate", "ok": true, "generation": 3,
               "flip_ms": 1.8, "overlay_rows": 2, "compacted": false}

Non-ok responses carry ``status`` in {rejected, deadline_exceeded,
error, shutdown} plus ``error``. Responses are emitted as queries
complete (batch order, not arrival order); ``id`` is the correlation
key. stdout carries ONLY protocol lines; logs and the periodic statsz
line go to stderr.

Adaptive dispatch (ISSUE 3): the service holds a small geometric WIDTH
LADDER of warmed engines (default rungs lanes/16, lanes/4, lanes — e.g.
32/128/512) and routes each coalesced batch to the narrowest rung that
fits, so a 3-query batch stops paying 512 lanes of compute; and result
extraction runs on a dedicated worker (PIPELINED, the engines'
dispatch/fetch split), so the scheduler thread is already forming and
dispatching batch N+1 while batch N's distances are still being pulled.
The scheduler thread owns all BFS dispatch as before; the extraction
worker's device work is limited to result readback of already-completed
batches.
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import io
import json
import os
import queue as _queue
import signal
import sys
import threading
import time

import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs
from tpu_bfs.resilience.failover import floor_config, next_mesh_rung
from tpu_bfs.serve.executor import (
    BatchExecutor,
    CircuitBreaker,
    MeshFaultRequeue,
    OomRequeue,
)
from tpu_bfs.serve.answercache import AnswerCache
from tpu_bfs.serve.metrics import ServeMetrics
from tpu_bfs.serve.registry import DEFAULT_PLANES, EngineRegistry, EngineSpec
from tpu_bfs.serve.scheduler import (
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHUTDOWN,
    AdmissionQueue,
    InflightIndex,
    PendingQuery,
    QueryResult,
)
from tpu_bfs.utils.recovery import (
    COUNTERS,
    is_mesh_fault,
    is_oom_failure,
    is_transient_failure,
)
from tpu_bfs.workloads import (
    KINDS,
    METADATA_ONLY_KINDS,
    kind_unsupported_reason,
    supported_kinds,
)

MIN_LANES = 32
# Auto ladder spacing: each rung 4x the previous (32/128/512 at the
# default 512 max). Factor 4 keeps the rung count (and the HBM cost of
# resident engines) low while bounding pad waste per batch below the
# dispatched width's 3/4 — the routing histogram in /statsz shows where
# traffic actually lands.
LADDER_FACTOR = 4


def ladder_bounds(lanes: int, *, devices: int = 1,
                  engine: str = "wide") -> tuple[int, int]:
    """``(floor, quantum)`` of the serving widths for this engine/mesh.

    The single-chip defaults (floor 32, quantum 32) are sized for one
    chip's lane budget; a mesh ladder must scale both (ISSUE 11):

    - the HYBRID engines' dense MXU kernel takes whole 4096-lane steps
      (single-chip and distributed alike), so both floor and quantum are
      4096 — an auto ladder stops warming widths the engine cannot even
      build;
    - other mesh engines keep the 32-lane quantum but raise the floor to
      ``32 * devices``: a whole mesh dispatching a 32-lane batch pays P
      chips' collectives for work one chip holds in a single rung — no
      partition benefits from rungs below that line, while the widest
      rungs (the ones a mesh can actually hold) stay.
    """
    from tpu_bfs.serve.registry import HYBRID_LANE_QUANTUM

    if engine == "hybrid":
        return HYBRID_LANE_QUANTUM, HYBRID_LANE_QUANTUM
    if devices > 1:
        return min(lanes, MIN_LANES * devices), MIN_LANES
    return MIN_LANES, MIN_LANES


def build_width_ladder(lanes: int, ladder="auto", *, devices: int = 1,
                       engine: str = "wide") -> list:
    """The service's resident widths, ascending, topped by ``lanes``.

    ``"auto"`` walks down from ``lanes`` by :data:`LADDER_FACTOR` to the
    engine/mesh floor (:func:`ladder_bounds` — 32 on one chip, scaled on
    a mesh); ``"off"``/None serves one fixed width (the pre-ladder
    behavior, and the A/B baseline); an explicit sequence gives the rungs
    directly (each a multiple of the width quantum in [floor, lanes])."""
    floor, quantum = ladder_bounds(lanes, devices=devices, engine=engine)
    if ladder in (None, "off"):
        return [lanes]
    if isinstance(ladder, str) and ladder != "auto":
        ladder = [int(tok) for tok in ladder.replace(",", " ").split()]
    if ladder == "auto":
        rungs = {lanes}
        w = lanes
        while w > floor:
            w = max(floor, (w // LADDER_FACTOR) // quantum * quantum)
            rungs.add(w)
        return sorted(rungs)
    rungs = sorted({int(w) for w in ladder} | {lanes})
    for w in rungs:
        if w % quantum or not (floor <= w <= lanes):
            raise ValueError(
                f"ladder width {w} must be a multiple of {quantum} in "
                f"[{floor}, {lanes}]"
            )
    return rungs


@dataclasses.dataclass(frozen=True)
class MeshServeConfig:
    """The service's CURRENT engine/mesh configuration — everything
    ``_spec`` stamps into a registry key. One immutable object swapped
    atomically (the ``_closed``/``_draining`` lock-free-flag idiom):
    the mesh failover ladder (ISSUE 12) replaces it wholesale when a
    mesh fault degrades the service to a smaller device count, and the
    health probe swaps it back, so every reader sees a consistent
    config with no lock on the routing hot path."""

    engine: str
    devices: int
    exchange: str
    wire_pack: bool
    delta_bits: tuple
    sieve: bool
    predict: bool
    mesh_shape: tuple
    resume_levels: int

    def degraded(self, new_devices: int) -> "MeshServeConfig":
        """This config one mesh rung down. At the single-chip floor the
        exchange knobs drop and mesh-only engines map to their
        single-chip equivalent (resilience.failover.floor_config); a
        still-multi-chip rung keeps the exchange family (the compiled
        collective program is rebuilt for the smaller mesh)."""
        if new_devices == 1:
            engine, exchange = floor_config(self.engine, self.exchange)
            return MeshServeConfig(
                engine=engine, devices=1, exchange=exchange,
                wire_pack=False, delta_bits=(), sieve=False, predict=False,
                mesh_shape=(), resume_levels=0,
            )
        return dataclasses.replace(
            self, devices=new_devices,
            # An explicit RxC factorization described the FULL mesh;
            # the degraded shape re-derives most-square.
            mesh_shape=(),
        )


class BfsService:
    """Long-lived lane-batching BFS query service over one graph.

    ``graph`` is a loaded ``Graph`` or a CLI graph spec string (path /
    ``rmat:scale=...`` / ``random:n=...``). Queries submitted from any
    thread are coalesced into packed batches of up to ``lanes`` sources
    by one scheduler thread; each batch is routed to the narrowest
    ``width_ladder`` rung that fits ("auto" builds the geometric ladder,
    "off" pins the single fixed width). With ``devices > 1`` the rungs
    are DISTRIBUTED engines spanning the mesh (ISSUE 11): wide/hybrid
    run the 1D-partition packed MS engines, ``engine='dist2d'`` the 2D
    edge partition; ``exchange``/``wire_pack``/``delta_bits``/``sieve``/
    ``predict`` pick the exchange format (PRs 5/7), ``mesh_shape`` the
    explicit RxC factorization, and the ladder floor, OOM halving grid,
    and circuit-breaker keys all become partition-aware. A MESH FAULT
    (device loss / hung collective / backend restart —
    utils/recovery.is_mesh_fault) runs the failover ladder (ISSUE 12):
    the service rebuilds its rungs on a halved mesh (down to one chip),
    re-admits the failed batch's queries, and — with
    ``mesh_probe_interval_s > 0`` — heartbeats the wider rungs in the
    background, promoting back once the mesh is healthy again;
    ``resume_levels=K`` (dist2d) adds level-checkpointed resume so the
    re-admitted queries continue from their last snapshot instead of
    the source. ``linger_ms`` bounds how long a
    partial batch waits for fill; ``queue_cap`` bounds the backlog
    (overload sheds with REJECTED); ``deadline_ms`` (default: none)
    bounds each query's QUEUE wait — see scheduler.py for the semantics.
    An OOM at rung W evicts W and every wider rung and re-admits the
    batch's queries below W (floor_lanes halving, down to 32); transient
    failures retry in place. With ``pipeline=True`` (default) result
    extraction overlaps the next batch's dispatch on a worker thread
    (``pipeline_depth`` bounds the in-flight handoff). ``distances``
    (default True) is the service-wide default for whether responses
    carry the distance table; per-query ``want_distances`` overrides, and
    distance-free queries never transfer the O(V) row off the device.
    """

    def __init__(
        self,
        graph,
        *,
        engine: str = "wide",
        lanes: int = 512,
        planes: int = DEFAULT_PLANES,
        pull_gate: bool = False,
        expand_impl: str = "xla",
        devices: int = 1,
        exchange: str = "",
        wire_pack: bool = False,
        delta_bits=(),
        sieve: bool = False,
        predict: bool = False,
        mesh_shape=(),
        resume_levels: int = 0,
        mesh_probe_interval_s: float = 0.0,
        width_ladder="auto",
        pipeline: bool = True,
        pipeline_depth: int = 2,
        linger_ms: float = 2.0,
        queue_cap: int = 1024,
        deadline_ms: float = 0.0,
        max_retries: int = 2,
        max_requeues: int = 8,
        watchdog_ms: float = 0.0,
        breaker_threshold: int = 3,
        breaker_cooldown_ms: float = 30_000.0,
        audit_rate: float = 0.0,
        audit_structural: bool = False,
        audit_checksum: bool = False,
        audit_seed: int = 0,
        cache_bytes: int = 0,
        landmarks: int = 0,
        dynamic=(),
        generation_dir: str | None = None,
        staleness_bound: int = 0,
        single_flight: bool = True,
        distances: bool = True,
        kinds=None,
        registry: EngineRegistry | None = None,
        registry_capacity: int = 4,
        aot_dir: str | None = None,
        autostart: bool = True,
        log=None,
    ):
        self._log = log or (lambda msg: None)
        # Widths and the degrade cap share one lock: the scheduler routes
        # while the extraction worker may be shrinking the ladder after a
        # fetch-time OOM.
        self._width_lock = threading.Lock()
        self._ladder = build_width_ladder(  # guarded-by: _width_lock
            lanes, width_ladder, devices=devices, engine=engine
        )
        self._max_lanes = self._ladder[-1]  # guarded-by: _width_lock
        # The engine/mesh width grid (ISSUE 11): the OOM halving ladder
        # quantizes onto it and stops at its floor — a mesh service never
        # degrades into widths no partition benefits from (or, for the
        # hybrid engines, widths that cannot even build).
        self._width_floor, self._width_quantum = ladder_bounds(
            lanes, devices=devices, engine=engine
        )
        # An internally-created registry must hold the WHOLE ladder
        # resident (plus one degrade-rung slot) or routing thrashes
        # rebuilds; a caller-supplied registry keeps its own policy.
        # ``aot_dir`` arms the registry's artifact store (the --preheat
        # path, ISSUE 9): every rung whose artifacts are present adopts
        # deserialized executables instead of compiling.
        self._registry = registry or EngineRegistry(
            capacity=max(registry_capacity, len(self._ladder) + 1),
            log=self._log,
            aot_store=aot_dir,
        )
        if isinstance(graph, str):
            self._graph_key = graph
        else:
            self._graph_key = f"graph@{id(graph):x}"
            self._registry.add_graph(self._graph_key, graph)
        self._graph = self._registry.graph(self._graph_key)
        self._planes = planes
        self._pull_gate = pull_gate
        self._expand_impl = expand_impl
        # The CURRENT engine/mesh config: one immutable object swapped
        # atomically by the mesh failover ladder (degrade) and the
        # health probe (restore) — see MeshServeConfig. _cfg0 is the
        # as-launched config a restore climbs back to; _ladder_arg lets
        # the degraded width ladder re-derive from the operator's
        # original intent at the new device count (topped by the
        # current _max_lanes so an OOM cap survives the failover).
        self._mesh_cfg = self._cfg0 = MeshServeConfig(
            engine=engine, devices=devices, exchange=exchange,
            wire_pack=bool(wire_pack), delta_bits=tuple(delta_bits),
            sieve=bool(sieve), predict=bool(predict),
            mesh_shape=tuple(mesh_shape),
            resume_levels=int(resume_levels),
        )
        self._ladder_arg = width_ladder
        self._mesh_probe_interval_s = max(mesh_probe_interval_s, 0.0)
        self._mesh_probe = None  # guarded-by: _lock (lifecycle state)
        # Served query kinds (ISSUE 14; full-mesh serving ISSUE 20):
        # None = everything this engine/mesh/graph supports
        # (workloads.supported_kinds — sssp needs a weights plane, p2p
        # an undirected graph; on a mesh the kinds ride the wide/dist2d
        # substrates). An explicit list is validated here, at
        # construction.
        auto_kinds = supported_kinds(engine, devices, self._graph)
        if kinds is None:
            self._kinds = auto_kinds
        else:
            kinds = tuple(kinds)
            for kind in kinds:
                if kind not in KINDS:
                    raise ValueError(
                        f"unknown kind {kind!r} (one of {KINDS})"
                    )
                if kind not in auto_kinds:
                    why = kind_unsupported_reason(
                        kind, engine, devices, self._graph
                    )
                    raise ValueError(
                        f"kind {kind!r} is not servable by this config: "
                        f"{why} (servable: {auto_kinds})"
                    )
            self._kinds = kinds
        if not self._kinds:
            raise ValueError("service must serve at least one kind")
        # Dynamic-graph tier (ISSUE 19): ``dynamic=(rows, kcap)`` (or
        # True for the default capacity) arms streaming edge updates —
        # every engine builds with a bounded overlay of that shape, the
        # flip lock serializes mutation flips against batch dispatch,
        # and ``apply_edge_updates`` becomes the mutation API. The flip
        # state below exists (cheap, inert) even on static services so
        # the scheduler loop stays branch-free.
        self._flip_lock = threading.RLock()
        self._dynamic = None
        self._gen_store = None
        self._gen_tmp = None
        self._overlay_cap = ()
        # Writes serialize under _flip_lock; reads are deliberately
        # lock-free (a torn-free CPython int snapshot) — _spec and the
        # cache straggler guard run on paths that also hold _width_lock,
        # and taking the flip lock there would close a lock-order cycle.
        self._graph_generation = 0
        self._overlay_tables = None  # guarded-by: _flip_lock
        self._overlay_epoch = 0  # guarded-by: _flip_lock
        self._flips = 0  # guarded-by: _flip_lock
        self._compactions = 0  # guarded-by: _flip_lock
        self._flip_ms: list = []  # guarded-by: _flip_lock (last 64)
        self._staleness = None
        if dynamic:
            from tpu_bfs.graph.dynamic import (
                DEFAULT_CAPACITY,
                DynamicGraph,
                GenerationStore,
            )

            cap = (DEFAULT_CAPACITY if dynamic is True
                   else (int(dynamic[0]), int(dynamic[1])))
            self._overlay_cap = cap
            # Raises on an undirected/engine/pull_gate mismatch before
            # any build (DynamicGraph checks the base; EngineSpec
            # .validate below checks the engine combos).
            self._dynamic = DynamicGraph(
                self._graph, capacity=cap, log=self._log
            )
            if generation_dir is None:
                import tempfile

                # Service-owned store: compactions still get the full
                # crash-safe commit protocol, just not a survivable
                # location (pass generation_dir for that).
                self._gen_tmp = tempfile.TemporaryDirectory(
                    prefix="tpu-bfs-generations-"
                )
                generation_dir = self._gen_tmp.name
            self._gen_store = GenerationStore(generation_dir,
                                              log=self._log)
            if "p2p" in self._kinds:
                # p2p's path reconstruction scans the BUILD-TIME edge
                # tables (parent_scan), which the overlay fold never
                # touches — a reconstructed path could walk a removed
                # edge. Dropped from dynamic serving until the scan
                # learns the overlay (EngineSpec.validate enforces the
                # same).
                self._kinds = tuple(
                    k for k in self._kinds if k != "p2p"
                )
                self._log(
                    "dynamic serving: p2p dropped from the served kinds "
                    "(path reconstruction reads build-time edge tables)"
                )
                if not self._kinds:
                    raise ValueError(
                        "dynamic serving cannot serve p2p alone"
                    )
            if audit_rate > 0:
                from tpu_bfs.integrity.staleness import StalenessAuditor

                # The generation-staleness arm of the integrity tier:
                # same sampling rate as the shadow audits, replaying
                # against the generation ring instead of a disjoint
                # rung. Disarmed (with the rest of the audits) at
                # rate 0.
                self._staleness = StalenessAuditor(
                    rate=audit_rate, seed=audit_seed,
                    bound=staleness_bound,
                    on_over_bound=self._on_stale_generation,
                    log=self._log,
                )
                self._staleness.push_generation(0, self._graph)
        elif generation_dir is not None:
            raise ValueError(
                "generation_dir without dynamic=(rows, kcap): the "
                "generation store only exists to persist compactions"
            )
        if registry is None and len(self._kinds) > 1:
            # The internally-owned registry must hold the warmed primary
            # ladder PLUS one resident engine per additional kind (their
            # serving rungs build lazily) or multi-kind traffic thrashes
            # rebuilds; a caller-supplied registry keeps its own policy.
            self._registry.capacity = max(
                self._registry.capacity,
                len(self._ladder) + len(self._kinds),
            )
        for w in self._ladder:
            self._spec(w).validate()  # fail at construction, not first dispatch
        self._linger_s = max(linger_ms, 0.0) / 1e3
        self._default_deadline_s = max(deadline_ms, 0.0) / 1e3
        self._queue = AdmissionQueue(queue_cap)
        self.metrics = ServeMetrics()
        # Per-width circuit breaker over deterministic batch failures:
        # routing skips an open rung (see _route_width) instead of paying
        # its full retry ladder per batch; half-opens on a timer.
        self._breaker = CircuitBreaker(
            threshold=breaker_threshold,
            cooldown_s=max(breaker_cooldown_ms, 0.0) / 1e3,
            log=self._log,
        )
        self._executor = BatchExecutor(
            self.metrics, max_retries=max_retries, log=self._log,
            watchdog_s=max(watchdog_ms, 0.0) / 1e3, breaker=self._breaker,
        )
        self._max_retries = max_retries
        # Bounded OOM requeue budget: a query re-admitted more than this
        # many times resolves with an explicit error carrying its attempt
        # history instead of looping forever when every rung is broken.
        self._max_requeues = max(int(max_requeues), 0)
        # Online integrity tier (ISSUE 15, tpu_bfs/integrity): armed by
        # any audit knob — structural tree checks on every served batch,
        # sampled shadow re-execution on a disjoint rung, wire checksums
        # on the audited transfers, and corruption quarantine. Disarmed
        # services hold None and pay nothing anywhere.
        # Audit-flush barrier state: how many batches are inside the
        # finish+observe window right now (flush_audits waits for zero
        # with an empty pipeline, so counters read complete).
        self._audit_quiesce = threading.Lock()
        self._finishing = 0  # guarded-by: _audit_quiesce
        if audit_rate > 0 or audit_structural or audit_checksum:
            from tpu_bfs.integrity import IntegrityTier

            self._integrity = IntegrityTier(
                self, rate=audit_rate,
                structural=bool(audit_structural) or bool(audit_checksum),
                checksum=audit_checksum, seed=audit_seed,
            )
            if registry is None:
                # The shadow replays keep one disjoint rung (plus a
                # rebuild slot) resident next to the serving ladder; an
                # internally-owned registry must fit it or audits thrash
                # the warm rungs they exist to check.
                self._registry.capacity = self._registry.capacity + 2
        else:
            self._integrity = None
        # Answer tier (ISSUE 18). Single-flight collapsing is on by
        # default (N concurrent identical queries admit one traversal)
        # and independent of the cache knobs; ``single_flight=False``
        # exists for saturation/load harnesses that hammer one source
        # to fill lanes on purpose. The result cache and the landmark
        # distance columns are armed by their knobs. Hits bypass the
        # scheduler entirely and stamp cache_hit/landmark provenance.
        self._inflight = InflightIndex() if single_flight else None
        self._cache = (
            AnswerCache(
                graph_key=self._graph_key, max_bytes=int(cache_bytes),
                metrics=self.metrics, log=self._log,
            )
            if cache_bytes else None
        )
        self._landmark_k = max(int(landmarks), 0)
        self._landmarks = None  # built by start()'s warm-up when armed
        self._want_distances_default = bool(distances)
        self._pipe_q: _queue.Queue | None = (
            _queue.Queue(maxsize=max(1, int(pipeline_depth)))
            if pipeline else None
        )
        # _closed/_draining stay deliberately lock-free single-word flags
        # (submit must never block behind start()'s minutes-long builds),
        # hence unannotated; the thread handles are lifecycle state only
        # ever touched under the service lock.
        self._closed = False
        self._draining = False
        self._thread: threading.Thread | None = None  # guarded-by: _lock
        self._extract_thread: threading.Thread | None = None  # guarded-by: _lock
        self._lock = threading.Lock()
        if autostart:
            self.start()

    # --- lifecycle --------------------------------------------------------

    def _spec(self, width: int | None = None,
              cfg: MeshServeConfig | None = None,
              kind: str = "bfs") -> EngineSpec:
        cfg = self._mesh_cfg if cfg is None else cfg
        if kind == "sssp" and cfg.devices > 1:
            # The service-wide exchange config speaks the base family's
            # OR dialect; the distributed sssp engine exchanges under
            # (min, +) (ISSUE 20). Map the spirit of the config onto the
            # kind's own family: queue-style stays queue-style (sparse +
            # delta_bits + predict ride along), everything dense-like
            # becomes the engine default; wire_pack/sieve are OR-only
            # knobs with no min twin and drop here.
            sparse = cfg.exchange == "sparse"
            return EngineSpec(
                graph_key=self._graph_key,
                graph_generation=self._graph_generation,
                kind=kind,
                engine=cfg.engine,
                lanes=self.lanes if width is None else width,
                planes=self._planes,
                expand_impl=self._expand_impl,
                devices=cfg.devices,
                exchange=cfg.exchange if sparse else "",
                delta_bits=cfg.delta_bits if sparse else (),
                predict=cfg.predict if sparse else False,
                mesh_shape=cfg.mesh_shape,
            )
        return EngineSpec(
            graph_key=self._graph_key,
            graph_generation=self._graph_generation,
            overlay=self._overlay_cap,
            kind=kind,
            engine=cfg.engine,
            lanes=self.lanes if width is None else width,
            planes=self._planes,
            pull_gate=self._pull_gate,
            expand_impl=self._expand_impl,
            devices=cfg.devices,
            exchange=cfg.exchange,
            wire_pack=cfg.wire_pack,
            delta_bits=cfg.delta_bits,
            sieve=cfg.sieve,
            predict=cfg.predict,
            mesh_shape=cfg.mesh_shape,
            resume_levels=cfg.resume_levels,
        )

    def start(self) -> "BfsService":
        """Build-and-warm every ladder rung's engine (widest first, so
        the width most likely to OOM degrades the ladder before anything
        narrower is paid for), then start the scheduler thread and — when
        pipelining — the extraction worker. Idempotent; called by the
        constructor unless ``autostart=False`` (tests that stage queries
        before dispatch)."""
        with self._lock:
            if self._closed:
                raise RuntimeError("service is closed")
            if self._thread is not None:
                return self
            for w in sorted(self.width_ladder, reverse=True):
                if w <= self.lanes:  # rungs above a degraded cap died
                    self._acquire_engine(w, self._primary_kind)
            if self._landmark_k > 0:
                # Landmark warm-up (ISSUE 18): one flagship MS-BFS
                # batch on the cold-start path, before READY — the K
                # distance columns then answer p2p in microseconds.
                self._warm_landmarks()
            if (self._mesh_probe_interval_s > 0
                    and self._cfg0.devices > 1
                    and self._mesh_probe is None):
                from tpu_bfs.resilience.probe import MeshHealthProbe

                # Background mesh prober: heartbeats the rungs above a
                # degraded service and promotes back onto the widest
                # healthy one — the half-open side of the failover
                # ladder (no-op while the service is at full width).
                self._mesh_probe = MeshHealthProbe(
                    self._cfg0.devices,
                    interval_s=self._mesh_probe_interval_s,
                    current=lambda: self._mesh_cfg.devices,
                    on_healthy=self._on_mesh_healthy,
                    log=self._log,
                ).start()
            if self._pipe_q is not None:
                self._extract_thread = threading.Thread(
                    target=self._extract_loop, name="bfs-serve-extract",
                    daemon=True,
                )
                self._extract_thread.start()
            self._thread = threading.Thread(
                target=self._loop, name="bfs-serve-scheduler", daemon=True
            )
            self._thread.start()
            if self._integrity is not None:
                self._integrity.start()
        return self

    def drain(self) -> None:
        """Stop ADMISSION only: new submits shed with REJECTED while
        queued and in-flight queries run to resolution. The first half of
        a graceful shutdown (the JSONL server's SIGTERM path); ``close``
        completes it. Idempotent."""
        self._draining = True

    def close(self) -> None:
        """Stop serving: in-flight batches complete (the extraction
        worker drains its handoff before exiting), queued queries resolve
        with SHUTDOWN. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            thread = self._thread
            extract_thread = self._extract_thread
            probe, self._mesh_probe = self._mesh_probe, None
        if probe is not None:
            probe.stop()
        self._queue.stop()
        if thread is not None:
            thread.join()
            if extract_thread is not None:
                self._pipe_q.put(None)  # after scheduler exit: no more puts
                extract_thread.join()
            if self._integrity is not None:
                # After both serving threads: no more observe_batch
                # calls; close() drains every queued audit first, so the
                # final statsz carries complete audit counts.
                self._integrity.close()
        else:
            # Never started: drain staged queries here instead.
            for q in self._queue.next_batch(self._queue.cap, 0.0):
                if q.resolve_status(STATUS_SHUTDOWN, error="service closed"):
                    self.metrics.record_shutdown()
        if self._gen_tmp is not None:
            # Service-owned generation store (no generation_dir given):
            # reclaim it now instead of at interpreter teardown.
            try:
                self._gen_tmp.cleanup()
            except OSError:
                pass
            self._gen_tmp = None

    def __enter__(self) -> "BfsService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --- client API -------------------------------------------------------

    @property
    def num_vertices(self) -> int:
        return self._graph.num_vertices

    @property
    def kinds(self) -> tuple:
        """Query kinds this service answers (ISSUE 14)."""
        return self._kinds

    @property
    def _primary_kind(self) -> str:
        """The kind whose ladder start() warms eagerly ("bfs" when
        served). Other kinds' engines build lazily on first query and
        stay resident per the registry LRU — per-kind correct because
        EngineSpec.kind keys the residency."""
        return "bfs" if "bfs" in self._kinds else self._kinds[0]

    @property
    def lanes(self) -> int:
        """Current maximum serving batch width (halves on OOM degrade)."""
        with self._width_lock:
            return self._max_lanes

    @property
    def width_ladder(self) -> list:
        """Resident dispatch widths, ascending (shrinks on OOM degrade)."""
        with self._width_lock:
            return list(self._ladder)

    def submit(self, source, *, id=None, deadline_ms: float | None = None,
               want_distances: bool | None = None, kind: str = "bfs",
               k: int | None = None,
               target: int | None = None) -> PendingQuery:
        """Enqueue one query; returns a PendingQuery whose ``result()``
        always resolves (ok / rejected / deadline_exceeded / error /
        shutdown — never a hang, never a silent drop).
        ``want_distances=False`` asks for a metadata-only answer (levels/
        reached) that never pulls the distance row off the device; None
        uses the service-wide ``distances`` default.

        ``kind`` picks the query family (ISSUE 14: bfs | sssp | cc |
        khop | p2p; the kinds this service actually serves are in
        ``self.kinds``); khop requires ``k`` (hop bound >= 0), p2p a
        ``target`` vertex. An unknown or unserved kind, a missing/bad
        parameter, or an out-of-range endpoint resolves the query with a
        STRUCTURED error — never a dropped request."""
        now = time.monotonic()
        ddl_s = (
            self._default_deadline_s
            if deadline_ms is None
            else max(deadline_ms, 0.0) / 1e3
        )
        kind = "bfs" if kind is None else kind
        if kind in METADATA_ONLY_KINDS:
            # cc/khop/p2p answer from summaries / the cached index; no
            # distance table exists to pull.
            want_distances = False
        q = PendingQuery(
            source, id=id, now=now,
            deadline=(now + ddl_s) if ddl_s > 0 else None,
            want_distances=(
                self._want_distances_default
                if want_distances is None else want_distances
            ),
            kind=kind if kind in KINDS else "bfs",
            k=k, target=target,
        )
        err = self._validate_query(kind, q, k, target)
        if err is not None:
            q.resolve_status(STATUS_ERROR, error=err)
            self.metrics.record_errors()
            return q
        # Answer tier (ISSUE 18), ahead of admission: a cache or
        # landmark hit resolves here — microseconds of host work, no
        # scheduler, no lane — and a duplicate of an in-flight query
        # becomes a single-flight follower that rides the leader's
        # dispatch. Order matters: the cache is consulted first (exact
        # stored payloads beat recomputed bounds), and single-flight
        # last (only queries that will actually admit need a leader).
        if not (self._closed or self._draining):
            if self._try_answer_tier(q):
                return q
            leader = (self._inflight.attach(q)
                      if self._inflight is not None else None)
            if leader is not None:
                self.metrics.record_single_flight()
                q.add_done_callback(self._account_follower)
                return q
        if self._closed or self._draining or not self._queue.offer(q):
            q.resolve_status(
                STATUS_REJECTED,
                error=(
                    "service closed" if self._closed
                    else "service draining" if self._draining
                    else "queue full"
                ),
            )
            self.metrics.record_rejected()
        return q

    def _validate_query(self, kind: str, q: PendingQuery,
                        k, target) -> str | None:
        """The per-kind admission contract (ISSUE 14 satellite): the
        error string for a malformed query, None when admissible. Every
        failure is a structured per-id response, never a drop."""
        if kind not in KINDS:
            return f"unknown kind {kind!r} (one of {KINDS})"
        if kind not in self._kinds:
            # Name WHY (ISSUE 20 satellite): the structural blocker when
            # there is one (engine family, mesh, missing weights plane,
            # directedness), else the service's own kinds= selection.
            why = kind_unsupported_reason(
                kind, self._mesh_cfg.engine, self._mesh_cfg.devices,
                self._graph,
            )
            return (
                f"kind {kind!r} is not served by this service: "
                + (why if why is not None else
                   f"excluded by this service's kinds= selection "
                   f"(engine={self._mesh_cfg.engine!r}, "
                   f"devices={self._mesh_cfg.devices})")
                + f"; serving {self._kinds}"
            )
        if not (0 <= q.source < self._graph.num_vertices):
            return (
                f"source {q.source} out of range "
                f"[0, {self._graph.num_vertices})"
            )
        if kind == "khop":
            if k is None or int(k) < 0:
                return f'khop needs "k" >= 0, got {k!r}'
        if kind == "p2p":
            if target is None:
                return 'p2p needs a "target" vertex id'
            if not (0 <= int(target) < self._graph.num_vertices):
                return (
                    f"target {target} out of range "
                    f"[0, {self._graph.num_vertices})"
                )
        return None

    # --- answer tier (ISSUE 18) -------------------------------------------

    def _try_answer_tier(self, q: PendingQuery) -> bool:
        """Resolve ``q`` from the answer cache or the landmark columns
        without traversing; False sends it on to single-flight and
        admission. Only EXACT landmark answers are served — a bounded
        bracket falls back to traversal so an armed service stays
        bit-identical to a disarmed one."""
        cache = self._cache
        if cache is not None:
            hit = cache.get(
                kind=q.kind, source=q.source, k=q.k, target=q.target,
                want_distances=q.want_distances,
            )
            if hit is not None:
                self._resolve_hit(q, hit)
                return True
        lm = self._landmarks
        if lm is not None and q.kind == "p2p" and lm.warmed:
            extras = lm.answer_p2p(q.source, q.target)
            if extras is not None:
                self._resolve_landmark(q, extras)
                return True
        return False

    def _resolve_hit(self, q: PendingQuery, hit: dict) -> None:
        extras = dict(hit["extras"]) if hit["extras"] else {}
        extras["cache_hit"] = True
        lat = (time.monotonic() - q.t_submit) * 1e3
        if q.resolve(QueryResult(
            id=q.id, source=q.source, status=STATUS_OK, kind=q.kind,
            distances=hit["distances"] if q.want_distances else None,
            levels=hit["levels"], reached=hit["reached"], extras=extras,
            latency_ms=lat,
            # No batch existed: 0/0 says "no lane was paid for", and the
            # gteps property correctly reports None.
            batch_lanes=0, dispatched_lanes=0, devices=hit["devices"],
        )):
            self.metrics.record_cache_hit(lat)
            self._audit_answer(q, origin="cache")

    def _resolve_landmark(self, q: PendingQuery, extras: dict) -> None:
        lat = (time.monotonic() - q.t_submit) * 1e3
        if q.resolve(QueryResult(
            id=q.id, source=q.source, status=STATUS_OK, kind=q.kind,
            extras=extras, latency_ms=lat,
            batch_lanes=0, dispatched_lanes=0,
        )):
            self.metrics.record_cache_hit(lat, landmark=True)
            self._audit_answer(q, origin="landmark")

    def _audit_answer(self, q: PendingQuery, *, origin: str) -> None:
        """Sampled shadow audit of a cache/landmark-resolved answer
        (ISSUE 18 x PR 15): the same deterministic sampler and disjoint
        replay rung as served batches, with the job tagged by origin so
        a confirmed mismatch quarantines the cache GENERATION (or drops
        the landmark tier), never a serving rung."""
        tier = self._integrity
        if tier is not None:
            tier.observe_answer(q, origin=origin)

    def _account_follower(self, q: PendingQuery) -> None:
        """Metrics for a single-flight follower's resolution: followers
        never enter the queue or a batch, so the batch-side counters
        never see them — account by terminal status here (the
        completed/rejected/... totals must still sum to submissions)."""
        r = q.result(0)
        if r.ok:
            self.metrics.record_follower_completed()
        elif r.status == STATUS_REJECTED:
            self.metrics.record_rejected()
        elif r.status == STATUS_EXPIRED:
            self.metrics.record_expired()
        elif r.status == STATUS_SHUTDOWN:
            self.metrics.record_shutdown()
        else:
            self.metrics.record_errors()

    def _warm_landmarks(self) -> None:
        """Build + warm the landmark distance columns with ONE flagship
        batch on a ladder rung (landmarks are just lanes). Degrades to
        disarmed on any failure — the tier is an optimization, and a
        service that cannot warm it must still reach READY."""
        if "p2p" not in self._kinds:
            # The tier only answers p2p (the symmetric triangle bound
            # needs an undirected graph — the same gate as the p2p
            # workload itself, so "p2p unserved" covers directed too).
            self._log(
                "landmark tier requested but p2p is not served by this "
                "config; skipping warm-up"
            )
            return
        from tpu_bfs.workloads.landmarks import LandmarkIndex

        k = min(self._landmark_k, self.lanes)
        try:
            index = LandmarkIndex(self._graph, k, metrics=self.metrics)
            engine = self._acquire_engine(
                self._route_width(index.k), "bfs"
            )
            ms = index.warm(
                lambda sources: engine.run(
                    np.asarray(sources, dtype=np.int64), time_it=False
                )
            )
            self._landmarks = index
            self._log(
                f"landmark tier warmed: K={index.k} columns in {ms:.0f}ms"
            )
        except Exception as exc:  # noqa: BLE001 — optimization, not liveness
            self._log(
                f"landmark warm-up failed ({type(exc).__name__}: "
                f"{str(exc)[:200]}); serving without the landmark tier"
            )

    def quarantine_answer_tier(self, origin: str, detail: str = "") -> None:
        """A CONFIRMED stale/corrupt cached or landmark answer (the
        shadow audit's finding). The suspect is stored state, not a
        rung: quarantine the cache generation (every resident entry
        becomes unreachable at the key level), or drop the landmark
        columns entirely — they are one batch to recompute and a wrong
        column poisons every bound it touches."""
        if origin == "landmark":
            self._landmarks = None
            self._log(
                f"landmark tier DROPPED after a confirmed stale answer"
                + (f" ({detail[:200]})" if detail else "")
            )
            rec = _obs.ACTIVE
            if rec is not None:
                rec.event("landmark_quarantine", cat="serve.cache",
                          detail=detail[:300])
                rec.flight_dump("landmark_quarantine")
            return
        if self._cache is not None:
            self._cache.quarantine_generation(detail=detail)

    # --- dynamic graphs (ISSUE 19) ----------------------------------------

    @property
    def graph_generation(self) -> int:
        """The served graph generation: bumps on every applied mutation
        batch (0 on a static service, and before the first mutation)."""
        return self._graph_generation

    def apply_edge_updates(self, add=(), remove=()) -> dict:
        """One streaming mutation batch: ``add`` edges ``(u, v)`` /
        ``(u, v, w)``, ``remove`` edges ``(u, v)``. Stages the bounded
        overlay on the host, CRC-verifies it across the hand-off, and
        flips the served generation atomically BETWEEN batches (the flip
        lock excludes the scheduler's dispatch section): the registry
        rekeys resident engines to the new generation, the answer cache
        invalidates by key, the landmark columns recompute, and the
        staleness auditor adopts the generation's host truth. When the
        batch does not fit the overlay, a COMPACTION runs first (new
        persisted base generation, every engine rebuilt over the
        verified artifact) and the batch re-applies on the empty
        overlay; a compaction failure rolls back — serving continues on
        base + overlay and the error propagates with nothing mutated.
        Thread-safe; callable from any thread (the JSONL server calls
        it from the reader thread). Returns a stats dict (generation,
        flip_ms, overlay_rows, compacted)."""
        if self._dynamic is None:
            raise RuntimeError(
                "service is static: construct with dynamic=(rows, kcap) "
                "(or --mutations) to serve edge updates"
            )
        if self._closed:
            raise RuntimeError("service is closed")
        from tpu_bfs.graph.dynamic import OverlayCapacityError

        t0 = time.monotonic()
        with self._flip_lock:
            compacted = False
            try:
                tables, stats = self._dynamic.apply(add=add, remove=remove)
            except OverlayCapacityError as exc:
                self._log(
                    f"overlay at capacity ({str(exc)[:200]}); compacting "
                    f"before applying the batch"
                )
                self._compact_locked()  # raises on failure (rolled back)
                compacted = True
                # Re-apply on the empty overlay over the new base. A
                # second capacity error (a single batch larger than the
                # whole overlay, or an edge at a still-inactive vertex)
                # is a caller error and propagates — the compaction
                # stands, nothing was mutated.
                tables, stats = self._dynamic.apply(add=add, remove=remove)
            self._install_overlay_locked(tables)
            gen = self._graph_generation
            flip_ms = (time.monotonic() - t0) * 1e3
            self._flips += 1
            self._flip_ms.append(flip_ms)
            del self._flip_ms[:-64]
            overlay_rows = stats["overlay_rows"]
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("generation_flip", cat="serve.dynamic",
                      generation=gen, overlay_rows=overlay_rows,
                      compacted=compacted, flip_ms=round(flip_ms, 3))
        return {
            "generation": gen,
            "flip_ms": round(flip_ms, 3),
            "overlay_rows": overlay_rows,
            "compacted": compacted,
        }

    def _install_overlay_locked(self, tables) -> None:  # requires-lock: _flip_lock
        """The flip proper (caller holds the flip lock): CRC the staged
        tables across the host hand-off, then advance the generation and
        rekey every serve tier. Engines adopt the new tables lazily at
        their next acquire (_sync_engine_overlay) — the flip lock makes
        that indistinguishable from an eager swap, with no dependence on
        the registry's non-blocking resident listing."""
        from tpu_bfs.graph.dynamic import overlay_crc32

        dyn = self._dynamic
        gen = dyn.generation
        want_crc = overlay_crc32(tables)
        if _faults.ACTIVE is not None:
            # Chaos site generation_flip / corrupt_overlay: one table
            # word flips between CRC computation and installation —
            # exactly the host-memory rot window the re-check covers.
            tables, _fired = _faults.maybe_corrupt_overlay(
                tables, generation=gen
            )
        if overlay_crc32(tables) != want_crc:
            self._log(
                "staged overlay failed its CRC re-check before install "
                "— restaging from the host truth"
            )
            rec = _obs.ACTIVE
            if rec is not None:
                rec.event("overlay_corrupt", cat="serve.dynamic",
                          generation=gen)
                rec.flight_dump("overlay_corrupt")
            tables = dyn.overlay_tables()
        torn = (_faults.ACTIVE is not None
                and _faults.ACTIVE.take("generation_flip", "torn_flip",
                                        generation=gen))
        if torn:
            # Chaos site generation_flip / torn_flip: the metadata
            # advances (generation, registry keys, cache) but the DATA
            # does not — the previous tables stay installed, so every
            # answer is one flip stale while claiming the new
            # generation. Only the staleness auditor can catch this
            # (structural checks pass, a shadow replay reproduces it).
            self._log(
                "TORN FLIP injected: generation advanced without the "
                "overlay table swap"
            )
        else:
            self._overlay_tables = tables
        self._overlay_epoch += 1
        self._graph_generation = gen
        self._registry.rekey_generation(self._graph_key, gen)
        if self._cache is not None:
            self._cache.set_graph_generation(gen)
        lm = self._landmarks
        if lm is not None:
            # Satellite fix for the tier's frozen-at-warm-up staleness
            # hole: one added edge can tighten d(l, v) everywhere, so
            # the columns are disabled FIRST (no answer window over
            # stale bounds) and recomputed over the flipped engine.
            lm.invalidate()
            try:
                self._rewarm_landmarks_locked(lm)
            except Exception as exc:  # noqa: BLE001 — optimization tier
                self._landmarks = None
                self._log(
                    f"landmark re-warm failed after the flip "
                    f"({type(exc).__name__}: {str(exc)[:200]}); tier "
                    f"disabled"
                )
        if self._staleness is not None:
            self._staleness.push_generation(gen, dyn.materialize())
        tier = self._integrity
        if tier is not None and tier._structural is not None:
            # The structural auditor's edge tables must track the live
            # generation: a removed edge left in them would read a
            # CORRECT post-flip answer as an edge-slack violation. The
            # tier's generation gate sheds audits of superseded batches.
            tier._structural.rebind(dyn.materialize())

    def _rewarm_landmarks_locked(self, index) -> None:
        """Recompute the landmark columns over the flipped graph with
        one flagship batch (caller holds the flip lock, so the acquired
        engine is overlay-synced to the new generation)."""
        engine = self._acquire_engine(self._route_width(index.k), "bfs")
        index.warm(
            lambda sources: engine.run(
                np.asarray(sources, dtype=np.int64), time_it=False
            )
        )

    def _sync_engine_overlay(self, engine) -> None:
        """Bring one engine's overlay tables up to the installed epoch
        (every acquire path funnels here, under the flip lock). Engines
        build with an EMPTY armed overlay; lazily-built ones (a degrade
        rung, a shadow rung, a non-primary kind's first query) would
        otherwise silently serve the base graph after a flip — the
        per-engine epoch stamp closes that hole, and re-arms every
        engine after a restage heals a torn flip."""
        if self._dynamic is None:
            return
        with self._flip_lock:
            epoch = self._overlay_epoch
            if getattr(engine, "_overlay_epoch", 0) == epoch:
                return
            if self._overlay_tables is not None:
                engine.set_overlay(self._overlay_tables)
            engine._overlay_epoch = epoch

    def _restage_overlay(self) -> None:
        """Re-install the CURRENT overlay from the dynamic graph's host
        truth — the heal after a confirmed torn flip (or staged-table
        corruption): the epoch bump forces every engine to re-adopt the
        true tables at its next acquire."""
        with self._flip_lock:
            if self._dynamic is None:
                return
            self._overlay_tables = self._dynamic.overlay_tables()
            self._overlay_epoch += 1

    def _compact_locked(self) -> None:  # requires-lock: _flip_lock
        """Fold the overlay into a new persisted base generation (caller
        holds the flip lock). On success the registry's graph is
        replaced by the VERIFIED loaded artifact and every resident
        engine drops (their ELL tables bake the old base; rebuilds are
        lazy). On ANY failure — the compactor dying at the
        ``compaction_crash`` site, or the new artifact failing its CRC
        at load (quarantined ``.corrupt``) — the previous generation
        stays served (base + overlay), orphaned uncommitted artifacts
        are quarantined, and the error propagates to the mutation
        caller."""
        dyn = self._dynamic
        store = self._gen_store
        t0 = time.monotonic()
        try:
            new_graph = dyn.compact(store)
        except Exception as exc:
            quarantined = store.quarantine_orphans()
            err = f"{type(exc).__name__}: {str(exc)[:300]}"
            self._log(
                f"compaction FAILED ({err}); rolled back — serving "
                f"continues on the previous generation"
                + (f"; quarantined {quarantined}" if quarantined else "")
            )
            rec = _obs.ACTIVE
            if rec is not None:
                # Flight-recorder trigger naming the quarantined
                # artifact(s): the run-up to a dead compactor is exactly
                # the window worth keeping.
                rec.event("compaction_failed", cat="serve.dynamic",
                          error=err, quarantined=quarantined)
                rec.flight_dump("compaction_failed")
            raise
        self._registry.add_graph(self._graph_key, new_graph)
        self._graph = new_graph
        dropped = self._registry.drop_graph_engines(self._graph_key)
        self._overlay_tables = None
        self._overlay_epoch += 1
        self._compactions += 1
        ms = (time.monotonic() - t0) * 1e3
        self._log(
            f"compacted into base generation {store.current()} in "
            f"{ms:.0f}ms ({dropped} resident engines dropped; rebuilds "
            f"are lazy)"
        )
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("compaction", cat="serve.dynamic",
                      base_generation=store.current(), dropped=dropped,
                      ms=round(ms, 1))

    def _on_stale_generation(self, *, query_id, kind, source,
                             served_generation, matched_generation,
                             staleness, detail) -> None:
        """A CONFIRMED over-bound stale answer (the staleness auditor's
        oracle replay). The suspect is the stale serving STATE — the old
        generation's tables still installed past a flip — not a rung:
        quarantine the old generation (flight dump naming its artifact),
        drop the answer cache's trust, and heal by restaging the true
        overlay onto every engine."""
        art = None
        if self._gen_store is not None:
            p = self._gen_store._path(matched_generation)
            art = p if os.path.exists(p) else None
        self._log(
            f"STALE GENERATION on query {query_id!r}: {detail[:300]} — "
            f"quarantining generation {matched_generation}"
            + (f" (artifact {art})" if art else "")
        )
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("stale_generation", cat="serve.dynamic",
                      query=query_id, kind=kind, source=source,
                      served_generation=served_generation,
                      stale_generation=matched_generation,
                      staleness=staleness,
                      artifact=art or f"generation {matched_generation} "
                                      f"(in-memory overlay state)",
                      detail=detail[:300])
            rec.flight_dump("stale_generation")
        if self._cache is not None:
            # Cache entries were admitted under the torn state's keys.
            self._cache.quarantine_generation(
                detail=f"stale generation {matched_generation} served "
                       f"as {served_generation}"
            )
        self._restage_overlay()

    def query(self, source, *, timeout: float | None = None,
              deadline_ms: float | None = None,
              want_distances: bool | None = None, kind: str = "bfs",
              k: int | None = None, target: int | None = None):
        """Blocking submit-and-wait convenience."""
        return self.submit(
            source, deadline_ms=deadline_ms, want_distances=want_distances,
            kind=kind, k=k, target=target,
        ).result(timeout)

    def statsz_extras(self) -> dict:
        """Service-level observations beyond the metrics counters —
        merged into both the statsz() snapshot and the JSONL server's
        periodic/final statsz lines."""
        cfg = self._mesh_cfg
        out = {
            "breaker_open": self._breaker.open_keys(),
            "breaker_opens": self._breaker.opens,
            "draining": self._draining,
            # Mesh failover state (ISSUE 12): the CURRENT device count
            # (shrinks on degrade, recovers on restore) and the
            # level-checkpointed resume audit when armed.
            "devices": cfg.devices,
        }
        if self._expand_impl != "xla":
            # Kernel-tier config echo (ISSUE 16): which expansion tier
            # every resident engine on this line was built with.
            out["expand_impl"] = self._expand_impl
        if self._cfg0.devices > 1:
            out["mesh_degraded"] = cfg.devices < self._cfg0.devices
        if cfg.resume_levels:
            from tpu_bfs.resilience.resume import cache_for_graph

            counts = cache_for_graph(self._graph).counts()
            out["query_resumes"] = counts["resumes"]
            out["resume_snapshots"] = counts["snapshots"]
        if self._integrity is not None:
            # Integrity-tier config echo (ISSUE 15): what the audit
            # counters on this line were produced under.
            out["audit"] = self._integrity.config_summary()
        if self._cache is not None:
            # Answer-cache residency echo (ISSUE 18): what the cache_*
            # counters on this line were produced under.
            out["cache"] = self._cache.config_summary()
        lm = self._landmarks
        if lm is not None:
            out["landmarks"] = lm.config_summary()
        if self._dynamic is not None:
            # Dynamic-graph echo (ISSUE 19): what generation the
            # counters on this line were served under, how full the
            # overlay is, and the staleness-audit verdict counters.
            with self._flip_lock:
                dyn = {
                    "generation": self._graph_generation,
                    "overlay_rows": self._dynamic.overlay_rows_used(),
                    "overlay_capacity": list(self._overlay_cap),
                    "flips": self._flips,
                    "compactions": self._compactions,
                }
            if self._staleness is not None:
                dyn["staleness"] = self._staleness.stats()
            out["dynamic"] = dyn
        store = self._registry.aot_store
        if store is not None:
            # AOT preheat visibility: artifact hits vs JIT fallbacks —
            # the cold-start A/B's statsz-side record (BENCHMARKS.md
            # "Cold start and preheat").
            out["aot"] = store.counts()
        if _faults.ACTIVE is not None:
            # Chaos-harness visibility: per-kind injected-fault counts so
            # a soak can check every scheduled fault actually landed.
            out["faults"] = _faults.ACTIVE.counts()
        return out

    def export_aot(self, store=None) -> dict:
        """Export every resident (warmed) engine's compiled programs
        into an artifact store (a path, an ArtifactStore, or None for
        the registry's own) — the ``--export-aot`` path: this warmed
        server populates the store a successor ``--preheat``s from.
        Returns ``{"programs": total exported, "engines": count}``."""
        out = self._registry.export_resident(store)
        return {
            "programs": sum(len(v) for v in out.values()),
            "engines": len(out),
        }

    def statsz(self) -> dict:
        out = self.metrics.snapshot(
            queue_depth=self._queue.depth(), lanes=self.lanes,
            extra=self.statsz_extras(),
        )
        out["ladder"] = self.width_ladder
        out["kinds"] = list(self._kinds)
        out["pipeline"] = self._pipe_q is not None
        resident = self._registry.resident()
        # None: a build holds the registry lock right now (resident() is
        # deliberately non-blocking — see registry.py).
        out["resident_engines"] = None if resident is None else len(resident)
        return out

    def metricz(self) -> str:
        """The one-shot /metricz observation: statsz()'s snapshot
        through the ONE renderer (ServeMetrics.prometheus_text). The
        JSONL server's periodic ``--metricz-out`` instead renders the
        exact snapshot dict its statsz line just printed — one
        observation, two formats, never disagreeing (this one-shot form
        takes its own fresh snapshot, deliberately without
        mark_interval so it cannot consume the periodic line's
        interval-QPS window)."""
        return self.metrics.prometheus_text(snapshot=self.statsz())

    # --- scheduler thread -------------------------------------------------

    def _route_width(self, n: int, kind: str = "bfs") -> int:
        """The narrowest ladder rung that fits ``n`` queries (the cap when
        nothing does — the caller splits and re-admits the tail), skipping
        rungs whose circuit breaker is open. Breaker keys are
        (width, devices[, kind]): this service's mesh span — a rung
        tripped by the single-chip path never blackholes the same width
        here, and a broken workload adapter never blackholes the width's
        bfs engine. When EVERY candidate is open the narrowest fitting
        rung is used anyway — the breaker routes around broken rungs, it
        must never wedge the service. A p2p query occupies TWO base
        lanes, so its demand doubles against the (base-lane) rung
        widths."""
        from tpu_bfs.serve.executor import breaker_key

        need = 2 * n if kind == "p2p" else n
        with self._width_lock:
            fits = [w for w in self._ladder if w >= need] or [self._max_lanes]
        devices = self._mesh_cfg.devices
        for w in fits:
            if self._breaker.allow(breaker_key(w, devices, kind)):
                return w
        return fits[0]

    def _acquire_engine(self, width: int, kind: str = "bfs"):
        """The warmed engine for ``width`` x ``kind`` (clamped to the
        degrade cap), retrying transient build failures and degrading on
        build-time OOM (an engine build allocates the packed tables, so
        it can OOM exactly like a dispatch)."""
        attempt = 0
        while True:
            width = min(width, self.lanes)
            try:
                engine = self._registry.get(self._spec(width, kind=kind))
                self._sync_engine_overlay(engine)
                return engine
            except Exception as exc:  # noqa: BLE001 — gated by classifiers
                if is_oom_failure(exc) and self._degrade(width):
                    continue
                devices = self._mesh_cfg.devices
                if devices > 1 and is_mesh_fault(exc):
                    # A mesh death during the BUILD/warm-up itself (the
                    # engine's first collectives run in the warm batch):
                    # degrade the mesh and rebuild on the smaller shape
                    # instead of retrying into the same dead collective.
                    COUNTERS.bump("mesh_faults")
                    self.metrics.record_mesh_fault()
                    rec = _obs.ACTIVE
                    if rec is not None:
                        rec.event("mesh_fault", cat="serve.mesh",
                                  site="engine_build", devices=devices,
                                  error=f"{type(exc).__name__}: "
                                        f"{str(exc)[:120]}")
                        rec.flight_dump("mesh_fault")
                    if self._degrade_mesh(devices, exc):
                        continue
                if is_transient_failure(exc) and attempt < self._max_retries:
                    attempt += 1
                    self.metrics.record_retry()
                    COUNTERS.bump("transient_retries")
                    self._log(
                        f"transient engine-build failure (attempt "
                        f"{attempt}/{self._max_retries}): {str(exc)[:200]}"
                    )
                    time.sleep(min(0.05 * attempt, 2.0))
                    continue
                raise

    def _degrade(self, at_width: int, requeued: int = 0) -> bool:
        """Shrink the ladder after an OOM at ``at_width`` (dispatch-,
        fetch-, or build-time); False at the floor. The new cap is one
        halving below the OOM'd width; every rung >= it is evicted from
        the registry FIRST — the narrower rebuild must not have to fit
        next to the dying engines' tables, and wider rungs than an OOM'd
        width can only OOM harder. ``requeued`` is the query count the
        caller is about to re-admit, for the metrics record."""
        with self._width_lock:
            # Halve onto the engine/mesh width grid (ladder_bounds):
            # quantized to the width quantum (4096 for the hybrid
            # engines), floored at the mesh-scaled floor — the single-chip
            # halving specialized to floor=quantum=32.
            new = max(
                self._width_floor,
                (at_width // 2) // self._width_quantum * self._width_quantum,
            )
            if new >= at_width:
                # At the floor: no narrower width exists. Wider rungs can
                # only OOM harder, so still collapse the ladder onto the
                # floor — routing must stop dispatching into guaranteed
                # OOMs even though this batch's queries resolve as errors.
                dying = [w for w in self._ladder if w > at_width]
                self._ladder = [w for w in self._ladder if w <= at_width]
                self._max_lanes = at_width
            else:
                dying = [w for w in self._ladder if w > new]
                self._ladder = [w for w in self._ladder if w <= new]
                if new not in self._ladder:
                    self._ladder.append(new)
                self._max_lanes = new
        for w in dying:
            # Every served kind's engine at a dying width frees: the
            # kinds share one width ladder, and a width that OOM'd for
            # one kind's tables leaves no headroom for another's.
            for kind in self._kinds:
                self._registry.evict(self._spec(w, kind=kind))
        if new >= at_width:
            if dying:
                self._log(
                    f"OOM at the {at_width}-lane floor: ladder collapsed "
                    f"to {at_width} (evicted {dying})"
                )
            return False
        self._log(f"OOM degrade: {at_width} -> {new} lanes (cap {new})")
        COUNTERS.bump("oom_degrades")
        self.metrics.record_oom_degrade(requeued)
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("oom_degrade", cat="serve.batch", from_width=at_width,
                      to_width=new, requeued=requeued)
        return True

    def _drop_resume_snapshots(self, queries) -> None:
        """Evict resume snapshots for queries that will never complete a
        resumable drive — terminally resolved (shed / floor errors) or
        re-admitted onto a config without resume (the single-chip
        floor). Without this their ~3x[V] host arrays (and spool files)
        would pin the per-graph cache for the process lifetime; dropping
        is always safe (resume degrades to starting over)."""
        if not self._cfg0.resume_levels:
            return
        from tpu_bfs.resilience.resume import cache_for_graph

        cache = cache_for_graph(self._graph)
        for q in queries:
            cache.drop(q.source)

    def _shed_over_budget(self, queries, at_width: int, why: str) -> list:
        """The bounded re-admission budget shared by the OOM and mesh
        failover paths: count this attempt on every query, resolve the
        over-budget ones with their attempt history, return the live
        rest."""
        live = []
        shed = 0
        for q in queries:
            q.requeues += 1
            q.attempt_widths.append(at_width)
            if q.requeues > self._max_requeues:
                if q.resolve_status(
                    STATUS_ERROR,
                    error=(
                        f"requeue budget exhausted: {q.requeues} {why} "
                        f"re-admissions (attempted widths "
                        f"{q.attempt_widths}) — every remaining rung is "
                        f"failing"
                    ),
                ):
                    shed += 1
            else:
                live.append(q)
        if shed:
            self._drop_resume_snapshots(
                [q for q in queries if q not in live]
            )
            self._log(f"shed {shed} queries at the requeue budget "
                      f"({self._max_requeues})")
            COUNTERS.bump("requeue_sheds", shed)
            self.metrics.record_requeue_shed(shed)
            self.metrics.record_errors(shed)
            rec = _obs.ACTIVE
            if rec is not None:
                # Flight-recorder trigger: queries dying at the requeue
                # budget mean every remaining rung is failing — exactly
                # the incident whose run-up the ring buffer holds.
                rec.event("requeue_shed", cat="serve.batch", shed=shed,
                          width=at_width)
                rec.flight_dump("requeue_shed")
        return live

    def _handle_batch_oom(self, queries, at_width: int, cause) -> None:
        """Degrade below the OOM'd width and re-admit, or resolve with
        explicit errors at the floor. Shared by the dispatch half (the
        scheduler thread) and the fetch half (the extraction worker).

        Re-admission carries a BOUNDED attempt budget (``max_requeues``):
        a query whose every attempted rung keeps OOMing resolves with an
        explicit error naming its attempt history instead of cycling
        through the ladder forever."""
        queries = self._shed_over_budget(queries, at_width, "OOM")
        if not queries:
            # Still account the degrade attempt below even when every
            # query shed: the rung DID fail, and routing must move off it.
            self._degrade(at_width)
            return
        if self._degrade(at_width, requeued=len(queries)):
            self._queue.requeue(queries)
            if self._queue.stopped:
                # The scheduler may already have drained and exited;
                # re-admitted queries must still resolve (exactly-once).
                n = 0
                for q in self._queue.next_batch(self._queue.cap, 0.0):
                    if q.resolve_status(
                        STATUS_SHUTDOWN, error="service closed"
                    ):
                        n += 1
                if n:
                    self.metrics.record_shutdown(n)
            return
        err = (
            f"out of memory at the minimum lane count "
            f"({at_width}): {str(cause)[:200]}"
        )
        self._log(err)
        self._drop_resume_snapshots(queries)
        n = 0
        for q in queries:
            if q.resolve_status(STATUS_ERROR, error=err):
                n += 1
        if n:
            self.metrics.record_errors(n)

    # --- mesh failover (ISSUE 12) -----------------------------------------

    def _degrade_mesh(self, at_devices: int, cause,
                      requeued: int = 0) -> bool:
        """Rebuild the serving ladder one MESH rung down after a mesh
        fault at ``at_devices`` (full -> half -> ... -> single chip).
        True when the service now serves from a smaller (or
        concurrently-degraded) mesh and re-admission makes sense; False
        only at the single-chip floor. The rebuild is an eviction plus
        a config swap: the next dispatch builds — or AOT-adopts, when
        the store holds the degraded shape's artifacts (utils/aot keys
        on ``devices``) — engines for the smaller mesh through the
        ordinary registry path, while the (width, devices) breaker keys
        the fault fed keep routing off the dead shape if anything
        re-offers it."""
        with self._width_lock:
            cfg = self._mesh_cfg
            if cfg.devices != at_devices:
                # Another batch already degraded (or restored) the mesh
                # out from under this fault: nothing to rebuild, but the
                # caller's queries still re-admit onto the live config.
                return True
            new_devices = next_mesh_rung(at_devices)
            if new_devices is None:
                return False
            new_cfg = cfg.degraded(new_devices)
            old_specs = [self._spec(w, cfg) for w in self._ladder]
            top = self._max_lanes  # keep any OOM degrade's width cap
            try:
                ladder = build_width_ladder(
                    top, self._ladder_arg, devices=new_devices,
                    engine=new_cfg.engine,
                )
            except ValueError:
                # The operator's explicit ladder does not fit the
                # degraded grid (e.g. an earlier OOM cap dropped its top
                # rung): re-derive geometrically rather than refuse to
                # fail over.
                ladder = build_width_ladder(
                    top, "auto", devices=new_devices, engine=new_cfg.engine,
                )
            self._mesh_cfg = new_cfg
            self._ladder = ladder
            self._max_lanes = ladder[-1]
            self._width_floor, self._width_quantum = ladder_bounds(
                top, devices=new_devices, engine=new_cfg.engine,
            )
        for spec in old_specs:
            # Free the dead mesh shape's device tables BEFORE the
            # degraded rebuilds (the OOM ladder's lesson).
            self._registry.evict(spec)
        COUNTERS.bump("mesh_degrades")
        self.metrics.record_mesh_degrade(requeued)
        self._log(
            f"MESH DEGRADE: {at_devices} -> {new_devices} devices "
            f"(engine {new_cfg.engine}, ladder {ladder}) after: "
            f"{str(cause)[:200]}"
        )
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("mesh_degrade", cat="serve.mesh",
                      from_devices=at_devices, to_devices=new_devices,
                      engine=new_cfg.engine, ladder=list(ladder),
                      requeued=requeued)
        return True

    def _handle_mesh_fault(self, queries, at_width: int, at_devices: int,
                           cause) -> None:
        """Degrade the MESH one rung and re-admit (the failover ladder),
        sharing the OOM path's bounded requeue budget — a query bouncing
        through repeated mesh faults resolves with its attempt history
        instead of cycling forever. Reached only from mesh-spanning
        batches (the executor classifies single-chip errors as plain
        transients), so the floor branch is a never-expected backstop."""
        queries = self._shed_over_budget(queries, at_width, "mesh-fault")
        if self._degrade_mesh(at_devices, cause, requeued=len(queries)):
            if not self._mesh_cfg.resume_levels:
                # Degraded onto a config without resume (the single-chip
                # floor): the re-admitted queries complete on an engine
                # that never drops snapshots — evict theirs now.
                self._drop_resume_snapshots(queries)
            if queries:
                self._queue.requeue(queries)
                if self._queue.stopped:
                    # Same exactly-once discipline as the OOM handler.
                    n = 0
                    for q in self._queue.next_batch(self._queue.cap, 0.0):
                        if q.resolve_status(
                            STATUS_SHUTDOWN, error="service closed"
                        ):
                            n += 1
                    if n:
                        self.metrics.record_shutdown(n)
            return
        err = (
            f"mesh fault with no smaller mesh to fail over to "
            f"({at_devices} devices): {str(cause)[:200]}"
        )
        self._log(err)
        self._drop_resume_snapshots(queries)
        n = 0
        for q in queries:
            if q.resolve_status(STATUS_ERROR, error=err):
                n += 1
        if n:
            self.metrics.record_errors(n)

    def mesh_restore(self, devices: int | None = None, *,
                     probe: bool = True) -> bool:
        """Promote a degraded service back onto a wider mesh: the widest
        original-ladder rung that heartbeats healthy (or exactly
        ``devices`` when given). Engines for the restored shape rebuild
        lazily through the registry on the next dispatch. False when the
        service is not degraded or nothing wider is healthy.
        ``probe=False`` skips the heartbeat when the caller just ran it
        (the background prober's path)."""
        from tpu_bfs.resilience.failover import degrade_ladder
        from tpu_bfs.resilience.probe import mesh_heartbeat

        target0 = self._cfg0.devices
        current = self._mesh_cfg.devices
        if current >= target0:
            return False
        rungs = degrade_ladder(target0)
        if devices and int(devices) not in rungs:
            # Only the halving-ladder rungs are valid restore targets:
            # the config walk below (and the ladders/breaker keys built
            # from it) is defined rung by rung, so an off-ladder count
            # would leave cfg.devices disagreeing with the width grid.
            self._log(
                f"mesh restore: {devices} is not a failover rung of the "
                f"{target0}-device mesh ({rungs}); refusing"
            )
            return False
        candidates = (
            [int(devices)] if devices
            else [d for d in rungs if d > current]
        )
        chosen = None
        for d in candidates:
            if not (current < d <= target0):
                continue
            if probe:
                try:
                    mesh_heartbeat(d)
                except Exception as exc:  # noqa: BLE001 — dead mesh expected
                    self._log(
                        f"mesh restore: {d}-device heartbeat failed "
                        f"({type(exc).__name__}: {str(exc)[:120]})"
                    )
                    continue
            chosen = d
            break
        if chosen is None:
            return False
        with self._width_lock:
            cfg = self._mesh_cfg
            if cfg.devices >= chosen:
                return False
            new_cfg = self._cfg0
            while new_cfg.devices > chosen:
                new_cfg = new_cfg.degraded(next_mesh_rung(new_cfg.devices))
            old_specs = [self._spec(w, cfg) for w in self._ladder]
            top = self._max_lanes  # an OOM cap survives the restore
            try:
                ladder = build_width_ladder(
                    top, self._ladder_arg, devices=chosen,
                    engine=new_cfg.engine,
                )
            except ValueError:
                ladder = build_width_ladder(
                    top, "auto", devices=chosen, engine=new_cfg.engine,
                )
            self._mesh_cfg = new_cfg
            self._ladder = ladder
            self._max_lanes = ladder[-1]
            self._width_floor, self._width_quantum = ladder_bounds(
                top, devices=chosen, engine=new_cfg.engine,
            )
        for spec in old_specs:
            self._registry.evict(spec)
        self._log(
            f"MESH RESTORE: {current} -> {chosen} devices "
            f"(engine {new_cfg.engine}, ladder {ladder})"
        )
        rec = _obs.ACTIVE
        if rec is not None:
            rec.event("mesh_restore", cat="serve.mesh",
                      from_devices=current, to_devices=chosen,
                      engine=new_cfg.engine)
        return True

    def _on_mesh_healthy(self, devices: int) -> None:
        """The background prober's promotion hook (it already ran the
        heartbeat on ``devices``)."""
        self.mesh_restore(devices, probe=False)

    # --- integrity tier (ISSUE 15) ----------------------------------------

    def _quarantine_rung(self, width: int, kind: str) -> None:
        """Corruption quarantine: evict the suspect rung (the rebuild
        clears wedged device state and recompiles) and force-open its
        (width, devices, kind) breaker so routing stops offering it until
        the cooldown's probe batch. The breaker's existing
        every-candidate-open backstop still applies — a single-rung
        service keeps serving through the rebuilt engine rather than
        wedging."""
        from tpu_bfs.serve.executor import breaker_key

        devices = self._mesh_cfg.devices
        self._registry.evict(self._spec(width, kind=kind))
        self._breaker.trip(breaker_key(width, devices, kind))

    def _escalate_mesh(self, devices: int, cause) -> None:
        """Repeated device-attributed corruption -> the PR 11 mesh
        degrade ladder: a mesh whose answers keep failing audits after
        rung rebuilds is a hardware incident, handled exactly like a
        mesh death (smaller mesh, re-warmed engines, probe-gated
        restore)."""
        if devices > 1:
            self._degrade_mesh(devices, cause)

    def _shadow_spec(self, width: int, kind: str) -> EngineSpec:
        """The DISJOINT engine config a shadow replay of a ``width``-lane
        ``kind`` answer runs on — a different compiled program, so a
        miscompiled or corrupted serving rung cannot re-produce its own
        wrong answer: another ladder rung when one exists, else the
        alternate exchange family on a mesh (a different collective
        program over the same devices), else a width off the ladder."""
        others = [w for w in self.width_ladder if w != width]
        if others:
            return self._spec(others[0], kind=kind)
        cfg = self._mesh_cfg
        if cfg.devices > 1:
            alt = {
                "": "allreduce", "ring": "allreduce", "allreduce": "ring",
            }.get(cfg.exchange) if cfg.engine == "dist2d" else {
                "": "sparse", "dense": "sparse", "sparse": "dense",
                "sliced": "dense",
            }.get(cfg.exchange)
            if alt:
                return dataclasses.replace(
                    self._spec(width, kind=kind), exchange=alt,
                    wire_pack=False, delta_bits=(), sieve=False,
                    predict=False,
                )
        floor, quantum = self._width_floor, self._width_quantum
        w2 = max(floor, (width // 2) // quantum * quantum)
        if w2 == width:
            w2 = width + quantum
        return self._spec(w2, kind=kind)

    def _acquire_shadow_engine(self, width: int, kind: str):
        """The shadow auditor's engine hook: warm (and keep resident) the
        disjoint rung through the ordinary registry path. The overlay
        sync matters here too — a shadow replay must run against the
        SERVED generation or every audited answer on a dynamic service
        would spuriously mismatch."""
        engine = self._registry.get(self._shadow_spec(width, kind))
        self._sync_engine_overlay(engine)
        return engine

    def flush_audits(self, timeout: float = 60.0) -> bool:
        """Barrier: every enqueued shadow audit processed (bench/smoke
        callers read the audit counters after this). True when armed and
        fully flushed, or trivially when disarmed."""
        if self._integrity is None:
            return True
        return self._integrity.flush(timeout)

    def _finish(self, pending) -> None:
        """The extraction half, wherever it runs (inline or worker).
        Never lets an exception escape with queries unresolved: an error
        the executor's classifier didn't translate (e.g. a device failure
        inside result extraction itself) still resolves the batch with
        explicit errors — the exactly-once bar."""
        with self._audit_quiesce:
            self._finishing += 1
        try:
            self._executor.finish_batch(pending)
            self._populate_cache(pending)
            if self._staleness is not None:
                # Generation-staleness arm (ISSUE 19): sampled oracle
                # replay against the generation ring, synchronous on
                # this worker, sealed internally like observe_batch.
                self._staleness.observe_batch(pending)
            tier = self._integrity
            if tier is not None:
                # The audit hook (ISSUE 15): every query of this batch is
                # already resolved, so audits add zero client latency;
                # observe_batch catches everything internally — an audit
                # bug must never turn a served batch into an incident.
                tier.observe_batch(pending)
        except OomRequeue as exc:
            width = pending.lanes
            # Drop the references to the OOM'd engine before the narrower
            # rebuild (the registry eviction in _degrade frees the tables
            # only once nothing else holds them).
            pending.engine = None
            pending.handle = None
            self._handle_batch_oom(exc.queries, width, exc.cause)
        except MeshFaultRequeue as exc:
            width = pending.lanes
            # Same reference discipline: the dead mesh shape's engines
            # evict during the degrade and their tables must free.
            pending.engine = None
            pending.handle = None
            self._handle_mesh_fault(exc.queries, width, exc.devices,
                                    exc.cause)
        except Exception as exc:  # noqa: BLE001 — resolve, never strand
            err = f"{type(exc).__name__}: {str(exc)[:300]}"
            self._log(f"batch extraction failed: {err}")
            rec = _obs.ACTIVE
            if rec is not None:
                # Flight-recorder trigger: an error the executor's
                # classifier did not translate is by definition the
                # unexpected kind — dump the run-up.
                rec.event("executor_error", cat="serve.batch",
                          batch=getattr(pending, "bid", None), error=err,
                          queries=[q.id for q in pending.queries])
                rec.flight_dump("executor_error")
            n = 0
            for q in pending.queries:
                if q.resolve_status(STATUS_ERROR, error=err):
                    n += 1  # idempotent: count only queries WE resolved
            if n:
                self.metrics.record_errors(n)
        finally:
            with self._audit_quiesce:
                self._finishing -= 1

    def _populate_cache(self, pending) -> None:
        """Cache-population half of the ISSUE 18 tier: AFTER a batch's
        queries resolved (extraction worker — the dispatch path never
        writes the cache), store every ok payload under the current
        generation. Best-effort by contract: a cache failure must never
        turn a served batch into an incident."""
        cache = self._cache
        if cache is None:
            return
        if (self._dynamic is not None
                and pending.generation != self.graph_generation):
            # A flip landed while this batch was in flight: its answers
            # are correct for the generation they were pinned to, but
            # caching them now would file generation G-1 payloads under
            # generation G keys — the exact staleness the key axis
            # exists to prevent. Stragglers just don't cache.
            return
        for q in pending.queries:
            try:
                r = q.result(0)
            except TimeoutError:  # a racing path owns this query
                continue
            if not r.ok:
                continue
            try:
                cache.put(
                    kind=r.kind, source=r.source, k=q.k, target=q.target,
                    want_distances=q.want_distances,
                    distances=r.distances, levels=r.levels,
                    reached=r.reached, extras=r.extras,
                    width=r.dispatched_lanes, devices=r.devices,
                )
            except Exception as exc:  # noqa: BLE001 — cache is best-effort
                self._log(
                    f"cache put failed (query {q.id!r}): "
                    f"{type(exc).__name__}: {str(exc)[:200]}"
                )

    def _extract_loop(self) -> None:
        while True:
            pending = self._pipe_q.get()
            if pending is None:
                return
            self._finish(pending)  # resolves its own failures
            # Don't pin the finished batch's engine/handle refs (device
            # tables) while idling in get() for the next one.
            pending = None  # noqa: F841 — releases device state

    def _loop(self) -> None:
        while True:
            batch = self._queue.next_batch(self.lanes, self._linger_s)
            if self._queue.stopped:
                n = 0
                for q in batch:
                    if q.resolve_status(STATUS_SHUTDOWN, error="service closed"):
                        n += 1
                if n:
                    self.metrics.record_shutdown(n)
                if not batch:
                    return
                continue
            now = time.monotonic()
            live = []
            expired = 0
            for q in batch:
                if q.expired(now):
                    if q.resolve_status(
                        STATUS_EXPIRED,
                        error="deadline expired before dispatch",
                    ):
                        expired += 1
                else:
                    live.append(q)
            if expired:
                self.metrics.record_expired(expired)
            if not live:
                continue
            try:
                # The batch is kind-uniform by construction (the queue
                # only coalesces same-batch-key queries, ISSUE 14).
                kind = getattr(live[0], "kind", "bfs")
                width = self._route_width(len(live), kind)
                rec = _obs.ACTIVE
                if rec is not None:
                    # The coalesce record: which queries formed this
                    # batch and which ladder rung routing picked — the
                    # span-chain link between admission and dispatch.
                    rec.event("coalesce", cat="serve.batch", n=len(live),
                              width=width, kind=kind,
                              queries=[q.id for q in live],
                              queue_depth=self._queue.depth())
                # The dispatch section runs under the flip lock (ISSUE
                # 19): generation flips happen BETWEEN batches, never
                # between an engine's overlay sync and its dispatch, so
                # the generation stamp below always names the tables the
                # batch actually traversed. Uncontended (and reentrant —
                # _acquire_engine syncs under it) on static services.
                with self._flip_lock:
                    engine = self._acquire_engine(width, kind)
                    if len(live) > engine.lanes:
                        # An OOM degraded the cap AFTER this batch was
                        # popped at the old one: serve what fits,
                        # re-admit the tail at the front (same contract
                        # as OomRequeue — degrade must never turn into
                        # error responses).
                        self._queue.requeue(live[engine.lanes:])
                        live = live[: engine.lanes]
                    pending = self._executor.dispatch_batch(engine, live)
                    if pending is not None:
                        pending.generation = self._graph_generation
                        pending.overlay_epoch = self._overlay_epoch
            except OomRequeue as exc:
                # Drop this frame's reference to the OOM'd engine before
                # the narrower rebuild (OomRequeue is only raised by
                # dispatch_batch, so `engine` is always bound here).
                # Ladder units (p2p's capacity counts pairs).
                width = getattr(engine, "ladder_lanes", engine.lanes)
                engine = None  # noqa: F841 — releases device tables
                self._handle_batch_oom(exc.queries, width, exc.cause)
                continue
            except MeshFaultRequeue as exc:
                width = getattr(engine, "ladder_lanes", engine.lanes)
                engine = None  # noqa: F841 — releases device tables
                self._handle_mesh_fault(exc.queries, width, exc.devices,
                                        exc.cause)
                continue
            except Exception as exc:  # noqa: BLE001 — engine build failed
                engine = None  # noqa: F841 — don't pin a half-built engine
                err = f"{type(exc).__name__}: {str(exc)[:300]}"
                self._log(f"engine unavailable: {err}")
                for q in live:
                    q.resolve_status(STATUS_ERROR, error=err)
                self.metrics.record_errors(len(live))
                continue
            if pending is not None:
                if self._pipe_q is not None:
                    # Bounded handoff: blocks when the extraction worker
                    # falls behind (pipeline_depth batches) — natural
                    # backpressure.
                    self._pipe_q.put(pending)
                else:
                    self._finish(pending)
            # This frame must not pin the batch's engine/device refs while
            # blocked in the next next_batch(): a fetch-OOM on the worker
            # may evict and rebuild narrower, and the dying tables have to
            # actually free (the same invariant the OomRequeue handler
            # documents).
            engine = pending = None  # noqa: F841 — releases device state


# --- JSONL protocol -------------------------------------------------------


def _encode_distances(d: np.ndarray) -> str:
    buf = io.BytesIO()
    np.save(buf, d)
    return base64.b64encode(buf.getvalue()).decode("ascii")


def decode_distances(payload: str) -> np.ndarray:
    """Inverse of the response's ``distances_npy`` field (client helper,
    also what the tests and `make serve-smoke` round-trip through)."""
    return np.load(io.BytesIO(base64.b64decode(payload)))


def result_to_response(r, *, with_distances: bool = True) -> dict:
    out = {"id": r.id, "source": r.source, "status": r.status}
    if getattr(r, "kind", "bfs") != "bfs":
        out["kind"] = r.kind
    if r.ok:
        out["levels"] = r.levels
        out["reached"] = r.reached
        out["latency_ms"] = round(r.latency_ms, 3)
        out["batch_lanes"] = r.batch_lanes
        out["dispatched_lanes"] = r.dispatched_lanes
        if r.devices is not None and r.devices > 1:
            # Mesh-served responses carry the traversal-rate record
            # (ISSUE 11): the mesh span, this query's edge count and
            # GTEPS under the batch time share, and its share of the
            # batch's modeled exchange bytes.
            out["devices"] = r.devices
            if r.edges is not None:
                out["edges"] = r.edges
            if r.gteps is not None:
                # 6 significant digits, not fixed decimals: CPU-mesh
                # figures live around 1e-5 GTEPS and must not round to 0.
                out["gteps"] = float(f"{r.gteps:.6g}")
            if r.wire_bytes is not None:
                out["wire_bytes"] = round(r.wire_bytes, 1)
        if getattr(r, "extras", None):
            # Kind-specific fields (ISSUE 14): khop's k, cc's component
            # record, p2p's target/distance/path, sssp's weighted flag.
            # Merged last-but-reserved: protocol keys always win.
            for key, val in r.extras.items():
                out.setdefault(key, val)
        if with_distances and r.distances is not None:
            out["distances_npy"] = _encode_distances(r.distances)
    else:
        out["error"] = r.error
        if r.latency_ms is not None:
            out["latency_ms"] = round(r.latency_ms, 3)
    return out


def build_arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="tpu-bfs-serve",
        description="Lane-batching BFS query server: JSONL requests "
        '({"id":..,"source":..}) on stdin, one JSON response line each '
        "on stdout; logs and periodic statsz on stderr.",
    )
    ap.add_argument("graph", help="graph file path or generator spec "
                    "(rmat:scale=20,ef=16 | random:n=...,m=...)")
    ap.add_argument("--engine", default="wide",
                    choices=["wide", "hybrid", "packed", "dist2d"],
                    help="serving engine (default wide; hybrid needs "
                    ">= 4096 lanes; dist2d is the 2D-partition mesh "
                    "engine and needs --devices >= 2)")
    ap.add_argument("--lanes", type=int, default=512,
                    help="maximum batch width = max queries per dispatch "
                    "(multiple of 32; default 512)")
    ap.add_argument("--ladder", default="auto",
                    help="adaptive dispatch widths: 'auto' (geometric "
                    "rungs down from --lanes, e.g. 32/128/512), 'off' "
                    "(single fixed width), or an explicit list like "
                    "'32,128,512'; each batch routes to the narrowest "
                    "rung that fits (default auto)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="extract results on the scheduler thread instead "
                    "of overlapping extraction with the next dispatch")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="max dispatched-but-unextracted batches in "
                    "flight (default 2)")
    ap.add_argument("--planes", type=int, default=DEFAULT_PLANES,
                    choices=range(1, 9), metavar="P",
                    help=f"bit-plane count (depth cap 2**P; default "
                    f"{DEFAULT_PLANES} — serving favors depth headroom)")
    ap.add_argument("--pull-gate", action="store_true",
                    help="frontier-aware pull gate (wide/hybrid engines)")
    ap.add_argument("--expand-impl", default="xla",
                    choices=("xla", "pallas"),
                    help="pull-expansion tier (default xla): 'pallas' "
                    "serves the fused bucketed-ELL kernel "
                    "(ops/ell_expand) on the wide/hybrid engines — "
                    "bit-identical answers, one HBM write per 128-row "
                    "tile per level; a program-key axis, so --preheat/"
                    "--export-aot stores keep tiers separate")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the engine over N devices (default 1): "
                    "wide/hybrid run the 1D-partition packed MS engines, "
                    "dist2d the 2D edge partition")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="explicit 2D mesh shape for --engine dist2d "
                    "(e.g. 2x4; default: the most-square factorization "
                    "of --devices)")
    ap.add_argument("--exchange", default="",
                    help="mesh exchange family (engine default when "
                    "omitted): dense|sparse (wide), dense|sparse|sliced "
                    "(hybrid), ring|allreduce|sparse (dist2d)")
    ap.add_argument("--wire-pack", action="store_true",
                    help="bit-packed exchange wire format (ISSUE 5; mesh "
                    "engines — a validated no-op on the packed MS "
                    "engines, whose lane words already carry 1 bit)")
    ap.add_argument("--sparse-delta", default=None, metavar="BITS",
                    help="delta-encoded sparse-exchange ids (ISSUE 7), "
                    "e.g. '8,16'; needs --exchange sparse")
    ap.add_argument("--sparse-sieve", action="store_true",
                    help="backward visited sieve on the dist2d sparse "
                    "row exchange (ISSUE 7 planner)")
    ap.add_argument("--sparse-predict", action="store_true",
                    help="history-predictive dense selection on the "
                    "dist2d sparse row exchange (ISSUE 7 planner)")
    ap.add_argument("--resume-levels", type=int, default=0, metavar="K",
                    help="level-checkpointed query resume (ISSUE 12, "
                    "--engine dist2d): snapshot each query's loop carry "
                    "every K levels so a mid-query mesh fault resumes "
                    "from the last intact level on the degraded mesh "
                    "(bounded recompute <= K); 0 disables (default)")
    ap.add_argument("--resume-dir", default=None, metavar="DIR",
                    help="also persist resume snapshots to DIR through "
                    "the CRC checkpoint machinery (atomic writes, "
                    "quarantine on corruption), so a restarted replica "
                    "can resume too; default: in-memory only (or the "
                    "TPU_BFS_RESUME_DIR env var)")
    ap.add_argument("--mesh-probe-interval-s", type=float, default=0.0,
                    metavar="S",
                    help="background mesh health probe cadence: a "
                    "degraded service (mesh failover, ISSUE 12) "
                    "heartbeats the wider mesh rungs every S seconds "
                    "and promotes back onto the widest healthy one; "
                    "0 disables (default)")
    ap.add_argument("--linger-ms", type=float, default=2.0,
                    help="max wait for batch fill before dispatching a "
                    "partial batch (default 2.0)")
    ap.add_argument("--queue-cap", type=int, default=1024,
                    help="admission queue bound; beyond it queries are "
                    "shed with status=rejected (default 1024)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="default per-query queue-wait deadline; 0 = none "
                    "(per-request \"deadline_ms\" overrides)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="transient-failure re-dispatches per batch "
                    "(default 2)")
    ap.add_argument("--max-requeues", type=int, default=8,
                    help="OOM re-admission budget per query; beyond it the "
                    "query resolves with an explicit error carrying its "
                    "attempt history (default 8)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="dispatch watchdog: a batch's device fetch "
                    "exceeding this is classified as transient and "
                    "re-dispatched instead of hanging the executor; 0 "
                    "disables (default 0)")
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive deterministic batch failures at one "
                    "width before its circuit breaker opens and routing "
                    "skips the rung (default 3)")
    ap.add_argument("--breaker-cooldown-ms", type=float, default=30000.0,
                    help="how long an open breaker waits before admitting "
                    "one half-open probe batch (default 30000)")
    ap.add_argument("--audit-rate", type=float, default=0.0, metavar="R",
                    help="online integrity tier (tpu_bfs/integrity): "
                    "replay this fraction of resolved queries on a "
                    "DISJOINT engine config (another ladder rung / the "
                    "alternate exchange family) and bit-compare; a "
                    "mismatch quarantines the serving rung (eviction + "
                    "forced-open breaker + flight dump) and repeated "
                    "device-attributed findings escalate to the mesh "
                    "degrade ladder. 0 disables (default); sampling is "
                    "deterministic in --audit-seed")
    ap.add_argument("--audit-structural", action="store_true",
                    help="structural tree checks on every served batch "
                    "(sampled lanes): the Graph500 edge-level property "
                    "for bfs, weighted relaxation for sssp, path "
                    "validity for p2p, consistency for cc/khop — the "
                    "validate.py predicates as fused device kernels")
    ap.add_argument("--audit-checksum", action="store_true",
                    help="wire checksums on the audited transfers "
                    "(integrity/wire.py): the host and device folds "
                    "over each audited distance row must agree, or the "
                    "transfer corrupted it (implies --audit-structural)")
    ap.add_argument("--audit-seed", type=int, default=0,
                    help="seed of the deterministic audit sampler "
                    "(default 0)")
    ap.add_argument("--cache-bytes", type=int, default=0, metavar="N",
                    help="answer cache (ISSUE 18): byte-budgeted LRU of "
                    "resolved payloads, CRC32-verified at every hit; "
                    "hits bypass the scheduler and stamp cache_hit "
                    "provenance. N is the payload budget in bytes "
                    "(e.g. 67108864 for 64 MB); 0 disables (default). "
                    "Single-flight dedupe of identical in-flight "
                    "queries is always on, independent of this knob")
    ap.add_argument("--landmarks", type=int, default=0, metavar="K",
                    help="landmark distance tier (ISSUE 18): warm K "
                    "high-degree landmark distance columns with one "
                    "flagship MS-BFS batch; p2p queries whose triangle "
                    "bounds meet answer exactly in microseconds, the "
                    "rest fall back to traversal. 0 disables (default); "
                    "needs p2p served (undirected graph)")
    ap.add_argument("--mutations", default=None, metavar="DxK", nargs="?",
                    const="default",
                    help="dynamic-graph serving (ISSUE 19): arm streaming "
                    "edge updates over a bounded overlay of D mutated "
                    "rows x K neighbor slots (bare --mutations uses "
                    "256x16). Requests {\"op\":\"mutate\",\"add\":[[u,v],"
                    "[u,v,w]...],\"remove\":[[u,v]...]} flip the served "
                    "generation atomically between batches; an "
                    "overflowing batch compacts into a new persisted "
                    "base generation first. Needs the single-chip wide "
                    "engine on an undirected graph; p2p drops from the "
                    "served kinds")
    ap.add_argument("--generation-dir", default=None, metavar="DIR",
                    help="persist compacted base generations here "
                    "through the CRC checkpoint machinery (atomic "
                    "writes, CURRENT pointer committed last, corrupt "
                    "artifacts quarantined .corrupt); default: a "
                    "service-owned temporary directory")
    ap.add_argument("--staleness-bound", type=int, default=0, metavar="N",
                    help="max generation flips a sampled served answer "
                    "may trail before the staleness auditor quarantines "
                    "the stale generation (needs --mutations and "
                    "--audit-rate > 0; default 0 — answers must match "
                    "the generation they were stamped with)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm a deterministic fault-injection schedule "
                    "(tpu_bfs/faults.py), e.g. 'seed=7:transient@dispatch:"
                    "p=0.05,oom@rung=512:n=2,slow_extract:ms=200'; "
                    "default: the TPU_BFS_FAULTS env var, else disabled")
    ap.add_argument("--kinds", default=None, metavar="K1,K2,...",
                    help="query kinds to serve (ISSUE 14): any of "
                    "bfs,sssp,cc,khop,p2p; default: every kind this "
                    "engine/graph supports (sssp needs a weighted "
                    "graph, p2p an undirected one; on a mesh the kinds "
                    "ride the wide/dist2d substrates). Requests carry "
                    "{\"kind\": ...} (+ "
                    "\"k\" for khop, \"target\" for p2p); unknown or "
                    "unserved kinds answer a structured per-id error")
    ap.add_argument("--no-distances", action="store_true",
                    help="metadata-only serving by default: responses "
                    "omit distances_npy AND the distance rows are never "
                    "pulled off the device (per-request "
                    "\"want_distances\" overrides)")
    ap.add_argument("--statsz-interval-s", type=float, default=None,
                    metavar="S",
                    help="seconds between periodic telemetry emissions "
                    "(the stderr statsz line AND the --metricz-out text, "
                    "which render the same snapshot); 0 disables. "
                    "Default: the TPU_BFS_STATSZ_INTERVAL env var, else "
                    "10")
    ap.add_argument("--statsz-every", type=float, default=None,
                    help="legacy alias of --statsz-interval-s")
    ap.add_argument("--obs", default=None, metavar="SPEC", nargs="?",
                    const="1",
                    help="arm the telemetry recorder (tpu_bfs/obs): span "
                    "tracing through the serve lifecycle, per-level "
                    "engine traces, and the flight recorder (auto-dumps "
                    "the last window on watchdog trip / breaker open / "
                    "requeue shed / executor error / SIGTERM drain). "
                    "SPEC e.g. 'dump_dir=/tmp/fr,window=60'; bare --obs "
                    "uses defaults; default: the TPU_BFS_OBS env var, "
                    "else disabled")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                    "whole serving session here at exit (implies --obs)")
    ap.add_argument("--metricz-out", default=None, metavar="PATH",
                    help="write the Prometheus-style /metricz text here, "
                    "atomically replaced every statsz interval and once "
                    "at exit")
    ap.add_argument("--xprof-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the "
                    "serving session into DIR, so device profiles line "
                    "up with the host spans in --trace-out")
    ap.add_argument("--registry-cap", type=int, default=4,
                    help="LRU bound on resident warmed engines (default 4, "
                    "raised automatically to fit the width ladder's rungs "
                    "plus one degrade slot)")
    ap.add_argument("--preheat", default=None, metavar="DIR",
                    help="AOT artifact store to preheat from (utils/aot): "
                    "every ladder rung whose exported programs are "
                    "present installs deserialized executables instead "
                    "of compiling, so the server reaches the READY line "
                    "without paying trace/lower/compile per rung; "
                    "stale or corrupt artifacts fall back to JIT "
                    "per program (corrupt files are quarantined)")
    ap.add_argument("--export-aot", default=None, metavar="DIR",
                    help="after warm-up, export every resident engine's "
                    "compiled programs into DIR so a successor started "
                    "with --preheat DIR skips the cold start (the warm "
                    "handoff pair — scripts/warm_handoff.py drives both "
                    "ends)")
    return ap


def _int_field(req: dict, name: str):
    """Strict integer request field (None when absent): exactly ints and
    integral floats — bool is an int subclass and json floats arrive for
    "7.0"; a lenient int() would silently truncate 7.9 to vertex 7."""
    val = req.get(name)
    if val is None:
        return None
    if isinstance(val, bool) or not isinstance(val, (int, float)):
        raise TypeError(f"{name} must be an integer, got {val!r}")
    if isinstance(val, float):
        if not val.is_integer():
            raise TypeError(f"{name} must be an integer, got {val!r}")
        val = int(val)
    return val


def _parse_request_line(line: str):
    """Parse one JSONL request into (id, source, deadline_ms, want,
    kind, k, target). Raises on ANYTHING malformed — the caller answers
    with a structured error line; nothing a client sends may kill the
    reader loop. ``kind`` is only TYPE-checked here (a string); the
    unknown-kind / kind-vs-engine / missing-parameter checks live in
    ``BfsService.submit`` so the in-process API and the wire agree on
    one contract (README protocol grammar)."""
    req = json.loads(line)
    if not isinstance(req, dict):
        raise TypeError("request must be a JSON object")
    qid = req.get("id")
    try:
        if "source" not in req:
            raise KeyError("source")
        source = _int_field(req, "source")
        if source is None:  # JSON null — absent-but-present
            raise TypeError(
                f"source must be an integer vertex id, got "
                f"{req['source']!r}"
            )
        kind = req.get("kind")
        if kind is not None and not isinstance(kind, str):
            raise TypeError(f"kind must be a string, got {kind!r}")
        k = _int_field(req, "k")
        target = _int_field(req, "target")
        ddl = req.get("deadline_ms")
        if ddl is not None:
            # Same strictness as source: float(True) == 1.0 and
            # float("100") == 100.0 would silently accept a client bug
            # and surface it later as a bogus deadline expiry.
            if isinstance(ddl, bool) or not isinstance(ddl, (int, float)):
                raise TypeError(
                    f"deadline_ms must be a JSON number, got {ddl!r}"
                )
            ddl = float(ddl)
        want = req.get("want_distances")
        if want is not None and not isinstance(want, bool):
            # bool("false") is True — a lenient coercion would silently
            # invert the client's intent.
            raise TypeError(
                f"want_distances must be a JSON boolean, got {want!r}"
            )
    except Exception as exc:
        exc._request_id = qid  # the error line must still correlate
        raise
    return qid, source, ddl, want, kind, k, target


DEFAULT_STATSZ_INTERVAL_S = 10.0


def resolve_statsz_interval(args, *, env=None) -> float:
    """The periodic-emission interval precedence (ISSUE 6 satellite):
    ``--statsz-interval-s`` wins, then the legacy ``--statsz-every``
    alias, then ``TPU_BFS_STATSZ_INTERVAL``, then 10 s. One resolved
    value drives BOTH renderings of the snapshot — the stderr statsz
    line and the ``--metricz-out`` text — so they stay on one cadence.
    An unparsable env value falls back to the default (a typo'd fleet
    variable must not kill the periodic line)."""
    interval = getattr(args, "statsz_interval_s", None)
    if interval is None:
        interval = getattr(args, "statsz_every", None)
    if interval is None:
        env_iv = (env if env is not None
                  else os.environ.get("TPU_BFS_STATSZ_INTERVAL", "")).strip()
        try:
            interval = float(env_iv) if env_iv else DEFAULT_STATSZ_INTERVAL_S
        except ValueError:
            interval = DEFAULT_STATSZ_INTERVAL_S
    return float(interval)


def run_server(args, stdin=None, stdout=None, stderr=None,
               registry=None) -> int:
    """The JSONL loop, parameterized over streams (and optionally a
    shared registry) so tests run it in-process. Reads requests until
    EOF, then drains outstanding responses, prints a final statsz line,
    and closes the service.

    LIFECYCLE (robustness issue): requests are read on a dedicated
    reader thread; the main thread waits for either the reader's normal
    EOF drain or a SIGTERM/SIGINT. A signal triggers a GRACEFUL DRAIN —
    admission stops (late submits shed REJECTED), in-flight batches
    flush, still-queued queries resolve as SHUTDOWN, every resolution is
    emitted, and the final statsz line lands — instead of the default
    die-mid-batch. Handlers are only installed when running on the main
    thread and are restored on exit, so in-process test runs are
    unaffected."""
    stdin = sys.stdin if stdin is None else stdin
    stdout = sys.stdout if stdout is None else stdout
    stderr = sys.stderr if stderr is None else stderr

    def log(msg: str) -> None:
        print(f"# {msg}", file=stderr, flush=True)

    sched = _faults.arm_from_spec_or_env(args.faults)
    if sched is not None:
        log(f"fault-injection schedule ARMED: {sched.to_spec()}")

    # Telemetry arming: --obs SPEC wins, else TPU_BFS_OBS; --trace-out
    # needs a recorder, so it arms one with defaults when nothing else
    # did. The recorder is armed BEFORE the service so registry
    # build/warm spans land in the trace (cold start is the expensive
    # part worth seeing).
    recorder = _obs.arm_for_run(getattr(args, "obs", None),
                                getattr(args, "trace_out", None))
    if recorder is not None:
        log(f"telemetry recorder ARMED (capacity "
            f"{recorder.capacity}, flight window "
            f"{recorder.window_s:.0f}s, dump dir {recorder.dump_dir!r})")
    statsz_interval = resolve_statsz_interval(args)
    xprof = getattr(args, "xprof_dir", None)
    if xprof:
        import jax

        jax.profiler.start_trace(xprof)
        log(f"jax.profiler trace started -> {xprof}")

    mesh_shape = ()
    if getattr(args, "mesh", None):
        try:
            r, c = (int(x) for x in str(args.mesh).lower().split("x"))
            mesh_shape = (r, c)
        except ValueError:
            raise SystemExit(
                f"--mesh must look like RxC (e.g. 2x4), got {args.mesh!r}"
            ) from None
    delta_raw = getattr(args, "sparse_delta", None)
    delta_bits = ()
    if delta_raw:
        try:
            delta_bits = tuple(
                int(b) for b in str(delta_raw).replace(",", " ").split()
            )
        except ValueError:
            raise SystemExit(
                f"--sparse-delta must be comma-separated bit widths "
                f"(e.g. 8,16), got {delta_raw!r}"
            ) from None
    resume_dir = getattr(args, "resume_dir", None)
    if resume_dir:
        from tpu_bfs.resilience.resume import set_default_dir

        set_default_dir(resume_dir)
    dyn_raw = getattr(args, "mutations", None)
    dynamic = ()
    if dyn_raw:
        if dyn_raw == "default":
            dynamic = True
        else:
            try:
                d, k = (int(x) for x in str(dyn_raw).lower().split("x"))
                dynamic = (d, k)
            except ValueError:
                raise SystemExit(
                    f"--mutations must look like DxK (e.g. 256x16), "
                    f"got {dyn_raw!r}"
                ) from None
    service = BfsService(
        args.graph,
        engine=args.engine,
        lanes=args.lanes,
        planes=args.planes,
        pull_gate=args.pull_gate,
        expand_impl=getattr(args, "expand_impl", "xla"),
        devices=args.devices,
        exchange=getattr(args, "exchange", "") or "",
        wire_pack=getattr(args, "wire_pack", False),
        delta_bits=delta_bits,
        sieve=getattr(args, "sparse_sieve", False),
        predict=getattr(args, "sparse_predict", False),
        mesh_shape=mesh_shape,
        resume_levels=getattr(args, "resume_levels", 0),
        mesh_probe_interval_s=getattr(args, "mesh_probe_interval_s", 0.0),
        width_ladder=args.ladder,
        pipeline=not args.no_pipeline,
        pipeline_depth=args.pipeline_depth,
        linger_ms=args.linger_ms,
        queue_cap=args.queue_cap,
        deadline_ms=args.deadline_ms,
        max_retries=args.max_retries,
        max_requeues=args.max_requeues,
        watchdog_ms=args.watchdog_ms,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_ms=args.breaker_cooldown_ms,
        audit_rate=getattr(args, "audit_rate", 0.0),
        audit_structural=getattr(args, "audit_structural", False),
        audit_checksum=getattr(args, "audit_checksum", False),
        audit_seed=getattr(args, "audit_seed", 0),
        cache_bytes=getattr(args, "cache_bytes", 0),
        landmarks=getattr(args, "landmarks", 0),
        dynamic=dynamic,
        generation_dir=getattr(args, "generation_dir", None),
        staleness_bound=getattr(args, "staleness_bound", 0),
        distances=not args.no_distances,
        kinds=(
            tuple(t for t in str(args.kinds).replace(",", " ").split())
            if getattr(args, "kinds", None) else None
        ),
        registry=registry,
        registry_capacity=args.registry_cap,
        aot_dir=getattr(args, "preheat", None),
        log=log,
    )
    export_aot = getattr(args, "export_aot", None)
    if export_aot:
        # Populate the artifact store from THIS warmed server (every
        # ladder rung is resident and compiled by now) so a successor
        # started with --preheat skips the cold start entirely.
        try:
            counts = service.export_aot(export_aot)
            log(f"aot export -> {export_aot}: {counts['programs']} "
                f"programs from {counts['engines']} engines")
        except Exception as exc:  # noqa: BLE001 — export is an optimization
            log(f"aot export failed ({exc!r}); continuing without")
    # The readiness line (stderr, like every non-protocol line): every
    # ladder rung is warmed — from artifacts when preheating — and the
    # service will now take traffic. The warm-handoff driver
    # (scripts/warm_handoff.py) keys the old server's SIGTERM on this.
    store = service._registry.aot_store
    ready_extra = ""
    if store is not None:
        c = store.counts()
        ready_extra = (f" aot_hits={c['aot_hits']}"
                       f" aot_fallbacks={c['aot_fallbacks']}")
    log(f"READY engine={args.engine} lanes={args.lanes} "
        f"ladder={service.width_ladder} "
        f"kinds={','.join(service.kinds)}{ready_extra}")
    out_lock = threading.Lock()
    outstanding = [0]
    drained = threading.Condition(out_lock)

    def emit(resp: dict) -> None:
        # Never let a dead client pipe propagate into the resolver
        # threads (a callback exception would kill the scheduler or the
        # extraction worker mid-drain).
        try:
            with out_lock:
                stdout.write(json.dumps(resp) + "\n")
                stdout.flush()
        except (OSError, ValueError) as exc:
            log(f"response emit failed ({exc!r}); dropping line")

    def on_done(q: PendingQuery) -> None:
        emit(result_to_response(q.result()))
        with drained:
            outstanding[0] -= 1
            if outstanding[0] == 0:
                drained.notify_all()

    stop = threading.Event()  # reader EOF-drain complete
    got_signal = [None]

    def on_signal(signum, frame) -> None:
        # ONLY plain attribute stores here: the handler runs on the main
        # thread between bytecodes, possibly while the interrupted frame
        # holds the stop-Event's internal (non-reentrant) lock inside
        # stop.wait() — calling stop.set() from the handler could
        # deadlock the exact shutdown it implements. The main loop polls
        # got_signal every wait timeout instead.
        got_signal[0] = signum
        service.drain()  # stop admission immediately (a plain bool store)

    old_handlers = {}
    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                old_handlers[sig] = signal.signal(sig, on_signal)
            except (ValueError, OSError):  # exotic embedding: skip
                pass

    metricz_out = getattr(args, "metricz_out", None)

    def emit_telemetry() -> None:
        """ONE observation, two renderings: the stderr statsz line and
        the --metricz-out text are the same snapshot dict, so they can
        never disagree — and the interval-QPS window is consumed exactly
        once per cycle (a second snapshot microseconds later would read
        a near-empty interval and export garbage interval_qps)."""
        snap = service.metrics.snapshot(
            mark_interval=True, queue_depth=service._queue.depth(),
            lanes=service.lanes, extra=service.statsz_extras(),
        )
        print(service.metrics.statsz_line(snapshot=snap), file=stderr,
              flush=True)
        if not metricz_out:
            return
        from tpu_bfs.obs.exporters import write_metricz

        try:
            write_metricz(service.metrics.prometheus_text(snapshot=snap),
                          metricz_out)
        except OSError as exc:
            log(f"metricz write failed ({exc!r})")

    stop_statsz = threading.Event()
    statsz_thread = None
    if statsz_interval > 0:
        def statsz_loop() -> None:
            while not stop_statsz.wait(statsz_interval):
                emit_telemetry()

        statsz_thread = threading.Thread(
            target=statsz_loop, name="bfs-serve-statsz", daemon=True
        )
        statsz_thread.start()

    log(f"serving {args.graph!r}: engine={args.engine} lanes={args.lanes} "
        f"ladder={service.width_ladder} "
        f"pipeline={not args.no_pipeline} linger={args.linger_ms}ms "
        f"queue_cap={args.queue_cap}")

    def mutate_line(line: str) -> bool:
        """The {"op": "mutate"} request (ISSUE 19), handled ON the
        reader thread — mutations serialize with each other for free
        and apply_edge_updates flips between dispatched batches via the
        flip lock. Returns False when the line is not a mutate op (it
        falls through to the query path). Every failure answers a
        structured line; nothing here may kill the reader."""
        try:
            req = json.loads(line)
        except Exception:  # noqa: BLE001 — the query path answers it
            return False
        if not (isinstance(req, dict) and req.get("op") == "mutate"):
            return False
        qid = req.get("id")
        try:
            add = req.get("add") or ()
            remove = req.get("remove") or ()
            if not isinstance(add, (list, tuple)) or not isinstance(
                    remove, (list, tuple)):
                raise TypeError(
                    "add/remove must be arrays of [u, v] / [u, v, w]"
                )
            out = service.apply_edge_updates(
                add=[tuple(int(x) for x in e) for e in add],
                remove=[tuple(int(x) for x in e) for e in remove],
            )
            emit({"id": qid, "op": "mutate", "ok": True, **out})
        except Exception as exc:  # noqa: BLE001 — answered, never fatal
            emit({
                "id": qid, "op": "mutate", "ok": False,
                "error": f"{type(exc).__name__}: {str(exc)[:300]}",
            })
        return True

    def reader() -> None:
        try:
            for line in stdin:
                line = line.strip()
                if not line:
                    continue
                if '"op"' in line and mutate_line(line):
                    continue
                qid = None
                try:
                    try:
                        (qid, source, ddl, want,
                         kind, k, target) = _parse_request_line(line)
                    except Exception as exc:  # noqa: BLE001 — answered, never fatal
                        # Includes RecursionError from hostile nesting and
                        # any parser surprise: one bad line must get one
                        # structured error response, never kill the loop.
                        emit({
                            "id": getattr(exc, "_request_id", None),
                            "status": STATUS_ERROR,
                            "error": f"bad request: {exc!r}",
                        })
                        continue
                    with drained:
                        outstanding[0] += 1
                    try:
                        service.submit(
                            source, id=qid, deadline_ms=ddl,
                            want_distances=want,
                            # None = absent = bfs; an empty or unknown
                            # string flows through to submit's structured
                            # unknown-kind error (never silently bfs).
                            kind="bfs" if kind is None else kind,
                            k=k, target=target,
                        ).add_done_callback(on_done)
                    except Exception:
                        # No response will ever fire for this query: the
                        # increment must be unwound or the EOF drain
                        # waits on it forever.
                        with drained:
                            outstanding[0] -= 1
                            if outstanding[0] == 0:
                                drained.notify_all()
                        raise
                except Exception as exc:  # noqa: BLE001 — keep reading
                    log(f"request line dropped ({exc!r})")
            # EOF: wait for every outstanding response, then finish.
            with drained:
                while outstanding[0] > 0 and not stop.is_set():
                    drained.wait(0.2)
        finally:
            stop.set()
            with drained:
                drained.notify_all()

    reader_t = threading.Thread(
        target=reader, name="bfs-serve-reader", daemon=True
    )
    try:
        reader_t.start()
        # Main thread parks here so signal handlers can run promptly;
        # each wait timeout polls the handler's signal flag.
        while not stop.wait(0.2):
            if got_signal[0] is not None:
                break
        if got_signal[0] is not None:
            name = signal.Signals(got_signal[0]).name
            log(f"{name} received: draining — admission stopped, flushing "
                f"in-flight batches, resolving queued queries as shutdown")
            rec = _obs.ACTIVE
            if rec is not None:
                # Flight-recorder trigger: the drain snapshot is the last
                # chance to capture what the dying process was doing.
                rec.event("signal_drain", cat="serve.lifecycle", signal=name)
                rec.flight_dump(f"{name.lower()}_drain")
    finally:
        # Drain to completion: close() flushes in-flight batches and
        # resolves still-queued queries as SHUTDOWN; their callbacks emit
        # the response lines, so wait for outstanding to hit zero (with a
        # hard bound — a graceful drain must never become a hang).
        service.close()
        deadline = time.monotonic() + 30.0
        with drained:
            while outstanding[0] > 0 and time.monotonic() < deadline:
                drained.wait(0.2)
            if outstanding[0] > 0:
                log(f"drain timeout: {outstanding[0]} responses unemitted")
        stop_statsz.set()
        if statsz_thread is not None:
            # Joined, not left to interpreter exit: a daemon thread still
            # waking at finalization aborted the process (rc 134).
            statsz_thread.join()
        emit_telemetry()  # the final statsz line + --metricz-out text
        if xprof:
            import jax

            try:
                jax.profiler.stop_trace()
                log(f"jax.profiler trace stopped -> {xprof}")
            except Exception as exc:  # noqa: BLE001 — exit path, best effort
                log(f"jax.profiler stop failed ({exc!r})")
        trace_out = getattr(args, "trace_out", None)
        if trace_out and recorder is not None:
            from tpu_bfs.obs.exporters import write_perfetto

            # Engine level tracks ride along when any resident engine
            # recorded a per-level trace (armed runs only).
            level_traces = []
            for spec, eng in service._registry.resident_engines():
                trace = getattr(eng, "last_run_trace", None)
                if trace:
                    # Mesh-labeled tracks: a dist rung's trace names its
                    # device span so single-chip and mesh rungs of the
                    # same width stay distinguishable in the viewer.
                    label = f"{spec.engine}/w{spec.lanes}"
                    if spec.devices > 1:
                        label += f"/d{spec.devices}"
                    level_traces.append((label, trace))
            try:
                write_perfetto(
                    recorder.snapshot(), trace_out, t0=recorder.t0,
                    level_traces=level_traces,
                    meta={"tool": "tpu-bfs-serve", "graph": args.graph},
                )
                log(f"trace written -> {trace_out}")
            except OSError as exc:
                log(f"trace write failed ({exc!r})")
        for sig, handler in old_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass
    return 0


def main(argv=None) -> int:
    return run_server(build_arg_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
