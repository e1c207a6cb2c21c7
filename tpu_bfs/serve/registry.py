"""Warm-engine registry: graphs loaded once, engines built-and-warmed once.

A fresh engine build costs an ELL/tile build plus an XLA compile of the
packed level loop (~20-40 s first-compile on chip); a server cannot pay
that per query. The registry keys resident engines by
``(graph_key, engine, lanes, pull_gate, devices, exchange config,
mesh_shape)`` — every axis that changes the compiled program — warms
each build with one full-width
batch so serving dispatches never see the compile, and bounds residency
with an LRU (each resident engine holds its packed tables in HBM, so
"cache them all" is not an option).

``enable_compile_cache`` (utils/compile_cache.py) is armed at registry
construction: the warm-up run populates the persistent XLA cache, so
even an evicted-and-rebuilt engine (or a restarted server) pays a disk
hit, not a recompile.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs
from tpu_bfs.utils.compile_cache import enable_compile_cache

ENGINE_KINDS = ("wide", "hybrid", "packed", "dist2d")

# The distributed hybrid's dense MXU kernel runs on every shard, so its
# serving widths come in whole 4096-lane steps (dist_msbfs_hybrid.LANES;
# the single-chip hybrid shares the quantum). Kept as a literal here so
# spec validation never imports the engine modules (they stay lazy).
HYBRID_LANE_QUANTUM = 4096

# Per-engine legal exchange families ("" = the engine's own default).
# Mesh-only: single-chip engines run no exchange at all.
ENGINE_EXCHANGES = {
    "wide": ("", "dense", "sparse"),
    "hybrid": ("", "dense", "sparse", "sliced"),
    "dist2d": ("", "ring", "allreduce", "sparse"),
    "packed": ("",),
}

# Kind-specific mesh exchange families (ISSUE 20): a kind whose
# distributed engine is NOT the engine family's own loop overrides the
# family's exchange list. sssp on a mesh runs the (min, +) delta-stepping
# engine (parallel/dist_sssp.py) whose value exchanges are
# ring/allreduce/sparse — not the wide family's OR row gathers.
KIND_EXCHANGES = {
    "sssp": ("", "ring", "allreduce", "sparse"),
}

# Serving engines default to 8 planes (254-level depth cap) where the
# one-shot CLI defaults to 5 (32 levels): a server answers arbitrary
# sources on a long-lived process, and one high-eccentricity query
# truncating a whole batch into error responses costs far more than the
# 3 extra planes' HBM.
DEFAULT_PLANES = 8


def mesh_shape_2d(devices: int, mesh_shape=()) -> tuple[int, int]:
    """The (rows, cols) factorization the 2D engine serves on: an
    explicit ``mesh_shape`` wins; otherwise the most-square factorization
    of ``devices`` (Buluç & Madduri's 2D decomposition wants R ~ C — both
    per-chip collective terms then shrink as O(vp/sqrt(P)))."""
    if mesh_shape:
        r, c = int(mesh_shape[0]), int(mesh_shape[1])
        if r < 1 or c < 1 or r * c != devices:
            raise ValueError(
                f"mesh_shape {r}x{c} does not cover {devices} devices"
            )
        return r, c
    r = int(np.sqrt(devices))
    while devices % r:
        r -= 1
    return r, devices // r


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One resident engine's identity — everything that changes the
    compiled program or its tables. The mesh axes (``devices``,
    ``mesh_shape``) and the exchange configuration (``exchange``,
    ``wire_pack``, ``delta_bits``, ``sieve``, ``predict``) are key fields
    too: each changes the compiled collective program, so two configs can
    never alias one resident engine (or one AOT artifact — utils/aot.py
    keys off the same axes)."""

    graph_key: str
    engine: str = "wide"
    lanes: int = 512
    planes: int = DEFAULT_PLANES
    pull_gate: bool = False
    #: ISSUE 16 expansion tier: "xla" (the fori-loop form XLA fuses) or
    #: "pallas" (the fused gather-combine kernel, ops/ell_expand.py).
    #: A key field — the tiers compile different programs over different
    #: table sets — carried by utils/aot.program_key only when
    #: non-default, so existing stores stay adoptable.
    expand_impl: str = "xla"
    devices: int = 1
    #: Query kind this residency serves (ISSUE 14): "bfs" (the base
    #: engines themselves) or a tpu_bfs/workloads adapter over them
    #: (sssp/cc/khop/p2p). A key field — per-kind engines hold
    #: different device state (SSSP's weighted tiles, CC's cached
    #: index) and answer through different programs, so kinds never
    #: alias one resident engine; utils/aot.program_key carries the
    #: axis the same way (only when non-default, so existing stores
    #: stay adoptable).
    kind: str = "bfs"
    #: exchange family ("" = engine default): wide/hybrid row gathers
    #: (dense/sparse; hybrid also 'sliced'), dist2d row exchange
    #: (ring/allreduce/sparse). Mesh engines only.
    exchange: str = ""
    #: ISSUE 5 bit-packed wire format (mesh engines; validated no-op on
    #: the packed MS engines whose lane words already carry 1 bit).
    wire_pack: bool = False
    #: ISSUE 7 planner pieces (sparse exchanges only; sieve/predict are
    #: the 1D/2D planner's — the MS row gathers take delta_bits alone).
    delta_bits: tuple = ()
    sieve: bool = False
    predict: bool = False
    #: explicit (rows, cols) for the dist2d engine; () = most-square.
    mesh_shape: tuple = ()
    #: ISSUE 19 dynamic graphs: the served graph's version, bumped on
    #: every applied mutation batch. A KEY field — post-flip queries
    #: must never alias a pre-flip residency by key — but NOT a compiled
    #: axis: the registry REKEYS the resident engine across a flip
    #: (:meth:`EngineRegistry.rekey_generation`) instead of rebuilding,
    #: because only the overlay table VALUES change; utils/aot.program_key
    #: omits it for the same reason.
    graph_generation: int = 0
    #: ISSUE 19 delta-overlay capacity ``(rows, kcap)``; () = static
    #: graph. A key AND compiled axis: the overlay engine's core carries
    #: the delta fold over fixed-shape tables sized by this.
    overlay: tuple = ()
    #: ISSUE 12 level-checkpointed resume cadence K (dist2d only; 0 =
    #: off): the serving loop runs K levels per chunk and snapshots its
    #: carry at each boundary (tpu_bfs/resilience/resume), so a
    #: mid-query mesh fault resumes from the last intact level on the
    #: degraded mesh. NOT part of the compiled program (the chunks
    #: re-drive one compiled loop with new level bounds), but a key
    #: field so a resuming and a non-resuming service never alias one
    #: resident engine; utils/aot.program_key deliberately omits it, so
    #: both adopt the same artifacts.
    resume_levels: int = 0

    def __post_init__(self):
        # Hashability + registry-key stability: list-valued knobs arrive
        # from argparse/env parsing; freeze them.
        object.__setattr__(self, "delta_bits", tuple(self.delta_bits))
        object.__setattr__(self, "mesh_shape", tuple(self.mesh_shape))
        object.__setattr__(
            self, "overlay", tuple(int(x) for x in self.overlay)
        )

    def validate(self) -> None:
        if self.engine not in ENGINE_KINDS:
            raise ValueError(
                f"engine must be one of {ENGINE_KINDS}, got {self.engine!r}"
            )
        if self.lanes % 32 or self.lanes < 32:
            raise ValueError(
                f"lanes must be a multiple of 32 >= 32, got {self.lanes}"
            )
        if self.engine == "hybrid" and self.lanes % HYBRID_LANE_QUANTUM:
            raise ValueError(
                f"the hybrid engine's dense kernel takes whole "
                f"{HYBRID_LANE_QUANTUM}-lane steps, got {self.lanes}"
            )
        if self.engine == "packed" and self.pull_gate:
            raise ValueError(
                "pull_gate applies to the wide/hybrid engines (the packed "
                "engine keeps no settled-mask state)"
            )
        if self.engine == "packed" and self.devices > 1:
            raise ValueError("the packed engine is single-device")
        if self.expand_impl not in ("xla", "pallas"):
            raise ValueError(
                "expand_impl must be one of ('xla', 'pallas'), got "
                f"{self.expand_impl!r}"
            )
        if self.expand_impl != "xla" and self.engine in ("packed", "dist2d"):
            raise ValueError(
                "expand_impl='pallas' fuses the bucketed-ELL pull "
                "expansion of the wide/hybrid engines; the packed and "
                "dist2d engines run no ELL pull loop to lower"
            )
        if self.engine == "dist2d" and self.devices < 2:
            raise ValueError(
                "the dist2d engine is the 2D-partition mesh path; "
                "use devices >= 2 (single-chip serving has no exchange "
                "to partition)"
            )
        if self.engine == "wide" and self.devices > 1 and self.pull_gate:
            # Mirrors the CLI's rejection: the distributed wide engine has
            # no gate machinery — silently serving ungated would lie.
            raise ValueError(
                "pull_gate on a mesh runs through the distributed hybrid "
                "engine; use engine='hybrid' with devices > 1"
            )
        if self.engine == "dist2d" and self.pull_gate:
            raise ValueError(
                "pull_gate gates the packed MS engines' pull expansion; "
                "the 2D engine has no settled-mask machinery"
            )
        if self.devices == 1 and (
            self.exchange or self.wire_pack or self.delta_bits
            or self.sieve or self.predict
        ):
            raise ValueError(
                "exchange/wire_pack/delta_bits/sieve/predict shape the "
                "MESH exchanges; single-chip engines (devices=1) run none"
            )
        legal_exchanges = (
            KIND_EXCHANGES.get(self.kind, ENGINE_EXCHANGES[self.engine])
            if self.devices > 1 else ENGINE_EXCHANGES[self.engine]
        )
        if self.exchange not in legal_exchanges:
            raise ValueError(
                f"exchange {self.exchange!r} is not one of "
                f"{legal_exchanges} for engine {self.engine!r}"
                + (f" serving kind {self.kind!r}"
                   if self.kind in KIND_EXCHANGES and self.devices > 1
                   else "")
            )
        if self.delta_bits and self.exchange != "sparse":
            raise ValueError(
                "delta_bits compresses the SPARSE exchanges' id streams; "
                f"set exchange='sparse' (got {self.exchange!r})"
            )
        if (self.sieve or self.predict) and not (
            (self.engine == "dist2d" and self.exchange == "sparse")
            or (self.kind == "sssp" and self.devices > 1
                and self.exchange == "sparse" and not self.sieve)
        ):
            raise ValueError(
                "sieve/predict are the exchange planner's pieces; on the "
                "serve tier they apply to engine='dist2d' with "
                "exchange='sparse', plus predict (alone — min carries no "
                "sieve residue to compact) on the distributed sssp "
                "engine's sparse exchange (the MS row gathers take "
                "delta_bits only)"
            )
        if self.mesh_shape:
            if self.engine != "dist2d" and not (
                self.kind == "sssp" and self.devices > 1
            ):
                raise ValueError(
                    "mesh_shape picks a 2D (rows, cols) partition — the "
                    "dist2d engine's, or the distributed sssp engine's "
                    f"(kind='sssp', devices > 1); engine {self.engine!r} "
                    "runs a 1D mesh"
                )
            mesh_shape_2d(self.devices, self.mesh_shape)  # raises on mismatch
            if self.kind == "sssp" and self.exchange not in ("", "allreduce"):
                raise ValueError(
                    "the 2D distributed sssp engine exchanges "
                    "hierarchically (pmin over both axes) — exchange must "
                    f"be '' or 'allreduce', got {self.exchange!r}"
                )
        if self.resume_levels < 0:
            raise ValueError(
                f"resume_levels must be >= 0, got {self.resume_levels}"
            )
        if self.resume_levels and self.engine != "dist2d":
            raise ValueError(
                "resume_levels drives the dist2d serve adapter's chunked "
                "level loop (one single-source loop per unique lane); the "
                "packed MS engines answer a whole batch in one fused loop "
                "with no per-query carry to snapshot — a mesh fault there "
                "re-traverses the batch on the degraded mesh instead"
            )
        if self.graph_generation < 0:
            raise ValueError(
                f"graph_generation must be >= 0, got {self.graph_generation}"
            )
        if self.overlay:
            if len(self.overlay) != 2 or min(self.overlay) < 1:
                raise ValueError(
                    f"overlay must be (rows, kcap) with both >= 1, got "
                    f"{self.overlay}"
                )
            if self.engine != "wide" or self.devices > 1:
                raise ValueError(
                    "the delta overlay rides the single-chip wide "
                    "substrate (ISSUE 19); the mesh generalization "
                    "follows the partitioned tiles"
                )
            if self.pull_gate:
                raise ValueError(
                    "overlay does not compose with pull_gate (the gate "
                    "skips settled BASE rows; overlay edges would escape "
                    "it untraversed)"
                )
            if self.kind == "p2p":
                raise ValueError(
                    "kind 'p2p' is excluded from dynamic serving: its "
                    "path reconstruction scans the BUILD-TIME edge "
                    "tables, so a post-mutation path could silently "
                    "traverse removed edges"
                )
        if self.kind != "bfs":
            from tpu_bfs.workloads import KIND_ENGINES, KINDS

            if self.kind not in KINDS:
                raise ValueError(
                    f"kind must be one of {KINDS}, got {self.kind!r}"
                )
            if self.engine not in KIND_ENGINES[self.kind]:
                raise ValueError(
                    f"kind {self.kind!r} runs on engines "
                    f"{KIND_ENGINES[self.kind]}, not {self.engine!r}"
                )
            if (self.devices > 1 and self.kind == "sssp"
                    and self.wire_pack):
                raise ValueError(
                    "wire_pack packs the OR exchanges' frontier words; "
                    "the distributed sssp engine ships int32 distance "
                    "rows (delta_bits compresses its id stream instead)"
                )
            if self.kind in ("p2p", "sssp") and self.pull_gate:
                raise ValueError(
                    f"kind {self.kind!r} does not compose with pull_gate "
                    "(p2p steps the resumable core level by level under "
                    "its own lane pairing; sssp runs min-plus tiles with "
                    "no settled-mask machinery)"
                )


class EngineRegistry:
    """LRU-bounded store of warmed engines over once-loaded graphs.

    ``aot_store`` (an ``utils.aot.ArtifactStore`` or a directory path)
    turns builds into ADOPTIONS where artifacts exist: ``_build`` still
    constructs the graph tables, but installs deserialized executables
    (``adopt_programs``) over the engine's jit entries instead of
    compiling — the ``--preheat`` path (ISSUE 9). Stale or corrupt
    artifacts fall back to JIT per program; the store's hit/fallback
    counts surface in statsz.
    """

    def __init__(self, *, capacity: int = 4, warm: bool = True, log=None,
                 aot_store=None):
        if capacity < 1:
            raise ValueError(f"registry capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._warm = warm
        self._log = log or (lambda msg: None)
        if isinstance(aot_store, str):
            from tpu_bfs.utils.aot import ArtifactStore

            aot_store = ArtifactStore(aot_store, log=self._log)
        self.aot_store = aot_store
        self._graphs: dict = {}  # guarded-by: _lock
        self._engines: OrderedDict = OrderedDict()  # guarded-by: _lock
        # One build at a time: engine builds allocate device tables, and
        # two concurrent builds of the same spec would double-build AND
        # double-allocate. RLock so get() -> _build() -> graph() nests.
        self._lock = threading.RLock()
        self.builds = 0  # guarded-by: _lock
        self.adoptions = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        enable_compile_cache(log=self._log)

    # --- graphs -----------------------------------------------------------

    def add_graph(self, key: str, graph) -> str:
        """Register an already-loaded Graph under ``key``."""
        with self._lock:
            self._graphs[key] = graph
        return key

    def graph(self, key: str):
        """The graph for ``key``, loading it on first use when the key is
        a CLI graph spec (path / rmat:... / random:...)."""
        with self._lock:
            g = self._graphs.get(key)
            if g is None:
                from tpu_bfs.cli import load_graph

                t0 = time.perf_counter()
                g = load_graph(key)
                self._graphs[key] = g
                self._log(
                    f"graph {key!r} loaded: V={g.num_vertices} "
                    f"E={g.num_edges} in {time.perf_counter() - t0:.1f}s"
                )
            return g

    # --- engines ----------------------------------------------------------

    def get(self, spec: EngineSpec):
        """The warmed engine for ``spec``, building it on first use and
        evicting least-recently-served engines over ``capacity``."""
        spec.validate()
        with self._lock:
            eng = self._engines.get(spec)
            if eng is not None:
                self._engines.move_to_end(spec)
                return eng
            eng = self._build(spec)
            if self._warm:
                self._warm_up(spec, eng)
            self._engines[spec] = eng
            while len(self._engines) > self.capacity:
                old_spec, _ = self._engines.popitem(last=False)
                self.evictions += 1
                self._log(f"evicted engine {old_spec}")
            return eng

    def _build(self, spec: EngineSpec):  # requires-lock: _lock
        rec = _obs.ACTIVE
        store = self.aot_store
        # Span naming is honest about what the build will cost: a spec
        # whose core artifact probes valid becomes an engine_adopt span
        # (table construction + executable install, no compile); only a
        # true from-scratch build emits engine_build — the span name the
        # preheat smoke asserts is ABSENT from a preheated cold start.
        adopting = store is not None and store.probe(spec)
        span = "engine_adopt" if adopting else "engine_build"
        if rec is not None:
            # Registry lifecycle span: builds are the 30-second events a
            # trace of a cold start is mostly made of.
            rec.begin(span, f"w{spec.lanes}", cat="serve.registry",
                      engine=spec.engine, width=spec.lanes,
                      planes=spec.planes, devices=spec.devices)
        adopted: list = []
        try:
            eng = self._build_inner(spec)
            if store is not None:
                from tpu_bfs.utils.aot import adopt_engine_programs

                adopted = adopt_engine_programs(
                    eng, spec, store, log=self._log
                )
                if adopted:
                    with self._lock:
                        self.adoptions += 1
                elif adopting:
                    # The probe said adoptable but nothing installed
                    # (payload undeserializable here, or a concurrent
                    # quarantine): the engine_adopt span would otherwise
                    # read as a phantom no-compile — flag it loudly.
                    self._log(
                        f"aot adoption of {spec} installed nothing; "
                        f"this build pays the full JIT path"
                    )
                    if rec is not None:
                        rec.event("aot_adopt_failed", cat="serve.registry",
                                  width=spec.lanes, engine=spec.engine)
        except Exception as exc:
            if rec is not None:
                rec.end(span, f"w{spec.lanes}",
                        cat="serve.registry", width=spec.lanes,
                        error=f"{type(exc).__name__}: {str(exc)[:120]}")
            raise
        if rec is not None:
            rec.end(span, f"w{spec.lanes}", cat="serve.registry",
                    width=spec.lanes, adopted=len(adopted))
        return eng

    def _build_inner(self, spec: EngineSpec):  # requires-lock: _lock
        if _faults.ACTIVE is not None:
            # Chaos-harness injection site: a transient raised here runs
            # the service's engine-build retry; an OOM runs the width
            # degrade — exactly like a real build failure.
            _faults.ACTIVE.hit("engine_build", lanes=spec.lanes)
        g = self.graph(spec.graph_key)
        t0 = time.perf_counter()
        if spec.kind == "sssp":
            # SSSP builds its own weighted substrate (no base BFS engine
            # to wrap): the delta-stepping tiles + weight planes.
            from tpu_bfs.workloads import build_workload_engine

            eng = build_workload_engine("sssp", None, g, spec)
            self.builds += 1
            self._log(
                f"engine built {spec} in {time.perf_counter() - t0:.1f}s"
            )
            return eng
        if spec.engine == "dist2d":
            from tpu_bfs.parallel.dist_bfs2d import (
                Dist2DServeEngine,
                make_mesh_2d,
            )

            r, c = mesh_shape_2d(spec.devices, spec.mesh_shape)
            eng = Dist2DServeEngine(
                g, make_mesh_2d(r, c), lanes=spec.lanes,
                exchange=spec.exchange or "ring",
                wire_pack=spec.wire_pack, delta_bits=spec.delta_bits,
                sieve=spec.sieve, predict=spec.predict,
                resume_levels=spec.resume_levels,
            )
        elif spec.devices > 1:
            from tpu_bfs.parallel.dist_bfs import make_mesh

            mesh = make_mesh(spec.devices)
            if spec.engine == "wide":
                from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

                eng = DistWideMsBfsEngine(
                    g, mesh, num_planes=spec.planes, lanes=spec.lanes,
                    exchange=spec.exchange or "dense",
                    wire_pack=spec.wire_pack, delta_bits=spec.delta_bits,
                    expand_impl=spec.expand_impl,
                )
            else:
                from tpu_bfs.parallel.dist_msbfs_hybrid import (
                    DistHybridMsBfsEngine,
                )

                eng = DistHybridMsBfsEngine(
                    g, mesh, num_planes=spec.planes, lanes=spec.lanes,
                    pull_gate=spec.pull_gate,
                    exchange=spec.exchange or "dense",
                    wire_pack=spec.wire_pack, delta_bits=spec.delta_bits,
                    expand_impl=spec.expand_impl,
                )
        elif spec.engine == "packed":
            from tpu_bfs.algorithms.msbfs_packed import PackedMsBfsEngine

            eng = PackedMsBfsEngine(g, lanes=spec.lanes)
        elif spec.engine == "hybrid":
            from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine

            eng = HybridMsBfsEngine(
                g, lanes=spec.lanes, num_planes=spec.planes,
                pull_gate=spec.pull_gate, expand_impl=spec.expand_impl,
            )
        else:
            from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine

            eng = WidePackedMsBfsEngine(
                g, lanes=spec.lanes, num_planes=spec.planes,
                pull_gate=spec.pull_gate, expand_impl=spec.expand_impl,
                overlay=spec.overlay,
            )
        if spec.kind != "bfs":
            # Workload adapter over the base engine (ISSUE 14): khop/cc/
            # p2p reuse the packed substrate's compiled programs behind
            # their kind's dispatch/fetch semantics.
            from tpu_bfs.workloads import build_workload_engine

            eng = build_workload_engine(spec.kind, eng, g, spec)
        self.builds += 1
        self._log(f"engine built {spec} in {time.perf_counter() - t0:.1f}s")
        return eng

    def _warm_up(self, spec: EngineSpec, eng) -> None:
        """One full-width batch so the serving shape is compiled (and the
        persistent XLA cache populated) before the first real dispatch.
        The serving executor always pads batches to exactly ``lanes``
        sources, so this warm run compiles THE shape every later dispatch
        reuses. Vertex 0 always exists; its answer is discarded."""
        t0 = time.perf_counter()
        with _obs.host_span("tpu_bfs.setup.engine_warm", span_id=f"w{spec.lanes}",
                            cat="serve.registry", width=spec.lanes,
                            engine=spec.engine):
            eng.run(np.zeros(eng.lanes, dtype=np.int64), time_it=False)
            # Residency warm-up hook (ISSUE 15 satellite / ROADMAP 3b):
            # engines with per-residency caches beyond the compiled
            # programs build them HERE, inside the warm span, so the
            # first real query never pays a cold path — the p2p adapter
            # builds its cached parent scanner (without it, every first
            # path reconstruction paid the O(E) host scatter-min).
            warm = getattr(eng, "warm_residency", None)
            if warm is not None:
                warm()
        self._log(f"engine warmed {spec} in {time.perf_counter() - t0:.1f}s")

    def rekey_generation(self, graph_key: str, generation: int) -> int:
        """Move every resident engine of ``graph_key`` onto the new
        ``graph_generation`` key WITHOUT a rebuild (ISSUE 19): a
        mutation flip swaps overlay table values under the same compiled
        program, so the residency survives — only its registry identity
        moves, atomically under the lock, preserving LRU order.
        In-flight batches keep their pinned engine reference; the next
        ``get`` under the new-generation spec hits the moved residency
        instead of paying a build. Returns how many residencies moved."""
        moved = 0
        with self._lock:
            items = list(self._engines.items())
            self._engines.clear()
            for spec, eng in items:
                if (spec.graph_key == graph_key
                        and spec.graph_generation != generation):
                    spec = dataclasses.replace(
                        spec, graph_generation=generation
                    )
                    moved += 1
                self._engines[spec] = eng
            return moved

    def drop_graph_engines(self, graph_key: str) -> int:
        """Evict every resident engine of ``graph_key`` (the compaction
        path: a NEW base generation's tables invalidate every compiled
        residency — unlike a flip, the ELL itself changed). Returns the
        eviction count."""
        dropped = 0
        with self._lock:
            for spec in [s for s in self._engines
                         if s.graph_key == graph_key]:
                self._engines.pop(spec)
                self.evictions += 1
                dropped += 1
                self._log(f"evicted engine {spec} (compaction)")
            return dropped

    def evict(self, spec: EngineSpec) -> bool:
        """Drop ``spec``'s engine (if resident) so its device tables can
        free. The OOM-degrade ladder calls this on the JUST-OOM'd width
        BEFORE building the narrower engine — the rebuild must not have
        to fit next to the dying engine's allocations (the same lesson
        bench.py's adaptive-shed dance encodes)."""
        with self._lock:
            if self._engines.pop(spec, None) is None:
                return False
            self.evictions += 1
            self._log(f"evicted engine {spec} (explicit)")
            return True

    def resident(self) -> list | None:
        """Resident specs, least-recently-served first (for /statsz), or
        None when a build currently holds the registry lock — the
        observability read must never block behind a minutes-long
        compile (it exists to watch exactly those incidents)."""
        if not self._lock.acquire(timeout=0.05):
            return None
        try:
            return list(self._engines)
        finally:
            self._lock.release()

    def resident_engines(self) -> list:
        """``(spec, engine)`` pairs, same non-blocking discipline as
        :meth:`resident` (empty when a build holds the lock) — the trace
        exporter walks these for ``last_run_trace`` level tracks."""
        if not self._lock.acquire(timeout=0.05):
            return []
        try:
            return list(self._engines.items())
        finally:
            self._lock.release()

    def export_resident(self, store=None) -> dict:
        """Export every resident engine's serving programs into
        ``store`` (default: the registry's own) — the ``--export-aot``
        path: a warmed server populates the artifact store a successor
        preheats from. Returns ``{spec: [exported names]}``. Builds are
        serialized by the registry lock as usual; the export itself
        holds no registry state."""
        from tpu_bfs.utils.aot import ArtifactStore, export_engine_programs

        if isinstance(store, str):
            store = ArtifactStore(store, log=self._log)
        store = store or self.aot_store
        if store is None:
            raise ValueError(
                "export_resident needs an artifact store (construct the "
                "registry with aot_store=... or pass one here)"
            )
        out = {}
        for spec, eng in self.resident_engines():
            names = export_engine_programs(eng, spec, store, log=self._log)
            self._log(
                f"aot export {spec.engine}/w{spec.lanes}: "
                f"{len(names)} programs -> {store.root}"
            )
            out[spec] = names
        return out
