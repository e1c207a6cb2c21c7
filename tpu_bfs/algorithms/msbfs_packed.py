"""Bit-packed multi-source BFS — the TPU flagship engine.

Measured on TPU v5e: a random gather costs ~8 ns *per index* no matter how
little it fetches, while fetching a whole 32-byte row at each index costs the
same (dense ops run 30-60x faster per byte). So the one thing this engine
never does is spend a gather on a single frontier bit: the frontier is a
[V, W] uint32 table — 32*W sources bit-packed per vertex — and every gather
in the level loop retrieves one *row* (32*W lanes at once), amortizing the
per-index tax to ~0.03 ns per (edge, source).

This replaces the reference's one-BFS-at-a-time driver loop (main,
bfs.cu:783-823, one source per process run) with the Graph500 usage pattern
(64 search keys per run) executed as one fused device program:

- expansion: bucketed ELL column gathers + dense OR-fold pyramid
  (tpu_bfs/graph/ell.py) — no atomics (queueBfs's atomicMin/atomicAdd,
  bfs.cu:146-150, have no TPU analog), no scatters, no dynamic shapes;
- visited/claim: ``next = hit & ~visited`` on packed words — the race-free
  reformulation of the atomicMin claim protocol;
- per-lane distances: bit-sliced counters (8 uint32 planes) incremented by
  ripple-carry on the still-unvisited mask each level — dist stays packed in
  the loop and is unpacked once at the end;
- termination: ``any(next != 0)`` inside ``lax.while_loop`` — the device-side
  analog of the host-side queueSize sum (bfs.cu:569) and MPI_Allreduce
  (bfs_mpi.cu:621), with zero host round-trips per level.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs import obs as _obs
from tpu_bfs.graph.csr import Graph, INF_DIST
from tpu_bfs.graph.ell import EllGraph, build_ell
from tpu_bfs.utils.aot import AotProgramProtocol

UNREACHED = np.uint8(255)  # uint8 sentinel; convert with distances_int32()
MAX_LEVELS = 254  # bit-sliced counters are 8 planes wide


@dataclasses.dataclass
class _PackedPending:
    """An in-flight packed batch (dispatch/fetch split — see
    _packed_common.PackedDispatch for the serving-pipeline rationale)."""

    sources: np.ndarray
    src_bits: object  # seed table minus the sentinel row (device)
    planes: tuple
    vis: object
    levels: object  # device scalar; int() blocks
    t0: float


@dataclasses.dataclass
class PackedBfsResult:
    sources: np.ndarray  # [S] int32
    num_levels: int  # joint level count (max over sources)
    reached: np.ndarray  # [S] int64
    edges_traversed: np.ndarray  # [S] int64 (Graph500 TEPS numerator per source)
    elapsed_s: float | None = None  # wall time for the whole batch
    # [S] int32 per-lane eccentricity, reduced on device (ISSUE 3): levels
    # and reached are answerable without any distance transfer.
    ecc: np.ndarray | None = None
    # Host edge list for parents_int32; None when built from a prebuilt ELL.
    _graph: object = None
    # Engine backref for the device parent scan (parent_scan.py) and the
    # lazy distance materialization; None on results deserialized without
    # one (host path still works off a materialized _dist_u8).
    _engine: object = None
    # Bit-sliced device state (planes, vis, src_bits) the distance table
    # materializes from on first access — distance-free consumers (the
    # serve path's want_distances=false) never pay the O(V * lanes)
    # device->host transfer.
    _dist_state: tuple | None = None
    _dist_u8: np.ndarray | None = None  # materialized [S, V] cache
    _parent_cache: dict = dataclasses.field(default_factory=dict)

    @property
    def distance_u8(self) -> np.ndarray:
        """[S, V] uint8 distances, UNREACHED where not reached. Lazily
        unpacked from the bit-sliced device state on first access; the
        device state is released once the host copy exists (reached/ecc/
        edges were already reduced at fetch), so a retained result stops
        pinning ~(planes + 2) [act, w] tables in device memory."""
        if self._dist_u8 is None:
            if self._dist_state is None or self._engine is None:
                raise ValueError(
                    "distances were not materialized and no engine is "
                    "attached to unpack them"
                )
            self._dist_u8 = self._engine._materialize_distances(
                self.sources, *self._dist_state
            )
            self._dist_state = None
        return self._dist_u8

    @property
    def teps(self) -> float | None:
        """Harmonic-mean per-source TEPS: each source's TEPS under the batch
        time share (total time / S per source)."""
        if not self.elapsed_s:
            return None
        per_source_time = self.elapsed_s / len(self.sources)
        t = self.edges_traversed / per_source_time
        return float(len(t) / np.sum(1.0 / np.maximum(t, 1e-9)))

    def distances_int32(self, s: int) -> np.ndarray:
        """Distance row for batch entry s, INF_DIST where unreached."""
        d = self.distance_u8[s].astype(np.int32)
        return np.where(self.distance_u8[s] == UNREACHED, INF_DIST, d)

    def parents_int32(self, s: int) -> np.ndarray:
        """BFS tree of batch entry s: [V] int32 deterministic min-parents
        (source maps to itself, unreached to NO_PARENT). One O(E)
        scatter-min per requested lane, cached — see
        PackedBatchResult.parents_int32 (_packed_common.py) for the
        protocol rationale vs the reference's unvalidatable atomic-race
        parent (bfs.cu:146-147, 940)."""
        if not (0 <= s < len(self.sources)):
            raise IndexError(s)
        if s not in self._parent_cache:
            from tpu_bfs.algorithms._packed_common import min_parents_lane

            self._parent_cache[s] = min_parents_lane(
                self._graph, int(self.sources[s]), self.distances_int32(s)
            )
        return self._parent_cache[s]

    def parents_into(self, out: np.ndarray, *, device: str = "auto") -> np.ndarray:
        """Fill ``out[s]`` with every lane's parent tree.

        Same contract as PackedBatchResult.parents_into: ``auto`` runs the
        batched device min-key scan when available (this engine's own ELL
        tables are borrowed — zero extra HBM — so the scan also serves
        prebuilt-ELL results the host path cannot), falling back to the
        per-lane host scatter-min; ``host``/``device`` force a path."""
        n = len(self.sources)
        v = self.distance_u8.shape[1]
        if out.shape != (n, v):
            raise ValueError(f"out is {out.shape}, need ({n}, {v})")
        from tpu_bfs.algorithms._packed_common import (
            acquire_parent_scanner,
            parents_scan_with_fallback,
        )

        def host() -> np.ndarray:
            for s in range(n):
                out[s] = self.parents_int32(s)
                self._parent_cache.pop(s, None)
            return out

        host_serves = self._graph is not None
        # Same loud-fallback gate as PackedBatchResult.parents_into: above
        # ~1e5 lanes x vertices the host path stops being interactive.
        work_desc = (
            f"{n} lanes x {v} vertices" if n * v > 100_000 else None
        )
        scanner = acquire_parent_scanner(
            self._engine, device, host_serves=host_serves,
            work_desc=work_desc,
        )
        if scanner is None:
            return host()
        return parents_scan_with_fallback(
            lambda: self._parents_into_scan(out, scanner),
            host,
            device,
            host_serves=host_serves,
            work_desc=work_desc,
        )

    def _parents_into_scan(self, out: np.ndarray, scanner) -> np.ndarray:
        n = len(self.sources)
        ell = scanner.ell
        act = ell.num_active
        ids = ell.old_of_new[:act]
        # Distances are already materialized host-side in old-id order;
        # transpose the active rows into scanner row space per pass.
        dist_rank = np.ascontiguousarray(self.distance_u8[:, ids].T)
        L = scanner.lanes_per_pass
        for c0 in range(0, n, L):
            cols = dist_rank[:, c0 : c0 + L]
            real = cols.shape[1]
            if real < L:
                cols = np.concatenate(
                    [cols, np.full((act, L - real), UNREACHED, np.uint8)],
                    axis=1,
                )
            pc = np.asarray(scanner.scan(jnp.asarray(cols)))
            for j in range(real):
                row = out[c0 + j]
                row.fill(-1)
                row[ids] = pc[:, j]
                # Sources always map to themselves — including isolated
                # sources, which have no scanner row at all.
                src = int(self.sources[c0 + j])
                row[src] = src
        return out


def fold_heavy(acc, arrs, combine, ident_row, fold_steps: int):
    """The heavy rows' outputs from their virtual rows' (``acc``): the
    fold pyramid combines each heavy row's virtual rows pairwise, and
    ``heavy_pick`` reads each heavy row's level of it (graph/ell.py). One
    copy for every expansion tier, under the ``level.heavy`` scope."""
    with _obs.scope("level.heavy"):
        vr_ext = jnp.concatenate([acc, ident_row])
        cur = vr_ext[arrs["fold_pad_map"]]
        pyramid = [cur]
        for _ in range(fold_steps):
            pairs = cur.reshape(-1, 2, cur.shape[-1])
            cur = combine(pairs[:, 0], pairs[:, 1])
            pyramid.append(cur)
        pyr = jnp.concatenate(pyramid) if len(pyramid) > 1 else pyramid[0]
        return pyr[arrs["heavy_pick"]]


def make_packed_expand(
    *, w: int, kcap: int, fold_steps: int, num_virtual: int,
    light_meta: list[tuple[int, int]], heavy: bool, tail_rows: int,
):
    """Build the bucketed-ELL expansion: frontier table ``fw`` [rows+1, w] ->
    OR of the frontier words of each row's in-neighbors.

    Shared by the single-chip engine (rows = V) and each chip of the
    distributed engine (rows = its v_loc owned rows); ``light_meta`` is a list
    of (k, n) bucket shapes, ``tail_rows`` the appended all-zero rows.
    """

    @_obs.scope("level.gather")
    def expand(arrs, fw):
        parts = []
        if heavy:
            vr_t = arrs["virtual_t"]  # [kcap, M]
            acc = jnp.zeros((num_virtual, w), jnp.uint32)
            for k in range(kcap):
                acc = acc | fw[vr_t[k]]
            parts.append(fold_heavy(acc, arrs, jnp.bitwise_or,
                                    jnp.zeros((1, w), jnp.uint32),
                                    fold_steps))
        for i, (k, n) in enumerate(light_meta):
            bt = arrs[f"light{i}_t"]  # [k, n]
            acc = jnp.zeros((n, w), jnp.uint32)
            for kk in range(k):
                acc = acc | fw[bt[kk]]
            parts.append(acc)
        if tail_rows:
            parts.append(jnp.zeros((tail_rows, w), jnp.uint32))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    return expand


def ripple_increment(planes, carry_bits):
    """Bit-sliced ripple-carry: planes + 1 wherever carry_bits is set."""
    new_planes = []
    for p in planes:
        new_planes.append(p ^ carry_bits)
        carry_bits = p & carry_bits
    return tuple(new_planes)


def _make_core(ell: EllGraph, w: int):
    """Build the jitted level loop for one ELL structure; arrays are passed as
    a pytree so they live on device once and never get baked into the HLO."""
    # Tables cover active rows only; isolated vertices (rank >= num_active)
    # have no row — the engine patches their lanes host-side.
    act = ell.num_active
    expand = make_packed_expand(
        w=w,
        kcap=ell.kcap,
        fold_steps=ell.fold_steps,
        num_virtual=ell.num_virtual,
        light_meta=[(b.k, b.n) for b in ell.light],
        heavy=ell.num_heavy > 0,
        tail_rows=act - ell.num_nonzero,
    )

    @jax.jit
    @_obs.scope(f"lanes{32 * w}")
    def core(arrs, fw0, vis0, max_levels):
        with _obs.scope("batch.seed"):
            planes0 = tuple(
                jnp.zeros((act, w), jnp.uint32) for _ in range(8)
            )

        def cond(carry):
            _, _, _, level, alive = carry
            return alive & (level < max_levels)

        def body(carry):
            fw, vis, planes, level, _ = carry
            hit = expand(arrs, fw)
            with _obs.scope("level.claim"):
                nxt = hit & ~vis
                vis2 = vis | nxt
                # Increment the per-lane level counter wherever the lane
                # is still unvisited after this level.
                planes = ripple_increment(planes, ~vis2)
                fw_next = jnp.concatenate(
                    [nxt, jnp.zeros((1, w), jnp.uint32)]
                )
                alive = jnp.any(nxt != 0)
                return fw_next, vis2, planes, level + 1, alive

        # The loop itself (its termination test and carry) is claim work;
        # the body's gathers name themselves inside it.
        with _obs.scope("level.claim"):
            fw_f, vis_f, planes_f, levels, _ = jax.lax.while_loop(
                cond, body,
                (fw0, vis0, planes0, jnp.int32(0), jnp.bool_(True)),
            )
        return planes_f, vis_f, levels

    @jax.jit
    @_obs.scope("batch.stats")
    def extract(planes, vis, src_bits):
        """Unpack bit-sliced counters to per-lane uint8 distances [act, 32w]."""
        shifts = jnp.arange(32, dtype=jnp.uint32)
        cols = []
        for wi in range(w):
            cnt = jnp.zeros((act, 32), jnp.uint8)
            for i, p in enumerate(planes):
                bit = ((p[:, wi, None] >> shifts) & 1).astype(jnp.uint8)
                cnt = cnt + (bit << i)
            visw = ((vis[:, wi, None] >> shifts) & 1) != 0
            srcw = ((src_bits[:, wi, None] >> shifts) & 1) != 0
            dist_w = jnp.where(
                srcw,
                jnp.uint8(0),
                jnp.where(visw, cnt + jnp.uint8(1), UNREACHED),
            )
            cols.append(dist_w)
        return jnp.concatenate(cols, axis=1)

    return core, extract


class PackedMsBfsEngine(AotProgramProtocol):
    """Runs up to ``lanes`` BFS sources concurrently, bit-packed.

    ``lanes`` must be a multiple of 32; 256 (w=8 words) is the measured
    sweet spot on v5e — wider rows gather no faster, narrower waste lanes.
    """

    def export_programs(self):
        # AOT inventory (ISSUE 9; utils/aot.py): custom rather than the
        # shared packed_aot_programs (this engine's ``_seed`` is a
        # host-numpy pass, not a compiled program — deliberately absent).
        import jax

        act = self.ell.num_active
        u32 = jnp.uint32
        fw_s = jax.ShapeDtypeStruct((act + 1, self.w), u32)
        vis_s = jax.ShapeDtypeStruct((act, self.w), u32)
        arrs_s = {
            k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
            for k, v in self.arrs.items()
        }
        planes_s = (vis_s,) * 8
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        return [
            ("core", "_core", self._core, (arrs_s, fw_s, vis_s, i32)),
            ("extract", "_extract", self._extract,
             (planes_s, vis_s, vis_s)),
            ("lane_stats", "_lane_stats", self._lane_stats, (vis_s,)),
            ("lane_ecc", "_lane_ecc", self._lane_ecc,
             (planes_s, vis_s, vis_s)),
        ]

    def __init__(
        self,
        graph: Graph | EllGraph,
        *,
        lanes: int = 256,
        kcap: int = 64,
        undirected: bool | None = None,
    ):
        if lanes % 32:
            raise ValueError("lanes must be a multiple of 32")
        self.w = lanes // 32
        self.lanes = lanes
        if isinstance(graph, Graph):
            self.ell = build_ell(graph, kcap=kcap)
        else:
            self.ell = graph
        # Host-side edge list for post-loop parent extraction
        # (PackedBfsResult.parents_int32); a prebuilt ELL has dropped it.
        self.host_graph = graph if isinstance(graph, Graph) else None
        self.undirected = self.ell.undirected if undirected is None else undirected
        ell = self.ell
        arrs = {}
        if ell.num_heavy:
            arrs["virtual_t"] = jnp.asarray(np.ascontiguousarray(ell.virtual.idx.T))
            arrs["fold_pad_map"] = jnp.asarray(ell.fold_pad_map)
            arrs["heavy_pick"] = jnp.asarray(ell.heavy_pick)
        for i, b in enumerate(ell.light):
            arrs[f"light{i}_t"] = jnp.asarray(np.ascontiguousarray(b.idx.T))
        self.arrs = arrs
        self._core, self._extract = _make_core(ell, self.w)
        # Shared per-lane device reductions (reached / degree sum / ecc) —
        # the same state kernels the wide/hybrid engines use; lazy import
        # because _packed_common imports this module at its top.
        from tpu_bfs.algorithms._packed_common import make_state_kernels

        act = self.ell.num_active
        _, self._lane_stats, _, self._lane_ecc = make_state_kernels(
            self.ell.num_vertices, act, self.w, 8, active=act,
            in_deg_host=self.ell.in_degree[self.ell.old_of_new].astype(
                np.int32
            ),
        )
        # Depth cap of the 8-plane bit-sliced counters; the parent scan's
        # key encoding sizes its distance field from this.
        self.max_levels_cap = MAX_LEVELS
        self._warmed = False

    def _full_parent_ell(self):
        """Full-coverage ELL + device arrays for the batched parent scan
        (parent_scan.py) — this engine's own, borrowed for free."""
        return self.ell, self.arrs

    @property
    def num_vertices(self) -> int:
        return self.ell.num_vertices

    def _seed(self, sources: np.ndarray):
        act = self.ell.num_active
        fw0 = np.zeros((act + 1, self.w), np.uint32)
        ranks = self.ell.rank[sources]
        for i, r in enumerate(ranks):
            if r < act:  # isolated sources have no row; patched in run()
                fw0[r, i // 32] |= np.uint32(1 << (i % 32))
        return fw0

    def dispatch(self, sources, *, max_levels: int = MAX_LEVELS):
        """Launch one packed batch without blocking on it (JAX dispatch is
        async) — the serve pipeline's entry; ``fetch`` is the blocking
        half. Returns an opaque pending handle."""
        sources = np.asarray(sources, dtype=np.int64)
        if sources.ndim != 1 or len(sources) == 0 or len(sources) > self.lanes:
            raise ValueError(f"need 1..{self.lanes} sources, got {sources.shape}")
        if sources.min() < 0 or sources.max() >= self.ell.num_vertices:
            raise ValueError("source out of range")
        max_levels = min(max_levels, MAX_LEVELS)
        with _obs.host_span("tpu_bfs.engine.dispatch", lambda: {
            "lanes": self.lanes, "n": len(sources), "max_levels": max_levels,
        }):
            fw0 = jnp.asarray(self._seed(sources))
            vis0 = fw0[:-1]
            t0 = time.perf_counter()
            planes, vis, levels = self._core(
                self.arrs, fw0, vis0, jnp.int32(max_levels)
            )
        return _PackedPending(
            sources=sources, src_bits=vis0, planes=planes, vis=vis,
            levels=levels, t0=t0,
        )

    def fetch(self, pend, *, time_it: bool = False) -> PackedBfsResult:
        """Block on a :meth:`dispatch` handle and assemble its result.

        ``reached``/``ecc``/``edges_traversed`` reduce on device
        (lane_stats / lane_ecc); the distance table stays bit-sliced on
        device and unpacks lazily on first ``distance_u8`` access, so
        distance-free consumers never pay the O(V * lanes) transfer."""
        levels = None
        with _obs.host_span("tpu_bfs.engine.fetch", lambda: {
            "lanes": self.lanes, "levels": levels,
        }):
            levels = int(pend.levels)  # blocks until the loop finishes
            elapsed = (time.perf_counter() - pend.t0) if time_it else None
            self._warmed = True

            sources = pend.sources
            s = len(sources)
            act = self.ell.num_active
            r, d = self._lane_stats(pend.vis)
            e = self._lane_ecc(pend.planes, pend.vis, pend.src_bits)
            reached = np.asarray(r).reshape(-1)[:s].astype(np.int64)
            ecc = np.asarray(e).reshape(-1)[:s].astype(np.int32)
        slot_sum = (
            np.asarray(d).astype(np.int64).sum(axis=1).reshape(-1)[:s]
        )
        edges = slot_sum // 2 if self.undirected else slot_sum
        # Isolated sources were never seeded; their component is {source}.
        iso = np.flatnonzero(self.ell.rank[sources] >= act)
        reached[iso], ecc[iso], edges[iso] = 1, 0, 0
        return PackedBfsResult(
            sources=sources.astype(np.int32),
            # Max eccentricity over lanes, not loop iterations (which
            # include the final empty-frontier step) — BfsEngine semantics.
            num_levels=int(ecc.max()) if s else 0,
            reached=reached,
            edges_traversed=edges.astype(np.int64),
            elapsed_s=elapsed,
            ecc=ecc,
            _graph=self.host_graph,
            _engine=self,
            _dist_state=(pend.planes, pend.vis, pend.src_bits),
        )

    def run(
        self,
        sources,
        *,
        max_levels: int = MAX_LEVELS,
        time_it: bool = False,
    ) -> PackedBfsResult:
        if time_it and not self._warmed:
            int(self.dispatch(sources, max_levels=max_levels).levels)
        return self.fetch(
            self.dispatch(sources, max_levels=max_levels), time_it=time_it
        )

    def _materialize_distances(self, sources, planes, vis, src_bits):
        """[S, V] uint8 distance table in old-id order — the one full
        unpack + transfer, deferred until someone asks for distances."""
        dn = np.asarray(self._extract(planes, vis, src_bits))  # rank space
        s = len(sources)
        act = self.ell.num_active
        v = self.ell.num_vertices
        ranks = self.ell.rank
        if act < v:
            full = np.full((v, dn.shape[1]), UNREACHED, np.uint8)
            m = ranks < act
            full[m] = dn[ranks[m]]
        else:
            full = dn[ranks]
        dist = np.ascontiguousarray(full[:, :s].T)  # [S, V], old ids
        # Isolated sources were never seeded; their component is {source}.
        for i in np.flatnonzero(ranks[sources] >= act):
            dist[i, sources[i]] = 0
        return dist
