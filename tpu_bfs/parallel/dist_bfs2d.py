"""Multi-chip BFS over a 2D (R x C) edge partition.

The scale-out path the reference lacks (its only distribution mode replicates
the full CSR per device and partitions ownership 1D, bfs.cu:29-32, 346-351;
SURVEY.md §2c flags 2D partitioning as the gap to close for Graph500 scales).
Level structure (see partition2d):

    col all-gather (ICI, 'r' axis)  ->  local expand  ->
    row OR-reduce-scatter (ICI, 'c' axis)  ->  claim owned slice  ->
    psum termination over the whole mesh

Both collectives move O(vp/mesh-dimension) bits per chip instead of the 1D
exchange's O(vp).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from tpu_bfs.algorithms.bfs import BfsResult
from tpu_bfs.algorithms.frontier import (
    INT32_MAX,
    EdgeData,
    default_dopt_caps,
    expand_or,
    make_dopt_expand,
)
from tpu_bfs.graph.csr import Graph, INF_DIST
from tpu_bfs.parallel.collectives import (
    check_delta_bits,
    column_gather_wire_bytes,
    default_sparse_caps,
    dense_2d_wire_bytes,
    gate_and_stamp_chain,
    merge_exchange_counts,
    normalize_caps,
    pack_bits,
    planned_branch_count,
    planned_branch_labels,
    planned_sparse_exchange_or,
    planned_sparse_wire_bytes_per_level,
    reduce_scatter_min,
    reduce_scatter_or,
    rows_gather_branch_labels,
    sparse_exchange_or,
    sparse_wire_bytes_per_level,
    unpack_bits,
)
from tpu_bfs.obs.engine_trace import TRACE_LEVELS, assemble_dist_trace
from tpu_bfs.parallel.dist_bfs import VertexCheckpointMixin
from tpu_bfs.parallel.partition2d import out_csr_2d, partition_2d
from tpu_bfs.utils.aot import AotProgramProtocol
from tpu_bfs.utils.timing import run_timed


def make_mesh_2d(rows: int, cols: int, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    if rows * cols > len(devices):
        raise ValueError(f"mesh {rows}x{cols} needs {rows * cols} devices")
    arr = np.array(devices[: rows * cols]).reshape(rows, cols)
    return Mesh(arr, ("r", "c"))


def _dist2d_bfs_fn(mesh: Mesh, rows: int, cols: int, w: int, exchange: str,
                   backend: str, dopt_caps: tuple[int, ...] = (),
                   wire_pack: bool = False,
                   sparse_caps: tuple[int, ...] = (),
                   delta_bits: tuple[int, ...] = (), sieve: bool = False,
                   predict: bool = False):
    """2D level loop. ``backend='dopt'`` = the BASELINE scale-26 config
    ("2D edge partition + direction-optimizing BFS"): after the column
    all-gather, each chip independently runs the sparse top-down branch
    when its column frontier's local out-degree sum fits a ``dopt_caps``
    rung — the branch is collective-free (both collectives sit outside the
    `lax.cond`), so per-chip divergence is safe.

    ``wire_pack=True`` bit-packs BOTH per-level collectives (ISSUE 5): the
    column all-gather over 'r' ships each chip's [w] slice as ceil(w/32)
    uint32 words, and the row reduce-scatter over 'c' runs the packed
    dense exchange — same collective count, 1/8+ the bytes.

    ``exchange='sparse'`` (ISSUE 7) runs the ROW exchange over 'c' as the
    queue-style id exchange — the row contribution buffer has exactly the
    1D exchange's [cols * w] per-destination-chunk shape, so the same
    machinery applies chunk for chunk; ``delta_bits``/``sieve``/
    ``predict`` upgrade it to the full planner
    (collectives.planned_sparse_exchange_or). The column all-gather stays
    dense (its [w] slices have no id form to win with). The carry counts
    the per-branch levels exactly like the 1D loop; the history scalars
    ride the termination psum, already mesh-global over ('r','c')."""
    row_block = cols * w
    col_block = rows * w
    dopt = backend == "dopt"
    planned = exchange == "sparse" and bool(delta_bits or sieve or predict)
    if exchange == "sparse":
        nb = (
            planned_branch_count(sparse_caps, delta_bits)
            if planned else len(normalize_caps(sparse_caps)) + 1
        )
    else:
        nb = 1

    def local_loop(
        src_g, dst_l, rp_l, aux, frontier, visited, dist, level0, max_levels
    ):
        src_g = src_g[0, 0]
        dst_l = dst_l[0, 0]
        rp_l = rp_l[0, 0]

        def dense_fn(col_frontier):
            active = col_frontier[src_g]
            return expand_or(
                active, dst_l, rp_l, row_block,
                backend="scan" if dopt else backend,
            )

        if dopt:
            edata = EdgeData(
                src=src_g, dst=dst_l, in_rp=rp_l,
                out_rp=aux[0][0, 0],  # [R*w+1] CSR by col-gather-local src
                nbr_sm=aux[1][0, 0],  # [ep2] row-block-local dst, src-major
            )
            expand_local = make_dopt_expand(
                edata, dopt_caps, vert_limit=col_block, out_size=row_block,
                dense_fn=dense_fn,
            )
        else:
            expand_local = dense_fn

        sparse_mode = exchange == "sparse"

        def cond(state):
            count, level = state[4], state[3]
            return (count > 0) & (level < max_levels)

        def body(state):
            # Dense impls keep the legacy 6-element carry (their single
            # branch is synthesized after the loop); the sparse row
            # exchange carries its branch arrays, and the planner its
            # history scalars on top — legacy programs stay carry-for-
            # carry identical.
            if planned:
                (frontier, visited, dist, level, front_count, front_seq,
                 branch_counts, branch_seq, prev_biggest, prev_count,
                 vis_total) = state
            elif sparse_mode:
                (frontier, visited, dist, level, front_count, front_seq,
                 branch_counts, branch_seq) = state
            else:
                (frontier, visited, dist, level, front_count,
                 front_seq) = state
            # Column exchange: assemble this mesh column's frontier slices.
            if wire_pack and rows > 1:
                # Packed wire: gather uint32 words (one per 32 vertices of
                # each chip's slice), unpack per chunk after landing.
                gw = lax.all_gather(pack_bits(frontier), "r", tiled=True)
                col_frontier = unpack_bits(gw.reshape(rows, -1), w).reshape(
                    rows * w
                )
            else:
                col_frontier = lax.all_gather(frontier, "r", tiled=True)  # [R*w]
            contrib = expand_local(col_frontier)
            # Row exchange: combine row-block contributions, keep own chunk.
            if planned:
                # The planner's selection scalars (biggest, max gap,
                # sieve/predict decisions) are pmax'd over 'c' ONLY:
                # uniform within each mesh row — which is all the row
                # exchange's per-row collectives need to stay matched —
                # but rows may take DIFFERENT branches at the same level.
                # The sieve density normalizes by the planner's own
                # [cols*w] row block; vis_total counts the whole
                # rows*cols*w mesh, so scale it down by the row count
                # (that one IS mesh-uniform — every chip divides the
                # same psum).
                hit, branch, biggest = planned_sparse_exchange_or(
                    contrib, "c", cols, caps=sparse_caps,
                    delta_bits=delta_bits, sieve=sieve, visited=visited,
                    visited_total=vis_total // rows, predict=predict,
                    prev_biggest=prev_biggest,
                    growing=front_count >= prev_count, wire_pack=wire_pack,
                )
            elif exchange == "sparse":
                hit, branch = sparse_exchange_or(
                    contrib, "c", cols, caps=sparse_caps, wire_pack=wire_pack
                )
            else:
                hit = reduce_scatter_or(
                    contrib, "c", cols, impl=exchange, wire_pack=wire_pack
                )
                branch = None
            new = hit & ~visited
            dist = jnp.where(new, level + 1, dist)
            visited = visited | new
            count = lax.psum(jnp.sum(new.astype(jnp.int32)), ("r", "c"))
            # Engine-trace slots (tpu_bfs/obs/engine_trace): frontier
            # popcount — already paid by the termination psum — and, in
            # sparse mode, the row-exchange branch. ADD, not set, on the
            # frontier so the clamp slot aggregates levels past the
            # window.
            slot = jnp.minimum(level - level0, TRACE_LEVELS - 1)
            front_seq = front_seq.at[slot].add(count)
            out = (new, visited, dist, level + 1, count, front_seq)
            if sparse_mode:
                if rows > 1:
                    # The recorded branch must be MESH-uniform (it leaves
                    # through replicated out_specs — without this, the
                    # host would read an arbitrary device's row-local
                    # view): record the row-MAX branch index, a single
                    # deterministic representative when rows split. Pure
                    # telemetry, outside the wire-byte models' stated
                    # scope like the termination psum.
                    branch = lax.pmax(branch, "r")
                branch_counts = branch_counts + (
                    jnp.arange(nb, dtype=jnp.int32) == branch
                )
                branch_seq = branch_seq.at[slot].set(branch)
                out = out + (branch_counts, branch_seq)
            if planned:
                # The planner's history scalars: the 2D visited total
                # counts the WHOLE mesh's claims, but the sieve prices
                # against this row's [cols*w] chunks — both mesh-uniform
                # either way, and the density ratio is partition-
                # invariant in expectation.
                out = out + (biggest, front_count, vis_total + count)
            return out

        init_count = lax.psum(jnp.sum(frontier.astype(jnp.int32)), ("r", "c"))
        init = (frontier, visited, dist, jnp.int32(level0), init_count,
                jnp.zeros(TRACE_LEVELS, jnp.int32))
        if sparse_mode:
            init = init + (
                jnp.zeros(nb, jnp.int32),
                jnp.full(TRACE_LEVELS, -1, jnp.int32),
            )
        if planned:
            init = init + (
                jnp.int32(-1), jnp.int32(0),
                lax.psum(jnp.sum(visited.astype(jnp.int32)), ("r", "c")),
            )
        out = lax.while_loop(cond, body, init)
        frontier, visited, dist, level, _, front_seq = out[:6]
        if sparse_mode:
            branch_counts, branch_seq = out[6], out[7]
        else:
            # Single dense branch: every run level took it — synthesized
            # outside the loop so the legacy carry stays untouched.
            levels_run = level - level0
            branch_counts = levels_run[None].astype(jnp.int32)
            branch_seq = jnp.where(
                jnp.arange(TRACE_LEVELS) < jnp.minimum(levels_run, TRACE_LEVELS),
                0, -1,
            ).astype(jnp.int32)
        return frontier, visited, dist, level, front_seq, branch_counts, branch_seq

    aux_specs = (P("r", "c", None), P("r", "c", None)) if dopt else ()
    # Carry donation, same contract as the 1D loop (dist_bfs.py): every
    # caller hands in fresh buffers — _init_state copies, advance
    # device_puts, and the serve adapter's chunked drive reads its
    # snapshot to host BEFORE relaunching from the device outputs — so
    # argnums 4-6 alias out instead of doubling per-chunk residency.
    fn = jax.jit(
        shard_map(
            local_loop,
            mesh=mesh,
            in_specs=(
                P("r", "c", None),
                P("r", "c", None),
                P("r", "c", None),
                aux_specs,
                P(("r", "c")),
                P(("r", "c")),
                P(("r", "c")),
                P(),
                P(),
            ),
            out_specs=(P(("r", "c")), P(("r", "c")), P(("r", "c")), P(), P(),
                       P(), P()),
            check_vma=False,
        ),
        donate_argnums=(4, 5, 6),
    )
    fn._donate_argnums = (4, 5, 6)
    return fn


def _dist2d_parents_fn(mesh: Mesh, rows: int, cols: int, w: int, exchange: str):
    row_block = cols * w

    def local_parents(src_g, dst_l, dist_loc):
        src_g = src_g[0, 0]
        dst_l = dst_l[0, 0]
        i = lax.axis_index("r")
        j = lax.axis_index("c")
        dist_full = lax.all_gather(dist_loc, ("r", "c"), tiled=True)  # [vp]
        # Reconstruct global padded src ids from column-gather-local indices.
        src_global = ((src_g // w) * cols + j) * w + src_g % w
        dst_global = i * row_block + dst_l
        du = dist_full[src_global]
        ok = (du != INT32_MAX) & (du + 1 == dist_full[dst_global])
        cand = jnp.where(ok, src_global, INT32_MAX)
        contrib = (
            jnp.full((row_block,), INT32_MAX, jnp.int32)
            .at[dst_l]
            .min(cand, mode="drop")
        )
        parent_loc = reduce_scatter_min(contrib, "c", cols, impl=exchange)
        parent_loc = jnp.where(parent_loc == INT32_MAX, -1, parent_loc)
        return jnp.where(dist_loc == INT32_MAX, -1, parent_loc)

    return jax.jit(
        shard_map(
            local_parents,
            mesh=mesh,
            in_specs=(P("r", "c", None), P("r", "c", None), P(("r", "c"))),
            out_specs=P(("r", "c")),
            check_vma=False,
        )
    )


class Dist2DBfsEngine(VertexCheckpointMixin, AotProgramProtocol):
    """BFS over an R x C mesh with 2D edge partitioning.

    API mirrors DistBfsEngine; use for meshes large enough that the 1D
    exchange's O(vp) per-chip traffic dominates."""

    def __init__(
        self,
        graph: Graph,
        mesh: Mesh | None = None,
        *,
        rows: int | None = None,
        cols: int | None = None,
        exchange: str = "ring",
        backend: str = "scan",
        dopt_caps: tuple[int, ...] | None = None,
        wire_pack: bool = False,
        sparse_caps: int | tuple[int, ...] | None = None,
        delta_bits: tuple[int, ...] = (),
        sieve: bool = False,
        predict: bool = False,
    ):
        if mesh is None:
            mesh = make_mesh_2d(rows or 1, cols or 1)
        if tuple(mesh.axis_names) != ("r", "c"):
            raise ValueError("2D engine needs a mesh with axes ('r', 'c')")
        if exchange not in ("ring", "allreduce", "sparse"):
            # Reject loudly at build time (not deep inside shard_map tracing).
            raise ValueError(
                f"unknown exchange {exchange!r} for the 2D engine; "
                "have 'ring', 'allreduce', 'sparse' (the queue-style row "
                "exchange, ISSUE 7)"
            )
        if (delta_bits or sieve or predict) and exchange != "sparse":
            raise ValueError(
                "delta_bits/sieve/predict reshape the SPARSE row exchange "
                f"(the ISSUE 7 planner); exchange={exchange!r} has no id "
                "buffers to compress — use exchange='sparse'"
            )
        self.mesh = mesh
        self.rows, self.cols = (
            mesh.devices.shape[0],
            mesh.devices.shape[1],
        )
        self.graph_meta = (graph.num_input_edges, graph.undirected)
        self._degrees = graph.degrees
        part, src_gidx, dst_stacked, rp_stacked = partition_2d(
            graph, self.rows, self.cols
        )
        self.part = part
        edge_sharding = NamedSharding(mesh, P("r", "c", None))
        self.src_g = jax.device_put(src_gidx, edge_sharding)
        self.dst_l = jax.device_put(dst_stacked, edge_sharding)
        self.rp = jax.device_put(rp_stacked, edge_sharding)
        self._vec_sharding = NamedSharding(mesh, P(("r", "c")))
        self._aux = ()
        if backend == "dopt":
            out_rp, nbr = out_csr_2d(part, src_gidx, dst_stacked)
            self._aux = (
                jax.device_put(out_rp, edge_sharding),
                jax.device_put(nbr, edge_sharding),
            )
            if dopt_caps is None:
                dopt_caps = default_dopt_caps(src_gidx.shape[2])
        self.dopt_caps = tuple(sorted(set(dopt_caps))) if dopt_caps else ()
        self._exchange = exchange
        #: bit-packed wire format (ISSUE 5): both per-level collectives
        #: (column all-gather, row reduce-scatter) ship uint32 words.
        #: Bit-identical results; default OFF until chip-measured.
        self.wire_pack = bool(wire_pack)
        #: ISSUE 7 planner knobs for the sparse ROW exchange (same
        #: contract as DistBfsEngine; all default OFF until chip-measured).
        self.delta_bits = check_delta_bits(delta_bits)
        self.sieve = bool(sieve)
        self.predict = bool(predict)
        self._planned = exchange == "sparse" and bool(
            self.delta_bits or self.sieve or self.predict
        )
        if exchange == "sparse":
            if sparse_caps is None:
                sparse_caps = default_sparse_caps(
                    part.w, wire_pack=self.wire_pack,
                    delta_bits=self.delta_bits,
                )
            elif isinstance(sparse_caps, int):
                sparse_caps = (sparse_caps,)
            self.sparse_caps = normalize_caps(sparse_caps)
        else:
            self.sparse_caps = ()
        self._loop = _dist2d_bfs_fn(
            mesh, self.rows, self.cols, part.w, exchange, backend,
            self.dopt_caps, self.wire_pack, self.sparse_caps,
            self.delta_bits, self.sieve, self.predict,
        )
        # The parent merge is a one-shot int32 MIN reduce-scatter over
        # 'c' — queue-style ids don't apply; 'sparse' rides the ring
        # there (the 1D engine's convention).
        parent_impl = "ring" if exchange == "sparse" else exchange
        self._parents = _dist2d_parents_fn(
            mesh, self.rows, self.cols, part.w, parent_impl
        )
        #: level count of the last traversal (one branch — the 2D loop has
        #: no cap ladder) and the modeled off-chip bytes one chip moved in
        #: it (column all-gather + row reduce-scatter per level) — the 2D
        #: analog of DistBfsEngine's exchange accounting.
        self.last_exchange_level_counts: np.ndarray | None = None
        self.last_exchange_bytes: float | None = None
        # Raw loop carries of the last core invocation; the per-level
        # rows assemble lazily on first last_run_trace access (same
        # contract as DistBfsEngine.last_run_trace).
        self._trace_pending: tuple | None = None
        self._trace_cache: list[dict] | None = None
        self._direction = "dopt" if backend == "dopt" else "push"
        self._warmed = False

    def wire_bytes_per_level(self) -> list[float]:
        """Modeled off-chip bytes one chip moves per level, per
        row-exchange branch (single entry for the dense impls; the sparse
        ladder's branches — or the ISSUE 7 planner's full layout — each
        plus the per-level column all-gather, which runs on EVERY branch).
        Same contract as DistBfsEngine.wire_bytes_per_level — with the 2D
        caveat that sparse branch selection is per mesh ROW (pmax over
        'c'); when rows split at a level, the recorded branch is the
        row-MAX index (the loop uniformizes it), so the priced bytes are
        one deterministic representative rather than an exact per-chip
        figure."""
        if self._exchange != "sparse":
            return [
                dense_2d_wire_bytes(
                    self.rows, self.cols, self.part.w, self._exchange,
                    wire_pack=self.wire_pack,
                )
            ]
        ag = column_gather_wire_bytes(
            self.rows, self.part.w, wire_pack=self.wire_pack
        )
        if self._planned:
            per = planned_sparse_wire_bytes_per_level(
                self.cols, self.part.w, self.sparse_caps, self.delta_bits,
                wire_pack=self.wire_pack,
            )
        else:
            per = sparse_wire_bytes_per_level(
                self.cols, self.part.w, self.sparse_caps,
                wire_pack=self.wire_pack,
            )
        return [ag + x for x in per]

    def exchange_branch_labels(self) -> list[str] | None:
        """Branch labels for the sparse row exchange (engine-trace hook);
        None for the dense impls."""
        if self._planned:
            return planned_branch_labels(self.sparse_caps, self.delta_bits)
        if self._exchange == "sparse":
            return rows_gather_branch_labels(self.sparse_caps, ())
        return None

    def _record_exchange(
        self, branch_counts, *, resumed_level: int = 0, chain_nonce=None
    ) -> None:
        prev = gate_and_stamp_chain(self, resumed_level, chain_nonce)
        counts = merge_exchange_counts(prev, branch_counts, resumed_level)
        self.last_exchange_level_counts = counts
        self.last_exchange_bytes = float(
            np.dot(counts, self.wire_bytes_per_level())
        )

    def _init_state(self, source: int):
        part = self.part
        pid = int(part.to_padded(source))
        frontier0 = np.zeros(part.vp, dtype=bool)
        frontier0[pid] = True
        dist0 = np.full(part.vp, INF_DIST, dtype=np.int32)
        dist0[pid] = 0
        put = partial(jax.device_put, device=self._vec_sharding)
        return put(frontier0), put(frontier0.copy()), put(dist0)

    def analysis_programs(self):
        """Static-analyzer hook (tpu_bfs/analysis): the 2D level loop —
        whose sparse row-exchange branches are uniform per mesh ROW (pmax
        over 'c'), exactly what the taint pass verifies — and the parent
        merge. Same contract as DistBfsEngine.analysis_programs."""
        f0, vis0, d0 = self._init_state(0)
        rep = NamedSharding(self.mesh, P())
        l0, ml = (
            jax.device_put(jnp.int32(0), rep),
            jax.device_put(jnp.int32(64), rep),
        )
        return [
            ("level_loop", self._loop,
             (self.src_g, self.dst_l, self.rp, self._aux, f0, vis0, d0,
              l0, ml)),
            ("parents", self._parents, (self.src_g, self.dst_l, d0)),
        ]

    def export_programs(self):
        """AOT inventory (ISSUE 9/11; utils/aot.py): the sharded 2D level
        loop under the dist engines' shared ``dist_core`` name — the
        compile a mesh replica's ``--preheat`` skips. The serve adapter
        dispatches this exact signature (scalars included), so the
        adopted executable's shape precheck passes on every serving
        call."""
        return [
            ("dist_core", "_loop", fn, args)
            for name, fn, args in self.analysis_programs()
            if name == "level_loop"
        ]

    def distances_padded(self, source: int, *, max_levels: int | None = None):
        frontier0, visited0, dist0 = self._init_state(source)
        ml = jnp.int32(max_levels if max_levels is not None else self.part.vp)
        _, _, dist, level, front_seq, branch_counts, branch_seq = self._loop(
            self.src_g, self.dst_l, self.rp, self._aux,
            frontier0, visited0, dist0, jnp.int32(0), ml,
        )
        self._record_exchange(branch_counts)
        self._record_trace(front_seq, branch_seq, int(level), 0)
        return dist, level

    # --- checkpoint/resume: VertexCheckpointMixin (dist_bfs.py) provides
    # start/advance/finish; checkpoints are real-id [V] arrays shared with
    # the 1D engine, so traversals resume across partition topologies. ---

    @property
    def _num_real_vertices(self) -> int:
        return self.part.base.num_vertices

    def _advance_loop(self, f0, vis0, d0, level0: int, cap: int, *, chain_nonce=None):
        frontier, visited, dist, level, front_seq, branch_counts, branch_seq = (
            self._loop(
                self.src_g, self.dst_l, self.rp, self._aux, f0, vis0, d0,
                jnp.int32(level0), jnp.int32(cap),
            )
        )
        self._record_exchange(
            branch_counts, resumed_level=level0, chain_nonce=chain_nonce
        )
        self._record_trace(front_seq, branch_seq, int(level) - level0, level0)
        return frontier, visited, dist, level

    def _record_trace(
        self, front_seq, branch_seq, levels_run: int, level0: int
    ) -> None:
        self._trace_pending = (front_seq, branch_seq, int(levels_run),
                               int(level0))
        self._trace_cache = None

    @property
    def last_run_trace(self) -> list[dict] | None:
        """Per-level rows of the last core invocation — assembled lazily
        (same contract and rationale as DistBfsEngine.last_run_trace;
        tpu_bfs/obs/engine_trace). The branch column is the loop-carried
        row-exchange branch (always 0 for the dense impls; the sparse
        ladder / planner index otherwise)."""
        pend = self._trace_pending
        if pend is not None:
            front_seq, branch_seq, levels_run, level0 = pend
            self._trace_pending = None
            self._trace_cache = assemble_dist_trace(
                self, levels_run, front_seq, branch_seq,
                direction=self._direction, level0=level0,
            )
        return self._trace_cache

    @last_run_trace.setter
    def last_run_trace(self, rows: list[dict] | None) -> None:
        self._trace_pending = None
        self._trace_cache = rows

    def run(
        self,
        source: int,
        *,
        max_levels: int | None = None,
        with_parents: bool = True,
        time_it: bool = False,
    ) -> BfsResult:
        part = self.part
        if not (0 <= source < part.base.num_vertices):
            raise ValueError(f"source {source} out of range")
        elapsed = None
        if time_it:
            (dist_dev, _), elapsed = run_timed(
                lambda: self.distances_padded(source, max_levels=max_levels),
                warm=not self._warmed,
            )
            self._warmed = True
        else:
            dist_dev, _ = self.distances_padded(source, max_levels=max_levels)
        return self._package(dist_dev, source, with_parents, elapsed)

    def _package(self, dist_dev, source, with_parents, elapsed) -> BfsResult:
        part = self.part
        parent = None
        if with_parents:
            parent_dev = self._parents(self.src_g, self.dst_l, dist_dev)
            parent_pad = part.unshard(np.asarray(parent_dev))
            parent = np.where(
                parent_pad >= 0, part.from_padded(np.abs(parent_pad)), -1
            ).astype(np.int32)
            parent[source] = source

        dist = part.unshard(np.asarray(dist_dev))
        reached_mask = dist != INF_DIST
        reached = int(reached_mask.sum())
        num_levels = int(dist[reached_mask].max()) if reached else 0
        _, undirected = self.graph_meta
        slots = int(self._degrees[reached_mask].sum()) if reached else 0
        return BfsResult(
            source=source,
            distance=dist,
            parent=parent,
            num_levels=num_levels,
            reached=reached,
            edges_traversed=slots // 2 if undirected else slots,
            elapsed_s=elapsed,
        )


# --- serving adapter (ISSUE 11) -------------------------------------------


@dataclasses.dataclass
class _Pending2D:
    """An in-flight 2D serving batch: one async level-loop launch per
    UNIQUE source (JAX dispatch is async; nothing host-side has blocked),
    plus the lane -> unique-run map that rebuilds the padded batch.

    With level-checkpointed resume armed (ISSUE 12), ``cursors`` carries
    each run's chunk state — the launched chunk's start level, the chain
    nonce, and the drive's wall-clock origin — and ``stats`` holds None
    until the final chunk completes in ``fetch``."""

    sources: np.ndarray  # [S] the padded lane sources
    uniq: np.ndarray  # [U] unique sources actually launched
    inv: np.ndarray  # [S] lane -> unique-run index
    runs: list  # per-unique raw loop outputs (device)
    stats: list  # per-unique (reached, ecc, edges) device scalars
    cursors: list | None = None  # per-unique chunk state (resume mode)
    total_cap: int = 0  # absolute level cap of the whole query


class Dist2DServeResult:
    """Serving-protocol result over the unique 2D runs: lazy per-lane
    distance extraction (one unshard per UNIQUE source, cached), with the
    on-device ``reached``/``ecc``/``edges_traversed`` summaries the
    executor's metadata-only path reads without ever pulling an O(V)
    row."""

    def __init__(self, part, uniq_dists, inv, sources, reached, ecc,
                 edges):
        self._part = part
        self._uniq_dists = uniq_dists  # [U] device dist arrays
        self._inv = inv
        self.sources = sources
        self.reached = reached  # [S] int64, lane-mapped
        self.ecc = ecc  # [S] int32 eccentricity (levels) per lane
        self.edges_traversed = edges  # [S] int64
        self._cache: dict = {}

    def _dist_of(self, u: int) -> np.ndarray:
        d = self._cache.get(u)
        if d is None:
            d = self._part.unshard(np.asarray(self._uniq_dists[u]))
            self._cache[u] = d
        return d

    def distances_int32(self, i: int) -> np.ndarray:
        """[V] int32 distances of lane ``i`` (INF_DIST unreached) — the
        2D loop labels int32 distances natively, so no plane decode."""
        if not (0 <= i < len(self.sources)):
            raise IndexError(i)
        return self._dist_of(int(self._inv[i]))


class Dist2DServeEngine:
    """The 2D engine behind the serve executor's batch protocol.

    The packed MS engines answer a ``lanes``-wide batch in ONE sharded
    level loop; the 2D engine is single-source, so this adapter maps a
    coalesced batch onto one async loop launch per UNIQUE source (the
    executor pads partial batches by repeating a real source, so a
    3-query batch padded to 32 lanes runs 3 loops, not 32). ``dispatch``
    launches every run without blocking; ``fetch`` blocks, records the
    exchange accounting per run, and assembles a result whose per-lane
    views index the unique runs. ``backend='dopt'`` is the default — the
    paper's baseline scale-26 configuration (2D edge partition +
    direction-optimizing BFS).

    ``resume_levels=K`` (ISSUE 12) arms LEVEL-CHECKPOINTED RESUME: each
    run drives the SAME compiled loop K levels at a time (new level
    bounds, no retrace) and snapshots its carry at every chunk boundary
    into the process-wide per-graph resume cache
    (tpu_bfs/resilience/resume — host real-id checkpoints through the
    PR 4 CRC machinery, portable across mesh shapes). A later dispatch
    of the same source — e.g. the service's re-admission after a mesh
    fault, on an engine rebuilt over a DEGRADED mesh — starts from the
    last intact level instead of the source: bounded recompute <= K
    levels. Completed runs drop their snapshots."""

    def __init__(
        self,
        graph: Graph,
        mesh: Mesh,
        *,
        lanes: int = 32,
        exchange: str = "ring",
        backend: str = "dopt",
        wire_pack: bool = False,
        delta_bits: tuple[int, ...] = (),
        sieve: bool = False,
        predict: bool = False,
        resume_levels: int = 0,
    ):
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if resume_levels < 0:
            raise ValueError(
                f"resume_levels must be >= 0, got {resume_levels}"
            )
        self.lanes = int(lanes)
        self.resume_levels = int(resume_levels)
        if resume_levels:
            from tpu_bfs.resilience.resume import (
                ResumePolicy,
                cache_for_graph,
            )

            self._resume = ResumePolicy(every_levels=int(resume_levels))
            self._resume_cache = cache_for_graph(graph)
        else:
            self._resume = None
            self._resume_cache = None
        self.engine = Dist2DBfsEngine(
            graph, mesh, exchange=exchange, backend=backend,
            wire_pack=wire_pack, delta_bits=delta_bits, sieve=sieve,
            predict=predict,
        )
        eng = self.engine
        self._undirected = graph.undirected
        # Per-run on-device summaries: padded phantoms are never reached,
        # so the reductions over the padded space equal the real-vertex
        # figures; the sums ride GSPMD all-reduces, not host pulls.
        part = eng.part
        deg_pad = np.zeros(part.vp, dtype=np.uint32)
        deg_pad[part.to_padded(np.arange(graph.num_vertices))] = (
            graph.degrees.astype(np.uint32)
        )
        deg_dev = jax.device_put(deg_pad, eng._vec_sharding)

        @jax.jit
        def run_stats(dist):
            # 32-bit on purpose (the analysis dtype lint bans 64-bit
            # avals): reached <= V < 2^31 fits int32; the edge-slot sum
            # rides uint32, which holds the Graph500 scale-26 slot count
            # (2E ~ 2^31.1) — revisit past scale 27.
            fin = dist != INT32_MAX
            reached = jnp.sum(fin.astype(jnp.int32))
            ecc = jnp.max(jnp.where(fin, dist, 0))
            edges = jnp.sum(jnp.where(fin, deg_dev, jnp.uint32(0)))
            return reached, ecc, edges

        self._run_stats = run_stats
        #: modeled off-chip bytes one chip moved for the LAST fetched
        #: batch (summed over its unique runs) — the serve tier's
        #: wire-bytes-per-query record.
        self.last_exchange_bytes: float | None = None

    # --- passthroughs the serve/obs/analysis layers read ------------------

    @property
    def mesh(self):
        return self.engine.mesh

    @property
    def num_vertices(self) -> int:
        return self.engine.part.base.num_vertices

    @property
    def max_levels_cap(self) -> int:
        """Deepest level bound a dispatch can run (the khop adapter's
        clamp point, ISSUE 20). The 2D loop labels int32 distances with
        no plane cap, so the bound is the padded vertex count — the
        trivial upper bound on any eccentricity."""
        return int(self.engine.part.vp)

    @property
    def last_run_trace(self):
        return self.engine.last_run_trace

    @property
    def _aot_adopted(self):
        return getattr(self.engine, "_aot_adopted", ())

    def exchange_branch_labels(self):
        return self.engine.exchange_branch_labels()

    def wire_bytes_per_level(self):
        return self.engine.wire_bytes_per_level()

    def analysis_programs(self):
        return self.engine.analysis_programs()

    def export_programs(self):
        return self.engine.export_programs()

    def adopt_programs(self, programs: dict) -> list:
        return self.engine.adopt_programs(programs)

    # --- the dispatch/fetch serving protocol ------------------------------

    @property
    def _devices_n(self) -> int:
        from tpu_bfs.faults import mesh_devices

        return mesh_devices(self)

    def dispatch(self, sources, *, max_levels: int | None = None) -> _Pending2D:
        from tpu_bfs import faults as _faults

        eng = self.engine
        if _faults.ACTIVE is not None:
            # Mesh-site chaos consultation (ISSUE 12): device_lost /
            # collective_hang / backend_restart rules target this
            # engine's launches; devices context feeds rank qualifiers.
            _faults.ACTIVE.hit(
                "dispatch", lanes=self.lanes, devices=self._devices_n
            )
        sources = np.asarray(sources, dtype=np.int64)
        if len(sources) > self.lanes:
            raise ValueError(
                f"batch of {len(sources)} exceeds {self.lanes} lanes"
            )
        nv = self.num_vertices
        if sources.size and (sources.min() < 0 or sources.max() >= nv):
            raise ValueError(f"source out of range [0, {nv})")
        uniq, inv = np.unique(sources, return_inverse=True)
        total_cap = int(max_levels if max_levels is not None else eng.part.vp)
        runs, stats = [], []
        if self._resume is None:
            for s in uniq:
                f0, vis0, d0 = eng._init_state(int(s))
                out = eng._loop(
                    eng.src_g, eng.dst_l, eng.rp, eng._aux, f0, vis0, d0,
                    jnp.int32(0), jnp.int32(total_cap),
                )
                runs.append(out)
                stats.append(self._run_stats(out[2]))
            return _Pending2D(sources=sources, uniq=uniq, inv=inv,
                              runs=runs, stats=stats, total_cap=total_cap)
        # Resume mode: launch each run's FIRST chunk async (K levels);
        # fetch drives the remaining chunks. A source with an intact
        # snapshot — typically left by a mesh-faulted predecessor engine
        # over the same graph — starts from its last checkpointed level.
        from tpu_bfs.utils.checkpoint import _new_nonce

        k = self._resume.every_levels
        cursors = []
        for s in uniq:
            s = int(s)
            start, nonce = 0, _new_nonce()
            f0 = vis0 = d0 = None
            ckpt = self._resume_cache.get(s)
            if (
                ckpt is not None and ckpt.source == s
                and len(ckpt.frontier) == nv
                # A snapshot DEEPER than this call's level cap cannot be
                # adopted: the capped loop would no-op and hand back
                # levels/distances beyond the requested bound. Start
                # over instead (max_levels-capped calls are the one-shot
                # API's; the serve path always runs to termination).
                and int(ckpt.level) <= total_cap
            ):
                fh, vh, dh = eng._pad_state(ckpt)
                put = partial(jax.device_put, device=eng._vec_sharding)
                f0, vis0, d0 = put(fh), put(vh), put(dh)
                start = int(ckpt.level)
                nonce = ckpt.nonce
                self._resume_cache.mark_resumed(s)
            if f0 is None:
                f0, vis0, d0 = eng._init_state(s)
            cap = min(start + k, total_cap)
            out = eng._loop(
                eng.src_g, eng.dst_l, eng.rp, eng._aux, f0, vis0, d0,
                jnp.int32(start), jnp.int32(cap),
            )
            runs.append(out)
            stats.append(None)  # final-chunk stats land in fetch
            cursors.append({
                "source": s, "start": start, "nonce": nonce,
                "t0": time.monotonic(),
            })
        return _Pending2D(sources=sources, uniq=uniq, inv=inv, runs=runs,
                          stats=stats, cursors=cursors, total_cap=total_cap)

    def _drive_chunks(self, pend: _Pending2D, u: int):
        """Complete run ``u``: block each chunk, snapshot the carry at
        chunk boundaries (the resume cache's CRC-checkpoint machinery),
        relaunch from the DEVICE outputs (no host round trip for the
        carry itself), and return the final ``(loop outputs, stats)``.
        A mesh kind injected at the fetch site fires here mid-query —
        after >= 1 snapshot — so the failover's re-dispatch proves the
        bounded-recompute contract."""
        from tpu_bfs import faults as _faults
        from tpu_bfs.utils.checkpoint import BfsCheckpoint

        eng = self.engine
        cur = pend.cursors[u]
        k = self._resume.every_levels
        out = pend.runs[u]
        clock0 = cur["t0"]
        while True:
            if _faults.ACTIVE is not None:
                # ``level`` context = the in-flight chunk's start level,
                # so a schedule can target "the chunk after level N"
                # deterministically (scripts/mesh_chaos_smoke.py).
                _faults.ACTIVE.hit(
                    "fetch", lanes=self.lanes, devices=self._devices_n,
                    level=cur["start"],
                )
            frontier, visited, dist, level, front_seq, bc, bs = out
            level_i = int(level)  # blocks until the chunk finishes
            eng._record_exchange(
                bc, resumed_level=cur["start"], chain_nonce=cur["nonce"]
            )
            eng._record_trace(
                front_seq, bs, level_i - cur["start"], cur["start"]
            )
            f_host = np.asarray(frontier)
            if not f_host.any() or level_i >= pend.total_cap:
                self._resume_cache.drop(cur["source"])
                return out, self._run_stats(dist)
            if self._resume.should_snapshot(
                level_i, time.monotonic() - clock0
            ):
                part = eng.part
                self._resume_cache.put(cur["source"], BfsCheckpoint(
                    source=cur["source"], level=level_i,
                    frontier=part.unshard(f_host),
                    visited=part.unshard(np.asarray(visited)),
                    distance=part.unshard(np.asarray(dist)),
                    nonce=cur["nonce"],
                ))
            cur["start"] = level_i
            out = eng._loop(
                eng.src_g, eng.dst_l, eng.rp, eng._aux,
                frontier, visited, dist,
                jnp.int32(level_i),
                jnp.int32(min(level_i + k, pend.total_cap)),
            )

    def fetch(self, pend: _Pending2D, *, check_cap: bool = True,
              **_ignored) -> Dist2DServeResult:
        # ``check_cap`` is accepted for dispatch/fetch protocol
        # uniformity (the khop adapter passes it): the 2D loop's level
        # bound defaults to the padded vertex count, above any
        # eccentricity, so a capped run here is always the CALLER's
        # explicit max_levels — stopping at it is the point, never a
        # truncation to flag.
        from tpu_bfs import faults as _faults

        if _faults.ACTIVE is not None:
            # The blocking half's mesh-site consultation (no ``level``
            # context here — the chunked drive below consults per chunk
            # for level-targeted rules).
            _faults.ACTIVE.hit(
                "fetch", lanes=self.lanes, devices=self._devices_n
            )
        eng = self.engine
        u_count = len(pend.uniq)
        reached_u = np.empty(u_count, dtype=np.int64)
        ecc_u = np.empty(u_count, dtype=np.int32)
        edges_u = np.empty(u_count, dtype=np.int64)
        dists = []
        wire = 0.0
        for u, (out, st) in enumerate(zip(pend.runs, pend.stats)):
            if pend.cursors is not None:
                # Chunked resume drive: accounting is recorded per chunk
                # inside (chain-nonce-merged across chunks, so
                # last_exchange_* covers the whole query).
                out, st = self._drive_chunks(pend, u)
                dist = out[2]
            else:
                _, _, dist, level, front_seq, branch_counts, branch_seq = out
                # Per-run accounting: the branch counters price this
                # run's exchange; the LAST run's trace stands for the
                # batch (the unified last_run_trace contract).
                eng._record_exchange(branch_counts)
                eng._record_trace(front_seq, branch_seq, int(level), 0)
            wire += float(eng.last_exchange_bytes or 0.0)
            reached_u[u] = int(st[0])
            ecc_u[u] = int(st[1])
            edges_u[u] = int(st[2])
            dists.append(dist)
        self.last_exchange_bytes = wire
        inv = pend.inv
        edges = edges_u[inv]
        if self._undirected:
            edges = edges // 2
        return Dist2DServeResult(
            eng.part, dists, inv, pend.sources,
            reached_u[inv], ecc_u[inv], edges,
        )

    def run(self, sources, *, max_levels: int | None = None,
            time_it: bool = False) -> Dist2DServeResult:
        """Blocking batch entry (registry warm-up and one-shot callers);
        ``time_it`` is accepted for protocol uniformity."""
        return self.fetch(self.dispatch(sources, max_levels=max_levels))
