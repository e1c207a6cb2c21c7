"""Multi-chip distributed BFS.

The TPU-native replacement for BOTH reference drivers — single-process
multi-GPU ``runCudaQueueBfs`` (bfs.cu:542-629) and the MPI fork
(bfs_mpi.cu:549-643) — as ONE code path: a `lax.while_loop` level loop inside
`jax.shard_map` over a 1D device mesh. Per level, each chip:

  1. expands its owned frontier over its local (source-sharded) edges into a
     full-size contribution bitmap (the analog of the per-destination buckets,
     bfs.cu:148-150),
  2. reduce-scatters the bitmaps with OR over the mesh axis (replacing
     cudaMemcpyPeer, bfs.cu:604-606, and MPI_Sendrecv, bfs_mpi.cu:615),
  3. claims unvisited vertices in its owned slice (replacing the atomicMin
     claim, bfs.cu:146),
  4. psums the new-frontier popcount for global termination (replacing
     MPI_Allreduce, bfs_mpi.cu:621, and the host-side queueSize sum,
     bfs.cu:569).

No host round-trips during the traversal — the reference crosses host<->device
four times per level (SURVEY.md §3.1).
"""

from __future__ import annotations

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
    default_sparse_caps,
    dense_or_wire_bytes,
    gate_and_stamp_chain,
    merge_exchange_counts,
    normalize_caps,
    planned_branch_count,
    planned_branch_labels,
    planned_sparse_exchange_or,
    planned_sparse_wire_bytes_per_level,
    reduce_scatter_or,
    reduce_scatter_min,
    rows_gather_branch_labels,
    sparse_exchange_or,
    sparse_wire_bytes_per_level,
)
from tpu_bfs.obs.engine_trace import TRACE_LEVELS, assemble_dist_trace
from tpu_bfs.parallel.partition import out_csr_1d, partition_1d
from tpu_bfs.utils.timing import run_timed


def make_mesh(num_devices: int | None = None, devices=None) -> Mesh:
    """1D device mesh over the vertex-partition axis 'v'.

    Runtime-configurable, unlike the reference's compile-time DeviceNum
    (bfs.cu:19 — changing device count means recompiling)."""
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    f"requested {num_devices} devices, only {len(devices)} available"
                )
            devices = devices[:num_devices]
    return Mesh(np.array(devices), ("v",))


def _dist_bfs_fn(
    mesh: Mesh, p: int, vloc: int, exchange: str, backend: str,
    sparse_caps: tuple[int, ...], dopt_caps: tuple[int, ...] = (),
    wire_pack: bool = False, delta_bits: tuple[int, ...] = (),
    sieve: bool = False, predict: bool = False,
):
    """Build the shard_map'd BFS level loop for a fixed mesh/partition.

    ``exchange='sparse'`` swaps the dense bitmap reduce-scatter for the
    two-phase queue-style exchange (collectives.sparse_exchange_or — the
    analog of the reference's per-destination buckets, bfs.cu:148-150).
    The loop carry counts, per exchange branch, how many levels ran it
    (exact int32 — wire bytes are reconstructed on the host, immune to the
    float rounding a byte accumulator would hit at scale).

    ``backend='dopt'`` runs the direction-optimizing expansion per chip:
    each chip independently picks the sparse top-down branch when its OWN
    frontier's local out-degree sum fits a ``dopt_caps`` rung (the branch
    is collective-free, so per-chip divergence is safe — exchange and
    termination collectives sit outside the `lax.cond`).

    ``wire_pack=True`` ships every boolean exchange bit-packed (uint32
    words, 32 vertices/word — collectives.pack_bits): the dense ring/
    allreduce paths and the sparse exchange's dense fallback; the sparse
    id rungs already move 4-byte ids. Same collective count, 1/8-1/32 the
    bytes (wirecheck.check_packed_exchange proves it from the HLO).

    ``delta_bits`` / ``sieve`` / ``predict`` (ISSUE 7, sparse exchange
    only) swap the cap ladder for the full exchange planner
    (collectives.planned_sparse_exchange_or): delta-encoded id chunks, a
    backward visited sieve, and history-predictive dense selection. The
    loop carry gains three mesh-uniform scalars for it — the previous
    measured ``biggest``, the previous frontier popcount (growth), and
    the cumulative visited total (all derived from psum/pmax outputs, so
    every chip carries identical values and the planner's branches stay
    matched).

    The carry also records two tiny per-level arrays for the engine trace
    (tpu_bfs/obs/engine_trace, ISSUE 6): the new-frontier popcount and
    the exchange-branch index of each level, in [TRACE_LEVELS] int32
    slots (levels past the window clamp into the last slot). Both reuse
    scalars the loop already computes — the termination psum and the
    ladder branch — so the recording is two dynamic-updates of 256-byte
    replicated arrays per level, collective-free."""
    planned = exchange == "sparse" and bool(delta_bits or sieve or predict)
    if planned:
        nb = planned_branch_count(sparse_caps, delta_bits)
    else:
        nb = len(sparse_caps) + 1 if exchange == "sparse" else 1
    dopt = backend == "dopt"

    def local_loop(
        src_e, dst_e, rp_e, aux, frontier, visited, dist, level0, max_levels
    ):
        # Blocks: src_e/dst_e [1, ep], rp_e [1, vp+1], vertex arrays [vloc].
        src_e = src_e[0]
        dst_e = dst_e[0]
        rp_e = rp_e[0]
        k = lax.axis_index("v")
        src_local = src_e - k * vloc  # sources are owned: always in [0, vloc)
        vp = p * vloc

        def dense_fn(frontier):
            active = frontier[src_local]
            return expand_or(
                active, dst_e, rp_e, vp, backend="scan" if dopt else backend
            )

        if dopt:
            edata = EdgeData(
                src=src_e, dst=dst_e, in_rp=rp_e,
                out_rp=aux[0][0],  # [vloc+1] CSR-by-local-src
                nbr_sm=aux[1][0],  # [ep] global padded dst, src-major
            )
            expand_local = make_dopt_expand(
                edata, dopt_caps, vert_limit=vloc, out_size=vp,
                dense_fn=dense_fn,
            )
        else:
            expand_local = dense_fn

        def cond(state):
            front_count, level = state[4], state[3]
            return (front_count > 0) & (level < max_levels)

        def body(state):
            # The planner's history scalars extend the carry ONLY when a
            # planner feature is on — the legacy programs stay carry-for-
            # carry identical (compile time and HLO unchanged).
            if planned:
                (frontier, visited, dist, level, front_count, branch_counts,
                 front_seq, branch_seq, prev_biggest, prev_count,
                 vis_total) = state
            else:
                (frontier, visited, dist, level, front_count, branch_counts,
                 front_seq, branch_seq) = state
            contrib = expand_local(frontier)
            if planned:
                hit, branch, biggest = planned_sparse_exchange_or(
                    contrib, "v", p, caps=sparse_caps, delta_bits=delta_bits,
                    sieve=sieve, visited=visited, visited_total=vis_total,
                    predict=predict, prev_biggest=prev_biggest,
                    growing=front_count >= prev_count, wire_pack=wire_pack,
                )
            elif exchange == "sparse":
                hit, branch = sparse_exchange_or(
                    contrib, "v", p, caps=sparse_caps, wire_pack=wire_pack
                )
            else:
                hit = reduce_scatter_or(
                    contrib, "v", p, impl=exchange, wire_pack=wire_pack
                )
                branch = jnp.int32(0)
            branch_counts = branch_counts + (
                jnp.arange(nb, dtype=jnp.int32) == branch
            )
            new = hit & ~visited
            dist = jnp.where(new, level + 1, dist)
            visited = visited | new
            count = lax.psum(jnp.sum(new.astype(jnp.int32)), "v")
            # Engine-trace slot for the level just EXPANDED (relative to
            # this invocation's resume point; the assembler re-offsets).
            # Frontier counts ADD so the clamp slot aggregates every
            # level past the window (frontier_total stays exact); the
            # branch index is last-write-wins there (documented in
            # engine_trace.assemble_dist_trace).
            slot = jnp.minimum(level - level0, TRACE_LEVELS - 1)
            front_seq = front_seq.at[slot].add(count)
            branch_seq = branch_seq.at[slot].set(branch)
            out = (new, visited, dist, level + 1, count, branch_counts,
                   front_seq, branch_seq)
            if planned:
                out = out + (biggest, front_count, vis_total + count)
            return out

        init_count = lax.psum(jnp.sum(frontier.astype(jnp.int32)), "v")
        init = (frontier, visited, dist, jnp.int32(level0), init_count,
                jnp.zeros(nb, jnp.int32),
                jnp.zeros(TRACE_LEVELS, jnp.int32),
                jnp.full(TRACE_LEVELS, -1, jnp.int32))
        if planned:
            # Planner history seeds: biggest unknown (-1 blocks prediction
            # until the first measured level), no previous frontier, and
            # the cumulative visited popcount (psum'd, so mesh-uniform
            # like every carried planner scalar).
            init = init + (
                jnp.int32(-1), jnp.int32(0),
                lax.psum(jnp.sum(visited.astype(jnp.int32)), "v"),
            )
        out = lax.while_loop(cond, body, init)
        (frontier, visited, dist, level, _, branch_counts, front_seq,
         branch_seq) = out[:8]
        return frontier, visited, dist, level, branch_counts, front_seq, branch_seq

    aux_specs = (P("v", None), P("v", None)) if dopt else ()
    # The carry (frontier/visited/dist, argnums 4-6) is DONATED (ISSUE
    # 13, analysis pass 5): every call site constructs it fresh —
    # _init_state and advance's device_put both materialize distinct
    # buffers per call, and the serve adapter's chunked relaunch reads
    # its snapshot BEFORE handing the carry back in — so the loop's
    # outputs alias the inputs instead of doubling the sharded vectors'
    # residency. The analyzer's transfer-guard drive copies donated args
    # per invocation (analysis/transfer.py keys on _donate_argnums).
    fn = jax.jit(
        shard_map(
            local_loop,
            mesh=mesh,
            in_specs=(
                P("v", None),
                P("v", None),
                P("v", None),
                aux_specs,
                P("v"),
                P("v"),
                P("v"),
                P(),
                P(),
            ),
            out_specs=(P("v"), P("v"), P("v"), P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(4, 5, 6),
    )
    fn._donate_argnums = (4, 5, 6)
    return fn


def _dist_parents_fn(mesh: Mesh, p: int, vloc: int, exchange: str):
    """Post-loop deterministic parent extraction, distributed.

    Each chip all-gathers the final (padded-id) distance vector once — the
    analog of the reference's result merge download (finalizeCudaBfs,
    bfs.cu:424-441) — then scatter-mins parent candidates from its local
    edges and reduce-scatter-mins back to owners."""

    def local_parents(src_e, dst_e, dist_loc):
        src_e = src_e[0]
        dst_e = dst_e[0]
        vp = p * vloc
        dist_full = lax.all_gather(dist_loc, "v", tiled=True)  # [vp]
        du = dist_full[src_e]
        ok = (du != INT32_MAX) & (du + 1 == dist_full[dst_e])
        cand = jnp.where(ok, src_e, INT32_MAX)
        contrib = (
            jnp.full((vp,), INT32_MAX, jnp.int32).at[dst_e].min(cand, mode="drop")
        )
        parent_loc = reduce_scatter_min(contrib, "v", p, impl=exchange)
        parent_loc = jnp.where(parent_loc == INT32_MAX, -1, parent_loc)
        return jnp.where(dist_loc == INT32_MAX, -1, parent_loc)

    return jax.jit(
        shard_map(
            local_parents,
            mesh=mesh,
            in_specs=(P("v", None), P("v", None), P("v")),
            out_specs=P("v"),
            check_vma=False,
        )
    )


class VertexCheckpointMixin:
    """Checkpoint/resume shared by the distributed single-source engines
    (1D vertex partition and 2D edge partition; SURVEY.md §5: the
    reference has none).

    Checkpoints hold real-id [V] arrays, portable across engines, mesh
    shapes AND partition topologies — a traversal checkpointed under the
    1D partition resumes under the 2D edge partition mid-flight (elastic
    restart; the reference's compile-time DeviceNum, bfs.cu:19, and fixed
    2-rank world, bfs_mpi.cu:615, have no analog). Engines provide
    ``part`` (to_padded/unshard/vp), ``_num_real_vertices``,
    ``_vec_sharding``, ``_package``, and ``_advance_loop(f, vis, d,
    level0, cap)`` — the engine-specific jitted loop invocation plus its
    exchange accounting, returning (frontier, visited, dist, level)."""

    def start(self, source: int):
        """Level-0 traversal state as a host checkpoint (real vertex ids)."""
        from tpu_bfs.utils.checkpoint import initial_checkpoint

        return initial_checkpoint(self._num_real_vertices, source)

    def _pad_state(self, ckpt):
        """Real-id [V] checkpoint arrays -> padded-id [vp] arrays."""
        part = self.part
        if not hasattr(self, "_pids"):  # constant for the engine's lifetime
            self._pids = part.to_padded(np.arange(self._num_real_vertices))
        pids = self._pids
        f = np.zeros(part.vp, dtype=bool)
        f[pids] = ckpt.frontier
        vis = np.zeros(part.vp, dtype=bool)
        vis[pids] = ckpt.visited
        d = np.full(part.vp, INF_DIST, dtype=np.int32)
        d[pids] = ckpt.distance
        return f, vis, d

    def advance(self, ckpt, levels: int | None = None):
        """Run at most ``levels`` more levels across the mesh from a checkpoint."""
        from tpu_bfs.utils.checkpoint import BfsCheckpoint

        part = self.part
        if len(ckpt.frontier) != self._num_real_vertices:
            raise ValueError(
                f"checkpoint has {len(ckpt.frontier)} vertices, graph has "
                f"{self._num_real_vertices}"
            )
        f0, vis0, d0 = self._pad_state(ckpt)
        put = partial(jax.device_put, device=self._vec_sharding)
        cap = ckpt.level + levels if levels is not None else part.vp
        frontier, visited, dist, level = self._advance_loop(
            put(f0), put(vis0), put(d0), ckpt.level, min(cap, part.vp),
            chain_nonce=getattr(ckpt, "nonce", None),
        )
        return BfsCheckpoint(
            source=ckpt.source,
            level=int(level),
            frontier=part.unshard(np.asarray(frontier)),
            visited=part.unshard(np.asarray(visited)),
            distance=part.unshard(np.asarray(dist)),
            nonce=getattr(ckpt, "nonce", None),  # chain identity survives chunks
        )

    def finish(self, ckpt, *, with_parents: bool = True):
        """Convert a (finished or partial) checkpoint into a BfsResult."""
        _, _, d0 = self._pad_state(ckpt)
        put = partial(jax.device_put, device=self._vec_sharding)
        return self._package(put(d0), ckpt.source, with_parents, None)


class DistBfsEngine(VertexCheckpointMixin):
    """Multi-chip BFS over a 1D vertex partition.

    Usage mirrors BfsEngine but scales over a mesh; with a 1-device mesh it
    degrades to the single-chip path (the reference instead forks a whole
    second file for multi-node, bfs_mpi.cu)."""

    def __init__(
        self,
        graph: Graph,
        mesh: Mesh | None = None,
        *,
        num_devices: int | None = None,
        exchange: str = "ring",
        backend: str = "scan",
        sparse_caps: int | tuple[int, ...] | None = None,
        dopt_caps: tuple[int, ...] | None = None,
        wire_pack: bool = False,
        delta_bits: tuple[int, ...] = (),
        sieve: bool = False,
        predict: bool = False,
    ):
        if exchange not in ("ring", "allreduce", "sparse"):
            # Before the partition/device_put work, so a typo fails instantly.
            raise ValueError(
                f"unknown exchange {exchange!r}; have 'ring', 'allreduce', 'sparse'"
            )
        if (delta_bits or sieve or predict) and exchange != "sparse":
            raise ValueError(
                "delta_bits/sieve/predict reshape the SPARSE exchange "
                f"(the ISSUE 7 planner); exchange={exchange!r} has no id "
                "buffers to compress — use exchange='sparse'"
            )
        self._exchange = exchange
        #: bit-packed wire format (ISSUE 5): boolean exchanges ship uint32
        #: words, 32 vertices/word; results are bit-identical to unpacked
        #: (fuzz-pinned), only the wire encoding changes. Default OFF until
        #: chip-measured, like the pull gate.
        self.wire_pack = bool(wire_pack)
        #: ISSUE 7 exchange planner knobs (sparse exchange only; all
        #: default OFF until chip-measured, like wire_pack): delta-encoded
        #: id chunks, the backward visited sieve, and history-predictive
        #: dense selection. Results stay bit-identical to the plain sparse
        #: exchange (fuzz-pinned); only wire encoding and scalar traffic
        #: change.
        self.delta_bits = check_delta_bits(delta_bits)
        self.sieve = bool(sieve)
        self.predict = bool(predict)
        self._planned = exchange == "sparse" and bool(
            self.delta_bits or self.sieve or self.predict
        )
        self.mesh = mesh if mesh is not None else make_mesh(num_devices)
        self.p = self.mesh.devices.size
        self.graph_meta = (graph.num_input_edges, graph.undirected)
        part, src_stacked, dst_stacked, rp_stacked = partition_1d(graph, self.p)
        self.part = part
        self._degrees = graph.degrees  # host copy for TEPS accounting
        edge_sharding = NamedSharding(self.mesh, P("v", None))
        self.src = jax.device_put(src_stacked, edge_sharding)
        self.dst = jax.device_put(dst_stacked, edge_sharding)
        self.rp = jax.device_put(rp_stacked, edge_sharding)
        self._vec_sharding = NamedSharding(self.mesh, P("v"))
        self._aux = ()
        if backend == "dopt":
            # Src-major per-chip view + caps ladder for the top-down branch
            # (same rungs as BfsEngine's, scaled to the per-chip shard).
            out_rp, nbr = out_csr_1d(part, src_stacked, dst_stacked)
            self._aux = (
                jax.device_put(out_rp, edge_sharding),
                jax.device_put(nbr, edge_sharding),
            )
            if dopt_caps is None:
                dopt_caps = default_dopt_caps(part.ep_chip)
        self.dopt_caps = tuple(sorted(set(dopt_caps))) if dopt_caps else ()
        if sparse_caps is None:
            # The ladder calibrates against the dense fallback it competes
            # with AND the id encoding's per-entry cost: the packed bitmap
            # costs 1/8 (rungs three octaves lower), delta-encoded ids
            # cost min(delta_bits)/32 of plain (rungs shifted back up) —
            # collectives.default_sparse_caps.
            sparse_caps = default_sparse_caps(
                part.vloc, wire_pack=self.wire_pack,
                delta_bits=self.delta_bits,
            )
        elif isinstance(sparse_caps, int):
            sparse_caps = (sparse_caps,)
        self.sparse_caps = normalize_caps(sparse_caps)
        self._loop = _dist_bfs_fn(
            self.mesh, self.p, part.vloc, exchange, backend, self.sparse_caps,
            self.dopt_caps, self.wire_pack, self.delta_bits, self.sieve,
            self.predict,
        )
        # Parent merge is a one-shot int32 MIN reduce-scatter — queue-style
        # exchange does not apply; 'sparse' rides the ring there.
        parent_impl = "ring" if exchange == "sparse" else exchange
        self._parents = _dist_parents_fn(self.mesh, self.p, part.vloc, parent_impl)
        #: per-branch level counts of the last traversal (ascending sparse
        #: caps then dense fallback; dense impls have the single entry) and
        #: the off-chip bytes one chip moved — set by distances_padded/advance.
        self.last_exchange_level_counts: np.ndarray | None = None
        self.last_exchange_bytes: float | None = None
        # Raw loop carries of the last core invocation; the per-level
        # rows assemble lazily on first last_run_trace access (property
        # below) so the device->host transfers and row building stay out
        # of run_timed's wall clock.
        self._trace_pending: tuple | None = None
        self._trace_cache: list[dict] | None = None
        self._direction = "dopt" if backend == "dopt" else "push"
        self._warmed = False

    def wire_bytes_per_level(self) -> list[float]:
        """Modeled off-chip bytes one chip moves per level, per exchange
        branch (ascending sparse caps then the dense fallback; the dense
        impls have the single entry; the ISSUE 7 planner's full layout
        when delta/sieve/predict are on — ``exchange_branch_labels()``
        names the entries) — the price list behind
        ``last_exchange_bytes``, and the feed for the bench verdict's
        ``wire_bytes_per_level`` key (TPU_BFS_BENCH_MODE=dist) and the
        BENCHMARKS.md "Exchange bytes" table."""
        if self._planned:
            return planned_sparse_wire_bytes_per_level(
                self.p, self.part.vloc, self.sparse_caps, self.delta_bits,
                wire_pack=self.wire_pack,
            )
        if self._exchange == "sparse":
            return sparse_wire_bytes_per_level(
                self.p, self.part.vloc, self.sparse_caps,
                wire_pack=self.wire_pack,
            )
        return [
            dense_or_wire_bytes(
                self.p, self.part.vloc, self._exchange,
                wire_pack=self.wire_pack,
            )
        ]

    def exchange_branch_labels(self) -> list[str] | None:
        """Branch labels index-aligned with ``wire_bytes_per_level()`` /
        ``last_exchange_level_counts`` — the engine-trace hook
        (obs/engine_trace reads this when present); None for the dense
        impls (one branch, labeled by the impl itself)."""
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
        self.last_exchange_bytes = float(np.dot(counts, self.wire_bytes_per_level()))

    @property
    def last_run_trace(self) -> list[dict] | None:
        """Per-level rows of the last core invocation (frontier count,
        direction, exchange choice, modeled wire bytes) — the unified
        engine-trace contract (tpu_bfs/obs/engine_trace, ISSUE 6).
        Assembled lazily from the stashed loop carries so the timed path
        pays nothing for the trace."""
        pend = self._trace_pending
        if pend is not None:
            level, front_seq, branch_seq, level0 = pend
            self._trace_pending = None
            self._trace_cache = assemble_dist_trace(
                self, int(level) - level0, front_seq, branch_seq,
                direction=self._direction, level0=level0,
            )
        return self._trace_cache

    @last_run_trace.setter
    def last_run_trace(self, rows: list[dict] | None) -> None:
        # The roofline walk overwrites the trace with its own (richer,
        # exact-frontier) rows — honor direct assignment.
        self._trace_pending = None
        self._trace_cache = rows

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
        """Jit entry points + device-resident example args for the static
        analyzer (tpu_bfs/analysis): the level loop whose branch
        uniformity the taint pass proves, and the parent merge. Scalars
        are pre-placed replicated so the transfer-guard drive sees only
        what a real run transfers."""
        f0, vis0, d0 = self._init_state(0)
        rep = NamedSharding(self.mesh, P())
        l0, ml = (
            jax.device_put(jnp.int32(0), rep),
            jax.device_put(jnp.int32(64), rep),
        )
        return [
            ("level_loop", self._loop,
             (self.src, self.dst, self.rp, self._aux, f0, vis0, d0, l0, ml)),
            ("parents", self._parents, (self.src, self.dst, d0)),
        ]

    def distances_padded(self, source: int, *, max_levels: int | None = None):
        """Device (padded-id, sharded) distance vector + level counter."""
        frontier0, visited0, dist0 = self._init_state(source)
        ml = jnp.int32(max_levels if max_levels is not None else self.part.vp)
        _, _, dist, level, branch_counts, front_seq, branch_seq = self._loop(
            self.src, self.dst, self.rp, self._aux, frontier0, visited0, dist0,
            jnp.int32(0), ml,
        )
        self._record_exchange(branch_counts)
        self._trace_pending = (level, front_seq, branch_seq, 0)
        self._trace_cache = None
        return dist, level

    # --- checkpoint/resume: VertexCheckpointMixin provides
    # start/advance/finish over this hook. ---

    @property
    def _num_real_vertices(self) -> int:
        return self.part.num_vertices

    def _advance_loop(self, f0, vis0, d0, level0: int, cap: int, *, chain_nonce=None):
        frontier, visited, dist, level, branch_counts, front_seq, branch_seq = (
            self._loop(
                self.src, self.dst, self.rp, self._aux, f0, vis0, d0,
                jnp.int32(level0), jnp.int32(cap),
            )
        )
        self._record_exchange(
            branch_counts, resumed_level=level0, chain_nonce=chain_nonce
        )
        self._trace_pending = (level, front_seq, branch_seq, level0)
        self._trace_cache = None
        return frontier, visited, dist, level

    def run(
        self,
        source: int,
        *,
        max_levels: int | None = None,
        with_parents: bool = True,
        time_it: bool = False,
    ) -> BfsResult:
        part = self.part
        if not (0 <= source < part.num_vertices):
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
            parent_dev = self._parents(self.src, self.dst, dist_dev)
            parent_pad = part.unshard(np.asarray(parent_dev))
            # Padded ids -> real ids; -1 passes through; source -> itself.
            parent = np.where(
                parent_pad >= 0, part.from_padded(np.abs(parent_pad)), -1
            ).astype(np.int32)
            parent[source] = source

        dist = part.unshard(np.asarray(dist_dev))
        reached_mask = dist != INF_DIST
        reached = int(reached_mask.sum())
        num_levels = int(dist[reached_mask].max()) if reached else 0
        m_in, undirected = self.graph_meta
        # TEPS numerator from reached degrees: sum of degrees over reached
        # vertices counts each traversed slot once from its source side.
        slots = int(self._degrees[reached_mask].sum()) if reached else 0
        edges = slots // 2 if undirected else slots
        return BfsResult(
            source=source,
            distance=dist,
            parent=parent,
            num_levels=num_levels,
            reached=reached,
            edges_traversed=edges,
            elapsed_s=elapsed,
        )
