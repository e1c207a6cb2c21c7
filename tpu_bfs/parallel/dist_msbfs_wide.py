"""Distributed 4096-lane bit-packed multi-source BFS over a 1D device mesh.

The multi-chip form of the wide engine (tpu_bfs/algorithms/msbfs_wide.py),
sharing its batch driver and lazy extraction through _packed_common. Compared
to the reference's distribution — full CSR replicated to every device
(initCuda2, bfs.cu:346-351), with only distance *ownership* split — this
shards the expensive thing (the ELL edge structure, dealt round-robin over
degree-sorted rows so every chip gets the same degree mix) and replicates the
cheap thing (the packed frontier words, V * 4W bytes regardless of E):

- per level each chip expands only its owned rows through its ELL shard,
  claims ``& ~visited`` on owned words, and ``all_gather`` over the mesh
  rebuilds the replicated frontier (replacing cudaMemcpyPeer, bfs.cu:604-606,
  and MPI_Sendrecv, bfs_mpi.cu:615);
- termination reads the gathered frontier, so no extra Allreduce
  (bfs_mpi.cu:621) and zero host round-trips inside the level loop;
- the same shard_map program serves ICI and DCN meshes, collapsing the
  reference's two near-identical source files into one driver.

Row layout after the run is chip-major: row ``p * v_loc + l`` of the
reassembled tables holds global rank ``l * P + p``; ``_rank`` maps original
vertex ids straight to chip-major rows so the shared lazy extraction works
unchanged.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import ShardedEllGraph, build_ell_sharded
from tpu_bfs.ops.ell_expand import resolve_interpret
from tpu_bfs.algorithms.msbfs_packed import ripple_increment
from tpu_bfs.algorithms._packed_common import (
    AotProgramProtocol,
    ExpandSpec,
    PackedRunProtocol,
    lazy_full_parent_ell,
    make_expand,
    make_state_kernels,
    validate_expand_impl,
)
from tpu_bfs.parallel.collectives import (
    RowGatherExchangeAccounting,
    check_delta_bits,
    default_row_gather_caps,
    normalize_caps,
    rows_gather_branch_count,
    sparse_rows_gather,
)
from tpu_bfs.parallel.dist_bfs import make_mesh

W = 128
LANES = 32 * W
# Width generalization mirrors the single-chip wide engine: any multiple
# of 32 lanes up to MAX_LANES is legal (the sharded tables are [rows_loc,
# w] blocks — width-agnostic). The DISTRIBUTED default stays at 4096 even
# though the single-chip engines moved to 8192 after the round-4 sweep:
# the scale-26 per-chip HBM budget (BENCHMARKS.md) is written for 128-word
# rows, and doubling row bytes would halve the largest graph a given mesh
# can hold — width here is an explicit trade (``lanes=8192``), not a
# default.
from tpu_bfs.algorithms.msbfs_wide import MAX_LANES  # noqa: E402


def _make_dist_core(
    sell: ShardedEllGraph, w: int, num_planes: int, mesh: Mesh,
    exchange: str = "dense", sparse_caps: tuple[int, ...] = (),
    delta_bits: tuple[int, ...] = (),
    expand_impl: str = "xla", interpret: bool = False,
):
    p_count = sell.num_shards
    v_loc = sell.v_loc
    v_pad = sell.v_pad
    nb = (
        rows_gather_branch_count(sparse_caps, delta_bits)
        if exchange == "sparse" else 1
    )
    spec = ExpandSpec(
        kcap=sell.kcap,
        heavy=sell.heavy_per_shard > 0,
        num_virtual=sell.num_virtual,
        fold_steps=sell.fold_steps,
        light_meta=tuple((k, blocks.shape[1]) for k, blocks in sell.light),
        tail_rows=sell.tail_rows,
    )
    expand = make_expand(spec, w, impl=expand_impl, interpret=interpret)

    def _dense_gather(nxt):
        gathered = lax.all_gather(nxt, "v")  # [P, v_loc, W]
        return gathered.transpose(1, 0, 2).reshape(v_pad, w)

    def _sparse_gather(nxt):
        # The MS-engine form of the reference's per-destination buckets
        # (bfs.cu:148-150): collectives.sparse_rows_gather with this
        # engine's round-robin row map (local row l on chip q holds global
        # rank l*P + q). ``delta_bits`` ships the local row ids
        # delta-encoded (ISSUE 7); the receiver then applies the same map
        # per sender via the two-arg form.
        p = lax.axis_index("v")
        return sparse_rows_gather(
            nxt, "v",
            caps=sparse_caps,
            out_rows=v_pad,
            gid_of=lambda ids: ids * p_count + p,
            dense_fn=lambda: _dense_gather(nxt),
            delta_bits=delta_bits,
            gid_of_src=lambda ids, src: ids * p_count + src,
        )

    def _make_loop(arrs, max_levels):
        """This chip's level machinery (run_from + deeper probe pieces),
        shared by the fresh and checkpoint-resume entries."""

        def cond(carry):
            _, _, _, level, alive, _ = carry
            return alive & (level < max_levels)

        def body(carry):
            fw, vis, planes, level, _, branch_counts = carry
            hit = expand(arrs, fw)
            nxt = hit & ~vis
            vis2 = vis | nxt
            planes = ripple_increment(planes, ~vis2)
            if exchange == "sparse":
                fw_flat, branch = _sparse_gather(nxt)
            else:
                fw_flat, branch = _dense_gather(nxt), jnp.int32(0)
            branch_counts = branch_counts + (
                jnp.arange(nb, dtype=jnp.int32) == branch
            )
            fw_next = jnp.concatenate([fw_flat, jnp.zeros((1, w), jnp.uint32)])
            alive = jnp.any(fw_flat != 0)
            return fw_next, vis2, planes, level + 1, alive, branch_counts

        def run_from(fw, vis, planes, level0):
            return lax.while_loop(
                cond, body,
                (fw, vis, planes, level0, jnp.bool_(True),
                 jnp.zeros(nb, jnp.int32)),
            )

        return run_from

    def chip_fn(arrs, fw0, max_levels):
        # Block specs keep a leading shard axis of size 1; drop it.
        arrs = {k: a[0] for k, a in arrs.items()}
        p = lax.axis_index("v")
        own = lambda full: lax.dynamic_index_in_dim(
            full[:v_pad].reshape(v_loc, p_count, w), p, axis=1, keepdims=False
        )
        planes0 = tuple(jnp.zeros((v_loc, w), jnp.uint32) for _ in range(num_planes))
        run_from = _make_loop(arrs, max_levels)
        fw_f, vis_f, planes_f, levels, alive, branch_counts = run_from(
            fw0, own(fw0), planes0, jnp.int32(0)
        )

        # Claim-free truncation probe (see msbfs_wide): one more expand, only
        # when the loop exited at the cap with a live frontier.
        def deeper():
            local = jnp.any((expand(arrs, fw_f) & ~vis_f) != 0)
            return lax.psum(local.astype(jnp.int32), "v") > 0

        truncated = lax.cond(
            alive & (levels >= max_levels), deeper,
            lambda: lax.psum(jnp.int32(0), "v") > 0,
        )
        return (
            tuple(pl[None] for pl in planes_f),
            vis_f[None],
            levels,
            alive,
            truncated,
            branch_counts,
        )

    def chip_fn_from(arrs, fw, vis, planes, level0, max_levels):
        # Checkpoint-resume entry. Layouts match the loop carry: ``fw`` is
        # the replicated rank-order [v_pad+1, w] table (+ the ELL sentinel
        # row), ``vis``/``planes`` are this chip's [v_loc, w] blocks of the
        # chip-major tables (chip-major row p*v_loc+l IS shard p's row l,
        # so P('v') over the chip-major axis hands each chip its block).
        arrs = {k: a[0] for k, a in arrs.items()}
        run_from = _make_loop(arrs, max_levels)
        return run_from(fw, vis, planes, level0)

    def build(n_arrs):
        specs = {k: P("v") for k in n_arrs}
        core = jax.jit(
            shard_map(
                chip_fn,
                mesh=mesh,
                in_specs=(specs, P(), P()),
                out_specs=(
                    tuple(P("v") for _ in range(num_planes)),
                    P("v"),
                    P(),
                    P(),
                    P(),
                    P(),
                ),
                check_vma=False,
            )
        )
        core_from = jax.jit(
            shard_map(
                chip_fn_from,
                mesh=mesh,
                in_specs=(
                    specs,
                    P(),
                    P("v"),
                    tuple(P("v") for _ in range(num_planes)),
                    P(),
                    P(),
                ),
                out_specs=(
                    P(),
                    P("v"),
                    tuple(P("v") for _ in range(num_planes)),
                    P(),
                    P(),
                    P(),
                ),
                check_vma=False,
            )
        )
        device_arrs = {
            k: jax.device_put(v, NamedSharding(mesh, P("v")))
            for k, v in n_arrs.items()
        }
        return core, core_from, device_arrs

    return build


class DistWideMsBfsEngine(PackedRunProtocol, RowGatherExchangeAccounting,
                          AotProgramProtocol):
    """Multi-chip 4096-lane packed MS-BFS: sharded ELL, replicated frontier.

    Per-chip HBM is O(V * W/8 * num_planes) for the packed state plus the
    chip's edge shard — frontier replication is the scalability ceiling (use
    fewer lanes or more planes-frugal settings for very large V).
    """

    def __init__(
        self,
        graph: Graph | ShardedEllGraph,
        mesh: Mesh | int | None = None,
        *,
        lanes: int = LANES,
        kcap: int = 64,
        num_planes: int = 5,
        exchange: str = "dense",
        sparse_caps: int | tuple[int, ...] | None = None,
        wire_pack: bool = False,
        delta_bits: tuple[int, ...] = (),
        expand_impl: str = "xla",
        interpret: bool | None = None,
    ):
        if not (1 <= num_planes <= 8):
            raise ValueError("num_planes must be in [1, 8]")
        validate_expand_impl(expand_impl)
        self.expand_impl = expand_impl
        interpret = resolve_interpret(interpret)
        self._interpret = bool(interpret)
        if exchange not in ("dense", "sparse"):
            raise ValueError(
                f"unknown exchange {exchange!r}; have 'dense', 'sparse'"
            )
        if delta_bits and exchange != "sparse":
            raise ValueError(
                "delta_bits compresses the SPARSE row gather's id stream "
                f"(ISSUE 7); exchange={exchange!r} ships whole slabs — "
                "use exchange='sparse'"
            )
        # Wire format (ISSUE 5): this engine's exchange already ships
        # uint32 lane words — one BIT per (vertex, source) pair, the
        # information content — so there is nothing left to pack. The
        # flag is accepted so one --wire-pack / bench knob sweeps every
        # distributed engine uniformly; the fuzz suite pins it to a
        # no-op (bit-identical results either way).
        self.wire_pack = bool(wire_pack)
        if lanes % 32 or not (32 <= lanes <= MAX_LANES):
            raise ValueError(
                f"lanes must be a multiple of 32 in [32, {MAX_LANES}]"
            )
        self.w = lanes // 32
        self.lanes = lanes
        self.num_planes = num_planes
        self.max_levels_cap = min(1 << num_planes, 254)
        self.mesh = mesh if isinstance(mesh, Mesh) else make_mesh(mesh)
        p_count = self.mesh.devices.size
        self.sell = (
            build_ell_sharded(graph, p_count, kcap=kcap)
            if isinstance(graph, Graph)
            else graph
        )
        if self.sell.num_shards != p_count:
            raise ValueError(
                f"ELL built for {self.sell.num_shards} shards, mesh has {p_count}"
            )
        sell = self.sell
        # Host-side edge list for post-loop parent extraction
        # (PackedBatchResult.parents_int32); a prebuilt shard set dropped it.
        self.host_graph = graph if isinstance(graph, Graph) else None
        self.undirected = sell.undirected
        # Isolated-source convention (cross-engine checkpoints): real-id
        # checkpoints store no bits for sources that appear in NO edge (the
        # trimmed engines have no row for them), and the finishing engine
        # patches those lanes (reached=1). Every vertex has a row HERE, so
        # this engine's own runs don't need the patch — but finishing a
        # checkpoint started on a trimmed engine does. Exact from a Graph;
        # for a prebuilt undirected shard set in_degree==0 is equivalent; a
        # prebuilt directed one cannot distinguish out-only vertices (None
        # here) — but checkpoints persist the starting engine's exact mask
        # (PackedCheckpoint.iso), which finish_packed_batch prefers, so
        # even this engine patches resumed lanes correctly; None only
        # degrades its own fresh runs' iso reckoning.
        if isinstance(graph, Graph):
            src, dst = graph.coo
            seen = np.zeros(graph.num_vertices, dtype=bool)
            seen[src] = True
            seen[dst] = True
            self._iso_mask = ~seen
        elif sell.undirected:
            self._iso_mask = sell.in_degree == 0
        else:
            self._iso_mask = None

        w = self.w
        n_arrs = {}
        if sell.heavy_per_shard > 0:
            n_arrs["virtual_t"] = np.ascontiguousarray(sell.virtual.transpose(0, 2, 1))
            n_arrs["fold_pad_map"] = sell.fold_pad_map
            n_arrs["heavy_pick"] = sell.heavy_pick
        for i, (k, blocks) in enumerate(sell.light):
            n_arrs[f"light{i}_t"] = np.ascontiguousarray(blocks.transpose(0, 2, 1))
        if expand_impl == "pallas":
            from tpu_bfs.graph.ell import pad_gate_blocks
            from tpu_bfs.ops.ell_expand import validate_kernel_width

            validate_kernel_width(
                w, self._interpret, kernel="dist-wide expand_impl='pallas'"
            )
            # Per-shard sentinel-padded whole-block tables (stacked on the
            # shard axis like every other n_arrs entry; sentinel = the
            # replicated frontier's all-zero row v_pad).
            if sell.heavy_per_shard > 0:
                n_arrs["virtual_gt"] = np.stack([
                    pad_gate_blocks(n_arrs["virtual_t"][p], sell.v_pad)
                    for p in range(sell.num_shards)
                ])
            for i, (k, blocks) in enumerate(sell.light):
                n_arrs[f"light{i}_gt"] = np.stack([
                    pad_gate_blocks(n_arrs[f"light{i}_t"][p], sell.v_pad)
                    for p in range(sell.num_shards)
                ])
        #: delta-encoded sparse row-gather ids (ISSUE 7; sparse exchange
        #: only, default OFF until chip-measured).
        self.delta_bits = check_delta_bits(delta_bits)
        if sparse_caps is None:
            sparse_caps = default_row_gather_caps(
                sell.v_loc, self.w, self.delta_bits
            )
        elif isinstance(sparse_caps, int):
            sparse_caps = (sparse_caps,)
        self._exchange = exchange
        self.sparse_caps = normalize_caps(sparse_caps)
        # RowGatherExchangeAccounting host attributes (see collectives.py).
        self._gather_p = sell.num_shards
        self._gather_rows_loc = sell.v_loc
        self.last_exchange_level_counts: np.ndarray | None = None
        self.last_exchange_bytes: float | None = None
        build = _make_dist_core(
            sell, w, num_planes, self.mesh, exchange, self.sparse_caps,
            self.delta_bits, expand_impl=expand_impl,
            interpret=self._interpret,
        )
        self._dist_core, self._core_from_jit, self.arrs = build(n_arrs)
        # Checkpoint-conversion metadata: _rank (below) is the chip-major
        # vertex->row map the result tables use; every vertex has a row.
        self._table_rows = sell.v_pad
        self._act = sell.v_pad

        # Chip-major row of global rank r is (r % P) * v_loc + r // P.
        ranks = sell.rank.astype(np.int64)
        self._rank = ((ranks % p_count) * sell.v_loc + ranks // p_count).astype(
            np.int64
        )
        in_deg_cm = np.zeros(sell.v_pad, dtype=np.int32)
        in_deg_cm[self._rank] = sell.in_degree.astype(np.int32)
        # Stats/extraction over the reassembled chip-major tables: every row
        # participates (pad rows are never visited, so they contribute zero).
        _, self._lane_stats, self._extract_word, self._lane_ecc = (
            make_state_kernels(
                sell.v_pad, sell.v_pad, self.w, num_planes,
                in_deg_host=in_deg_cm,
            )
        )
        # Seed table is one row taller (the ELL sentinel row at v_pad).
        rows_seed, w = sell.v_pad + 1, self.w
        self._seed_k = jax.jit(
            lambda r, wd, b: jnp.zeros((rows_seed, w), jnp.uint32).at[r, wd].add(b)
        )
        self._warmed = False

    @property
    def num_vertices(self) -> int:
        return self.sell.num_vertices

    # Word-major lane map (same as the single-chip wide engine).
    @staticmethod
    def _word_col(i: int):
        return i // 32, i % 32

    @staticmethod
    def _lane_order(mat: np.ndarray) -> np.ndarray:
        return mat.reshape(-1)

    def _iso_of(self, sources: np.ndarray):
        if self._iso_mask is None:
            return None
        return self._iso_mask[np.asarray(sources, np.int64)]

    def _seed_dev(self, sources: np.ndarray):
        # The loop consumes the replicated [v_pad+1, w] table in RANK order
        # (the `own` selector and ELL neighbor ids are rank-space). Seed via
        # the device scatter — a host-built table would be ~1 GiB per run at
        # bench scale.
        sell = self.sell
        ranks = sell.rank[np.asarray(sources, dtype=np.int64)].astype(np.int32)
        lanes = np.arange(len(sources), dtype=np.int32)
        words = lanes // 32
        bits = np.uint32(1) << (lanes % 32).astype(np.uint32)
        return self._seed_k(
            jnp.asarray(ranks), jnp.asarray(words), jnp.asarray(bits)
        )

    def analysis_programs(self):
        """Static-analyzer hook (tpu_bfs/analysis): the distributed core
        whose sparse row-gather branch uniformity the taint pass proves.
        Same contract as DistBfsEngine.analysis_programs. The seed table
        is pre-replicated: per-batch seed movement is inherent to
        dispatch (fresh sources every batch), so the analyzer's
        transfer guard watches the LOOP, not the input staging."""
        rep = NamedSharding(self.mesh, P())
        fw0 = jax.device_put(self._seed_dev(np.asarray([0])), rep)
        ml = jax.device_put(jnp.int32(32), rep)
        return [("dist_core", self._dist_core, (self.arrs, fw0, ml))]

    def export_programs(self):
        """AOT inventory (ISSUE 9; utils/aot.py): the sharded level-loop
        core — THE multi-chip compile a preheat exists to skip — reusing
        the analysis hook's replicated example args (the sharded-export
        plumbing the Buluç & Madduri-style partitioned paths need)."""
        return [
            ("dist_core", "_dist_core", fn, args)
            for name, fn, args in self.analysis_programs()
            if name == "dist_core"
        ]

    def _src_bits_view(self, fw0):
        """Rank-order seed table -> chip-major view matching planes/vis."""
        sell = self.sell
        p = sell.num_shards
        return (
            fw0[: sell.v_pad]
            .reshape(sell.v_loc, p, self.w)
            .transpose(1, 0, 2)
            .reshape(sell.v_pad, self.w)
        )

    def _core(self, arrs, fw0, max_levels):
        planes, vis, levels, alive, truncated, bc = self._dist_core(
            arrs, fw0, max_levels
        )
        self._record_exchange(bc, 0)
        # [P, v_loc, w] blocks -> chip-major [v_pad, w] tables.
        planes = tuple(pl.reshape(self.sell.v_pad, self.w) for pl in planes)
        vis = vis.reshape(self.sell.v_pad, self.w)
        return planes, vis, levels, alive, truncated

    def _full_parent_ell(self):
        """Batched device parent scan structure (parent_scan.py): the
        sharded ELL's per-chip buckets don't concatenate into one coverage
        structure, so build a fresh single-device full ELL; the scan's
        row-space perm maps this engine's chip-major extraction tables
        into it. Owned tables — released after the export."""
        return lazy_full_parent_ell(self.host_graph, self.sell.kcap)

    # run/dispatch/fetch come from PackedRunProtocol (_packed_common).

    # --- checkpoint/resume. Checkpoints are real-vertex-id (portable to the
    # single-chip engines and other mesh sizes — elastic restart); the only
    # engine-specific pieces are the frontier layout hooks consumed by
    # _packed_common (the loop carries the frontier replicated in rank
    # order + ELL sentinel row, unlike the chip-major visited/planes).

    def _fw_table_from_real(self, real):
        sell = self.sell
        if real.shape != (self.num_vertices, self.w):
            raise ValueError(
                f"checkpoint table is {real.shape}, engine expects "
                f"({self.num_vertices}, {self.w}) — lane count and graph "
                "must match the engine the checkpoint resumes on"
            )
        t = np.zeros((sell.v_pad + 1, self.w), np.uint32)  # + sentinel row
        t[sell.rank] = real
        return jnp.asarray(t)

    def _fw_real_from_table(self, fw_rank):
        return np.asarray(fw_rank)[self.sell.rank]

    def start(self, sources):
        from tpu_bfs.algorithms._packed_common import start_packed_batch

        return start_packed_batch(self, sources)

    def advance(self, ckpt, levels: int | None = None):
        from tpu_bfs.algorithms._packed_common import advance_packed_batch

        return advance_packed_batch(self, ckpt, levels)

    def finish(self, ckpt):
        from tpu_bfs.algorithms._packed_common import finish_packed_batch

        return finish_packed_batch(self, ckpt)
