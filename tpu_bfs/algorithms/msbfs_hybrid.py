"""Hybrid dense-MXU + sparse-gather wide multi-source BFS.

The wide engine (msbfs_wide.py) pays ~13 ns of random-gather tax per edge
slot, every level, for every edge. But on a degree-sorted power-law graph the
edge mass is bimodal: measured on RMAT scale-21, 128x128 adjacency tiles
holding >= 64 edges cover ~57% of all edges in ~2% of the occupied tiles.
This engine splits the graph once at build time:

- **dense part**: tiles with >= ``tile_thr`` edges (trimmed to an HBM
  budget; tiles are bit-packed at 2 KB each), expanded per level by the
  Pallas MXU kernel
  (tpu_bfs/ops/tile_spmm.py) at ~0.5 us/tile — replacing ~128 x 13 ns of
  gather tax per tile;
- **residual part**: everything else, expanded by the same bucketed-ELL
  fori-loop gathers as the wide engine.

Row space is "rank0" order (active vertices first, by descending full
in-degree; isolated vertices get no row at all) padded to VT*128 rows
so the dense kernel's frontier DMAs are contiguous slabs. The residual ELL
buckets rows by *residual* degree, so its outputs come out in a different
(bucket) order; one static permutation gather per level routes them back to
rank0 before the claim. Everything else — packed claim ``& ~visited``,
bit-sliced distance planes, device-side stats, lazy extraction — is the
shared machinery in _packed_common.py.

Batch entries map to (word, bit) coordinates word-major, exactly like the
wide engine — tile_spmm's internal bit-major unpack/pack preserves every
(word, bit) position end-to-end, so the kernel imposes no constraint on how
entries are assigned to lanes.

Reference mapping: this is the capability of the reference's whole kernel
layer (queueBfs, bfs.cu:134-165; multiBfs, bfs.cu:101-130) re-planned around
the TPU's MXU/VPU split instead of CUDA thread divergence. Measured flagship:
45.3 GTEPS harmonic-mean per-source on RMAT scale-21 (37.0 at scale 22 with
auto-traded planes), 1 v5e chip — see BENCHMARKS.md.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs import obs as _obs
from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import (
    EllBucket,
    bucketize_rows,
    gate_forward_map,
    pad_gate_blocks,
    rank_vertices,
)
from tpu_bfs.algorithms._packed_common import (
    AotProgramProtocol,
    ExpandSpec,
    PullGateHost,
    advance_packed_batch,
    auto_lanes,
    auto_planes,
    PackedRunProtocol,
    build_push_table,
    expand_arrays,
    finish_packed_batch,
    floor_lanes,
    make_adaptive_hit,
    make_expand,
    make_gated_expand,
    make_packed_loop,
    make_state_kernels,
    pallas_expand_arrays,
    validate_expand_impl,
    packed_analysis_programs,
    packed_aot_programs,
    row_unsettled,
    seed_scatter_args,
    start_packed_batch,
    tpu_padded_words,
)
from tpu_bfs.ops.tile_spmm import AW, TILE, tile_spmm
from tpu_bfs.ops.ell_expand import resolve_interpret

W = 128
LANES = 32 * W
# The dense kernel needs w to be a MULTIPLE of 128 (Mosaic: the frontier
# slab's minor dim must be 128-aligned), so wider batches come in steps of
# 4096 lanes up to MAX_LANES.
MAX_LANES = 4 * LANES
# Default width cap: 8192 lanes (w=256), decided by the round-4 v5e sweep —
# RMAT scale-21 flagship measured 45.68 GTEPS hmean at 4096 lanes vs 55.96
# at 8192 (1.22x: the per-index gather cost stays near-flat past 128-word
# rows, so the wider batch amortizes the same index traffic over 2x the
# sources). A 16384-lane request auto-settled back at 8192 on the 16 GB
# chip (state doesn't fit), so 2*LANES is also the widest width that
# actually materializes there. Auto sizing still walks DOWN from the cap
# whenever the packed state doesn't fit next to the tiles.
DEFAULT_MAX_LANES = 2 * LANES


class LanesDontFitError(ValueError):
    """The graph's packed state cannot fit the 4096 lanes the dense kernel
    requires; callers fall back to the gather-only wide engine."""


@dataclasses.dataclass(frozen=True)
class HybridGraph:
    """Build-time split of a graph into dense MXU tiles + residual ELL.

    Rank0 space: row r of the frontier table is vertex ``old_of_new[r]``;
    rows [V, VT*128) are zero padding (the ELL pad sentinel is VT*128-1).
    Residual bucket space: output row p of the residual expansion is rank0
    row ``r_order[p]``; ``inv_perm_ext`` routes rank0 row -> bucket output
    row (pad/empty rows -> the appended all-zero row).
    """

    num_vertices: int
    num_edges: int
    undirected: bool
    kcap: int
    num_active: int  # non-isolated vertices; ranks >= num_active have no row
    vt: int  # frontier slabs of 128 rows; table height = vt * 128
    old_of_new: np.ndarray  # [V] int32
    rank: np.ndarray  # [V] int32
    in_degree: np.ndarray  # [V] int64, original ids
    # dense part
    num_dense_edges: int  # directed slots routed to tiles (duplicates collapse)
    row_start: np.ndarray  # [vt+1] int32 CSR over row-tiles
    col_tile: np.ndarray  # [NT] int32
    a_tiles: np.ndarray  # [NT, AW, TILE] u32 bit-packed, rows-in-bits (tile_spmm layout)
    # residual part (build_ell-style buckets over residual degree)
    res_heavy: int
    res_num_virtual: int
    res_fold_steps: int
    res_virtual: EllBucket | None
    res_fold_pad_map: np.ndarray | None
    res_heavy_pick: np.ndarray | None
    res_light: list[EllBucket]
    res_tail_rows: int  # zero rows appended after buckets (incl. the map target)
    inv_perm_ext: np.ndarray  # [vt*128] int32 rank0 row -> bucket output row

    # expand_arrays protocol
    @property
    def virtual(self):
        return self.res_virtual

    @property
    def fold_pad_map(self):
        return self.res_fold_pad_map

    @property
    def heavy_pick(self):
        return self.res_heavy_pick

    @property
    def light(self):
        return self.res_light

    @property
    def num_tiles(self) -> int:
        return len(self.col_tile)



def select_dense_tiles(r, c, vt, *, tile_thr: int, a_budget_bytes: int):
    """Pick dense 128x128 tiles over rank-space endpoints (r = dst rank,
    c = src rank): tiles holding >= tile_thr edges, trimmed to the bit-packed
    storage budget (2 KB/tile) by descending edge count.

    Returns (dense_edge mask [E], dense_uniq sorted tile ids, tid per edge).
    Shared by the single-chip and distributed hybrid builders.
    """
    max_tiles = max(a_budget_bytes // (TILE * AW * 4), 0)

    def select(counts):
        eligible = np.flatnonzero(counts >= max(tile_thr, 1))
        if len(eligible) > max_tiles:
            order = eligible[
                np.argsort(-counts[eligible], kind="stable")
            ][:max_tiles]
            eligible = np.sort(order)
        return eligible

    if vt * vt <= 3 * 10**8:
        # Dense tile-count histogram: one bincount over int32 tile ids beats
        # np.unique's 67M-element sort by ~20s at scale 21. The vt*vt count
        # array (~2 GiB at scale 21) only exists on host during the build.
        tid = (r // TILE).astype(np.int32) * np.int32(vt) + (
            c // TILE
        ).astype(np.int32)
        eligible = select(np.bincount(tid, minlength=vt * vt))
        dense_tile_mask = np.zeros(vt * vt, dtype=bool)
        dense_tile_mask[eligible] = True
        dense_edge = dense_tile_mask[tid]
        dense_uniq = eligible.astype(np.int64)
    else:
        # Graph500-scale vertex counts: vt*vt is too large to histogram.
        tid = (r.astype(np.int64) // TILE) * vt + (c.astype(np.int64) // TILE)
        uniq, inv, cnt = np.unique(tid, return_inverse=True, return_counts=True)
        eligible = select(cnt)
        is_dense_tile = np.zeros(len(uniq), dtype=bool)
        is_dense_tile[eligible] = True
        dense_edge = is_dense_tile[inv]
        dense_uniq = uniq[eligible]
    return dense_edge, dense_uniq, tid


def fill_a_tiles(dense_edge, dense_uniq, tid, r, c):
    """Bit-packed tiles, rows-in-bits (tile_spmm layout): A[row, col] at
    [t, row % AW, col] bit row // AW — 2 KB/tile instead of 16 KB dense int8.
    Bits OR via sort + reduceat (np.bitwise_or.at is ~40x slower at
    Graph500 scale)."""
    nt = len(dense_uniq)
    a_tiles = np.zeros((max(nt, 1), AW, TILE), dtype=np.uint32)
    if nt:
        de = np.flatnonzero(dense_edge)
        slot = np.searchsorted(dense_uniq, tid[de])
        rin = (r[de] % TILE).astype(np.int64)
        flat = slot * (AW * TILE) + (rin % AW) * TILE + c[de] % TILE
        comb = (flat << np.int64(5)) | (rin // AW)
        comb.sort()
        vals = np.uint32(1) << (comb & 31).astype(np.uint32)
        f2 = comb >> np.int64(5)
        starts = np.flatnonzero(np.r_[True, np.diff(f2) != 0])
        a_tiles.reshape(-1)[f2[starts]] = np.bitwise_or.reduceat(vals, starts)
    return a_tiles


def build_hybrid(
    g: Graph,
    *,
    kcap: int = 64,
    tile_thr: int = 64,
    a_budget_bytes: int = int(0.2e9),
) -> HybridGraph:
    """Split ``g`` into dense 128x128 tiles (>= tile_thr edges, trimmed to the
    bit-packed storage budget of 2 KB/tile by descending edge count) and a
    residual ELL. Defaults (thr=64, ~98k-tile budget) are the measured v5e
    optimum on RMAT scale-21: marginal tiles below ~64 edges cost more in
    kernel time (~2.3 us measured marginal, incl. DMA + grid effects) than
    their edges cost as gathers."""
    v = g.num_vertices
    src, dst = g.coo
    in_deg, num_active, rank_order, rank = rank_vertices(src, dst, v)

    # Table height covers only active (non-isolated) rows + the sentinel:
    # on RMAT graphs ~40% of vertices are isolated, and every [rows, w]
    # state table was paying for them. All edge endpoints rank < num_active
    # by construction, so tiles and residual gathers are unaffected.
    vt = -(-(num_active + 1) // TILE)
    r = rank[dst]  # int32 rank ids
    c = rank[src]
    dense_edge, dense_uniq, tid = select_dense_tiles(
        r, c, vt, tile_thr=tile_thr, a_budget_bytes=a_budget_bytes
    )

    # --- dense arrays (dense_uniq sorted: row-tile-major then col-tile) ---
    nt = len(dense_uniq)
    row_tiles = (dense_uniq // vt).astype(np.int64)
    col_tile = (dense_uniq % vt).astype(np.int32)
    row_start = np.searchsorted(row_tiles, np.arange(vt + 1)).astype(np.int32)
    a_tiles = fill_a_tiles(dense_edge, dense_uniq, tid, r, c)

    # --- residual ELL, bucketed by residual in-degree, targets in rank0 ids ---
    re_mask = ~dense_edge
    res_dst_rank = r[re_mask]
    res_src_rank = c[re_mask].astype(np.int32)
    res_deg_rank = np.bincount(res_dst_rank, minlength=v).astype(np.int64)

    r_order = np.argsort(-res_deg_rank, kind="stable").astype(np.int64)
    bucket_pos = np.empty(v, dtype=np.int64)
    bucket_pos[r_order] = np.arange(v)

    # Flatten residual in-neighbors grouped by destination row, in r_order —
    # native O(E) counting sort when built, np.lexsort otherwise (the minor
    # src key additionally makes within-row neighbor order deterministic).
    from tpu_bfs.graph.csr import _lexsort_pairs

    order_e = _lexsort_pairs(bucket_pos[res_dst_rank], res_src_rank, v)
    nbrs = res_src_rank[order_e]  # rank0-space sources, grouped by bucket row
    lens = res_deg_rank[r_order]
    new_rp = np.zeros(v + 1, dtype=np.int64)
    np.cumsum(lens, out=new_rp[1:])

    sentinel = vt * TILE - 1
    (
        num_heavy, num_nonzero, num_virtual, fold_steps,
        virtual, fold_pad_map, heavy_pick, light,
    ) = bucketize_rows(lens, nbrs, new_rp, kcap, sentinel)

    # Bucket outputs cover rows 0..num_nonzero in r_order; rows with zero
    # residual degree and pad rows all map to the appended zero row.
    inv_perm_ext = np.full(vt * TILE, num_nonzero, dtype=np.int32)
    real = r_order[:num_nonzero]
    inv_perm_ext[real] = np.arange(num_nonzero, dtype=np.int32)

    return HybridGraph(
        num_vertices=v,
        num_edges=g.num_edges,
        undirected=g.undirected,
        kcap=kcap,
        num_active=num_active,
        vt=vt,
        old_of_new=rank_order,
        rank=rank,
        in_degree=in_deg,
        num_dense_edges=int(dense_edge.sum()),
        row_start=row_start,
        col_tile=col_tile,
        a_tiles=a_tiles if nt else a_tiles[:0],
        res_heavy=num_heavy,
        res_num_virtual=num_virtual,
        res_fold_steps=fold_steps,
        res_virtual=virtual,
        res_fold_pad_map=fold_pad_map,
        res_heavy_pick=heavy_pick,
        res_light=light,
        res_tail_rows=1,  # one shared all-zero output row
        inv_perm_ext=inv_perm_ext,
    )


def expand_spec(hg: HybridGraph) -> ExpandSpec:
    """Residual-ELL expansion spec of a hybrid graph (shared between the
    engine core and the roofline phase slices, utils/roofline.py — one
    definition so attribution measures exactly what the loop runs)."""
    return ExpandSpec(
        kcap=hg.kcap,
        heavy=hg.res_heavy > 0,
        num_virtual=hg.res_num_virtual,
        fold_steps=hg.res_fold_steps,
        light_meta=tuple((b.k, b.n) for b in hg.res_light),
        tail_rows=hg.res_tail_rows,
    )


def _make_core(hg: HybridGraph, w: int, num_planes: int, interpret: bool,
               push_cfg=None, gate_levels: int = 0,
               expand_impl: str = "xla"):
    has_dense = hg.num_tiles > 0

    def with_dense(hit, arrs, fw):
        """OR the MXU tile pass's hits into the residual's: the
        ``level.tiles`` phase."""
        if not has_dense:
            return hit
        with _obs.scope("level.tiles"):
            return hit | tile_spmm(
                arrs["row_start"], arrs["col_tile"], arrs["a_tiles"], fw,
                num_row_tiles=hg.vt, w=w, interpret=interpret,
            )

    if gate_levels:
        # Pull gate (ISSUE 1): residual bucket outputs live in r_order, so
        # the per-rank0-row unsettled mask routes through the build-time
        # forward map (gate_forward_map) before keying the gated buckets.
        # The dense MXU pass stays ungated — its tiles are already the
        # compacted hot set, and the Pallas grid takes no dynamic tile
        # list; its hits on settled rows are claim-masked like any other.
        gated_residual = make_gated_expand(
            expand_spec(hg), w, impl=expand_impl, interpret=interpret
        )

        def hit_of(arrs, fw, vis, lane_mask):
            need = row_unsettled(vis, hg.num_active, lane_mask)
            need_ext = jnp.concatenate([need, jnp.zeros((1,), bool)])
            res, skipped = gated_residual(
                arrs, fw, need_ext[arrs["gate_fwd"]]
            )
            with _obs.scope("level.gather"):
                hit = res[arrs["inv_perm_ext"]]
            return with_dense(hit, arrs, fw), skipped

        return make_packed_loop(
            hit_of, num_planes, gate_levels=gate_levels, act=hg.num_active
        )

    expand_residual = make_expand(
        expand_spec(hg), w, impl=expand_impl, interpret=interpret
    )

    def hit_of(arrs, fw):
        with _obs.scope("level.gather"):
            hit = expand_residual(arrs, fw)[arrs["inv_perm_ext"]]
        return with_dense(hit, arrs, fw)

    if push_cfg is not None:
        # Level-adaptive expansion (experimental): light levels skip BOTH
        # the residual scan and the dense tile pass — see
        # _packed_common.make_adaptive_hit, shared with the wide engine.
        hit_of = make_adaptive_hit(
            hit_of, hg.num_active, w, hg.vt * TILE, push_cfg
        )
    return make_packed_loop(hit_of, num_planes)


class HybridMsBfsEngine(PackedRunProtocol, PullGateHost,
                        AotProgramProtocol):
    """Up to 8192 concurrent BFS sources by default (DEFAULT_MAX_LANES,
    the round-4 measured optimum; ``max_lanes`` moves the cap in 4096-lane
    steps up to MAX_LANES, and auto sizing walks down when the state
    doesn't fit); dense tiles on the MXU, residual on gathers. API mirrors
    WidePackedMsBfsEngine; results are PackedBatchResult.

    ``pull_gate=True`` (default off until chip-measured) keys the residual
    scan and the state passes on the per-row settled mask — late levels
    stop paying the whole-table pull bill; per-level skipped blocks land
    in ``last_gate_level_counts``. Bit-identical to the plain scan; the
    dense MXU pass stays ungated (see _make_core)."""

    def __init__(
        self,
        graph: Graph | HybridGraph,
        *,
        lanes: int | str = "auto",
        kcap: int = 64,
        tile_thr: int = 64,
        a_budget_bytes: int = int(0.2e9),
        num_planes: int | str = "auto",
        interpret: bool | None = None,
        undirected: bool | None = None,
        hbm_budget_bytes: int = int(14.0e9),
        max_lanes: int = DEFAULT_MAX_LANES,
        adaptive_push: tuple[int, int] | None = None,
        pull_gate: bool = False,
        expand_impl: str = "xla",
    ):
        validate_expand_impl(expand_impl)
        self.expand_impl = expand_impl
        if num_planes != "auto" and not (1 <= num_planes <= 8):
            # Validate the explicit case before the minutes-long build.
            raise ValueError("num_planes must be in [1, 8]")
        if pull_gate and adaptive_push is not None:
            # Same rule as the wide engine: both gate the per-level scan,
            # by different keys — measure the pull gate against the plain
            # scan first (ISSUE 1's A/B stage) before composing.
            raise ValueError(
                "pull_gate and adaptive_push cannot combine (yet): pick one"
            )
        if max_lanes % 32 or not (32 <= max_lanes <= MAX_LANES):
            # Same early-validation rule: a bad width cap must fail in
            # seconds, not after the build (and auto_lanes would otherwise
            # happily return an out-of-range width).
            raise ValueError(
                f"max_lanes must be a multiple of 32 in [32, {MAX_LANES}]"
            )
        # Floor once to a reachable width (power-of-two word count — all
        # auto sizing can ever select): a non-pow2 cap would otherwise make
        # auto_planes' full-width check unsatisfiable in EVERY auto branch.
        max_lanes = floor_lanes(max_lanes)
        interpret = resolve_interpret(interpret)
        self.hg = (
            build_hybrid(
                graph, kcap=kcap, tile_thr=tile_thr, a_budget_bytes=a_budget_bytes
            )
            if isinstance(graph, Graph)
            else graph
        )
        # Host-side edge list for post-loop parent extraction
        # (PackedBatchResult.parents_int32); a prebuilt HybridGraph dropped it.
        self.host_graph = graph if isinstance(graph, Graph) else None
        hg = self.hg
        if adaptive_push is not None and self.host_graph is None:
            raise ValueError(
                "adaptive_push needs the edge list: construct the engine "
                "from a Graph (a prebuilt HybridGraph has dropped it)"
            )
        res_slots = (
            hg.res_virtual.idx.size if hg.res_virtual is not None else 0
        ) + sum(b.idx.size for b in hg.res_light)
        fixed_bytes = hg.a_tiles.nbytes + int(res_slots * 4.4)
        if adaptive_push is not None:
            # The push table is a lane-independent resident, like the ELL;
            # its [act+1, deg_cap] int32 minor dim pads to 128 on TPU
            # (tpu_padded_words — the round-4 LJ OOM billed it at 2.0x).
            fixed_bytes += (
                (hg.num_active + 1)
                * (tpu_padded_words(adaptive_push[1]) * 4 + 1)
            )
        if num_planes == "auto" and lanes == "auto":
            # Trade depth capacity (2**planes levels) for batch width: on a
            # graph one scale step too big for 5 planes at 4096 lanes, 4
            # planes (16 levels — ample for power-law graphs) keeps the
            # dense MXU path instead of falling off to the gather engine.
            # With a raised max_lanes, walk the width ladder DOWN: a wider
            # cap that doesn't fit must degrade to exactly the default
            # 4096-lane sizing, never to a narrower width than the default
            # cap would have chosen (auto_planes only trades planes when
            # the full target width is reachable).
            cand = max_lanes  # already floored to a reachable width above
            while True:
                num_planes = auto_planes(
                    hg.vt * TILE,
                    fixed_bytes=fixed_bytes,
                    hbm_budget_bytes=hbm_budget_bytes,
                    max_lanes=cand,
                )
                lanes = auto_lanes(
                    hg.vt * TILE,
                    num_planes,
                    fixed_bytes=fixed_bytes,
                    hbm_budget_bytes=hbm_budget_bytes,
                    max_lanes=cand,
                )
                if lanes == cand or cand <= LANES:
                    break
                cand //= 2
        elif num_planes == "auto":
            num_planes = auto_planes(
                hg.vt * TILE,
                fixed_bytes=fixed_bytes,
                hbm_budget_bytes=hbm_budget_bytes,
                max_lanes=max_lanes,
            )
        if not (1 <= num_planes <= 8):
            raise ValueError("num_planes must be in [1, 8]")
        self.num_planes = num_planes
        self.max_levels_cap = min(1 << num_planes, 254)
        if lanes == "auto":
            lanes = auto_lanes(
                hg.vt * TILE,
                num_planes,
                fixed_bytes=fixed_bytes,
                hbm_budget_bytes=hbm_budget_bytes,
                max_lanes=max_lanes,
            )
        if lanes % 32 or not (32 <= lanes <= MAX_LANES):
            raise ValueError(
                f"lanes must be a multiple of 32 in [32, {MAX_LANES}]"
            )
        if lanes % LANES and not interpret and hg.num_tiles:
            # Mosaic requires the frontier-slab DMA's minor dimension to be
            # 128-aligned, so the dense kernel exists only at w multiples
            # of 128 (4096-lane steps).
            raise LanesDontFitError(
                f"hybrid dense kernel requires a multiple of {LANES} lanes "
                f"(w % 128 == 0); the packed state for this graph only fits "
                f"{lanes} lanes — use WidePackedMsBfsEngine (gather-only, "
                "any width) or shard over more chips (DistWideMsBfsEngine)"
            )
        self.w = lanes // 32
        self.lanes = lanes
        self.interpret = interpret
        if expand_impl == "pallas":
            from tpu_bfs.ops.ell_expand import validate_kernel_width

            # The residual kernel shares the dense kernel's width law
            # (w % 128 on real TPUs) but applies even on tile-free
            # graphs, where the LanesDontFitError check above doesn't.
            validate_kernel_width(
                self.w, interpret, kernel="hybrid expand_impl='pallas'"
            )
        self.adaptive_push = adaptive_push
        self.undirected = hg.undirected if undirected is None else undirected
        arrs = expand_arrays(hg)
        arrs["inv_perm_ext"] = jnp.asarray(hg.inv_perm_ext)
        if hg.num_tiles:
            arrs["row_start"] = jnp.asarray(hg.row_start)
            arrs["col_tile"] = jnp.asarray(hg.col_tile)
            arrs["a_tiles"] = jnp.asarray(hg.a_tiles)
        if adaptive_push is not None:
            pt, inelig = build_push_table(
                self.host_graph, hg.rank, hg.num_active, adaptive_push[1]
            )
            arrs["push_t"] = jnp.asarray(pt)
            arrs["push_inelig"] = jnp.asarray(inelig)
        self._act = hg.num_active
        self._table_rows = hg.vt * TILE
        if expand_impl == "pallas":
            # Kernel-side whole-block index tables for the residual
            # buckets (sentinel = the all-zero pad row vt*TILE-1; the
            # pull-gate branch below rebuilds the light tables
            # identically when both tiers are on).
            for name, tbl in pallas_expand_arrays(
                hg, hg.vt * TILE - 1
            ).items():
                arrs[name] = jnp.asarray(tbl)
        self.pull_gate = pull_gate
        if pull_gate:
            # Gate tables: sentinel-padded whole-block bucket indices (the
            # residual pad row vt*TILE-1 stays all-zero) and the forward
            # routing map bucket-position -> rank0 row (graph/ell.py).
            sentinel = hg.vt * TILE - 1
            for i, b in enumerate(hg.res_light):
                arrs[f"light{i}_gt"] = jnp.asarray(
                    pad_gate_blocks(np.ascontiguousarray(b.idx.T), sentinel)
                )
            num_real = hg.res_heavy + sum(b.n for b in hg.res_light)
            out_height = num_real + hg.res_tail_rows
            arrs["gate_fwd"] = jnp.asarray(
                gate_forward_map(hg.inv_perm_ext, out_height, num_real)
            )
            self._lane_mask_dev = jnp.full(
                (self.w,), 0xFFFFFFFF, jnp.uint32
            )
            (
                self._gate_core_jit, self._gate_core_from_jit,
                self._gate_core_from_donate_jit,
            ) = _make_core(
                hg, self.w, num_planes, interpret,
                gate_levels=self.max_levels_cap, expand_impl=expand_impl,
            )
            self._core = self._gated_core
            self._core_from = self._gated_core_from
            self._core_from_donate = self._gated_core_from_donate
        else:
            self._core, self._core_from, self._core_from_donate = _make_core(
                hg, self.w, num_planes, interpret, adaptive_push,
                expand_impl=expand_impl,
            )
        self.arrs = arrs
        in_deg_ranked = hg.in_degree[hg.old_of_new].astype(np.int32)
        (
            self._seed, self._lane_stats, self._extract_word, self._lane_ecc,
        ) = make_state_kernels(
            hg.num_vertices, hg.vt * TILE, self.w, num_planes,
            active=self._act, in_deg_host=in_deg_ranked,
        )
        self._rank = hg.rank
        self._warmed = False

    @property
    def num_vertices(self) -> int:
        return self.hg.num_vertices

    # Word-major lane map (same as the wide engine): batch entry i at word
    # i // 32, bit i % 32 — so 32 consecutive entries share one extraction.
    @staticmethod
    def _word_col(i: int):
        return i // 32, i % 32

    @staticmethod
    def _lane_order(mat: np.ndarray) -> np.ndarray:
        return mat.reshape(-1)

    def _iso_of(self, sources: np.ndarray):
        return self.hg.rank[sources] >= self._act

    def _seed_dev(self, sources: np.ndarray):
        return self._seed(*seed_scatter_args(self.hg.rank[sources], self._act))

    def _full_parent_ell(self):
        """Structure for the batched parent scan (parent_scan.py). The
        residual ELL alone cannot derive parents — dense-tile edges are
        missing from it — so build a full in-neighbor ELL lazily from the
        retained host graph (same rank_vertices row space by construction).
        Owned tables — released after the export."""
        from tpu_bfs.algorithms._packed_common import lazy_full_parent_ell

        return lazy_full_parent_ell(self.host_graph, self.hg.kcap)

    # run/dispatch/fetch come from PackedRunProtocol (_packed_common).

    def export_programs(self):
        """AOT inventory (ISSUE 9; utils/aot.py): the shared packed
        serving set — the MXU level-loop core (gated form carries the
        lane-mask arg), seed, lane stats, word extraction, lane ecc."""
        return packed_aot_programs(self)

    def analysis_programs(self):
        """Static-analyzer inventory (tpu_bfs/analysis): the level-loop
        core with REAL example args, under the engine's ACTUAL
        residual-expansion tier, so a pallas-tier core exposes its
        ``pallas_call`` body to the jaxpr walks and compiled audits
        (ISSUE 16)."""
        return packed_analysis_programs(self)

    # --- checkpoint/resume (_packed_common; SURVEY.md §5: reference has none) ---

    def start(self, sources):
        """Level-0 packed batch state as a host checkpoint (real-id rows)."""
        return start_packed_batch(self, sources)

    def advance(self, ckpt, levels: int | None = None):
        """Run at most ``levels`` more levels; bit-identical to no stop."""
        return advance_packed_batch(self, ckpt, levels)

    def finish(self, ckpt):
        """Package a (finished or partial) checkpoint as a batch result."""
        return finish_packed_batch(self, ckpt)
