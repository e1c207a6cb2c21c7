"""Distributed hybrid (MXU dense tiles + gather residual) multi-source BFS.

The multi-chip form of the flagship HybridMsBfsEngine, with **fully sharded
traversal state** — the design the reference could not express: it replicates
the whole graph per device and allocates full-size distance/frontier arrays
per device (bfs.cu:346-351, 339-344), so adding GPUs never adds capacity.
Here every O(V)-row table is sharded, and per-chip memory shrinks as the
mesh grows:

- **Ownership**: row-tiles (128 rank0 rows each) are dealt round-robin to
  chips (tile t -> chip t % P), so the hub-heavy top tiles spread evenly —
  the load balance the reference's contiguous getDev split lacks
  (bfs.cu:29-32). One ownership map covers the dense tiles, the residual
  rows, the frontier/visited shards, and the plane shards.
- **dense part**: global 128x128 tile selection (same rule as build_hybrid);
  each chip runs the tile_spmm Pallas kernel over its own row-tiles against
  the transient all-gathered frontier, producing hits for exactly the rows
  it owns.
- **residual part**: each chip gets a bucketed ELL over the residual
  in-edges of its own rows, with bucket shapes padded to a common maximum
  across chips so one jitted program serves every chip under shard_map; a
  per-chip static permutation routes bucket outputs to local row order.
- **state**: frontier, visited, and the bit-sliced distance planes are all
  sharded [rows/P, w] per chip. Per level, the GATHER layout (default) runs
  one all_gather that materializes the full frontier transiently (discarded
  after expansion); claim, visited update, and plane ripple run on owned
  rows only. Termination is a psum of local claim popcounts — one
  collective per level, like the reference's MPI_Allreduce (bfs_mpi.cu:621)
  but compiled into the on-device loop.
- **sliced layout** (``exchange='sliced'``): the graph-world ring-attention
  move (SURVEY.md §5). Edges regroup by (source chip, ring step); each chip
  expands against its RESIDENT frontier shard while an [A/P, w] accumulator
  rotates the ring, landing home after P partial accumulations — no
  gathered frontier ever exists, every edge still processed once per level,
  and the wire bytes equal the ring all-gather's. The O(A) transient below
  becomes O(A/P): adding chips then genuinely reaches bigger graphs.

Per-chip memory (at the default w=128 words = 4096 lanes, row bytes 4w =
512 B; A = active rows — scale row bytes linearly for wider ``lanes``):
  persistent: (num_planes + 2) * A/P * 512 B     (planes + visited + frontier)
  transient:  gather layout: A * 512 B (gathered frontier) + A/P * 512 B
              sliced layout: 2 * A/P * 512 B (rotating accumulator + hits)
  structures: dense tiles (2 KB each) + residual ELL slots / P
so with the sliced layout EVERY term falls as 1/P — see BENCHMARKS.md for
the Graph500 scale-26 budget on v5p.

Like the single-chip hybrid, the dense kernel constrains the lane count to
multiples of 4096 (w % 128 == 0; default 4096, ``lanes`` raises it); unlike
it, sharding lets that width fit graphs one chip cannot hold.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from tpu_bfs import obs as _obs
from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import (
    _ell_fill,
    gate_forward_map,
    pad_gate_blocks,
    pad_heavy_shards,
    rank_vertices,
)
from tpu_bfs.algorithms.msbfs_packed import ripple_increment
from tpu_bfs.algorithms._packed_common import (
    AotProgramProtocol,
    ExpandSpec,
    PackedRunProtocol,
    PullGateHost,
    lazy_full_parent_ell,
    make_expand,
    make_gated_expand,
    make_state_kernels,
    seed_scatter_args,
    validate_expand_impl,
)
from tpu_bfs.algorithms.msbfs_hybrid import fill_a_tiles, select_dense_tiles
from tpu_bfs.ops.tile_spmm import AW, TILE, tile_spmm
from tpu_bfs.ops.ell_expand import resolve_interpret
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
# Same width generalization as the single-chip engines (msbfs_hybrid):
# wider rows in 4096-lane steps, opt-in via ``lanes``. The DISTRIBUTED
# default stays 4096 (the single-chip default moved to 8192 after the
# round-4 sweep): the scale-26 per-chip budget below is written for
# 128-word rows, so width here is an explicit memory trade, not a default
# (see dist_msbfs_wide.py for the same rationale).
from tpu_bfs.algorithms.msbfs_hybrid import MAX_LANES  # noqa: E402


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _build_residual_groups(
    groups,
    rows_loc: int,
    n_minor: int,
    sentinel: int,
    kcap: int,
):
    """Common-shape bucketed ELL over an explicit list of edge groups.

    ``groups`` is a list of ``(ldst, nbr)`` pairs — per-group local
    destination rows (in [0, rows_loc)) and neighbor ids (any id space;
    ``n_minor`` bounds them for the sort, ``sentinel`` pads ELL slots).
    Every bucket shape is padded to the maximum across groups so one jitted
    program serves all groups under shard_map/scan. This is the group-
    generic core of both the per-chip residual shards (P groups, neighbor
    ids global rank0) and the ring-sliced pair shards (P*P groups, neighbor
    ids local to the source chip's frontier shard).
    Returns (spec, res_arrs stacks [G, ...], perm [G, rows_loc]).
    """
    from tpu_bfs.graph.csr import _lexsort_pairs

    per_chip = []
    for ldst, nbr in groups:
        lens_local = np.bincount(ldst, minlength=rows_loc).astype(np.int64)
        order_rows = np.argsort(-lens_local, kind="stable").astype(np.int64)
        pos_of_row = np.empty(rows_loc, dtype=np.int64)
        pos_of_row[order_rows] = np.arange(rows_loc)
        # Neighbors grouped by (sorted row, src) for determinism. Minor-key
        # values live in the caller's id space, hence the separate n_minor
        # bound (rows_loc alone could make the native sort reject calls).
        order_e = _lexsort_pairs(
            pos_of_row[ldst], nbr.astype(np.int64), rows_loc, n_minor
        )
        nbrs = nbr[order_e].astype(np.int32)
        lens = lens_local[order_rows]
        rp = np.zeros(rows_loc + 1, dtype=np.int64)
        np.cumsum(lens, out=rp[1:])
        per_chip.append((lens, nbrs, rp, order_rows))

    # --- Common heavy-section shapes (shared pyramid-padding helper). ---
    nh_p = [int(np.searchsorted(-t[0], -kcap, side="left")) for t in per_chip]
    (
        nh, num_virtual, fold_steps, _m2,
        virtual_s, fold_pad_map_s, heavy_pick_s,
    ) = pad_heavy_shards(
        [t[0][:n] for t, n in zip(per_chip, nh_p)],
        [t[1][: int(t[2][n])] for t, n in zip(per_chip, nh_p)],
        kcap,
        sentinel,
    )
    heavy = nh > 0

    # --- Common light ladder: union of buckets, counts padded to max. ---
    nz_p = [int(np.searchsorted(-t[0], 0, side="left")) for t in per_chip]
    bounds_p = []  # per chip: list of (k, lo, hi) sorted-row ranges
    for (lens, _, _, _), n_h, nz in zip(per_chip, nh_p, nz_p):
        row = n_h
        k = kcap
        b = {}
        while row < nz and k >= 1:
            hi = int(np.searchsorted(-lens, -(k // 2 + 1), side="right"))
            if k == 1:
                hi = nz
            if hi > row:
                b[k] = (row, hi)
                row = hi
            k //= 2
        bounds_p.append(b)
    ks = [
        k
        for k in (kcap >> i for i in range(kcap.bit_length()))
        if k >= 1 and any(k in b for b in bounds_p)
    ]
    n_of_k = {
        k: max(b[k][1] - b[k][0] if k in b else 0 for b in bounds_p) for k in ks
    }
    light_s = []
    for k in ks:
        blocks = []
        for (lens, nbrs, rp, _), b in zip(per_chip, bounds_p):
            lo, hi = b.get(k, (0, 0))
            flat = nbrs[int(rp[lo]) : int(rp[hi])]
            filled = _ell_fill(lens[lo:hi], flat, k, sentinel)
            pad = np.full((n_of_k[k] - (hi - lo), k), sentinel, np.int32)
            blocks.append(np.concatenate([filled, pad]) if len(pad) else filled)
        light_s.append((k, np.stack(blocks)))

    # --- Per-chip permutation: local row -> bucket-output position. ---
    out_height = nh + sum(n_of_k[k] for k in ks) + 1  # +1 zero row
    zero_pos = out_height - 1
    perms = []
    for (lens, _, _, order_rows), n_h, nz, b in zip(
        per_chip, nh_p, nz_p, bounds_p
    ):
        pos_of_sorted = np.full(rows_loc, zero_pos, dtype=np.int32)
        pos_of_sorted[:n_h] = np.arange(n_h, dtype=np.int32)
        off = nh
        for k in ks:
            lo, hi = b.get(k, (0, 0))
            pos_of_sorted[lo:hi] = off + np.arange(hi - lo, dtype=np.int32)
            off += n_of_k[k]
        perm = np.empty(rows_loc, dtype=np.int32)
        perm[order_rows] = pos_of_sorted  # rows with deg 0 -> zero_pos
        perms.append(perm)

    spec = ExpandSpec(
        kcap=kcap,
        heavy=heavy,
        num_virtual=num_virtual,
        fold_steps=fold_steps,
        light_meta=tuple((k, n_of_k[k]) for k in ks),
        tail_rows=1,
    )
    res_arrs = {}
    if heavy:
        res_arrs["virtual_t"] = np.ascontiguousarray(
            virtual_s.transpose(0, 2, 1)
        )
        res_arrs["fold_pad_map"] = fold_pad_map_s
        res_arrs["heavy_pick"] = heavy_pick_s
    for i, (k, blocks) in enumerate(light_s):
        res_arrs[f"light{i}_t"] = np.ascontiguousarray(
            blocks.transpose(0, 2, 1)
        )
    return spec, res_arrs, np.stack(perms)


def _build_residual_shards(
    res_dst: np.ndarray,
    res_src_rank: np.ndarray,
    p_count: int,
    nrt: int,
    rows: int,
    kcap: int,
):
    """Per-chip bucketed ELL over each chip's own residual in-edges.

    ``res_dst``/``res_src_rank`` are rank0-space endpoints of the residual
    edges. Chip p owns local rows of the row-tiles {t : t % P == p}; its
    rows sort by residual degree and bucket exactly like the single-chip
    hybrid, but bucket shapes are padded to the maximum across chips so one
    jitted program serves every chip. Neighbor ids stay global rank0 rows
    (sentinel ``rows - 1``, a pad row kept all-zero by the valid mask).
    Returns (spec, res_arrs [P,...] stacks, perm [P, nrt*128]) where perm
    routes each chip's bucket-output rows back to local row order.
    """
    rows_loc = nrt * TILE

    # Global row -> (owner chip, local row).
    g_tile = res_dst // TILE
    owner = g_tile % p_count
    local_row = (g_tile // p_count) * TILE + res_dst % TILE
    groups = []
    for p in range(p_count):
        sel = np.flatnonzero(owner == p)
        groups.append((local_row[sel], res_src_rank[sel]))
    return _build_residual_groups(groups, rows_loc, rows, rows - 1, kcap)


def _build_residual_pair_shards(
    res_dst: np.ndarray,
    res_src_rank: np.ndarray,
    p_count: int,
    nrt: int,
    kcap: int,
):
    """Ring-sliced residual layout: P*P edge groups, one per (source chip,
    ring step).

    Group (p, s) holds the residual edges whose SOURCE row lives in chip
    p's frontier shard and whose DESTINATION row is owned by chip
    d = (p - s - 1) mod P — the accumulator-rotation schedule: at step s
    chip p ORs its contribution into the accumulator destined for shard d,
    then passes it along the ring; after P steps each accumulator lands on
    its home chip. Neighbor ids are LOCAL to the source chip's frontier
    shard (sentinel ``rows_loc`` -> the appended all-zero row), so the
    expansion reads only the chip-resident frontier — no gathered table
    exists at any point, which is the whole memory win (O(A/P) transients,
    VERDICT r2 #4).
    Returns (spec, res_arrs [P, P, ...], perm [P, P, rows_loc]).
    """
    rows_loc = nrt * TILE

    d_tile = res_dst // TILE
    dst_owner = d_tile % p_count
    dst_local = (d_tile // p_count) * TILE + res_dst % TILE
    s_tile = res_src_rank // TILE
    src_owner = s_tile % p_count
    src_local = (s_tile // p_count) * TILE + res_src_rank % TILE

    groups = []
    for p in range(p_count):
        for s in range(p_count):
            d = (p - s - 1) % p_count
            sel = np.flatnonzero((src_owner == p) & (dst_owner == d))
            groups.append((dst_local[sel], src_local[sel]))
    spec, res_arrs, perm = _build_residual_groups(
        groups, rows_loc, rows_loc + 1, rows_loc, kcap
    )
    res_arrs = {
        k: a.reshape((p_count, p_count) + a.shape[1:]) for k, a in res_arrs.items()
    }
    return spec, res_arrs, perm.reshape(p_count, p_count, rows_loc)


def build_dist_hybrid(
    g: Graph,
    num_shards: int,
    *,
    kcap: int = 64,
    tile_thr: int = 64,
    a_budget_bytes: int = int(0.2e9),
    layout: str = "gather",
):
    """Build sharded dense tiles + per-chip residual ELL + glue maps.

    ``layout='gather'`` (default): destination-sharded structures expanded
    against a transiently gathered full frontier (O(A) transient/level).
    ``layout='sliced'``: ring-sliced pair structures — each chip's edges
    grouped by (source chip, ring step), expanded against the chip-resident
    frontier shard while an O(A/P) accumulator rotates (the graph-world
    ring-attention move, SURVEY.md §5; every edge still processed exactly
    once per level).
    Returns a dict of host arrays (see DistHybridMsBfsEngine).
    """
    if layout not in ("gather", "sliced"):
        raise ValueError(f"unknown layout {layout!r}; have 'gather', 'sliced'")
    p_count = num_shards
    v = g.num_vertices
    src, dst = g.coo
    in_deg, num_active, rank_order, rank = rank_vertices(src, dst, v)

    # Row-tiles over active rows only (isolated vertices get no row), padded
    # to a multiple of P so every chip owns the same tile count.
    vt = _round_up(-(-(num_active + 1) // TILE), p_count)
    rows = vt * TILE
    nrt = vt // p_count
    r = rank[dst]
    c = rank[src]
    dense_edge, dense_uniq, tid = select_dense_tiles(
        r, c, vt, tile_thr=tile_thr, a_budget_bytes=a_budget_bytes
    )

    # --- dense tile grouping ---
    nt = len(dense_uniq)
    g_row_tile = dense_uniq // vt
    g_col_tile = (dense_uniq % vt).astype(np.int32)
    a_global = (
        fill_a_tiles(dense_edge, dense_uniq, tid, r, c)
        if nt
        else np.zeros((1, AW, TILE), np.uint32)
    )
    if layout == "gather":
        # Per-chip: owner of tile = row_tile % P; columns index the
        # gathered full frontier.
        owner = (g_row_tile % p_count).astype(np.int64)
        nt_max = max(int(np.bincount(owner, minlength=p_count).max(initial=0)), 1)
        row_start_s = np.zeros((p_count, nrt + 1), np.int32)
        col_tile_s = np.zeros((p_count, nt_max), np.int32)
        a_tiles_s = np.zeros((p_count, nt_max, AW, TILE), np.uint32)
        if nt:
            for p in range(p_count):
                mine = np.flatnonzero(owner == p)
                local_rt = (g_row_tile[mine] // p_count).astype(np.int64)
                # dense_uniq is (row_tile, col) sorted; the filtered
                # subsequence is sorted by local row-tile already.
                row_start_s[p] = np.searchsorted(
                    local_rt, np.arange(nrt + 1)
                ).astype(np.int32)
                col_tile_s[p, : len(mine)] = g_col_tile[mine]
                a_tiles_s[p, : len(mine)] = a_global[mine]
    else:
        # Sliced: tile lives with its SOURCE columns (owner = col_tile % P),
        # grouped by ring step s = (p - d - 1) mod P toward the accumulator
        # of destination shard d = row_tile % P; columns index the
        # chip-RESIDENT frontier shard (local col tile = col_tile // P).
        src_own = (g_col_tile % p_count).astype(np.int64)
        dst_own = (g_row_tile % p_count).astype(np.int64)
        step = (src_own - dst_own - 1) % p_count
        pair = src_own * p_count + step
        nt_max = max(
            int(np.bincount(pair, minlength=p_count * p_count).max(initial=0)), 1
        )
        row_start_s = np.zeros((p_count, p_count, nrt + 1), np.int32)
        col_tile_s = np.zeros((p_count, p_count, nt_max), np.int32)
        a_tiles_s = np.zeros((p_count, p_count, nt_max, AW, TILE), np.uint32)
        if nt:
            for p in range(p_count):
                for s in range(p_count):
                    mine = np.flatnonzero(pair == p * p_count + s)
                    local_rt = (g_row_tile[mine] // p_count).astype(np.int64)
                    order = np.argsort(local_rt, kind="stable")
                    mine, local_rt = mine[order], local_rt[order]
                    row_start_s[p, s] = np.searchsorted(
                        local_rt, np.arange(nrt + 1)
                    ).astype(np.int32)
                    col_tile_s[p, s, : len(mine)] = g_col_tile[mine] // p_count
                    a_tiles_s[p, s, : len(mine)] = a_global[mine]

    # --- residual ELL ---
    re_mask = ~dense_edge
    if layout == "gather":
        spec, res_arrs, perm_s = _build_residual_shards(
            r[re_mask].astype(np.int64),
            c[re_mask].astype(np.int32),
            p_count,
            nrt,
            rows,
            kcap,
        )
    else:
        spec, res_arrs, perm_s = _build_residual_pair_shards(
            r[re_mask].astype(np.int64),
            c[re_mask].astype(np.int64),
            p_count,
            nrt,
            kcap,
        )

    # Valid mask: real active rows of each chip (global rank0 row < active).
    rows_loc = nrt * TILE
    j = np.arange(rows_loc) // TILE  # local tile
    i = np.arange(rows_loc) % TILE
    g_rows = (j[None, :] * p_count + np.arange(p_count)[:, None]) * TILE + i
    valid_s = ((g_rows < num_active).astype(np.uint32) * np.uint32(0xFFFFFFFF))[
        :, :, None
    ]

    # Vertex -> tau row (the sharded tables' global order: chip-major, then
    # local rows). Isolated vertices (rank >= active) -> rows (no row).
    g_tile_of = rank // TILE
    tau = (
        (g_tile_of % p_count).astype(np.int64) * rows_loc
        + (g_tile_of // p_count).astype(np.int64) * TILE
        + rank % TILE
    )
    tau_of_vertex = np.where(rank < num_active, tau, rows).astype(np.int64)

    return {
        "layout": layout,
        "num_vertices": v,
        "num_active": num_active,
        "num_edges": g.num_edges,
        "undirected": g.undirected,
        "num_shards": p_count,
        "vt": vt,
        "rows": rows,
        "rank": rank,
        "old_of_new": rank_order,
        "in_degree": in_deg,
        "tau_of_vertex": tau_of_vertex,
        "num_dense_edges": int(dense_edge.sum()),
        "num_tiles": nt,
        "row_start_s": row_start_s,
        "col_tile_s": col_tile_s,
        "a_tiles_s": a_tiles_s,
        "res_spec": spec,
        "res_arrs": res_arrs,
        "perm_s": perm_s,
        "valid_s": valid_s,
    }


def _make_dist_core(
    hd, w: int, num_planes: int, mesh: Mesh, interpret: bool,
    exchange: str = "dense", sparse_caps: tuple[int, ...] = (),
    gate_levels: int = 0, delta_bits: tuple[int, ...] = (),
    expand_impl: str = "xla",
):
    p_count = mesh.devices.size
    rows = hd["rows"]
    nrt = hd["vt"] // p_count
    rows_loc = nrt * TILE
    expand = make_expand(
        hd["res_spec"], w, impl=expand_impl, interpret=interpret
    )
    has_dense = hd["num_tiles"] > 0
    nb = (
        rows_gather_branch_count(sparse_caps, delta_bits)
        if exchange == "sparse" else 1
    )
    sliced = hd.get("layout", "gather") == "sliced"
    # Pull gate (ISSUE 1): gate_levels > 0 makes the cores take a trailing
    # replicated lane-mask argument and return a trailing per-chip
    # [1, gate_levels] skipped-block array (host-summed — deliberately NOT
    # psum'd, so the gated program adds no collective the ungated one
    # lacks; utils/wirecheck.check_gated_hybrid audits exactly that).
    # Gating keys differ by layout: the gather layout skips residual
    # bucket blocks whose destination rows all settled (chip-resident vis
    # decides, same rule as the single-chip engines); the sliced layout
    # skips a chip's contribution computes outright on levels where its
    # RESIDENT frontier shard is empty — destination settledness lives on
    # the accumulator's home chip there, so source-side emptiness is the
    # gate that composes with the rotation without new exchange. The ring
    # ppermutes themselves always run: a collective inside a per-chip cond
    # would deadlock chips that disagree — that is the "where legal" line.
    gated = gate_levels > 0
    gated_expand = (
        make_gated_expand(
            hd["res_spec"], w, impl=expand_impl, interpret=interpret
        )
        if gated and not sliced else None
    )

    def _global_any(x):
        with _obs.scope("level.sync"):
            return lax.psum(jnp.any(x != 0).astype(jnp.int32), "v") > 0

    def _make_loop_sliced(arrs, max_levels, lane_mask=None):
        """Ring-sliced level machinery: no gathered frontier ever exists.

        Each chip expands its (source-resident) edge groups against its own
        frontier shard while an [rows_loc, w] accumulator rotates around
        the ring — after P partial accumulations the accumulator for shard
        p lands on chip p (schedule: at step s chip p feeds the accumulator
        of shard (p - s - 1) mod P; see _build_residual_pair_shards). The
        per-level transient is O(A/P) instead of the gather layout's O(A);
        wire bytes match the ring all-gather exactly ((P-1) rotations of
        one shard) — the win is memory, not traffic, and every edge is
        still processed exactly once per level."""
        res_keys = [
            k for k in arrs
            if k.startswith("light")
            or k in ("virtual_t", "virtual_gt", "fold_pad_map", "heavy_pick")
        ]
        step_keys = res_keys + ["perm"] + (
            ["row_start", "col_tile", "a_tiles"] if has_dense else []
        )
        ring = [(i, (i + 1) % p_count) for i in range(p_count)]

        def contrib(fw, fw_ext, s_arrs):
            with _obs.scope("level.gather"):
                out = expand(
                    {k: s_arrs[k] for k in res_keys}, fw_ext
                )[s_arrs["perm"]]
            if has_dense:
                with _obs.scope("level.tiles"):
                    out = out | tile_spmm(
                        s_arrs["row_start"], s_arrs["col_tile"],
                        s_arrs["a_tiles"], fw, num_row_tiles=nrt, w=w,
                        interpret=interpret,
                    )
            return out

        def hit_claim(fw, vis):
            """(hit_own, skipped_contribs). Gated: a chip whose resident
            frontier shard is empty contributes identity at every ring
            step, so its P contribution computes (gathers + tiles) are
            skipped under lax.cond; the rotation itself still runs on
            every chip (see _make_dist_core's gating note)."""
            fw_ext = jnp.concatenate([fw, jnp.zeros((1, w), jnp.uint32)])
            if gated:
                empty = ~jnp.any(fw != 0)

                def step(s_arrs):
                    return lax.cond(
                        empty,
                        lambda: jnp.zeros((rows_loc, w), jnp.uint32),
                        lambda: contrib(fw, fw_ext, s_arrs),
                    )
            else:
                def step(s_arrs):
                    return contrib(fw, fw_ext, s_arrs)

            acc = step({k: arrs[k][0] for k in step_keys})

            def sbody(acc, xs):
                with _obs.scope("level.exchange"):
                    acc = lax.ppermute(acc, "v", ring)
                return acc | step(xs), None

            if p_count > 1:
                acc, _ = lax.scan(
                    sbody, acc, {k: arrs[k][1:] for k in step_keys}
                )
            skipped = (
                jnp.where(empty, p_count, 0) if gated else jnp.int32(0)
            )
            return acc & arrs["valid"], skipped

        def body_claim(fw, vis):
            hit, skipped = hit_claim(fw, vis)
            return hit, jnp.int32(0), skipped

        return _make_run_from(body_claim, max_levels), hit_claim

    def _make_run_from(body_claim, max_levels):
        """The shared while-loop shell of both layouts: ``body_claim(fw,
        vis) -> (hit_own, exchange_branch, skipped)`` plugs in the
        per-layout expansion; the carry grows the per-level skipped-block
        array in gated mode."""

        def cond(carry):
            level, alive = carry[3], carry[4]
            return alive & (level < max_levels)

        def body(carry):
            fw, vis, planes, level, _, bc = carry[:6]
            hit, branch, skipped = body_claim(fw, vis)
            with _obs.scope("level.claim"):
                nxt = hit & ~vis
                vis2 = vis | nxt
                planes = ripple_increment(planes, ~vis2)
            # One psum per level is the whole termination protocol (the
            # reference needs a host-visible MPI_Allreduce, bfs_mpi.cu:621).
            alive = _global_any(nxt)
            with _obs.scope("level.sync"):
                bc = bc + (jnp.arange(nb, dtype=jnp.int32) == branch)
                out = (nxt, vis2, planes, level + 1, alive, bc)
                if gated:
                    gc = carry[6].at[
                        jnp.minimum(level, gate_levels - 1)
                    ].set(skipped)
                    out = out + (gc,)
            return out

        def run_from(fw, vis, planes, level0):
            init = (fw, vis, planes, level0, jnp.bool_(True),
                    jnp.zeros(nb, jnp.int32))
            if gated:
                init = init + (jnp.zeros(gate_levels, jnp.int32),)
            # The loop itself (its termination test and carry) is sync
            # work; each phase of the body names itself inside it.
            with _obs.scope("level.sync"):
                return lax.while_loop(cond, body, init)

        return run_from

    def _make_loop(arrs, max_levels, lane_mask=None):
        """This chip's level machinery over its stripped arrays: returns
        (run_from, hit_claim) — shared by the fresh and resume entries.
        ``hit_claim(fw, vis) -> (hit_own, skipped)``; vis/lane_mask are
        only consulted in gated mode."""
        if sliced:
            return _make_loop_sliced(arrs, max_levels, lane_mask)

        def dense_gather(fw_own):
            # Transient full frontier in global rank0 order: global tile
            # t = local j * P + chip p, so the transpose interleaves.
            ag = lax.all_gather(fw_own.reshape(nrt, TILE, w), "v")
            return ag.transpose(1, 0, 2, 3).reshape(rows, w)

        def sparse_gather(fw_own):
            # collectives.sparse_rows_gather with this engine's tau row map:
            # local row l = tile j*TILE + r is global rank0 row
            # (j * P + chip) * TILE + r. The gathered table feeds the MXU
            # tiles and residual gathers exactly like the dense slab.
            p = lax.axis_index("v")
            return sparse_rows_gather(
                fw_own, "v",
                caps=sparse_caps,
                out_rows=rows,
                gid_of=lambda ids: ((ids // TILE) * p_count + p) * TILE
                + ids % TILE,
                dense_fn=lambda: dense_gather(fw_own),
                delta_bits=delta_bits,
                gid_of_src=lambda ids, src: (
                    ((ids // TILE) * p_count + src) * TILE + ids % TILE
                ),
            )

        def gather_frontier(fw_own):
            with _obs.scope("level.exchange"):
                if exchange == "sparse":
                    return sparse_gather(fw_own)
                return dense_gather(fw_own), jnp.int32(0)

        def hit_of_gathered(fw_g, vis):
            with _obs.scope("level.gather"):
                if gated:
                    # Destination-settled gating, chip-resident: this
                    # chip's vis shard covers exactly the rows its buckets
                    # produce.
                    valid_rows = arrs["valid"][:, 0] != 0
                    need = (
                        jnp.any((~vis & lane_mask[None, :]) != 0, axis=1)
                        & valid_rows
                    )
                    need_ext = jnp.concatenate([need, jnp.zeros((1,), bool)])
                    res, skipped = gated_expand(
                        arrs, fw_g, need_ext[arrs["gate_fwd"]]
                    )
                    hit = res[arrs["perm"]]
                else:
                    hit = expand(arrs, fw_g)[arrs["perm"]]  # own rows, local
                    skipped = jnp.int32(0)
            if has_dense:
                with _obs.scope("level.tiles"):
                    hit = hit | tile_spmm(
                        arrs["row_start"], arrs["col_tile"], arrs["a_tiles"],
                        fw_g, num_row_tiles=nrt, w=w, interpret=interpret,
                    )
            with _obs.scope("level.claim"):
                return hit & arrs["valid"], skipped

        def hit_claim(fw_own, vis):
            return hit_of_gathered(gather_frontier(fw_own)[0], vis)

        def body_claim(fw, vis):
            fw_g, branch = gather_frontier(fw)
            hit, skipped = hit_of_gathered(fw_g, vis)
            return hit, branch, skipped

        return _make_run_from(body_claim, max_levels), hit_claim

    # The per-chip bodies are named ``core`` and ``core_from``, as the
    # single-chip engines' jitted cores are: their ops read
    # ``jit(core)/shard_map/lanes<W>/level.*/...`` in a trace.
    lanes_scope = f"lanes{32 * w}"

    def core(arrs, fw0, max_levels, *mask):
        with _obs.scope(lanes_scope):
            arrs = {k: a[0] for k, a in arrs.items()}  # this chip's part
            run_from, hit_claim = _make_loop(arrs, max_levels, *mask)
            with _obs.scope("batch.seed"):
                planes0 = tuple(
                    jnp.zeros((rows_loc, w), jnp.uint32)
                    for _ in range(num_planes)
                )
            out = run_from(fw0, fw0, planes0, jnp.int32(0))
            fw_f, vis_f, planes_f, levels, alive, branch_counts = out[:6]

            def deeper():
                hit = hit_claim(fw_f, vis_f)[0]
                with _obs.scope("level.claim"):
                    nxt = hit & ~vis_f
                return _global_any(nxt)

            with _obs.scope("level.sync"):
                truncated = lax.cond(
                    alive & (levels >= max_levels), deeper,
                    lambda: jnp.bool_(False),
                )
            res = (planes_f, vis_f, levels, alive, truncated, branch_counts)
            if gated:
                res = res + (out[6][None],)  # [1, L]; host sums the chips
            return res

    def core_from(arrs, fw, vis, planes, level0, max_levels, *mask):
        # Checkpoint-resume entry: the while-loop carry (all in the same
        # sharded tau row space) restored mid-traversal — bit-identical to
        # never having stopped (_packed_common.advance_packed_batch).
        with _obs.scope(lanes_scope):
            arrs = {k: a[0] for k, a in arrs.items()}
            run_from, _ = _make_loop(arrs, max_levels, *mask)
            out = run_from(fw, vis, planes, level0)
            return out[:6] + ((out[6][None],) if gated else ())

    def build(n_arrs):
        mask_in = (P(),) if gated else ()  # replicated lane mask
        gc_out = (P("v"),) if gated else ()  # [P, L] per-chip counters
        core_jit = jax.jit(
            shard_map(
                core,
                mesh=mesh,
                in_specs=({k: P("v") for k in n_arrs}, P("v"), P())
                + mask_in,
                out_specs=(
                    tuple(P("v") for _ in range(num_planes)),
                    P("v"),
                    P(),
                    P(),
                    P(),
                    P(),
                )
                + gc_out,
                check_vma=False,
            )
        )
        core_from_jit = jax.jit(
            shard_map(
                core_from,
                mesh=mesh,
                in_specs=(
                    {k: P("v") for k in n_arrs},
                    P("v"),
                    P("v"),
                    tuple(P("v") for _ in range(num_planes)),
                    P(),
                    P(),
                )
                + mask_in,
                out_specs=(
                    P("v"),
                    P("v"),
                    tuple(P("v") for _ in range(num_planes)),
                    P(),
                    P(),
                    P(),
                )
                + gc_out,
                check_vma=False,
            )
        )
        device_arrs = {
            k: jax.device_put(a, NamedSharding(mesh, P("v")))
            for k, a in n_arrs.items()
        }
        return core_jit, core_from_jit, device_arrs

    return build


class DistHybridMsBfsEngine(
    PackedRunProtocol, RowGatherExchangeAccounting, PullGateHost,
    AotProgramProtocol,
):
    """Multi-chip 4096-lane hybrid MS-BFS: dense MXU tiles + gather residual.

    API mirrors HybridMsBfsEngine; frontier/visited/planes are all sharded
    [rows/P, w] per chip (tau order: chip-major, then each chip's local
    row-tiles), so per-chip state memory falls as the mesh grows — the
    scaling the reference's full-replication design forecloses
    (bfs.cu:346-351).

    ``pull_gate=True`` works on every exchange; NB the unit of
    ``last_gate_level_counts`` differs by layout: gather/sparse count
    skipped 128-row bucket blocks (chip-summed, like the single-chip
    engines), while the ring-sliced layout counts skipped per-chip
    CONTRIBUTION COMPUTES (<= P per level — a chip with an empty resident
    frontier shard skips all P of its expansion steps). Compare gated
    counters within one layout only.
    """

    def __init__(
        self,
        graph: Graph | dict,
        mesh: Mesh | int | None = None,
        *,
        kcap: int = 64,
        tile_thr: int = 64,
        a_budget_bytes: int = int(0.2e9),
        num_planes: int = 5,
        interpret: bool | None = None,
        exchange: str = "dense",
        sparse_caps: int | tuple[int, ...] | None = None,
        lanes: int = LANES,
        pull_gate: bool = False,
        wire_pack: bool = False,
        delta_bits: tuple[int, ...] = (),
        expand_impl: str = "xla",
    ):
        if not (1 <= num_planes <= 8):
            raise ValueError("num_planes must be in [1, 8]")
        validate_expand_impl(expand_impl)
        self.expand_impl = expand_impl
        if delta_bits and exchange != "sparse":
            raise ValueError(
                "delta_bits compresses the SPARSE row gather's id stream "
                f"(ISSUE 7); exchange={exchange!r} ships whole slabs — "
                "use exchange='sparse'"
            )
        # Wire format (ISSUE 5): every exchange this engine runs — the
        # dense/sparse row gathers AND the sliced layout's rotating
        # source-contribution accumulators — already moves uint32 lane
        # words, one BIT per (vertex, source) pair; bit-packing is the
        # packed MS representation itself, so there is nothing left to
        # compress. The flag is accepted for knob uniformity with the
        # single-source engines (CLI --wire-pack, bench A/B) and pinned
        # to a no-op by the fuzz suite.
        self.wire_pack = bool(wire_pack)
        if exchange not in ("dense", "sparse", "sliced"):
            raise ValueError(
                f"unknown exchange {exchange!r}; have 'dense', 'sparse', "
                "'sliced'"
            )
        if lanes % LANES or not (LANES <= lanes <= MAX_LANES):
            # The dense kernel runs on every shard, so the distributed
            # engine takes whole 4096-lane steps only (no narrow fallback
            # here — per-chip state already scales 1/P; shard wider
            # instead of narrowing).
            raise ValueError(
                f"lanes must be a multiple of {LANES} in [{LANES}, "
                f"{MAX_LANES}]"
            )
        self.w = lanes // 32
        self.lanes = lanes
        self.num_planes = num_planes
        self.max_levels_cap = min(1 << num_planes, 254)
        interpret = resolve_interpret(interpret)
        self.mesh = mesh if isinstance(mesh, Mesh) else make_mesh(mesh)
        p_count = self.mesh.devices.size
        layout = "sliced" if exchange == "sliced" else "gather"
        hd = (
            build_dist_hybrid(
                graph, p_count, kcap=kcap, tile_thr=tile_thr,
                a_budget_bytes=a_budget_bytes, layout=layout,
            )
            if isinstance(graph, Graph)
            else graph
        )
        if hd["num_shards"] != p_count:
            raise ValueError(
                f"built for {hd['num_shards']} shards, mesh has {p_count}"
            )
        if hd.get("layout", "gather") != layout:
            raise ValueError(
                f"prebuilt shard dict has layout {hd.get('layout', 'gather')!r} "
                f"but exchange {exchange!r} needs {layout!r}"
            )
        self.hd = hd
        self._parent_kcap = kcap
        # Host-side edge list for post-loop parent extraction
        # (PackedBatchResult.parents_int32); a prebuilt shard dict dropped it.
        self.host_graph = graph if isinstance(graph, Graph) else None
        self.undirected = hd["undirected"]
        rows = hd["rows"]

        n_arrs = dict(hd["res_arrs"])
        n_arrs["perm"] = hd["perm_s"]
        n_arrs["valid"] = hd["valid_s"]
        if hd["num_tiles"]:
            n_arrs["row_start"] = hd["row_start_s"]
            n_arrs["col_tile"] = hd["col_tile_s"]
            n_arrs["a_tiles"] = hd["a_tiles_s"]
        rows_loc = (hd["vt"] // hd["num_shards"]) * TILE
        #: delta-encoded sparse row-gather ids (ISSUE 7; sparse exchange
        #: only, default OFF until chip-measured).
        self.delta_bits = check_delta_bits(delta_bits)
        if sparse_caps is None:
            sparse_caps = default_row_gather_caps(
                rows_loc, self.w, self.delta_bits
            )
        elif isinstance(sparse_caps, int):
            sparse_caps = (sparse_caps,)
        self._exchange = exchange
        self.sparse_caps = normalize_caps(sparse_caps)
        # RowGatherExchangeAccounting host attributes (see collectives.py).
        self._gather_p = hd["num_shards"]
        self._gather_rows_loc = rows_loc
        self.last_exchange_level_counts: np.ndarray | None = None
        self.last_exchange_bytes: float | None = None
        self.pull_gate = pull_gate
        if pull_gate and layout == "gather":
            # Per-chip gate tables (common shapes, like every other array
            # under shard_map): sentinel-padded whole-block bucket indices
            # + the forward routing map bucket-position -> local row.
            spec = hd["res_spec"]
            sentinel = rows - 1
            for i, (_k, _n) in enumerate(spec.light_meta):
                lt = hd["res_arrs"][f"light{i}_t"]  # [P, k, n]
                n_arrs[f"light{i}_gt"] = np.stack(
                    [pad_gate_blocks(lt[p], sentinel) for p in range(p_count)]
                )
            nh = (
                hd["res_arrs"]["heavy_pick"].shape[1] if spec.heavy else 0
            )
            out_height = nh + sum(n for _, n in spec.light_meta) + spec.tail_rows
            num_real = out_height - 1  # the shared zero row is last
            n_arrs["gate_fwd"] = np.stack([
                gate_forward_map(hd["perm_s"][p], out_height, num_real)
                for p in range(p_count)
            ])
        if pull_gate:
            self._lane_mask_dev = jnp.full((self.w,), 0xFFFFFFFF, jnp.uint32)
        if expand_impl == "pallas":
            # Kernel-side whole-block index tables, per shard (gather
            # layout: [P, k, nb*T], sentinel = the gathered table's pad
            # row rows-1) or per (shard, ring step) (sliced layout:
            # [P, P, k, nb*T], sentinel = the appended zero row rows_loc).
            # The pull-gate block above builds the gather layout's light
            # tables identically when both tiers are on.
            spec = hd["res_spec"]
            sentinel = rows_loc if layout == "sliced" else rows - 1

            def _gt_stack(tbl):
                if layout == "sliced":
                    return np.stack([
                        np.stack([
                            pad_gate_blocks(tbl[p, s], sentinel)
                            for s in range(p_count)
                        ])
                        for p in range(p_count)
                    ])
                return np.stack([
                    pad_gate_blocks(tbl[p], sentinel) for p in range(p_count)
                ])

            if spec.heavy:
                n_arrs["virtual_gt"] = _gt_stack(hd["res_arrs"]["virtual_t"])
            for i, (_k, _n) in enumerate(spec.light_meta):
                n_arrs[f"light{i}_gt"] = _gt_stack(
                    hd["res_arrs"][f"light{i}_t"]
                )
        build = _make_dist_core(
            hd, self.w, num_planes, self.mesh, interpret, exchange,
            self.sparse_caps,
            gate_levels=self.max_levels_cap if pull_gate else 0,
            delta_bits=self.delta_bits, expand_impl=expand_impl,
        )
        if pull_gate:
            # The raw jitted resume loop takes the extra lane-mask arg and
            # returns the counter array; keep it OFF the _core_from_jit
            # name so the generic cap-boundary probe and the exchange-
            # accounting wrapper can't mis-call it (PullGateHost).
            self._dist_core, self._gate_core_from_jit, self.arrs = build(
                n_arrs
            )
        else:
            self._dist_core, self._core_from_jit, self.arrs = build(n_arrs)
        self._table_rows = hd["rows"]

        # Extraction maps vertices through tau (vertex -> sharded-table row);
        # isolated vertices map to `rows` and are masked host-side (_act).
        self._rank = hd["tau_of_vertex"]
        self._act = rows
        in_deg_tau = np.zeros(rows, dtype=np.int32)
        valid_v = hd["tau_of_vertex"] < rows
        in_deg_tau[hd["tau_of_vertex"][valid_v]] = hd["in_degree"][
            valid_v
        ].astype(np.int32)
        _, self._lane_stats, self._extract_word, self._lane_ecc = (
            make_state_kernels(
                rows, rows, self.w, num_planes, in_deg_host=in_deg_tau
            )
        )
        sharded = NamedSharding(self.mesh, P("v"))
        w_ = self.w

        @partial(jax.jit, out_shardings=sharded)
        def seed(rws, words, bits):
            fw0 = jnp.zeros((rows, w_), jnp.uint32)
            return fw0.at[rws, words].add(bits)

        self._seed_k = seed
        self._warmed = False

    @property
    def num_vertices(self) -> int:
        return self.hd["num_vertices"]

    # Word-major lane map, same as the single-chip engines.
    @staticmethod
    def _word_col(i: int):
        return i // 32, i % 32

    @staticmethod
    def _lane_order(mat: np.ndarray) -> np.ndarray:
        return mat.reshape(-1)

    def _iso_of(self, sources: np.ndarray):
        return self.hd["rank"][np.asarray(sources, np.int64)] >= self.hd[
            "num_active"
        ]

    def _seed_dev(self, sources: np.ndarray):
        tau = self.hd["tau_of_vertex"][np.asarray(sources, np.int64)]
        return self._seed_k(*seed_scatter_args(tau, self._act))

    def analysis_programs(self):
        """Static-analyzer hook (tpu_bfs/analysis): the distributed core
        (gated form carries the lane-mask arg). Same contract as
        DistBfsEngine.analysis_programs. The seed table is pre-replicated
        (per-batch seed movement is inherent to dispatch; the transfer
        guard watches the loop, not the input staging)."""
        rep = NamedSharding(self.mesh, P())
        fw0 = jax.device_put(self._seed_dev(np.asarray([0])), rep)
        ml = jax.device_put(jnp.int32(32), rep)
        args = (self.arrs, fw0, ml)
        if self.pull_gate:
            args = args + (jax.device_put(self._lane_mask_dev, rep),)
        return [("dist_core", self._dist_core, args)]

    def export_programs(self):
        """AOT inventory (ISSUE 9; utils/aot.py): the sharded level-loop
        core (gated form carries the lane-mask arg), reusing the
        analysis hook's replicated example args."""
        return [
            ("dist_core", "_dist_core", fn, args)
            for name, fn, args in self.analysis_programs()
            if name == "dist_core"
        ]

    def _core(self, arrs, fw0, max_levels):
        if self.pull_gate:
            planes, vis, levels, alive, truncated, bc, gc = self._dist_core(
                arrs, fw0, max_levels, self._lane_mask_dev
            )
            # [P, L] per-chip skipped blocks; the chip-axis sum stays a
            # DEVICE reduction (no collective was added for it — wirecheck
            # check_gated_hybrid pins that) and, like the exchange
            # counters, is not np.asarray'd here: _core runs inside the
            # async dispatch half, and readers pay the transfer.
            self.last_gate_level_counts = gc.sum(axis=0)
        else:
            planes, vis, levels, alive, truncated, bc = self._dist_core(
                arrs, fw0, max_levels
            )
        self._record_exchange(bc, 0)
        return planes, vis, levels, alive, truncated

    def _core_from(self, arrs, fw, vis, planes, level0, max_levels):
        if not self.pull_gate:
            return super()._core_from(
                arrs, fw, vis, planes, level0, max_levels
            )
        fw_f, vis_f, planes_f, level, alive, bc, gc = (
            self._gate_core_from_jit(
                arrs, fw, vis, planes, level0, max_levels,
                self._lane_mask_dev,
            )
        )
        self._record_exchange(
            bc, int(level0), getattr(self, "_pending_chain_nonce", None)
        )
        self.last_gate_level_counts = gc.sum(axis=0)
        return fw_f, vis_f, planes_f, level, alive

    def _full_parent_ell(self):
        """Batched device parent scan structure (parent_scan.py): neither
        the dense tiles nor the per-chip residual shards concatenate into
        one coverage structure, so build a fresh full in-neighbor ELL; the
        scan's row-space perm maps this engine's tau-ordered extraction
        tables into it. Owned tables — released after the export."""
        return lazy_full_parent_ell(self.host_graph, self._parent_kcap)

    # run/dispatch/fetch come from PackedRunProtocol (_packed_common).

    # --- checkpoint/resume: every table lives in one (tau, sharded) row
    # space, so the generic real-id protocol applies unchanged — and since
    # checkpoints are real-id, a batch checkpointed here resumes on the
    # single-chip engines (or a different mesh size: elastic restart).

    def start(self, sources):
        from tpu_bfs.algorithms._packed_common import start_packed_batch

        return start_packed_batch(self, sources)

    def advance(self, ckpt, levels: int | None = None):
        from tpu_bfs.algorithms._packed_common import advance_packed_batch

        return advance_packed_batch(self, ckpt, levels)

    def finish(self, ckpt):
        from tpu_bfs.algorithms._packed_common import finish_packed_batch

        return finish_packed_batch(self, ckpt)
