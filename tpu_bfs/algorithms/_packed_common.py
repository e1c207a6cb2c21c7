"""Shared machinery of the 4096-lane packed MS-BFS engines.

msbfs_wide.py (gather-only) and msbfs_hybrid.py (MXU dense tiles + gather
residual) differ in their frontier-table height, lane-to-(word, bit) map, and
per-level hit computation — everything else (fori-loop bucket expansion,
seeding, device-side lane stats, lazy per-word distance extraction, the
generic batch ``run``) lives here once.

Engines plug in via a small protocol: attributes ``arrs``, ``lanes``,
``max_levels_cap``, ``num_planes``, ``undirected``, ``_rank``, ``_warmed``,
``num_vertices``; jitted callables ``_core`` (returning planes, vis, levels,
alive, truncated), ``_seed_dev``, ``_lane_stats`` (degree data captured at
build, make_state_kernels), ``_extract_word``; and the two lane-map hooks
``_word_col`` / ``_lane_order``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial, wraps
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs
from tpu_bfs.graph.csr import INF_DIST
from tpu_bfs.algorithms.msbfs_packed import (
    UNREACHED,
    fold_heavy,
    ripple_increment,
)


def floor_lanes(lanes: int) -> int:
    """Largest REACHABLE lane count <= ``lanes``: a power-of-two uint32
    word count times 32 (all auto sizing can ever select). The one
    definition of "reachable width" shared by auto_lanes, the hybrid
    engine's width ladder, and the bench's env clamp."""
    w = max(lanes // 32, 1)
    return 32 << (w.bit_length() - 1)


def tpu_padded_words(w: int) -> int:
    """Physical minor-dim words XLA allocates for a [rows, w] 32-bit table
    on TPU: the native tile is (8, 128), so the minor dimension pads up to
    a multiple of 128. Measured, not theoretical: the round-4 LJ OOM
    report shows u32[2591042,64] allocated at 1.24G — 2.0x its 632.58M
    unpadded size ("Extra memory due to padding ... (2.0x expansion)").
    Sizing that ignores this believes narrow rows save HBM they don't:
    below 128 words (4096 lanes), narrowing the batch buys NOTHING on
    TPU — only fewer planes or fewer rows shrink the state."""
    return -(-w // 128) * 128


class PackedStateDoesntFitError(ValueError):
    """Even the narrowest packed table cannot fit the HBM budget: on TPU a
    32-lane [rows, 1]-word table occupies the same physical HBM as 128
    words (tpu_padded_words), so no width shrink can help — the real
    levers are fewer planes, fewer rows (shard over a mesh), or shedding
    optional state (push table, dense-tile budget)."""


def auto_lanes(
    rows: int,
    num_planes: int,
    *,
    fixed_bytes: int = 0,
    hbm_budget_bytes: int = int(14.0e9),
    max_lanes: int = 4096,
    on_unfit: str = "floor",
) -> int:
    """Largest lane count whose packed state fits the HBM budget.

    The level loop keeps ~(num_planes + 6) live [rows, w] uint32 tables
    (frontier, next, hit(s), visited, planes, expansion transients —
    calibrated against the scale-21 runs on a 16 GB v5e); ``fixed_bytes``
    covers lane-independent residents (ELL indices, dense tiles). Each
    table is priced at its PHYSICAL width (:func:`tpu_padded_words`:
    sub-128-word rows pad to 128 on TPU — the round-4 LJ run OOM'd
    because the previous byte-exact model credited w=64 with a halving
    it doesn't get). Returns the largest power-of-two word count times 32
    that fits, floored at 32 lanes. Below 128 words the TPU need no
    longer shrinks, so when w=128 doesn't fit the walk falls through to
    the 32-lane floor: the small batch is still cheaper to RUN (and
    genuinely smaller on CPU), but on TPU the caller's real levers are
    fewer planes, sharding over a mesh, or shedding optional state.

    ``on_unfit='raise'`` turns that fall-through into a
    :class:`PackedStateDoesntFitError` at SIZING time when even the
    floor's physical footprint exceeds the budget (ADVICE r4: the engine
    constructors otherwise accept the unfit width and die minutes later
    in an opaque runtime RESOURCE_EXHAUSTED); ``'floor'`` (default) keeps
    the legacy estimate semantics for callers that only compare widths
    (auto_planes' probe, the bench's engine-selection pre-check).
    """
    if on_unfit not in ("floor", "raise"):
        raise ValueError(f"on_unfit must be floor|raise, got {on_unfit!r}")
    w = floor_lanes(max_lanes) // 32
    while w > 1:
        need = (num_planes + 6) * rows * tpu_padded_words(w) * 4 + fixed_bytes
        if need <= hbm_budget_bytes:
            break
        w //= 2
    if on_unfit == "raise" and w == 1:
        need = (num_planes + 6) * rows * tpu_padded_words(1) * 4 + fixed_bytes
        if need > hbm_budget_bytes:
            raise PackedStateDoesntFitError(
                f"packed state cannot fit: {rows} rows x {num_planes} "
                f"planes needs {need/1e9:.2f} GB at the narrowest physical "
                f"width (32 lanes pads to 128 words on TPU) vs the "
                f"{hbm_budget_bytes/1e9:.2f} GB budget "
                f"({fixed_bytes/1e9:.2f} GB fixed residents). Levers: "
                f"fewer planes, shard rows over more chips, or shed "
                f"optional state (adaptive push table, dense-tile budget)."
            )
    return 32 * w


def auto_planes(
    rows: int,
    *,
    fixed_bytes: int = 0,
    hbm_budget_bytes: int = int(14.0e9),
    preferred: int = 5,
    min_planes: int = 4,
    max_lanes: int = 4096,
) -> int:
    """Largest plane count <= ``preferred`` whose packed state still fits
    ``max_lanes`` lanes in the HBM budget (same memory model as
    :func:`auto_lanes`).

    Each plane halves-or-doubles nothing about correctness — it bounds the
    traversal depth at 2**planes levels — so trading planes for lanes is the
    right call on low-diameter (power-law) graphs: 4 planes still label 16
    levels, ample for RMAT/social graphs, while keeping the full 4096-lane
    batch at one scale step larger than ``preferred`` planes would allow.
    When even ``min_planes`` cannot reach ``max_lanes``, returns
    ``preferred`` — depth capacity is worth more than lanes once the width
    has to shrink anyway (the engine then lowers lanes or falls back).
    """
    for p in range(preferred, min_planes - 1, -1):
        if (
            auto_lanes(
                rows, p, fixed_bytes=fixed_bytes,
                hbm_budget_bytes=hbm_budget_bytes, max_lanes=max_lanes,
            )
            == max_lanes
        ):
            return p
    return preferred


# --- The pull gate (ISSUE 1): frontier-aware pull expansion. -------------
#
# The pull phases are frontier-independent by construction — the whole lane
# table is scanned every level (the roofline byte model names this, see
# utils/roofline.py phase_bytes). The gate keys every level's pull work on
# a SETTLED mask instead: a row is settled once every ACTIVE lane (batch
# entries that actually seeded a device row) has visited it, i.e.
# ``vis[r] == lane_mask``. A settled row can never claim again
# (``hit & ~vis`` is empty on every active lane, and frontier words only
# ever carry seeded lanes' bits), so all work producing its hit — bucket
# gathers, the fold pyramid, the permutation, the claim and plane ripple —
# is skippable with bit-identical distances/parents. The skipped work is
# compacted away with the exact mechanism the adaptive push already uses
# (``jnp.where(..., size=cap)`` index tables + a dynamically-bounded fori),
# at GATE_TILE-row block granularity so slices stay TPU-tileable.

GATE_TILE = 128  # settled-mask granularity: rows per gate block
# The block-compacted serial loop only wins when most blocks are settled;
# at peak levels (everything active) the vectorized pass is strictly
# better, so each gated pass falls back densely above this active
# fraction. Pure performance policy — both branches are bit-identical.
GATE_DENSE_DEN = 4  # gated path only when active blocks <= total / 4


def host_lane_mask(rows_of_sources: np.ndarray, act: int, w: int) -> np.ndarray:
    """[w] uint32 active-lane mask for the pull gate: the OR of every
    non-isolated batch entry's (word, bit) seed slot (same keep rule as
    seed_scatter_args; word-major, the lane map every gated engine uses).
    Lanes outside the batch — and isolated-source lanes, which never touch
    the device — are vacuously settled. All-ones is always a SAFE
    fallback: an over-wide mask only delays settling, never changes
    results (a too-NARROW mask would skip live claims, so the mask must
    cover every seeded lane)."""
    ranks = np.asarray(rows_of_sources, dtype=np.int64)
    lanes = np.arange(len(ranks))
    keep = ranks < act
    mask = np.zeros(w, np.uint32)
    np.bitwise_or.at(
        mask,
        lanes[keep] // 32,
        np.uint32(1) << (lanes[keep] % 32).astype(np.uint32),
    )
    return mask


def row_unsettled(vis, act: int, lane_mask):
    """[rows] bool: True where a real row (< ``act``) still has an active
    lane unvisited — the row can still claim, so its pull work must run."""
    uns = jnp.any((~vis & lane_mask[None, :]) != 0, axis=1)
    rows = vis.shape[0]
    return uns & (jax.lax.iota(jnp.int32, rows) < act)


def _lanes_scoped(fn):
    """Trace a level-loop core ``fn(arrs, fw, ...)`` inside the
    ``lanes<W>`` scope of its [rows, w] frontier table (W = 32 w), so each
    width's ops are told apart in a trace (the serve ladder's rungs)."""

    @wraps(fn)
    def scoped(arrs, fw, *args, **kwargs):
        with _obs.scope(f"lanes{32 * fw.shape[1]}"):
            return fn(arrs, fw, *args, **kwargs)

    return scoped


def make_gated_fori_expand(spec: "ExpandSpec", w: int, *, combine=None,
                           identity: int = 0):
    """Frontier-gated bucketed-ELL expansion — make_fori_expand's shape,
    keyed on a per-bucket-output-row ``needed`` vector.

    Light buckets process only the GATE_TILE-row blocks holding a needed
    row (compacted block ids + a dynamically-bounded fori, each block
    sliced out of the padded ``light{i}_gt`` table —
    graph/ell.pad_gate_blocks); the heavy virtual/fold-pyramid section is
    skipped outright once every heavy destination row has settled (hubs
    settle first on power-law graphs, so the whole-section skip captures
    the win without per-virtual-row bookkeeping). Every gated pass falls
    back to the dense form via lax.cond when most blocks are still active
    (GATE_DENSE_DEN). Skipped rows come out as ``identity`` — exactly the
    value whose claim the caller masks away.

    Returns ``expand(arrs, fw, needed) -> (outputs, skipped_blocks)``.
    """
    if combine is None:
        combine = jnp.bitwise_or
    ident = jnp.uint32(identity)
    T = GATE_TILE

    def _full(shape):
        return jnp.full(shape, ident, jnp.uint32)

    heavy_blocks = -(-spec.num_virtual // T) if spec.heavy else 0

    @_obs.scope("level.gather")
    def expand(arrs, fw, needed):
        parts = []
        skipped = jnp.int32(0)
        off = 0
        if spec.heavy:
            nh = arrs["heavy_pick"].shape[0]
            vr_t = arrs["virtual_t"]

            def heavy_section():
                def vbody(kk, acc):
                    return combine(acc, fw[vr_t[kk]])

                acc = jax.lax.fori_loop(
                    0, spec.kcap, vbody, _full((spec.num_virtual, w))
                )
                return fold_heavy(acc, arrs, combine, _full((1, w)),
                                  spec.fold_steps)

            h_need = jnp.any(needed[:nh])
            parts.append(
                jax.lax.cond(h_need, heavy_section, lambda: _full((nh, w)))
            )
            skipped = skipped + jnp.where(h_need, 0, heavy_blocks)
            off = nh
        for i, (k, n) in enumerate(spec.light_meta):
            bt = arrs[f"light{i}_t"]  # [k, n]
            gt = arrs[f"light{i}_gt"]  # [k, nb*T] sentinel-padded
            nb = gt.shape[1] // T
            need = needed[off : off + n]
            pad = nb * T - n
            if pad:
                need = jnp.concatenate([need, jnp.zeros((pad,), bool)])
            blk = jnp.any(need.reshape(nb, T), axis=1)
            nzb = jnp.sum(blk.astype(jnp.int32))
            take_gated = nzb * GATE_DENSE_DEN <= nb

            def dense_pass(bt=bt, k=k, n=n):
                def lbody(kk, acc):
                    return combine(acc, fw[bt[kk]])

                return jax.lax.fori_loop(0, k, lbody, _full((n, w)))

            def gated_pass(gt=gt, k=k, n=n, nb=nb, blk=blk, nzb=nzb):
                idx = jnp.where(blk, size=nb, fill_value=0)[0]

                def bbody(j, acc):
                    b = idx[j]
                    cols = jax.lax.dynamic_slice(gt, (0, b * T), (k, T))

                    def kbody(kk, a):
                        return combine(a, fw[cols[kk]])

                    ablk = jax.lax.fori_loop(0, k, kbody, _full((T, w)))
                    return jax.lax.dynamic_update_slice(acc, ablk, (b * T, 0))

                acc = jax.lax.fori_loop(0, nzb, bbody, _full((nb * T, w)))
                return acc[:n]

            parts.append(jax.lax.cond(take_gated, gated_pass, dense_pass))
            skipped = skipped + jnp.where(take_gated, nb - nzb, 0)
            off += n
        if spec.tail_rows:
            parts.append(_full((spec.tail_rows, w)))
        out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        return out, skipped

    return expand


def gated_state_update(hit, vis, planes, need_rows):
    """Claim + visited-OR + plane ripple over only the GATE_TILE row blocks
    still holding an unsettled row — the pull gate's state pass.

    Skipped blocks are bit-identical to the dense update on everything any
    extraction reads: their claim is zero (settled rows' ``hit & ~vis`` is
    empty on every active lane), visited is unchanged, and the only plane
    bits the dense ripple would still move there belong to inactive lanes
    or pad rows — positions no distance extraction ever decodes. The
    ragged tail block (< GATE_TILE rows; sentinel/pad rows live there)
    always updates densely. Falls back to the one-shot dense update via
    lax.cond when most blocks are active (GATE_DENSE_DEN).

    Returns ``(nxt, vis2, planes2)``.
    """
    T = GATE_TILE
    rows, w = vis.shape
    nt = rows // T
    tail = rows - nt * T

    def dense():
        nxt = hit & ~vis
        vis2 = vis | nxt
        return nxt, vis2, ripple_increment(planes, ~vis2)

    if nt == 0:
        return dense()
    blk = jnp.any(need_rows[: nt * T].reshape(nt, T), axis=1)
    nzt = jnp.sum(blk.astype(jnp.int32))

    def gated():
        idx = jnp.where(blk, size=nt, fill_value=0)[0]

        def bbody(j, carry):
            nxt, vis2, pl = carry
            off = idx[j] * T
            h = jax.lax.dynamic_slice(hit, (off, 0), (T, w))
            v = jax.lax.dynamic_slice(vis2, (off, 0), (T, w))
            nx = h & ~v
            v2 = v | nx
            p_t = tuple(
                jax.lax.dynamic_slice(p, (off, 0), (T, w)) for p in pl
            )
            p2 = ripple_increment(p_t, ~v2)
            return (
                jax.lax.dynamic_update_slice(nxt, nx, (off, 0)),
                jax.lax.dynamic_update_slice(vis2, v2, (off, 0)),
                tuple(
                    jax.lax.dynamic_update_slice(p, q, (off, 0))
                    for p, q in zip(pl, p2)
                ),
            )

        nxt, vis2, pl = jax.lax.fori_loop(
            0, nzt, bbody, (jnp.zeros_like(vis), vis, planes)
        )
        if tail:
            h = hit[nt * T :]
            v = vis2[nt * T :]
            nx = h & ~v
            v2 = v | nx
            p2 = ripple_increment(tuple(p[nt * T :] for p in pl), ~v2)
            nxt = jax.lax.dynamic_update_slice(nxt, nx, (nt * T, 0))
            vis2 = jax.lax.dynamic_update_slice(vis2, v2, (nt * T, 0))
            pl = tuple(
                jax.lax.dynamic_update_slice(p, q, (nt * T, 0))
                for p, q in zip(pl, p2)
            )
        return nxt, vis2, pl

    return jax.lax.cond(nzt * GATE_DENSE_DEN <= nt, gated, dense)


class PullGateHost:
    """Mixin for pull-gated packed engines: host-side lane-mask bookkeeping
    plus the single-chip core wrappers that thread the mask into the gated
    jitted loop and record the per-level skipped-block counters
    (``last_gate_level_counts`` — same host-attribute idiom as the
    distributed engines' exchange accounting, collectives.py). Hosts set
    ``pull_gate``, ``_gate_core_jit`` / ``_gate_core_from_jit`` /
    ``_gate_core_from_donate_jit`` (make_packed_loop gated entries),
    ``_lane_mask_dev`` (all-ones until the first batch refines it —
    always safe, see host_lane_mask), and the engine-protocol attributes
    ``_rank`` / ``_act`` / ``w``."""

    pull_gate = False
    last_gate_level_counts = None

    def _note_batch_sources(self, sources) -> None:
        if not self.pull_gate:
            return
        rows = np.asarray(self._rank)[np.asarray(sources, dtype=np.int64)]
        self._lane_mask_dev = jnp.asarray(
            host_lane_mask(rows, self._act, self.w)
        )

    def _gated_core(self, arrs, fw0, max_levels):
        planes, vis, levels, alive, truncated, gc = self._gate_core_jit(
            arrs, fw0, max_levels, self._lane_mask_dev
        )
        # Kept as a device array so the record costs nothing inside a
        # timed batch; np.asarray it at read time (stats/CLI do).
        self.last_gate_level_counts = gc
        return planes, vis, levels, alive, truncated

    def _gated_core_from(self, arrs, fw, vis, planes, level0, max_levels):
        fw_f, vis_f, planes_f, level, alive, gc = self._gate_core_from_jit(
            arrs, fw, vis, planes, level0, max_levels, self._lane_mask_dev
        )
        self.last_gate_level_counts = gc
        return fw_f, vis_f, planes_f, level, alive

    def _gated_core_from_donate(self, arrs, fw, vis, planes, level0,
                                max_levels):
        """The donating resume entry (ISSUE 13): same loop, carry
        donated — advance_packed_batch's path, whose converted
        checkpoint carries are dead after the call."""
        fw_f, vis_f, planes_f, level, alive, gc = (
            self._gate_core_from_donate_jit(
                arrs, fw, vis, planes, level0, max_levels,
                self._lane_mask_dev
            )
        )
        self.last_gate_level_counts = gc
        return fw_f, vis_f, planes_f, level, alive

    def _core_from_probe(self, arrs, fw, vis, planes, level0, max_levels):
        """advance's cap-boundary probe entry: the same gated loop, minus
        the counter record — the probe's one boundary body must not
        clobber the real run's per-level counts. Ungated instances
        delegate to the exact pre-gate probe resolution (raw jitted loop
        where the engine has one, else _core_from)."""
        if not self.pull_gate:
            fn = getattr(self, "_core_from_jit", None) or self._core_from
            return fn(arrs, fw, vis, planes, level0, max_levels)
        return self._gate_core_from_jit(
            arrs, fw, vis, planes, level0, max_levels, self._lane_mask_dev
        )[:5]


def make_packed_loop(hit_of, num_planes: int, *, gate_levels: int = 0,
                     act: int | None = None):
    """The level loop shared by the wide and hybrid engines, as two jitted
    entry points over one body:

    - ``core(arrs, fw0, max_levels)`` — a fresh traversal (the historical
      signature): visited starts as the seed table, planes at zero;
    - ``core_from(arrs, fw, vis, planes, level0, max_levels)`` — resume from
      mid-traversal state, the checkpoint/restart entry (the reference has
      no checkpointing at all, SURVEY.md §5). Because the while-loop carry
      IS the traversal state, resuming from a saved carry is bit-identical
      to never having stopped.

    ``hit_of(arrs, fw)`` is the engine's one-level frontier expansion
    (gather-only for the wide engine; MXU tiles + gather residual +
    permutation for the hybrid).

    With ``gate_levels`` > 0 the loop runs in PULL-GATED mode (``act``
    required): ``hit_of(arrs, fw, vis, lane_mask)`` returns
    ``(hit, skipped_blocks)``, both entry points take a trailing
    ``lane_mask`` argument (host_lane_mask), the state pass runs gated
    over unsettled GATE_TILE blocks (gated_state_update), and both return
    a trailing [gate_levels] int32 per-level skipped-block array.

    Returns ``(core, core_from, core_from_donate)`` — the third is
    ``core_from`` with the carry (fw/vis/planes) DONATED (ISSUE 13,
    analysis pass 5): the resume path's outputs alias its inputs instead
    of doubling the table residency per chunk. ``advance_packed_batch``
    rides the donating entry (its converted checkpoint carries are dead
    after the call by construction); ``core_from`` stays copying for the
    callers that re-read their carries — the cap-boundary probe (which
    must keep the pre-probe tables) and the roofline's CPU stepping
    (which warms by double-calling the same arguments).
    """
    gated = gate_levels > 0
    if gated and act is None:
        raise ValueError("gated make_packed_loop needs act (real row count)")

    def call_hit(arrs, fw, vis, lane_mask):
        if gated:
            return hit_of(arrs, fw, vis, lane_mask)
        return hit_of(arrs, fw), jnp.int32(0)

    def _run(arrs, fw, vis, planes, level0, max_levels, lane_mask, gc):
        def cond(carry):
            _, _, _, level, alive, _ = carry
            return alive & (level < max_levels)

        def body(carry):
            fw, vis, planes, level, _, gc = carry
            with _obs.scope("level.gather"):
                hit, skipped = call_hit(arrs, fw, vis, lane_mask)
            with _obs.scope("level.claim"):
                if gated:
                    need = row_unsettled(vis, act, lane_mask)
                    nxt, vis2, planes = gated_state_update(
                        hit, vis, planes, need
                    )
                    gc = gc.at[jnp.minimum(level, gate_levels - 1)].set(
                        skipped
                    )
                else:
                    nxt = hit & ~vis
                    vis2 = vis | nxt
                    # Pad/sentinel rows count up harmlessly (never
                    # visited, sliced off at extraction).
                    planes = ripple_increment(planes, ~vis2)
                alive = jnp.any(nxt != 0)
                return nxt, vis2, planes, level + 1, alive, gc

        # The loop itself (its termination test and carry) is claim work;
        # each phase of the body names itself inside it.
        with _obs.scope("level.claim"):
            return jax.lax.while_loop(
                cond, body, (fw, vis, planes, level0, jnp.bool_(True), gc)
            )

    def _truncated(arrs, fw_f, vis_f, levels, alive, max_levels, lane_mask):
        # `alive` only says the last body claimed something. When the loop
        # exits at the cap, distances <= max_levels are all labeled
        # correctly; the traversal is incomplete only if one MORE level
        # would claim vertices. Decide that with a single claim-free
        # expand, so a traversal whose eccentricity lands exactly on the
        # cap does not falsely report truncation.
        def deeper():
            with _obs.scope("level.gather"):
                hit = call_hit(arrs, fw_f, vis_f, lane_mask)[0]
            return jnp.any((hit & ~vis_f) != 0)

        with _obs.scope("level.claim"):
            return jax.lax.cond(
                alive & (levels >= max_levels), deeper,
                lambda: jnp.bool_(False),
            )

    def _planes0(fw0):
        with _obs.scope("batch.seed"):
            return tuple(jnp.zeros_like(fw0) for _ in range(num_planes))

    def _gc0():
        return jnp.zeros((max(gate_levels, 1),), jnp.int32)

    if gated:

        @jax.jit  # no-donate: fw0 doubles as the batch's src-bits view (fetch reads it after the loop)
        @_lanes_scoped
        def core(arrs, fw0, max_levels, lane_mask):
            planes0 = _planes0(fw0)
            fw_f, vis_f, planes_f, levels, alive, gc = _run(
                arrs, fw0, fw0, planes0, jnp.int32(0), max_levels,
                lane_mask, _gc0(),
            )
            truncated = _truncated(
                arrs, fw_f, vis_f, levels, alive, max_levels, lane_mask
            )
            return planes_f, vis_f, levels, alive, truncated, gc

        @jax.jit  # no-donate: the cap-boundary probe and roofline re-read their carries; advance rides core_from_donate
        @_lanes_scoped
        def core_from(arrs, fw, vis, planes, level0, max_levels, lane_mask):
            return _run(
                arrs, fw, vis, planes, level0, max_levels, lane_mask, _gc0()
            )

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        @_lanes_scoped
        def core_from_donate(arrs, fw, vis, planes, level0, max_levels,
                             lane_mask):
            return _run(
                arrs, fw, vis, planes, level0, max_levels, lane_mask, _gc0()
            )

        core_from_donate._donate_argnums = (1, 2, 3)
        return core, core_from, core_from_donate

    @jax.jit  # no-donate: fw0 doubles as the batch's src-bits view (fetch reads it after the loop)
    @_lanes_scoped
    def core(arrs, fw0, max_levels):
        planes0 = _planes0(fw0)
        fw_f, vis_f, planes_f, levels, alive, _ = _run(
            arrs, fw0, fw0, planes0, jnp.int32(0), max_levels, None, _gc0()
        )
        truncated = _truncated(
            arrs, fw_f, vis_f, levels, alive, max_levels, None
        )
        return planes_f, vis_f, levels, alive, truncated

    @jax.jit  # no-donate: the cap-boundary probe and roofline re-read their carries; advance rides core_from_donate
    @_lanes_scoped
    def core_from(arrs, fw, vis, planes, level0, max_levels):
        out = _run(arrs, fw, vis, planes, level0, max_levels, None, _gc0())
        return out[:5]

    @partial(jax.jit, donate_argnums=(1, 2, 3))
    @_lanes_scoped
    def core_from_donate(arrs, fw, vis, planes, level0, max_levels):
        out = _run(arrs, fw, vis, planes, level0, max_levels, None, _gc0())
        return out[:5]

    core_from_donate._donate_argnums = (1, 2, 3)
    return core, core_from, core_from_donate


class ExpandSpec(NamedTuple):
    """Shape metadata of a bucketed-ELL expansion (see graph/ell.py)."""

    kcap: int
    heavy: bool
    num_virtual: int
    fold_steps: int
    light_meta: tuple  # ((k, n), ...)
    tail_rows: int  # all-zero rows appended after the buckets


def make_fori_expand(spec: ExpandSpec, w: int, *, combine=None,
                     identity: int = 0):
    """Bucketed-ELL expansion with fori-loop accumulation.

    ``fw`` is the packed frontier table; returns the concatenated bucket
    outputs (heavy rows, then light buckets, then ``tail_rows`` identity
    rows). Only one gather result is live at a time — the unrolled form kept
    ~20 padded [n, w] intermediates alive and OOM'd at w >= 64.

    ``combine``/``identity`` default to bitwise OR over 0 (the BFS frontier
    expansion). Any associative-commutative u32 op with an identity works
    over the same bucket structure — parent_scan.py runs this with
    ``jnp.minimum`` over 0xFFFFFFFF to min-reduce per-lane parent keys,
    because the fold pyramid and pad rows only assume those two algebraic
    properties (pads/sentinels must be absorbed, order must not matter).
    """
    if combine is None:
        combine = jnp.bitwise_or
    ident = jnp.uint32(identity)

    def _full(shape):
        return jnp.full(shape, ident, jnp.uint32)

    @_obs.scope("level.gather")
    def expand(arrs, fw):
        parts = []
        if spec.heavy:
            vr_t = arrs["virtual_t"]  # [kcap, M]

            def vbody(kk, acc):
                return combine(acc, fw[vr_t[kk]])

            acc = jax.lax.fori_loop(
                0, spec.kcap, vbody, _full((spec.num_virtual, w))
            )
            parts.append(fold_heavy(acc, arrs, combine, _full((1, w)),
                                    spec.fold_steps))
        for i, (k, n) in enumerate(spec.light_meta):
            bt = arrs[f"light{i}_t"]  # [k, n]

            def lbody(kk, acc, bt=bt):
                return combine(acc, fw[bt[kk]])

            acc = jax.lax.fori_loop(0, k, lbody, _full((n, w)))
            parts.append(acc)
        if spec.tail_rows:
            parts.append(_full((spec.tail_rows, w)))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    return expand


def expand_arrays(ell_like) -> dict:
    """Device-ready (transposed) bucket index arrays for make_fori_expand.

    ``ell_like`` needs attributes ``virtual`` (EllBucket or None),
    ``fold_pad_map``, ``heavy_pick``, ``light`` (list of EllBucket)."""
    arrs = {}
    if ell_like.virtual is not None:
        arrs["virtual_t"] = jnp.asarray(
            np.ascontiguousarray(ell_like.virtual.idx.T)
        )
        arrs["fold_pad_map"] = jnp.asarray(ell_like.fold_pad_map)
        arrs["heavy_pick"] = jnp.asarray(ell_like.heavy_pick)
    for i, b in enumerate(ell_like.light):
        arrs[f"light{i}_t"] = jnp.asarray(np.ascontiguousarray(b.idx.T))
    return arrs


#: Legal ``expand_impl`` values (ISSUE 16). ``xla`` is the fori-loop jnp
#: form XLA fuses; ``pallas`` is the hand-written gather-combine kernel
#: (ops/ell_expand.py) — bit-identical by construction, selected per
#: engine and A/B-priced by the roofline before any default flips.
EXPAND_IMPLS = ("xla", "pallas")

#: jnp combine per symbolic kernel op (the fold pyramid runs outside the
#: kernel and needs the callable form back).
_OP_COMBINE = {"or": jnp.bitwise_or, "min": jnp.minimum,
               "minplus": jnp.minimum}


def validate_expand_impl(impl: str, *, who: str = "expand_impl") -> str:
    if impl not in EXPAND_IMPLS:
        raise ValueError(
            f"{who} must be one of {EXPAND_IMPLS}, got {impl!r}"
        )
    return impl


def _pallas_op_of(combine, identity: int) -> str:
    """Map make_fori_expand's combine/identity callable contract onto the
    kernel's symbolic op names (a Pallas kernel cannot close over a jnp
    callable, so the contract goes symbolic at this boundary)."""
    if (combine is None or combine is jnp.bitwise_or) and identity == 0:
        return "or"
    if combine is jnp.minimum and identity == 0xFFFFFFFF:
        return "min"
    raise ValueError(
        "expand_impl='pallas' supports combine/identity pairs "
        "(bitwise_or, 0), (minimum, 0xFFFFFFFF) and the SSSP min-plus "
        f"form; got ({combine}, {identity:#x})"
    )


def pallas_expand_arrays(ell_like, sentinel: int) -> dict:
    """Host-side sentinel-padded whole-block index tables for the Pallas
    expansion tier (numpy int32; callers device-put/stack as their layout
    needs). Same pad_gate_blocks layout the pull gate's light tables use
    — when both tiers are on, the ``light{i}_gt`` tables are shared —
    plus ``virtual_gt`` so the heavy section runs through the kernel too.
    ``sentinel`` must gather the engine's identity frontier row."""
    from tpu_bfs.graph.ell import pad_gate_blocks

    arrs = {}
    if ell_like.virtual is not None:
        arrs["virtual_gt"] = pad_gate_blocks(
            np.ascontiguousarray(ell_like.virtual.idx.T), sentinel
        )
    for i, b in enumerate(ell_like.light):
        arrs[f"light{i}_gt"] = pad_gate_blocks(
            np.ascontiguousarray(b.idx.T), sentinel
        )
    return arrs


def make_pallas_expand(spec: "ExpandSpec", w: int, *, op: str = "or",
                       interpret: bool = False, wsuf: str | None = None):
    """make_fori_expand's drop-in built on the fused Pallas kernel
    (ops/ell_expand.py): per bucket, ONE kernel launch whose accumulator
    stays VMEM-resident across all k ELL slots with double-buffered row
    gathers — each output row tile hits HBM once per level. The heavy
    fold pyramid and heavy_pick stay jnp (cheap permutation work over the
    kernel's virtual-row output). Requires the ``virtual_gt``/
    ``light{i}_gt`` tables (pallas_expand_arrays); ``wsuf`` selects the
    SSSP min-plus weight planes (``{name}_{wsuf}_gt``) when op='minplus'.

    Returns ``expand(arrs, fw)`` — same signature, bit-identical output.
    """
    from tpu_bfs.ops.ell_expand import KERNEL_OPS, TILE, ell_expand

    combine = _OP_COMBINE[op]
    ident_val, dt = KERNEL_OPS[op]
    T = TILE

    def _full(shape):
        return jnp.full(shape, ident_val, dt)

    def _bucket(arrs, fw, name, k, n, need_blk=None):
        gt = arrs[f"{name}_gt"]  # [k, nb*T]
        nb = gt.shape[1] // T
        if need_blk is None:
            need_blk = jnp.ones((nb,), jnp.int32)
        wt = arrs[f"{name}_{wsuf}_gt"] if op == "minplus" else None
        out = ell_expand(
            need_blk, gt, fw, wt, w=w, op=op, interpret=interpret
        )
        return out[:n]

    @_obs.scope("level.gather")
    def expand(arrs, fw):
        parts = []
        if spec.heavy:
            acc = _bucket(
                arrs, fw, "virtual", spec.kcap, spec.num_virtual
            )
            parts.append(fold_heavy(acc, arrs, combine, _full((1, w)),
                                    spec.fold_steps))
        for i, (k, n) in enumerate(spec.light_meta):
            parts.append(_bucket(arrs, fw, f"light{i}", k, n))
        if spec.tail_rows:
            parts.append(_full((spec.tail_rows, w)))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    return expand


def make_gated_pallas_expand(spec: "ExpandSpec", w: int, *, op: str = "or",
                             interpret: bool = False):
    """make_gated_fori_expand's drop-in on the Pallas tier: the PR 1
    settled-mask gate moves INSIDE the kernel — the per-GATE_TILE block
    mask rides the scalar-prefetch channel and a gated-out tile skips its
    index DMA and gathers entirely, writing the combine identity. The
    gate POLICY is unchanged and computed outside in jnp (same block
    mask, same GATE_DENSE_DEN dense fallback — expressed as an all-ones
    mask rather than a lax.cond branch — and the same whole-section heavy
    skip), so ``skipped_blocks`` matches the XLA tier count-for-count and
    ``last_gate_level_counts`` stays comparable across impls.

    Returns ``expand(arrs, fw, needed) -> (outputs, skipped_blocks)``.
    """
    from tpu_bfs.ops.ell_expand import KERNEL_OPS, TILE, ell_expand

    combine = _OP_COMBINE[op]
    ident_val, dt = KERNEL_OPS[op]
    T = TILE

    def _full(shape):
        return jnp.full(shape, ident_val, dt)

    heavy_blocks = -(-spec.num_virtual // T) if spec.heavy else 0

    @_obs.scope("level.gather")
    def expand(arrs, fw, needed):
        parts = []
        skipped = jnp.int32(0)
        off = 0
        if spec.heavy:
            nh = arrs["heavy_pick"].shape[0]
            gt = arrs["virtual_gt"]
            nvb = gt.shape[1] // T

            def heavy_section():
                acc = ell_expand(
                    jnp.ones((nvb,), jnp.int32), gt, fw,
                    w=w, op=op, interpret=interpret,
                )[: spec.num_virtual]
                return fold_heavy(acc, arrs, combine, _full((1, w)),
                                  spec.fold_steps)

            h_need = jnp.any(needed[:nh])
            parts.append(
                jax.lax.cond(h_need, heavy_section, lambda: _full((nh, w)))
            )
            skipped = skipped + jnp.where(h_need, 0, heavy_blocks)
            off = nh
        for i, (k, n) in enumerate(spec.light_meta):
            gt = arrs[f"light{i}_gt"]  # [k, nb*T] sentinel-padded
            nb = gt.shape[1] // T
            need = needed[off : off + n]
            pad = nb * T - n
            if pad:
                need = jnp.concatenate([need, jnp.zeros((pad,), bool)])
            blk = jnp.any(need.reshape(nb, T), axis=1)
            nzb = jnp.sum(blk.astype(jnp.int32))
            take_gated = nzb * GATE_DENSE_DEN <= nb
            # Dense fallback = an all-ones mask: the kernel computes every
            # tile, which IS the dense pass (identical combines, one
            # output write either way) — no second code path to diverge.
            mask = jnp.where(take_gated, blk, True).astype(jnp.int32)
            out = ell_expand(
                mask, gt, fw, w=w, op=op, interpret=interpret
            )
            parts.append(out[:n])
            skipped = skipped + jnp.where(take_gated, nb - nzb, 0)
            off += n
        if spec.tail_rows:
            parts.append(_full((spec.tail_rows, w)))
        out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        return out, skipped

    return expand


def make_expand(spec: "ExpandSpec", w: int, *, combine=None,
                identity: int = 0, impl: str = "xla",
                interpret: bool = False):
    """The expand_impl dispatcher every packed engine builds through:
    ``xla`` -> make_fori_expand (ignores ``interpret``), ``pallas`` ->
    make_pallas_expand with the combine contract mapped to a kernel op.
    Same ``expand(arrs, fw)`` either way."""
    validate_expand_impl(impl)
    if impl == "xla":
        return make_fori_expand(spec, w, combine=combine, identity=identity)
    return make_pallas_expand(
        spec, w, op=_pallas_op_of(combine, identity), interpret=interpret
    )


def make_gated_expand(spec: "ExpandSpec", w: int, *, combine=None,
                      identity: int = 0, impl: str = "xla",
                      interpret: bool = False):
    """Gated twin of make_expand: ``expand(arrs, fw, needed) ->
    (outputs, skipped_blocks)`` with identical gate policy across impls."""
    validate_expand_impl(impl)
    if impl == "xla":
        return make_gated_fori_expand(
            spec, w, combine=combine, identity=identity
        )
    return make_gated_pallas_expand(
        spec, w, op=_pallas_op_of(combine, identity), interpret=interpret
    )


def build_push_table(host_graph, rank: np.ndarray, act: int, deg_cap: int):
    """Out-CSR push table in rank space for the level-adaptive expansion:
    ``([act+1, deg_cap] int32 out-neighbor rows (pad/sentinel = act),
    [act] bool ineligibility mask — rows with out-degree > deg_cap)``.
    Rank space must be active-first (every edge endpoint < act)."""
    src, dst = host_graph.coo
    rs = rank[src].astype(np.int64)
    rd = rank[dst].astype(np.int32)
    out_deg = np.bincount(rs, minlength=act)[:act]
    elig = out_deg <= deg_cap
    order = np.argsort(rs, kind="stable")
    rs_s, rd_s = rs[order], rd[order]
    rp = np.zeros(act + 1, np.int64)
    np.cumsum(out_deg, out=rp[1:])
    pos = np.arange(len(rs_s), dtype=np.int64) - rp[rs_s]
    keep = elig[rs_s]
    pt = np.full((act + 1, deg_cap), act, np.int32)
    pt[rs_s[keep], pos[keep]] = rd_s[keep]
    return pt, ~elig


def make_adaptive_hit(hit_of, act: int, w: int, out_rows: int, push_cfg):
    """Wrap a pull expansion with the level-adaptive push gate (VERDICT r3
    #8, experimental): a level whose packed union frontier has <= row_cap
    active rows, all with out-degree <= deg_cap, takes a push-style pass —
    a fori over the compacted active rows (trip count = the actual count,
    lowered to a while loop), each step OR-scattering its frontier words
    into its out-neighbors' hit rows — instead of the full ELL/tile scan.
    Push-over-out-edges equals pull-over-in-edges by construction (the
    push table is edge-exact, directed or not). Every other level rides
    ``hit_of`` unchanged via lax.cond.

    ``out_rows`` is the pull expansion's output height ([act+1] for the
    wide engine, [vt*TILE] for the hybrid); row ``act`` doubles as the
    pad-slot dump row and is re-zeroed after the scatter pass (it is a
    zero sentinel/pad row in every packed engine's table).
    Requires arrs keys ``push_t`` / ``push_inelig`` (build_push_table).
    """
    row_cap, _ = push_cfg

    def adaptive(arrs, fw):
        with _obs.scope("level.push"):
            rows_active = jnp.any(fw[:act] != 0, axis=1)
            nz = jnp.sum(rows_active.astype(jnp.int32))
            bad = jnp.any(rows_active & arrs["push_inelig"])
            light = (nz <= row_cap) & ~bad

        @_obs.scope("level.push")
        def push_fn():
            idx = jnp.where(rows_active, size=row_cap, fill_value=act)[0]
            pt = arrs["push_t"]

            def pbody(i, hit):
                r = idx[i]  # act when padding: fw[act] is a zero row
                nb = pt[r]  # [deg_cap], pad slots -> dump row act
                return hit.at[nb].set(hit[nb] | fw[r][None, :])

            hit = jax.lax.fori_loop(
                0, nz, pbody, jnp.zeros((out_rows, w), jnp.uint32)
            )
            # Pad slots OR real frontier words into the dump row; restore
            # its all-zero invariant (later levels gather/claim from it).
            return hit.at[act].set(0)

        return jax.lax.cond(light, push_fn, lambda: hit_of(arrs, fw))

    return adaptive


def seed_scatter_args(rows_of_sources: np.ndarray, act: int):
    """(rows, words, bits) device args for word-major lane seeding.

    ``rows_of_sources`` maps each batch entry to its table row; entries with
    no row (>= ``act`` — isolated sources) get their bit zeroed (a 0-OR is a
    no-op) and the row clamped, and run_packed_batch patches their lane
    results host-side. One copy of the protocol for every packed engine.
    """
    ranks = rows_of_sources.astype(np.int64)
    lanes = np.arange(len(ranks), dtype=np.int32)
    words = (lanes // 32).astype(np.int32)
    bits = np.uint32(1) << (lanes % 32).astype(np.uint32)
    keep = ranks < act
    return (
        jnp.asarray(np.where(keep, ranks, 0).astype(np.int32)),
        jnp.asarray(words),
        jnp.asarray(np.where(keep, bits, np.uint32(0))),
    )


def degree_sum_blocks(
    in_deg_host: np.ndarray, act: int, *, cap: int = 1 << 30
) -> tuple:
    """Static row-block boundaries for exact int32 degree summation.

    Greedy split of rows [0, act) so each block's total degree stays under
    ``cap`` (< 2**31): a per-block int32 sum of (visited_bit * degree) can
    then never overflow, making the TEPS numerator exact at any scale —
    the block partials are summed in int64 on host. A single vertex's
    degree is < V < 2**31, so a one-row block is always safe."""
    deg = np.asarray(in_deg_host[:act], dtype=np.int64)
    csum = np.cumsum(deg)  # one O(act) pass; blocks then binary-search it
    blocks = []
    s = 0
    while s < act:
        base = csum[s - 1] if s else 0
        e = int(np.searchsorted(csum, base + cap, side="left"))
        e = min(max(e, s + 1), act)  # at least one row per block
        blocks.append((s, e))
        s = e
    return tuple(blocks) if blocks else ((0, 0),)


def make_state_kernels(
    v: int,
    rows: int,
    w: int,
    num_planes: int,
    *,
    active: int | None = None,
    in_deg_host: np.ndarray | None = None,
):
    """Jitted (seed, lane_stats, extract_word) over a [rows, w] packed table
    whose first ``act`` rows are real vertices (in rank order).

    ``active`` (default: v) is the number of real rows when the table is
    trimmed to non-isolated vertices; stats and extraction scan only those.
    ``in_deg_host`` (table row order, length >= act) is captured by
    lane_stats — it both sizes the static degree-sum blocks and provides
    the summed values, so the overflow-safety analysis and the data can
    never diverge. Required for lane_stats; seed/extract_word/lane_ecc
    work without it.

    Returns ``(seed, lane_stats, extract_word, lane_ecc)``; ``lane_ecc``
    is the on-device per-lane eccentricity reduction (ISSUE 3): max
    finite distance per lane as [w, 32] int32, so distance-free serving
    queries read one [w, 32] summary instead of the O(V * lanes)
    distance table.
    """
    act = v if active is None else min(active, v)
    if in_deg_host is not None:
        blocks = degree_sum_blocks(in_deg_host, act)
        in_deg = jnp.asarray(np.asarray(in_deg_host, dtype=np.int32))
    else:
        blocks, in_deg = ((0, act),), None

    @jax.jit
    @_obs.scope("batch.seed")
    def seed(rws, words, bits):
        # Distinct lanes own distinct (word, bit) pairs, so scatter-add == OR.
        fw0 = jnp.zeros((rows, w), jnp.uint32)
        return fw0.at[rws, words].add(bits)

    @jax.jit
    @_obs.scope("batch.stats")
    def lane_stats(vis):
        """Per-word-column reached count and degree sum, on device.

        Returns (reached [w,32] i32, deg_sum [w, nblocks, 32] i32) — both
        EXACT: TPU has no int64, so the degree sum accumulates per static
        row-block (each bounded under 2**31 by degree_sum_blocks) and the
        caller reduces the block axis in int64 on host. Replaces the old
        f32 pairwise sum whose ~7 digits went inexact past ~10^7 edges
        per lane. The degree array is the captured ``in_deg_host`` — the
        same array the blocks were sized from, by construction."""
        if in_deg is None:
            raise ValueError("make_state_kernels needs in_deg_host for lane_stats")
        shifts = jnp.arange(32, dtype=jnp.uint32)

        def wbody(wi, acc):
            r_acc, d_acc = acc
            col = jax.lax.dynamic_slice(vis, (0, wi), (rows, 1))[:act]  # [act,1]
            bits = (col >> shifts) & 1  # [act, 32] u32
            rr = jnp.sum(bits.astype(jnp.int32), axis=0)
            dd = jnp.stack([
                jnp.sum(
                    bits[s:e].astype(jnp.int32) * in_deg[s:e, None], axis=0
                )
                for s, e in blocks
            ])  # [nblocks, 32] i32, each block exact
            return (
                jax.lax.dynamic_update_slice(r_acc, rr[None], (wi, 0)),
                jax.lax.dynamic_update_slice(d_acc, dd[None], (wi, 0, 0)),
            )

        r0 = jnp.zeros((w, 32), jnp.int32)
        d0 = jnp.zeros((w, len(blocks), 32), jnp.int32)
        return jax.lax.fori_loop(0, w, wbody, (r0, d0))

    @jax.jit
    @_obs.scope("batch.stats")
    def extract_word(planes, vis, src_bits, wi):
        """Distances of word-column wi's 32 lanes as [act, 32] uint8."""
        shifts = jnp.arange(32, dtype=jnp.uint32)
        cnt = jnp.zeros((act, 32), jnp.uint8)
        for i, p in enumerate(planes):
            col = jax.lax.dynamic_slice(p, (0, wi), (rows, 1))[:act]
            bit = ((col >> shifts) & 1).astype(jnp.uint8)
            cnt = cnt + (bit << i)
        visw = ((jax.lax.dynamic_slice(vis, (0, wi), (rows, 1))[:act] >> shifts) & 1) != 0
        srcw = (
            (jax.lax.dynamic_slice(src_bits, (0, wi), (rows, 1))[:act] >> shifts) & 1
        ) != 0
        return jnp.where(
            srcw, jnp.uint8(0), jnp.where(visw, cnt + jnp.uint8(1), UNREACHED)
        )

    @jax.jit
    @_obs.scope("batch.stats")
    def lane_ecc(planes, vis, src_bits):
        """Per-lane eccentricity (max finite distance) as [w, 32] int32.

        The same bit-sliced decode as extract_word, but reduced over rows
        on device: unvisited rows contribute 0 (a lane whose component is
        only its source has eccentricity 0), sources contribute 0, every
        other visited row its distance cnt + 1."""
        if act == 0:
            # Edgeless tables (every vertex isolated): no row is ever
            # visited, and the row-max below has no identity over zero
            # rows. Every lane's component is at most its source: ecc 0.
            return jnp.zeros((w, 32), jnp.int32)
        shifts = jnp.arange(32, dtype=jnp.uint32)

        def wbody(wi, acc):
            cnt = jnp.zeros((act, 32), jnp.int32)
            for i, p in enumerate(planes):
                col = jax.lax.dynamic_slice(p, (0, wi), (rows, 1))[:act]
                bit = ((col >> shifts) & 1).astype(jnp.int32)
                cnt = cnt + (bit << i)
            visw = (
                (jax.lax.dynamic_slice(vis, (0, wi), (rows, 1))[:act] >> shifts) & 1
            ) != 0
            srcw = (
                (jax.lax.dynamic_slice(src_bits, (0, wi), (rows, 1))[:act] >> shifts)
                & 1
            ) != 0
            dist = jnp.where(
                srcw, 0, jnp.where(visw, cnt + 1, 0)
            )  # [act, 32]
            return jax.lax.dynamic_update_slice(
                acc, jnp.max(dist, axis=0)[None], (wi, 0)
            )

        return jax.lax.fori_loop(0, w, wbody, jnp.zeros((w, 32), jnp.int32))

    return seed, lane_stats, extract_word, lane_ecc


@dataclasses.dataclass
class PackedBatchResult:
    """Batch result with lazy per-word distance extraction.

    Distances stay bit-sliced on device; ``distances_int32(i)`` unpacks the
    one 32-lane word-column containing lane i (then caches it), so querying a
    few lanes never materializes the full [S, V] array.
    """

    sources: np.ndarray  # [S] int32
    num_levels: int  # max distance over all lanes
    reached: np.ndarray  # [S] int64
    edges_traversed: np.ndarray  # [S] int64, exact (block-summed on device)
    elapsed_s: float | None
    _engine: object
    _planes: tuple
    _vis: jax.Array
    _src_bits: jax.Array
    # Lanes whose source is an isolated vertex (no table row; traversal is
    # trivially {source}); None when the engine's tables cover all vertices.
    _iso: np.ndarray | None = None
    # lane_ecc's [w, 32] device output when it was launched at dispatch
    # (PackedRunProtocol.launch_summaries); None computes it on first read.
    _ecc_dev: object = None
    _ecc_cache: np.ndarray | None = None
    _word_cache: dict = dataclasses.field(default_factory=dict)
    _parent_cache: dict = dataclasses.field(default_factory=dict)
    # Decoded parent columns of ONE word (32 lanes) from the cached-scanner
    # single-lane path; see _parent_lane_scan.
    _pword_cache: dict = dataclasses.field(default_factory=dict)

    @property
    def teps(self) -> float | None:
        """Harmonic-mean per-source TEPS under the batch time share."""
        if not self.elapsed_s:
            return None
        per_source_time = self.elapsed_s / len(self.sources)
        t = self.edges_traversed / per_source_time
        return float(len(t) / np.sum(1.0 / np.maximum(t, 1e-9)))

    @property
    def ecc(self) -> np.ndarray | None:
        """[S] int32 per-lane eccentricity (max finite distance), reduced
        ON DEVICE (make_state_kernels lane_ecc) and cached on first
        access — one [w, 32] summary transfer instead of decoding
        distance words host-side, so distance-free consumers (the serve
        path's want_distances=false) answer ``levels`` without ever
        pulling a distance row. Lazy: one-shot callers that never read it
        never pay the kernel, unless the serve path launched it at dispatch
        (``_ecc_dev``). None when the engine predates the kernel."""
        if self._ecc_cache is None:
            e_dev = self._ecc_dev
            if e_dev is None:
                lane_ecc = getattr(self._engine, "_lane_ecc", None)
                if lane_ecc is None:
                    return None
                e_dev = lane_ecc(self._planes, self._vis, self._src_bits)
            eng = self._engine
            e = eng._lane_order(
                np.asarray(e_dev)
            )[: len(self.sources)].astype(np.int32)
            if self._iso is not None:
                # Isolated sources never touch the device; their component
                # is {source} — eccentricity 0.
                e[self._iso] = 0
            self._ecc_cache = e
        return self._ecc_cache

    def distance_u8_lane(self, i: int) -> np.ndarray:
        """[V] uint8 distances of batch entry i (UNREACHED where unreached)."""
        if not (0 <= i < len(self.sources)):
            raise IndexError(i)
        eng = self._engine
        if self._iso is not None and self._iso[i]:
            # Isolated source: never seeded on device; its component is {src}.
            d = np.full(eng.num_vertices, UNREACHED, np.uint8)
            d[self.sources[i]] = 0
            return d
        wi, col = eng._word_col(i)
        if wi not in self._word_cache:
            dr = np.asarray(
                eng._extract_word(self._planes, self._vis, self._src_bits, wi)
            )
            act = getattr(eng, "_act", None)
            if act is not None:
                # Trimmed tables: a vertex has a row iff _rank[v] < _act;
                # isolated vertices map past the end and stay UNREACHED.
                full = np.full((eng.num_vertices, 32), UNREACHED, np.uint8)
                m = eng._rank < act
                full[m] = dr[eng._rank[m]]
            else:
                full = dr[eng._rank]  # old-id order
            self._word_cache[wi] = full
        return self._word_cache[wi][:, col]

    def distances_int32(self, i: int) -> np.ndarray:
        d8 = self.distance_u8_lane(i)
        return np.where(d8 == UNREACHED, INF_DIST, d8.astype(np.int32))

    def parents_int32(self, i: int) -> np.ndarray:
        """BFS tree of batch entry i: [V] int32 parents (source maps to
        itself, unreached to NO_PARENT).

        The packed level loop labels distances only (bit-sliced planes);
        the tree is extracted post-loop as one O(E) scatter-min per
        REQUESTED lane — lazy and cached like distance_u8_lane, so
        querying a few lanes never pays for the whole batch. The result
        is the deterministic min-parent tree (the same definition every
        single-source engine emits, validate.min_parent_from_dist),
        replacing the reference's nondeterministic atomic-race parent
        which it could never validate (bfs.cu:146-147, 940)."""
        if not (0 <= i < len(self.sources)):
            raise IndexError(i)
        if i not in self._parent_cache:
            self._parent_cache[i] = self._parent_lane(i)
        return self._parent_cache[i]

    def _parent_lane(self, i: int) -> np.ndarray:
        """One lane's tree: the cached-scanner fast path when available,
        with the guaranteed host scatter-min fallback — a device OOM here
        must degrade to the pre-scanner behavior, never propagate, as long
        as the host path can serve this result."""
        scanner = self._cached_scanner()
        if scanner is not None:
            try:
                return self._parent_lane_scan(i, scanner)
            except Exception as exc:  # noqa: BLE001 — OOM-only fallback
                if "RESOURCE_EXHAUSTED" not in str(exc) or (
                    getattr(self._engine, "host_graph", None) is None
                ):
                    raise
        return self._parent_lane_host(i)

    def _parent_lane_host(self, i: int) -> np.ndarray:
        """The device-free O(E) host scatter-min — the path every OOM
        fallback must bottom out in."""
        return min_parents_lane(
            getattr(self._engine, "host_graph", None),
            int(self.sources[i]),
            self.distances_int32(i),
        )

    def _cached_scanner(self):
        """An ALREADY-CACHED borrowed scanner, or None. Single-lane queries
        never trigger a scanner build (that can allocate a full ELL on
        device); they just reuse one a bulk export or an earlier query on
        a borrowing engine left behind. Guarded to scanners built from the
        engine's OWN ell (identity row space — true for every borrowing
        engine today); anything else takes the general host path."""
        scanner = getattr(self._engine, "_parent_scanner_cache", None) or None
        if scanner is not None and scanner.ell is not getattr(
            self._engine, "ell", None
        ):
            return None
        return scanner

    def _parent_lane_scan(self, i: int, scanner) -> np.ndarray:
        """One lane's tree via the cached scanner: scan the lane's 32-lane
        word column (UNREACHED-padded to a full pass) instead of an O(E)
        host scatter-min — the same deterministic tree, bit-equal. The
        word's decoded [act, 32] columns are cached (one word at a time,
        like distance_u8_lane's word cache), so querying 32 lanes of one
        word runs one scan, not 32."""
        eng = self._engine
        ell = scanner.ell
        act = ell.num_active
        src = int(self.sources[i])
        out = np.full(eng.num_vertices, -1, np.int32)
        if self._iso is not None and self._iso[i]:
            out[src] = src
            return out
        wi, col = eng._word_col(i)
        pc = self._pword_cache.get(wi)
        if pc is None:
            dist_cols = eng._extract_word(
                self._planes, self._vis, self._src_bits, wi
            )
            L = scanner.lanes_per_pass
            if L > 32:
                dist_cols = jnp.concatenate(
                    [dist_cols, jnp.full((act, L - 32), UNREACHED, jnp.uint8)],
                    axis=1,
                )
            pc = np.asarray(scanner.scan(dist_cols))[:, :32]
            self._pword_cache.clear()  # one word resident at a time
            self._pword_cache[wi] = pc
        out[ell.old_of_new[:act]] = pc[:, col]
        return out

    def parents_into(self, out: np.ndarray, *, device: str = "auto") -> np.ndarray:
        """Fill ``out[i]`` with every lane's parent tree.

        ``device='auto'`` (default) runs the batched min-key scan on device
        when the engine can supply a full-coverage ELL (parent_scan.py —
        one bucketed min-expansion per 128 lanes, replacing an O(S*E) host
        pass that cost ~an hour for the 4096-lane flagship batch), falling
        back to the per-lane host path otherwise or on device OOM.
        ``'host'`` forces the host path; ``'device'`` raises when the scan
        is unavailable instead of falling back (tests pin each path)."""
        n = len(self.sources)
        if out.shape != (n, self._engine.num_vertices):
            raise ValueError(
                f"out is {out.shape}, need ({n}, {self._engine.num_vertices})"
            )
        host_serves = getattr(self._engine, "host_graph", None) is not None
        # Above ~1e5 lanes x vertices the host path stops being interactive
        # (the flagship 8192-lane scale-21 batch prices at ~an hour); an
        # OOM fallback there must be loud (VERDICT r4 weak #4).
        work_desc = (
            f"{n} lanes x {self._engine.num_vertices} vertices"
            if n * self._engine.num_vertices > 100_000 else None
        )
        scanner = acquire_parent_scanner(
            self._engine, device, host_serves=host_serves,
            work_desc=work_desc,
        )
        if scanner is not None:
            return parents_scan_with_fallback(
                lambda: self._parents_into_scan(out, scanner),
                lambda: self._parents_into_host(out),
                device,
                host_serves=host_serves,
                work_desc=work_desc,
            )
        return self._parents_into_host(out)

    def _parents_into_host(self, out: np.ndarray) -> np.ndarray:
        """Per-lane host extraction (the guaranteed device-free path — the
        scan's OOM fallback lands here, so it must not re-enter the
        cached-scanner fast path), evicting each 32-lane distance word
        column once its lanes are done — peak host memory is ``out`` plus
        one word column, not a second cached [S, V] copy."""
        n = len(self.sources)
        prev_word = None
        for i in range(n):
            # Reuse (then evict) an already-cached tree; compute misses via
            # the device-free host scatter-min — NOT parents_int32, whose
            # fast path would re-enter the possibly-failing scan.
            cached = self._parent_cache.pop(i, None)
            out[i] = cached if cached is not None else self._parent_lane_host(i)
            wi = self._engine._word_col(i)[0]
            if prev_word is not None and wi != prev_word:
                self._word_cache.pop(prev_word, None)
            prev_word = wi
        if prev_word is not None:
            self._word_cache.pop(prev_word, None)
        return out

    def _parents_into_scan(self, out: np.ndarray, scanner) -> np.ndarray:
        """Device min-key scan over 128-lane column groups (parent_scan.py)."""
        eng = self._engine
        n = len(self.sources)
        ell = scanner.ell
        act = ell.num_active
        # Map engine extraction rows -> scanner rows through ORIGINAL ids,
        # so any engine row space works: the single-chip engines share the
        # scanner's active-first rank (identity, no gather), while the
        # distributed engines extract over chip-major padded tables of a
        # different height and order (dist_msbfs_wide.py: every vertex has
        # a row; tau order in the hybrid) — the perm pulls exactly the
        # scanner's active vertices out of whatever table the engine has.
        perm = None
        if eng._act != act or not np.array_equal(
            np.asarray(eng._rank), np.asarray(ell.rank)
        ):
            perm_np = np.asarray(eng._rank)[ell.old_of_new[:act]]
            if perm_np.min() < 0 or perm_np.max() >= eng._act:
                raise RuntimeError(
                    "engine row map does not cover the scanner's active "
                    f"vertices (rows [{perm_np.min()}, {perm_np.max()}] vs "
                    f"{eng._act} extraction rows)"
                )
            perm = jnp.asarray(perm_np)
        id_of_row = ell.old_of_new[:act]
        w = eng.w
        # lane_ids[l] = flat (word, bit) slot of batch entry l; inv is the
        # inverse map. Word-major engines make both the identity, but the
        # scan is lane-map-generic (the hybrid was bit-major until round 2).
        lane_ids = eng._lane_order(np.arange(w * 32).reshape(w, 32))
        inv = np.argsort(lane_ids)
        iso = self._iso
        L = scanner.lanes_per_pass
        nw = L // 32
        words = np.unique(lane_ids[:n] // 32)
        for c0 in range(0, len(words), nw):
            chunk = words[c0 : c0 + nw]
            cols = [
                eng._extract_word(self._planes, self._vis, self._src_bits, wi)
                for wi in chunk
            ]
            dist_cols = (
                jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
            )
            if perm is not None:
                dist_cols = dist_cols[perm]
            if len(chunk) * 32 < L:
                dist_cols = jnp.concatenate(
                    [
                        dist_cols,
                        jnp.full(
                            (act, L - len(chunk) * 32), UNREACHED, jnp.uint8
                        ),
                    ],
                    axis=1,
                )
            pc = np.asarray(scanner.scan(dist_cols))  # [act, L] int32
            for j, wi in enumerate(chunk):
                for b in range(32):
                    lane = int(inv[32 * wi + b])
                    if lane >= n or (iso is not None and iso[lane]):
                        continue
                    row = out[lane]
                    row.fill(-1)
                    row[id_of_row] = pc[:, 32 * j + b]
        if iso is not None:
            # Isolated sources never reach the device; their component is
            # {source} (same convention as distance_u8_lane).
            for lane in np.flatnonzero(iso[:n]):
                out[lane].fill(-1)
                out[lane][self.sources[lane]] = self.sources[lane]
        return out


def parent_scanner_of(engine):
    """Lazy per-engine ParentScanner; None when unavailable (no
    full-coverage ELL source, or V too large for the 32-bit key encoding
    at the engine's level cap).

    Caching policy follows who owns the device tables: a scanner that
    BORROWS the engine's existing ELL arrays (the wide engines — zero
    extra HBM) is cached on the engine; a scanner that had to build and
    transfer its OWN full-ELL tables (the hybrid, whose dense-tile design
    exists to avoid holding a full ELL) is returned uncached, so its
    device memory is released with the scanner after the bulk export
    instead of growing the engine's footprint for its whole lifetime.
    Unavailability is cached either way."""
    cached = getattr(engine, "_parent_scanner_cache", None)
    if cached is not None:
        return cached or None  # False marks a probed-and-unavailable engine
    from tpu_bfs.algorithms.parent_scan import (
        ParentScanner,
        ParentScanUnavailable,
    )

    scanner = None
    borrowed = False
    get = getattr(engine, "_full_parent_ell", None)
    if get is not None:
        ell, arrs = get()
        borrowed = arrs is not None
        if ell is not None:
            try:
                scanner = ParentScanner(
                    ell, arrs=arrs, max_dist=engine.max_levels_cap
                )
            except ParentScanUnavailable:
                scanner = None
    if scanner is None:
        engine._parent_scanner_cache = False
    elif borrowed:
        engine._parent_scanner_cache = scanner
    return scanner


def _warn_host_fallback(stage: str, work_desc: str | None) -> None:
    """Loud OOM-fallback notice (VERDICT r4 weak #4: at flagship scale the
    silent fallback is an ~hour/batch host scatter-min a user triggers
    with one flag). Emitted only when the caller judged the work big
    enough to matter (work_desc set); tiny exports stay quiet."""
    if work_desc:
        import sys

        print(
            f"WARNING: device parent scan unavailable ({stage}: "
            f"RESOURCE_EXHAUSTED); falling back to the per-lane host "
            f"scatter-min for {work_desc} — potentially hours at flagship "
            f"scale. Pass device='host' to choose the host path "
            f"explicitly, or device='device' to fail fast.",
            file=sys.stderr,
            flush=True,
        )


def acquire_parent_scanner(engine, device: str, *, host_serves: bool = True,
                           work_desc: str | None = None):
    """Shared scanner-acquisition policy of the packed result classes
    (PackedBatchResult here, PackedBfsResult in msbfs_packed.py): validate
    the ``device`` argument, return the engine's scanner or None for the
    host path, raise when ``'device'`` is forced but unavailable, and
    swallow a RESOURCE_EXHAUSTED during the scanner build in auto mode
    (the build itself may transfer full-ELL tables) — but ONLY when the
    host path can actually serve the result (``host_serves``; masking a
    build-time OOM behind the host path's 'needs the edge list' error
    would discard the real cause, the same rule
    parents_scan_with_fallback applies at scan time). One copy of the OOM
    policy, so the contracts cannot drift."""
    if device not in ("auto", "host", "device"):
        raise ValueError(f"device must be auto|host|device, got {device!r}")
    scanner = None
    if device != "host" and engine is not None:
        try:
            scanner = parent_scanner_of(engine)
        except Exception as exc:  # noqa: BLE001 — OOM-only fallback
            if (
                device == "device"
                or "RESOURCE_EXHAUSTED" not in str(exc)
                or not host_serves
            ):
                raise
            _warn_host_fallback("scanner build", work_desc)
    if scanner is None and device == "device":
        raise ValueError(
            "device parent scan unavailable for this engine (needs a "
            "full-coverage ELL or a retained host graph, and V small "
            "enough for the 32-bit key encoding)"
        )
    return scanner


def parents_scan_with_fallback(scan_fn, host_fn, device: str, *,
                               host_serves: bool = True,
                               work_desc: str | None = None):
    """Shared scan-time OOM policy of the packed result classes: run the
    device scan; in auto mode a RESOURCE_EXHAUSTED falls back to the host
    path — but ONLY when the host path can actually serve this result
    (``host_serves``; a prebuilt-ELL result has no edge list, and masking
    the OOM behind the host path's 'needs the edge list' error would
    discard the real cause). Forced-device mode and non-OOM errors always
    propagate. ``work_desc`` (set by callers for big exports) makes the
    fallback LOUD — the host path can be hours at flagship scale."""
    try:
        return scan_fn()
    except Exception as exc:  # noqa: BLE001 — OOM-only fallback
        if (
            device == "device"
            or "RESOURCE_EXHAUSTED" not in str(exc)
            or not host_serves
        ):
            raise
        _warn_host_fallback("scan", work_desc)
    # Partial scan output is harmless: the host path overwrites every row.
    return host_fn()


def lazy_full_parent_ell(host_graph, kcap: int = 64):
    """Shared `_full_parent_ell` body for engines whose own structures
    cannot serve the parent scan (dense tiles, per-chip residual shards):
    a fresh single-device full in-neighbor ELL from the retained host
    graph, with owned (engine-uncached) device tables."""
    if host_graph is None:
        return None, None
    from tpu_bfs.graph.ell import build_ell

    return build_ell(host_graph, kcap=kcap), None


def min_parents_lane(graph, source: int, dist: np.ndarray) -> np.ndarray:
    """One lane's deterministic min-parent tree from its distances — the
    shared core of PackedBatchResult.parents_int32 and
    PackedBfsResult.parents_int32 (msbfs_packed.py). ``graph`` is the
    engine's ``host_graph``; None means the engine was built from a
    prebuilt ELL/sharded graph that no longer has the edge list."""
    if graph is None:
        raise ValueError(
            "parent extraction needs the edge list: construct the engine "
            "from a Graph (a prebuilt ELL/sharded graph does not retain it)"
        )
    from tpu_bfs import validate

    return validate.min_parent_from_dist(graph, source, dist)


def _check_batch_sources(engine, sources) -> np.ndarray:
    sources = np.asarray(sources, dtype=np.int64)
    if sources.ndim != 1 or len(sources) == 0 or len(sources) > engine.lanes:
        raise ValueError(f"need 1..{engine.lanes} sources, got {sources.shape}")
    if sources.min() < 0 or sources.max() >= engine.num_vertices:
        raise ValueError("source out of range")
    return sources


def packed_table_to_real(engine, table) -> np.ndarray:
    """Engine-layout [rows, w] packed table -> real-vertex-id [V, w] host
    array. Rows of isolated vertices (no table row) and the engine's
    pad/sentinel rows come out all-zero — exactly their live information
    content. The real-id layout is what checkpoints store, so a checkpoint
    taken on one packed engine resumes on any other over the same graph."""
    t = np.asarray(table)
    real = np.zeros((engine.num_vertices, engine.w), np.uint32)
    m = engine._rank < engine._act
    real[m] = t[engine._rank[m]]
    return real


def packed_real_to_table(engine, real):
    """Real-vertex-id [V, w] checkpoint array -> engine-layout [rows, w]."""
    if real.shape != (engine.num_vertices, engine.w):
        raise ValueError(
            f"checkpoint table is {real.shape}, engine expects "
            f"({engine.num_vertices}, {engine.w}) — lane count and graph "
            "must match the engine the checkpoint resumes on"
        )
    t = np.zeros((engine._table_rows, engine.w), np.uint32)
    m = engine._rank < engine._act
    t[engine._rank[m]] = real[m]
    return jnp.asarray(t)


def _fw_hooks(engine):
    """Frontier layout-conversion hooks: engines whose loop carries the
    frontier in a different layout than their visited/plane tables (the
    distributed wide engine: replicated rank-order + sentinel row vs
    chip-major shards) provide ``_fw_table_from_real``/``_fw_real_from_table``;
    everyone else uses the shared real<->table conversion."""
    to_fw = getattr(
        engine, "_fw_table_from_real", None
    ) or (lambda real: packed_real_to_table(engine, real))
    from_fw = getattr(
        engine, "_fw_real_from_table", None
    ) or (lambda table: packed_table_to_real(engine, table))
    return to_fw, from_fw


def start_packed_batch(engine, sources):
    """Level-0 packed traversal state as a host checkpoint.

    The packed analog of the single-source engines' ``start`` (SURVEY.md §5:
    the reference has no checkpointing; a failed rank loses the whole
    traversal). State = frontier/visited tables + ``num_planes`` bit-sliced
    distance planes, all in real-vertex-id row order."""
    from tpu_bfs.utils.checkpoint import PackedCheckpoint, _new_nonce

    sources = _check_batch_sources(engine, sources)
    # The seed table may use a different row order than the result tables
    # (the distributed wide engine); the _src_bits_view hook converts.
    seed_view = getattr(engine, "_src_bits_view", lambda x: x)
    seed_real = packed_table_to_real(engine, seed_view(engine._seed_dev(sources)))
    planes = np.zeros(
        (engine.num_planes, engine.num_vertices, engine.w), np.uint32
    )
    # The starting engine knows its isolated lanes exactly; persist the
    # mask so ANY finishing engine applies the patch — including one whose
    # own _iso_mask is unknowable (prebuilt directed shard sets).
    iso = getattr(engine, "_iso_of", lambda s: None)(sources)
    return PackedCheckpoint(
        sources=sources,
        level=0,
        alive=True,
        frontier=seed_real,
        visited=seed_real.copy(),
        planes=planes,
        iso=None if iso is None else np.asarray(iso, dtype=bool),
        nonce=_new_nonce(),
    )


def advance_packed_batch(engine, ckpt, levels: int | None = None):
    """Run at most ``levels`` more level-steps from a packed checkpoint.

    The while-loop carry is restored exactly, so chunked advancing labels
    the same distances bit-for-bit as one uninterrupted run."""
    from tpu_bfs.utils.checkpoint import PackedCheckpoint

    if ckpt.planes.shape[0] != engine.num_planes:
        raise ValueError(
            f"checkpoint has {ckpt.planes.shape[0]} planes, engine has "
            f"{engine.num_planes}"
        )
    if not ckpt.alive:
        return ckpt
    # Pull-gated engines derive their active-lane mask from the batch's
    # sources (host_lane_mask) before any core dispatch; other engines
    # have no hook and skip this.
    note = getattr(engine, "_note_batch_sources", None)
    if note is not None:
        note(ckpt.sources)
    cap = engine.max_levels_cap
    ml = min(ckpt.level + levels, cap) if levels is not None else cap
    to_fw, from_fw = _fw_hooks(engine)
    # Chain identity for the distributed engines' exchange accounting
    # (read by RowGatherExchangeAccounting._core_from; a plain attribute
    # because the single-chip engines' _core_from is the raw jitted loop).
    engine._pending_chain_nonce = getattr(ckpt, "nonce", None)
    # visited converts first: packed_real_to_table raises the descriptive
    # lane-count/graph mismatch error before any custom frontier hook can
    # hit a raw broadcast failure.
    vis = packed_real_to_table(engine, ckpt.visited)
    planes = tuple(packed_real_to_table(engine, p) for p in ckpt.planes)
    fw = to_fw(ckpt.frontier)
    # The donating resume entry (ISSUE 13) where the engine provides one:
    # fw/vis/planes are fresh conversions of the host checkpoint, dead
    # after this call — donating them lets the loop's outputs alias their
    # buffers instead of doubling the table residency per chunk. Engines
    # without a donating twin (the 512-lane packed engine) keep copying.
    core_from = getattr(engine, "_core_from_donate", None) or engine._core_from
    fw_f, vis_f, planes_f, level, alive = core_from(
        engine.arrs, fw, vis, planes, jnp.int32(ckpt.level), jnp.int32(ml)
    )
    if bool(alive) and int(level) >= cap:
        # At the plane cap with the last body still claiming: run ONE
        # boundary body purely as a probe. An eccentricity that lands
        # exactly on the cap claims nothing more and terminates cleanly;
        # anything else is a genuine truncation and must raise rather than
        # let callers' advance loops spin forever on a level counter that
        # can no longer move. The probe's table mutations are DISCARDED:
        # its ripple_increment would bump still-unvisited rows' planes
        # past what an uninterrupted run (which stops at the cap) holds,
        # so keeping the pre-probe tables preserves bit-identical
        # checkpoints; only the probe's level/alive bookkeeping is kept
        # (level cap+1, alive False — matching the uninterrupted
        # num_levels accounting in _assemble_packed_result). The raw
        # jitted loop is used where the engine wraps it with exchange
        # accounting (_core_from_jit): re-recording at the probe's level
        # would collapse a restarted chain's counters, and the probe's one
        # extra gather is the same documented modeling gap as the
        # distributed hybrid's claim-free check
        # (collectives.record_row_gather_exchange).
        # Gated engines expose _core_from_probe for the same reason (their
        # raw jitted loop takes the extra lane-mask argument, and the
        # probe must not clobber the run's gate counters).
        probe_fn = (
            getattr(engine, "_core_from_probe", None)
            or getattr(engine, "_core_from_jit", None)
            or engine._core_from
        )
        out = probe_fn(
            engine.arrs, fw_f, vis_f, planes_f,
            jnp.int32(int(level)), jnp.int32(int(level) + 1),
        )
        p_level, p_alive = out[3], out[4]
        if bool(p_alive):
            raise RuntimeError(
                f"traversal truncated at {cap} levels; "
                f"num_planes={engine.num_planes} caps at {cap} — construct "
                "the engine with more planes for this graph"
            )
        level, alive = p_level, p_alive
    return PackedCheckpoint(
        sources=ckpt.sources,
        level=int(level),
        alive=bool(alive),
        frontier=from_fw(fw_f),
        visited=packed_table_to_real(engine, vis_f),
        planes=np.stack(
            [packed_table_to_real(engine, p) for p in planes_f]
        ),
        iso=ckpt.iso,
        nonce=getattr(ckpt, "nonce", None),
    )


def _assemble_packed_result(
    engine, sources, planes, vis, src_bits_raw, levels, alive, elapsed,
    iso_override=None, stats=None, ecc=None,
) -> PackedBatchResult:
    """Result assembly shared by run_packed_batch and finish_packed_batch:
    device-side lane stats, isolated-lane patching, sentinel-row src-bits
    view, and the final-empty-frontier level adjustment. ``iso_override``
    (from a checkpoint's persisted mask) wins over the engine's own
    isolated-lane reckoning — the finishing engine may not be able to
    reconstruct it (prebuilt directed shard sets). ``stats`` / ``ecc``
    are lane_stats' and lane_ecc's outputs where they were launched at
    dispatch (launch_summaries); lane_stats runs here otherwise."""
    s = len(sources)
    r, d = engine._lane_stats(vis) if stats is None else stats
    reached = engine._lane_order(np.asarray(r))[:s].astype(np.int64)
    # d is [w, nblocks, 32] int32 block partials; the int64 block reduction
    # happens here on host, so edges_traversed is exact at any scale.
    slot_sum = engine._lane_order(np.asarray(d).astype(np.int64).sum(axis=1))[:s]
    edges = slot_sum // 2 if engine.undirected else slot_sum

    # Engines whose result tables use a different row order than their seed
    # table (the distributed wide engine) provide a converting view.
    src_bits = getattr(engine, "_src_bits_view", lambda x: x)(src_bits_raw)

    # Lanes seeded at isolated sources have no device row: the table scan
    # sees nothing, but the source itself is trivially reached.
    iso = (
        iso_override
        if iso_override is not None
        else getattr(engine, "_iso_of", lambda s: None)(sources)
    )
    if iso is not None and iso.any():
        reached[iso] = 1
        edges[iso] = 0
    else:
        iso = None

    res = PackedBatchResult(
        sources=sources.astype(np.int32),
        num_levels=levels,
        reached=reached,
        edges_traversed=edges,
        elapsed_s=elapsed,
        _engine=engine,
        _planes=planes,
        _vis=vis,
        _src_bits=src_bits,
        _iso=iso,
        _ecc_dev=ecc,
    )
    # The loop's last body found an empty frontier iff not alive; then the
    # max eccentricity is one less than the body count.
    if levels > 0 and not alive:
        res.num_levels = levels - 1
    return res


def finish_packed_batch(engine, ckpt) -> PackedBatchResult:
    """Package a (finished or partial) packed checkpoint as a batch result,
    with the same lazy per-word distance extraction as a direct run. The
    checkpoint's persisted isolated-lane mask (stamped at start) is used
    when present, so lanes at isolated sources report reached=1 even on a
    finishing engine that cannot reconstruct the mask itself."""
    sources = _check_batch_sources(engine, ckpt.sources)
    vis = packed_real_to_table(engine, ckpt.visited)
    planes = tuple(packed_real_to_table(engine, p) for p in ckpt.planes)
    return _assemble_packed_result(
        engine, sources, planes, vis, engine._seed_dev(sources),
        ckpt.level, ckpt.alive, None,
        iso_override=getattr(ckpt, "iso", None),
    )


@dataclasses.dataclass
class PackedDispatch:
    """An in-flight packed batch: the level loop is LAUNCHED (JAX dispatch
    is async) but nothing host-side has blocked on it yet.

    The dispatch/fetch split exists for the serving pipeline (ISSUE 3):
    ``dispatch_packed_batch`` returns immediately with the device output
    references, so the serve executor can hand a completed batch to an
    extraction worker and form/dispatch the next batch while this one's
    results are still being pulled. ``fetch_packed_batch`` is the blocking
    half — level-count readback, plane-cap check, result assembly.
    Device-side failures of an async dispatch (OOM included) surface at
    the fetch, so callers must run their failure classifier on BOTH
    halves."""

    sources: np.ndarray
    fw0: object  # seed table (device)
    planes: tuple
    vis: object
    levels: object  # device scalar; int() blocks on the loop
    alive: object
    truncated: object
    max_levels: int
    t0: float
    # The answer reductions launched right behind the core
    # (PackedRunProtocol.launch_summaries): lane_stats' (reached,
    # deg_sum) and lane_ecc's output, device arrays; None until launched.
    stats: tuple | None = None
    ecc: object = None


def _engine_dispatch_lock(engine):
    """Per-engine lock serializing the note-mask -> core-launch window.

    The pull gate's lane mask is a host attribute the gated core reads at
    call time; with the serve pipeline, a transient-retry re-dispatch can
    run on the extraction worker while the scheduler dispatches the next
    batch on the SAME engine — without the lock their note/core pairs
    could interleave and bind the wrong batch's mask. dict.setdefault is
    atomic under the GIL, so both racers agree on one lock."""
    lock = getattr(engine, "_dispatch_lock", None)
    if lock is None:
        lock = engine.__dict__.setdefault("_dispatch_lock", threading.Lock())
    return lock


def dispatch_packed_batch(
    engine, sources, *, max_levels: int | None = None
) -> PackedDispatch:
    """Launch one packed batch without blocking on its result."""
    if _faults.ACTIVE is not None:
        # Chaos-harness injection site (tpu_bfs/faults.py): the guard is
        # one attribute check, so the un-armed hot path pays nothing.
        # ``devices`` context lets mesh-qualified rules (device_lost@
        # rank=K, ISSUE 12) target the distributed engines' dispatches.
        _faults.ACTIVE.hit(
            "dispatch", lanes=engine.lanes,
            devices=_faults.mesh_devices(engine),
        )
    sources = _check_batch_sources(engine, sources)
    cap = engine.max_levels_cap
    max_levels = cap if max_levels is None else min(max_levels, cap)
    with _obs.host_span("tpu_bfs.engine.dispatch", lambda: {
        "lanes": engine.lanes, "n": len(sources), "max_levels": max_levels,
    }), _engine_dispatch_lock(engine):
        # Same pull-gate hook as advance_packed_batch: the gated cores
        # need the batch's active-lane mask before dispatch. The mask is
        # bound into the core call inside the lock, so a concurrent
        # dispatch (serve pipeline retry vs scheduler) cannot interleave
        # its note between this batch's note and core launch.
        note = getattr(engine, "_note_batch_sources", None)
        if note is not None:
            note(sources)
        fw0 = engine._seed_dev(sources)
        t0 = time.perf_counter()
        planes, vis, levels, alive, truncated = engine._core(
            engine.arrs, fw0, jnp.int32(max_levels)
        )
    return PackedDispatch(
        sources=sources, fw0=fw0, planes=planes, vis=vis, levels=levels,
        alive=alive, truncated=truncated, max_levels=max_levels, t0=t0,
    )


def _fetch_stats(engine, levels) -> dict:
    """The fetch span's stats: the batch's width and level-loop passes,
    and on a mesh engine whose exchange has one branch (dense or sliced)
    the bytes each chip received in the batch's frontier exchanges, its
    passes times the modeled bytes a pass: static shapes, no device
    read. A sparse exchange's bytes follow the branch each level took
    on the device (``last_exchange_bytes``)."""
    stats = {"lanes": engine.lanes, "levels": levels}
    wire = getattr(engine, "wire_bytes_per_level", None)
    per = None if wire is None or levels is None else wire()
    if per is not None and len(per) == 1:
        stats["exchange_bytes"] = levels * per[0]
    return stats


def fetch_packed_batch(
    engine, pend: PackedDispatch, *, check_cap: bool = True,
    time_it: bool = False,
) -> PackedBatchResult:
    """Block on a dispatched batch and assemble its result."""
    if _faults.ACTIVE is not None:
        # Chaos-harness injection site: slow_extract sleeps here; a
        # transient/oom/mesh kind raised here surfaces on the blocking
        # half exactly like a real async-dispatch failure
        # (tpu_bfs/faults.py; devices context as at the dispatch site).
        _faults.ACTIVE.hit(
            "fetch", lanes=engine.lanes,
            devices=_faults.mesh_devices(engine),
        )
    levels = None
    with _obs.host_span("tpu_bfs.engine.fetch",
                        lambda: _fetch_stats(engine, levels)):
        levels = int(pend.levels)  # blocks until the loop finishes
        elapsed = (time.perf_counter() - pend.t0) if time_it else None
        engine._warmed = True
        if (
            check_cap
            and bool(pend.truncated)
            and pend.max_levels == engine.max_levels_cap
        ):
            raise RuntimeError(
                f"traversal truncated at {levels} levels; "
                f"num_planes={engine.num_planes} caps at "
                f"{engine.max_levels_cap} — construct the engine with more "
                "planes for this graph"
            )
        result = _assemble_packed_result(
            engine, pend.sources, pend.planes, pend.vis, pend.fw0, levels,
            bool(pend.alive), elapsed, stats=pend.stats, ecc=pend.ecc,
        )
    if _obs.ACTIVE is not None:
        # Engine-trace assembly (tpu_bfs/obs/engine_trace) reads the gate
        # counter — a device array whose transfer must stay behind the
        # ACTIVE guard: disarmed fetches pay this one attribute check and
        # nothing else (pinned by tests/test_obs.py's spy counter).
        from tpu_bfs.obs.engine_trace import record_packed_run

        record_packed_run(engine, levels, recorder=_obs.ACTIVE)
    return result


def run_packed_batch(
    engine,
    sources,
    *,
    max_levels: int | None = None,
    time_it: bool = False,
    check_cap: bool = True,
) -> PackedBatchResult:
    """Generic batch driver shared by the wide and hybrid engines: one
    dispatch immediately fetched (the split halves above are the serving
    pipeline's entry points; this is everyone else's)."""
    if time_it and not engine._warmed:
        int(dispatch_packed_batch(engine, sources, max_levels=max_levels).levels)
    pend = dispatch_packed_batch(engine, sources, max_levels=max_levels)
    return fetch_packed_batch(
        engine, pend, check_cap=check_cap, time_it=time_it
    )


# Re-exported here so the packed engine family imports its whole shared
# protocol surface from one module (the class itself lives in utils/aot
# so msbfs_packed — which _packed_common imports at its top — can
# inherit it without a cycle).
from tpu_bfs.utils.aot import AotProgramProtocol  # noqa: E402


def packed_aot_programs(engine):
    """The serving-path program inventory shared by the single-chip
    packed MS engines (wide + hybrid): the level-loop core (the
    30-second compile a cold start is mostly made of), seeding, the
    on-device lane reductions, and the lazy per-word distance
    extraction. Example args are ShapeDtypeStructs derived from the
    engine's own tables, so the export shapes are THE serving shapes
    (the executor always pads dispatches to exactly ``lanes``
    sources). The frontier-table shape comes from ``jax.eval_shape``
    over the seed kernel — a trace, never a compile: this inventory is
    enumerated on the preheat path, whose whole point is zero compiles
    (and on an adopted engine the unwrapped original is shaped, so the
    probe can't pollute the runtime-fallback audit)."""
    import jax

    def sds(x):
        return jax.ShapeDtypeStruct(tuple(np.shape(x)), x.dtype)

    one_i = jax.ShapeDtypeStruct((1,), jnp.int32)
    one_u = jax.ShapeDtypeStruct((1,), jnp.uint32)
    seed_fn = getattr(engine._seed, "_aot_original", engine._seed)
    fw_s = sds(jax.eval_shape(seed_fn, one_i, one_i, one_u))
    arrs_s = {k: sds(v) for k, v in engine.arrs.items()}
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    lanes = engine.lanes
    planes_s = (fw_s,) * engine.num_planes
    progs = []
    if getattr(engine, "pull_gate", False):
        lane_mask = jax.ShapeDtypeStruct((engine.w,), jnp.uint32)
        progs.append(("core", "_gate_core_jit", engine._gate_core_jit,
                      (arrs_s, fw_s, i32, lane_mask)))
    else:
        progs.append(("core", "_core", engine._core, (arrs_s, fw_s, i32)))
    lane_i32 = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    lane_u32 = jax.ShapeDtypeStruct((lanes,), jnp.uint32)
    progs += [
        ("seed", "_seed", engine._seed, (lane_i32, lane_i32, lane_u32)),
        ("lane_stats", "_lane_stats", engine._lane_stats, (fw_s,)),
        ("extract_word", "_extract_word", engine._extract_word,
         (planes_s, fw_s, fw_s, i32)),
        ("lane_ecc", "_lane_ecc", engine._lane_ecc, (planes_s, fw_s, fw_s)),
    ]
    return progs


def packed_analysis_programs(engine):
    """Static-analyzer inventory for the single-chip packed engines
    (tpu_bfs/analysis/configs.iter_programs contract): the level-loop
    core under the engine's ACTUAL expansion tier, so a pallas-tier
    core exposes its fused ``pallas_call`` body to the jaxpr walks and
    compiled audits (ISSUE 16). Unlike the AOT inventory above, the
    example args must be REAL device-resident arrays — the analyzer's
    transfer-guard pass EXECUTES each program under
    ``jax.transfer_guard('disallow')``, it does not just trace it."""
    sources = np.arange(engine.lanes, dtype=np.int64) % engine.num_vertices
    fw0 = engine._seed_dev(sources)
    ml = jnp.int32(8)
    if getattr(engine, "pull_gate", False):
        rows = np.asarray(engine._rank)[sources]
        mask = jnp.asarray(host_lane_mask(rows, engine._act, engine.w))
        return [("core", engine._gate_core_jit,
                 (engine.arrs, fw0, ml, mask))]
    return [("core", engine._core, (engine.arrs, fw0, ml))]


class PackedRunProtocol:
    """The packed-family batch entry points, defined once for every engine
    built on the shared level-loop machinery (wide, hybrid, and their
    distributed forms): blocking ``run``, and the async ``dispatch`` /
    ``fetch`` halves the serve pipeline overlaps (dispatch_packed_batch /
    fetch_packed_batch above)."""

    def run(self, sources, *, max_levels=None, time_it=False,
            check_cap=True):
        return run_packed_batch(
            self, sources, max_levels=max_levels, time_it=time_it,
            check_cap=check_cap,
        )

    def dispatch(self, sources, *, max_levels=None):
        """Launch a batch without blocking (JAX dispatch is async)."""
        return dispatch_packed_batch(self, sources, max_levels=max_levels)

    def fetch(self, pend, *, check_cap=True):
        """Block on a :meth:`dispatch` handle and assemble its result."""
        return fetch_packed_batch(self, pend, check_cap=check_cap)

    def launch_summaries(self, pend, *, ecc=True):
        """Enqueue a dispatched batch's answer reductions (lane_stats, and
        lane_ecc when ``ecc``) on its own outputs, right behind its core,
        keeping the device arrays on ``pend`` for :meth:`fetch` and the
        result's ``ecc`` to read. The same kernels on the same arrays as
        at fetch; only their place in the device's queue moves, so a
        batch's answers do not wait behind cores dispatched after it. The
        serve executor calls this; :meth:`run` never does."""
        pend.stats = self._lane_stats(pend.vis)
        lane_ecc = getattr(self, "_lane_ecc", None)
        if ecc and lane_ecc is not None:
            src_bits = getattr(self, "_src_bits_view", lambda x: x)(pend.fw0)
            pend.ecc = lane_ecc(pend.planes, pend.vis, src_bits)
