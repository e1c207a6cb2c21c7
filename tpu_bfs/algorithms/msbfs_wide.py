"""Wide bit-packed multi-source BFS: thousands of lanes per traversal
batch (default cap 8192 lanes = 256-word rows since the round-4 sweep).

Why a second packed engine: measured on TPU v5e, a chained random row-gather
(gather + OR, the level-loop's inner op) is latency-dominated — narrow rows
pay physical tile padding (every [n, w<128] uint32 intermediate is padded to
128 lanes: ~19 ns/index at 16 words, ~30 at 32), while widening past 128
words costs only ~1.2x per doubling (fence-corrected round-4 sweep: 14.5 /
16.5 / 19.7 / 26.8 ns/index at 64 / 128 / 256 / 512 words). Wide rows are
therefore the native shape: the same index traffic is amortized over up to
32x more sources than the 512-lane engine, and each width doubling buys
~1.67x more lane-bytes per second until HBM stops fitting the state.

Differences from PackedMsBfsEngine (tpu_bfs/algorithms/msbfs_packed.py):

- Bucket OR-accumulation runs in ``lax.fori_loop`` (one live gather result
  instead of ~20 padded intermediates — see _packed_common.make_fori_expand).
- The frontier table keeps its sentinel row inside the loop state ([V+1, w]
  throughout), removing the 1 GiB/level concatenate copy XLA emitted for the
  old shape dance.
- Bit-sliced distance counters are ``num_planes`` wide (default 5 -> max 32
  levels) instead of a fixed 8, saving 3 GiB of HBM at w=128; the engine
  raises if the traversal outlives the cap instead of mislabeling.
- Per-lane reached / traversed-edge counts reduce on device; distances unpack
  lazily one 32-lane word at a time (a [V, 4096] uint8 materialization would
  be 8 GiB of traffic before any host transfer).

Replaces the reference's one-source-per-process loop (main, bfs.cu:783-823)
with the Graph500 many-key pattern in one fused device program; claim protocol
is ``next = hit & ~visited`` on packed words — the race-free reformulation of
the atomicMin claim (bfs.cu:146-150), which has no TPU analog.

Lane convention: word-major — lane ``l`` at word ``l // 32``, bit ``l % 32``.
(The hybrid engine is bit-major instead, as its MXU kernel requires.)

Opt-in ``adaptive_push=(row_cap, deg_cap)`` gates light levels onto a
push-style pass over just the active rows' out-edges instead of the full
ELL scan (_packed_common.make_adaptive_hit; BENCHMARKS.md "Level-adaptive
expansion" for the measured keep-or-kill).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import EllGraph, build_ell, pad_gate_blocks
from tpu_bfs.ops.ell_expand import resolve_interpret
from tpu_bfs.algorithms._packed_common import (
    AotProgramProtocol,
    ExpandSpec,
    PackedRunProtocol,
    advance_packed_batch,
    auto_lanes,
    build_push_table,
    expand_arrays,
    finish_packed_batch,
    PullGateHost,
    make_adaptive_hit,
    make_expand,
    make_gated_expand,
    make_packed_loop,
    pallas_expand_arrays,
    validate_expand_impl,
    make_state_kernels,
    packed_analysis_programs,
    packed_aot_programs,
    row_unsettled,
    seed_scatter_args,
    start_packed_batch,
    tpu_padded_words,
)

W = 128  # uint32 words per row (narrower rows pay physical tile padding)
LANES = 32 * W
# Wider rows are legal (any multiple of 32 lanes up to MAX_LANES; the shared
# machinery in _packed_common is width-generic).
MAX_LANES = 4 * LANES
# Default width cap: 8192 lanes (w=256) — the round-4 v5e sweep measured the
# per-index gather cost near-flat from 128- to 256-word rows, and the hybrid
# flagship gained 1.22x (45.68 -> 55.96 GTEPS hmean) from the doubled batch.
# Auto sizing walks down from the cap whenever the packed state doesn't fit
# HBM (msbfs_hybrid.py has the full measurement note).
DEFAULT_MAX_LANES = 2 * LANES

# Re-exported for callers that consumed these from here before the
# _packed_common refactor.
from tpu_bfs.algorithms._packed_common import PackedBatchResult as WideBfsResult  # noqa: E402


def _make_core(ell: EllGraph, w: int, num_planes: int, push_cfg=None,
               gate_levels: int = 0, expand_impl: str = "xla",
               interpret: bool = False, overlay: bool = False):
    act = ell.num_active
    spec = ExpandSpec(
        kcap=ell.kcap,
        heavy=ell.num_heavy > 0,
        num_virtual=ell.num_virtual,
        fold_steps=ell.fold_steps,
        light_meta=tuple((b.k, b.n) for b in ell.light),
        # Zero-in-degree active rows + sentinel row. Isolated vertices get
        # no row at all (rank space is active-first, graph/ell.py).
        tail_rows=act - ell.num_nonzero + 1,
    )
    if gate_levels:
        # Pull gate (ISSUE 1): bucket outputs are table rows in order here
        # (no permutation), so the per-row unsettled mask IS the per-
        # bucket-output-row needed vector, no forward map required.
        gated_expand = make_gated_expand(
            spec, w, impl=expand_impl, interpret=interpret
        )

        def hit_of(arrs, fw, vis, lane_mask):
            need = row_unsettled(vis, act, lane_mask)
            return gated_expand(arrs, fw, need)

        return make_packed_loop(
            hit_of, num_planes, gate_levels=gate_levels, act=act
        )
    # fw is [act+1, w]: frontier bits; sentinel row act is all-zero and is
    # never written (expand emits zero there, and `& ~vis` keeps it zero).
    expand = make_expand(spec, w, impl=expand_impl, interpret=interpret)
    if overlay:
        # Dynamic-graph delta overlay (ISSUE 19): fold the bounded
        # mutation tables over the base expansion output — a jnp
        # epilogue outside either expansion tier's kernel, so xla and
        # pallas engines share one fold and one compiled-shape contract.
        from tpu_bfs.graph.dynamic import make_overlay_fold

        expand = make_overlay_fold(expand, op="or")
    if push_cfg is None:
        return make_packed_loop(expand, num_planes)
    # Level-adaptive expansion (experimental): see
    # _packed_common.make_adaptive_hit — the gate/push machinery is shared
    # with the hybrid engine.
    return make_packed_loop(
        make_adaptive_hit(expand, act, w, act + 1, push_cfg), num_planes
    )


class WidePackedMsBfsEngine(PackedRunProtocol, PullGateHost,
                            AotProgramProtocol):
    """Runs up to 4096 BFS sources concurrently, bit-packed 128 words wide.

    ``num_planes`` bit-sliced counter planes bound the level count at
    ``2**num_planes``; the default 5 (32 levels) fits scale-21+ RMAT and
    social graphs in HBM at w=128. ``run`` raises if the traversal is still
    alive at the cap (pass more planes for high-diameter graphs — or use the
    512-lane PackedMsBfsEngine, whose 8 planes reach 254 levels).

    ``pull_gate=True`` (default off until chip-measured) turns on the
    frontier-aware pull gate: settled rows' bucket blocks and state tiles
    are skipped per level (_packed_common.make_gated_fori_expand /
    gated_state_update), bit-identical to the plain scan; per-level skipped
    blocks land in ``last_gate_level_counts``.
    """

    def __init__(
        self,
        graph: Graph | EllGraph,
        *,
        lanes: int | str = "auto",
        kcap: int = 64,
        num_planes: int = 5,
        undirected: bool | None = None,
        hbm_budget_bytes: int = int(14.0e9),
        max_lanes: int = DEFAULT_MAX_LANES,
        adaptive_push: tuple[int, int] | None = None,
        pull_gate: bool = False,
        expand_impl: str = "xla",
        interpret: bool | None = None,
        overlay: tuple = (),
    ):
        if not (1 <= num_planes <= 8):
            raise ValueError("num_planes must be in [1, 8]")
        validate_expand_impl(expand_impl)
        self.overlay = tuple(int(x) for x in overlay) if overlay else ()
        if self.overlay and (pull_gate or adaptive_push is not None):
            # Both gate which rows/blocks the per-level scan touches by
            # BASE-graph keys; overlay edges would escape the gate and
            # silently go untraversed. The delta overlay serves the
            # plain scan only (ISSUE 19).
            raise ValueError(
                "overlay does not compose with pull_gate or adaptive_push"
            )
        interpret = resolve_interpret(interpret)
        self.expand_impl = expand_impl
        self._interpret = bool(interpret)
        if pull_gate and adaptive_push is not None:
            # Both gate the same per-level scan, by different keys (settled
            # destinations vs light frontiers); composing them is a
            # measurement question, not a wiring one — measure the pull
            # gate against the plain scan first (ISSUE 1's A/B stage).
            raise ValueError(
                "pull_gate and adaptive_push cannot combine (yet): pick one"
            )
        if max_lanes % 32 or not (32 <= max_lanes <= MAX_LANES):
            # Fail before the ELL build, like the num_planes check above.
            raise ValueError(
                f"max_lanes must be a multiple of 32 in [32, {MAX_LANES}]"
            )
        self.num_planes = num_planes
        # A vertex claimed in body i carries counter value i (incremented once
        # per body while unvisited) and distance i+1, so p planes label
        # distances up to 2**p; 254 keeps every distance below UNREACHED=255.
        self.max_levels_cap = min(1 << num_planes, 254)
        self.ell = build_ell(graph, kcap=kcap) if isinstance(graph, Graph) else graph
        # Host-side edge list for post-loop parent extraction
        # (PackedBatchResult.parents_int32); a prebuilt ELL has dropped it.
        self.host_graph = graph if isinstance(graph, Graph) else None
        self._act = self.ell.num_active
        if lanes == "auto":
            # Halve from max_lanes until the packed state fits HBM next to
            # the ELL (and the push table, when the adaptive path is on —
            # its [act+1, deg_cap] int32 rows are lane-independent
            # residents just like the ELL indices). The push table's minor
            # dim pads to 128 on TPU like every 2-D 32-bit table
            # (tpu_padded_words; the round-4 LJ OOM report billed the
            # s32[act, 64] table at 2.0x its logical bytes).
            push_bytes = (
                (self._act + 1) * (tpu_padded_words(adaptive_push[1]) * 4 + 1)
                if adaptive_push is not None
                else 0
            )
            # on_unfit='raise': when even the 32-lane floor's PHYSICAL
            # footprint exceeds the budget, fail here with the real levers
            # named instead of minutes later in an opaque runtime
            # RESOURCE_EXHAUSTED (ADVICE r4).
            lanes = auto_lanes(
                self._act + 1,
                num_planes,
                fixed_bytes=int(self.ell.total_slots * 4.4) + push_bytes,
                hbm_budget_bytes=hbm_budget_bytes,
                max_lanes=max_lanes,
                on_unfit="raise",
            )
        if lanes % 32 or not (32 <= lanes <= MAX_LANES):
            raise ValueError(
                f"lanes must be a multiple of 32 in [32, {MAX_LANES}]"
            )
        self.w = lanes // 32
        self.lanes = lanes
        self.undirected = self.ell.undirected if undirected is None else undirected
        ell = self.ell
        self.arrs = expand_arrays(ell)
        if expand_impl == "pallas":
            from tpu_bfs.ops.ell_expand import validate_kernel_width

            # Fail at build with the legal widths named, not at first
            # dispatch inside Mosaic lowering.
            validate_kernel_width(
                self.w, self._interpret, kernel="wide expand_impl='pallas'"
            )
            # Sentinel-padded whole-block tables the kernel DMAs (shared
            # layout with the pull gate's light tables; sentinel = the
            # all-zero row act).
            for name, tbl in pallas_expand_arrays(ell, self._act).items():
                self.arrs[name] = jnp.asarray(tbl)
        if self.overlay:
            # Arm the fold with all-pad tables (every row scatters the
            # combine identity into the sentinel row): the overlay keys
            # are part of the arrs pytree from the FIRST compile, so a
            # later mutation swaps values without a retrace.
            from tpu_bfs.graph.dynamic import empty_overlay_tables

            for name, tbl in empty_overlay_tables(
                self.overlay, self._act
            ).items():
                self.arrs[name] = jnp.asarray(tbl)
        if adaptive_push is not None:
            self._build_push_table(adaptive_push)
        self._table_rows = self._act + 1  # + the all-zero sentinel row
        self.pull_gate = pull_gate
        if pull_gate:
            # Sentinel-padded whole-block bucket tables for the gated
            # expansion (graph/ell.pad_gate_blocks; sentinel = the all-zero
            # row act, the buckets' own pad convention).
            for i, b in enumerate(ell.light):
                self.arrs[f"light{i}_gt"] = jnp.asarray(
                    pad_gate_blocks(
                        np.ascontiguousarray(b.idx.T), self._act
                    )
                )
            self._lane_mask_dev = jnp.full(
                (self.w,), 0xFFFFFFFF, jnp.uint32
            )
            (
                self._gate_core_jit, self._gate_core_from_jit,
                self._gate_core_from_donate_jit,
            ) = _make_core(
                ell, self.w, num_planes, gate_levels=self.max_levels_cap,
                expand_impl=expand_impl, interpret=self._interpret,
            )
            self._core = self._gated_core
            self._core_from = self._gated_core_from
            self._core_from_donate = self._gated_core_from_donate
        else:
            self._core, self._core_from, self._core_from_donate = _make_core(
                ell, self.w, num_planes, adaptive_push,
                expand_impl=expand_impl, interpret=self._interpret,
                overlay=bool(self.overlay),
            )
        in_deg_ranked = ell.in_degree[ell.old_of_new].astype(np.int32)
        (
            self._seed, self._lane_stats, self._extract_word, self._lane_ecc,
        ) = make_state_kernels(
            ell.num_vertices, self._act + 1, self.w, num_planes,
            active=self._act, in_deg_host=in_deg_ranked,
        )
        self._rank = ell.rank
        self._warmed = False

    def _build_push_table(self, push_cfg):
        """Device push arrays for the adaptive light-level path (the
        shared build_push_table); needs the retained host edge list."""
        if self.host_graph is None:
            raise ValueError(
                "adaptive_push needs the edge list: construct the engine "
                "from a Graph (a prebuilt ELL has dropped it)"
            )
        pt, inelig = build_push_table(
            self.host_graph, self.ell.rank, self._act, push_cfg[1]
        )
        self.arrs["push_t"] = jnp.asarray(pt)
        self.arrs["push_inelig"] = jnp.asarray(inelig)

    def set_overlay(self, tables) -> None:
        """Swap the delta-overlay tables under the already-compiled core
        (ISSUE 19): shapes must match the armed capacity (the compiled
        pytree is fixed — a shape change would be a silent retrace), and
        the swap is one atomic dict rebind so a concurrently-running
        batch sees either the old tables or the new, never a mix."""
        if not self.overlay:
            raise ValueError(
                "engine built without an overlay — pass overlay=(rows, "
                "kcap) at construction to serve a dynamic graph"
            )
        rows, kcap = self.overlay
        new = {}
        for name in ("ov_rows", "ov_idx", "ov_override"):
            arr = np.asarray(tables[name], np.int32)
            want = (rows, kcap) if name == "ov_idx" else (rows,)
            if arr.shape != want:
                raise ValueError(
                    f"{name} shape {arr.shape} != armed capacity {want}"
                )
            new[name] = jnp.asarray(arr)
        self.arrs = {**self.arrs, **new}

    @property
    def num_vertices(self) -> int:
        return self.ell.num_vertices

    # Word-major lane map: lane l at word l // 32, bit l % 32.
    @staticmethod
    def _word_col(i: int):
        return i // 32, i % 32

    @staticmethod
    def _lane_order(mat: np.ndarray) -> np.ndarray:
        return mat.reshape(-1)

    def _iso_of(self, sources: np.ndarray):
        return self.ell.rank[sources] >= self._act

    def _seed_dev(self, sources: np.ndarray):
        return self._seed(*seed_scatter_args(self.ell.rank[sources], self._act))

    def _full_parent_ell(self):
        """Full-coverage ELL + device arrays for the batched parent scan
        (parent_scan.py): the gather-only engine expands over every edge
        already, so the scan borrows its tables for free — this also makes
        bulk parent extraction work for prebuilt-ELL engines, which the
        host path cannot serve (no retained edge list)."""
        return self.ell, self.arrs

    # run/dispatch/fetch come from PackedRunProtocol (_packed_common).

    def export_programs(self):
        """AOT inventory (ISSUE 9; utils/aot.py): the shared packed
        serving set — level-loop core (gated form carries the lane-mask
        arg), seed, lane stats, lazy word extraction, lane ecc."""
        return packed_aot_programs(self)

    def analysis_programs(self):
        """Static-analyzer hook (tpu_bfs/analysis): the level-loop core
        with REAL example args, under the engine's ACTUAL expansion tier
        — a pallas engine's core carries the fused ``pallas_call``, so
        the dtype/uniformity jaxpr walks and the compiled audits see
        inside the kernel body (ISSUE 16)."""
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
