"""Per-phase attribution of the hybrid MS-BFS level loop (roofline).

"Is it actually fast, or just faster than before?" — the flagship number
(62 GTEPS hmean on RMAT scale-21, BENCHMARKS.md) is one fused
``lax.while_loop``; this module breaks a real traversal into its phases and
prices each against the chip's HBM bandwidth, so the binding term is NAMED
and the next optimization is attributable instead of guesswork.

Method: step the REAL engine one level at a time, device-resident
(``engine._core_from`` with ``max_levels = level+1`` — the checkpoint API's
host round-trip would move ~2 GB/table per level at flagship scale and
drown the phases). On each level's live frontier, separately dispatch
jitted PHASE SLICES rebuilt from the same specs the fused loop was built
from (msbfs_hybrid.expand_spec / tile_spmm / the adaptive push body /
the claim+ripple state update), each timed with the scalar-read fence and
floor subtraction of utils/timing.run_timed. The slices re-run work the
fused loop runs once, so their sum normally EXCEEDS the fused level time;
the difference is XLA's fusion dividend and is reported, not hidden.

The byte model is analytic and fusion-agnostic: for each phase, the HBM
bytes its algorithm must move at least once (tables read/written, index
arrays, gathered rows). Achieved GB/s = bytes / measured time; the phase
with the largest share of attributed time is the binding term, and the
implied ceiling is the batch rate if every phase ran at peak HBM bandwidth
(v5e: ~819 GB/s) — the batched analog of BENCHMARKS.md's single-stream
latency-wall analysis.

Correctness guard: the stepping loop's level count must equal a plain
``engine.run``'s (same sources), proving the slices did not perturb the
traversal. Reference analog: the reference has no attribution at all —
its record is one wall-clock print per run (bfs.cu:624-626).

Works on CPU/interpret for tests (tiny graphs); meaningful numbers need
the chip (scripts/roofline.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs.algorithms._packed_common import make_expand
from tpu_bfs.algorithms.msbfs_hybrid import expand_spec
from tpu_bfs.algorithms.msbfs_packed import ripple_increment
from tpu_bfs.obs.engine_trace import trace_summary as _trace_summary
from tpu_bfs.ops.tile_spmm import TILE, tile_spmm
from tpu_bfs.utils.timing import run_timed

#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" (819 GB/s HBM, 197 TFLOP/s bf16).
PEAKS = {
    "TPU v5 lite": {"hbm_gbs": 819.0, "bf16_tflops": 197.0},
}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device not in
    the table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); pass the peak explicitly"
        ) from None


def phase_fns(engine) -> dict:
    """Jitted phase slices of one hybrid level step.

    Keys (present when the engine has the phase): ``residual`` (bucketed
    ELL gathers + permutation back to rank0), ``dense`` (Pallas MXU tile
    pass), ``push`` (adaptive push body, gate-free), ``gate`` (the adaptive
    light-level decision inputs), ``hit`` (the full expansion exactly as
    the fused loop composes it, pull form), and the state update sliced
    as ``claim`` (hit & ~vis claim + visited OR + liveness) and
    ``ripple`` (bit-plane increment) — reported summed as 'state' in the
    attribution.
    """
    hg, w = engine.hg, engine.w
    act = hg.num_active
    out_rows = hg.vt * TILE
    # The residual slice runs THE SAME expansion tier as the fused loop
    # (ISSUE 16): a pallas-tier engine's attribution must time the fused
    # kernel, not the fori form it replaced. The engine's arrs already
    # carry the tier's tables (padded gt slabs on the pallas tier).
    expand_residual = make_expand(
        expand_spec(hg), w,
        impl=getattr(engine, "expand_impl", "xla"),
        interpret=engine.interpret,
    )
    fns = {}

    def residual(arrs, fw):
        return expand_residual(arrs, fw)[arrs["inv_perm_ext"]]

    fns["residual"] = jax.jit(residual)

    has_dense = hg.num_tiles > 0
    if has_dense:
        def dense(arrs, fw):
            return tile_spmm(
                arrs["row_start"], arrs["col_tile"], arrs["a_tiles"], fw,
                num_row_tiles=hg.vt, w=w, interpret=engine.interpret,
            )

        fns["dense"] = jax.jit(dense)

    def hit(arrs, fw):
        h = residual(arrs, fw)
        return h | dense(arrs, fw) if has_dense else h

    fns["hit"] = jax.jit(hit)

    if engine.adaptive_push is not None:
        row_cap, _deg_cap = engine.adaptive_push

        def gate(arrs, fw):
            rows_active = jnp.any(fw[:act] != 0, axis=1)
            nz = jnp.sum(rows_active.astype(jnp.int32))
            bad = jnp.any(rows_active & arrs["push_inelig"])
            return nz, bad

        fns["gate"] = jax.jit(gate)

        def push(arrs, fw):
            # The push body of _packed_common.make_adaptive_hit, without
            # the lax.cond gate (attribution wants the branch itself).
            rows_active = jnp.any(fw[:act] != 0, axis=1)
            nz = jnp.sum(rows_active.astype(jnp.int32))
            idx = jnp.where(rows_active, size=row_cap, fill_value=act)[0]
            pt = arrs["push_t"]

            def pbody(i, h):
                r = idx[i]
                nb = pt[r]
                return h.at[nb].set(h[nb] | fw[r][None, :])

            h = jax.lax.fori_loop(
                0, nz, pbody, jnp.zeros((out_rows, w), jnp.uint32)
            )
            return h.at[act].set(0)

        fns["push"] = jax.jit(push)

    # The state update is sliced in two so each dispatch's live set fits
    # next to the standing carry at flagship scale (claim's outputs can't
    # alias its inputs we still hold; ripple doubles the plane tables —
    # one fused state fn peaked ~4 extra tables and OOM'd the 16 GB chip
    # at scale 21 / w=256). Reported summed as 'state'.
    def claim(h, vis):
        nxt = h & ~vis
        return nxt, vis | nxt, jnp.any(nxt != 0)

    def ripple(planes, vis2):
        return ripple_increment(planes, ~vis2)

    fns["claim"] = jax.jit(claim)
    fns["ripple"] = jax.jit(ripple)
    return fns


def pallas_expand_bytes(engine, *, active_tiles: int | None = None) -> dict:
    """Per-kernel HBM bytes of ONE pallas-tier expansion level (ISSUE 16).

    One entry per kernel launch ('virtual', 'light0', ...), derived from
    the engine's padded gt slabs and priced by
    ``ops.ell_expand.ell_expand_hbm_bytes``: per computed 128-row tile,
    the index slab + k gathered frontier rows per row (+ the weight slab
    on min-plus kernels) + ONE output write. The VMEM-resident
    accumulator is what separates this from the fori tier's model
    (``phase_bytes``), which pays the accumulator round-trip on every
    bucket step — this dict is the bound the kernel is built to meet.

    Distributed engines hold per-shard gt stacks (leading axes); bytes
    count across shards. ``active_tiles`` (gated engines: unsettled
    GATE_TILE blocks this level) caps each light kernel's computed
    tiles; the heavy kernel is all-or-nothing, exactly like the gated
    program (gated-out tiles still pay their identity write). Returns
    ``{}`` when the engine runs the xla tier.
    """
    if getattr(engine, "expand_impl", "xla") != "pallas":
        return {}
    from tpu_bfs.ops.ell_expand import TILE as KTILE, ell_expand_hbm_bytes

    arrs = getattr(engine, "arrs", None) or {}
    w = engine.w
    out = {}
    for name in sorted(arrs):
        if not name.endswith("_gt"):
            continue
        base = name[: -len("_gt")]
        # Index slabs only: 'virtual' / 'light<i>'. Weight slabs
        # ('<base>_w'/'<base>_wl', sssp) ride their index kernel's
        # launch via the ``weighted`` flag below.
        if base != "virtual" and not (
            base.startswith("light") and "_" not in base
        ):
            continue
        t = arrs[name]
        k, pn = int(t.shape[-2]), int(t.shape[-1])
        shards = 1
        for d in t.shape[:-2]:
            shards *= int(d)
        if active_tiles is None:
            at = None
        elif base == "virtual":
            at = None if active_tiles > 0 else 0
        else:
            at = min(pn // KTILE, int(active_tiles))
        out[base] = shards * ell_expand_hbm_bytes(
            k, pn, w, active_tiles=at, weighted=f"{base}_w_gt" in arrs
        )
    return out


def phase_bytes(engine, *, nz_rows: int | None = None,
                active_tiles: int | None = None) -> dict:
    """Analytic HBM bytes per phase for ONE level (lower bounds: bytes the
    phase's algorithm must move at least once; XLA fusion can only reduce
    intermediate traffic below this for `state`, so achieved-GB/s figures
    derived from these are conservative for the expansion phases).

    ``nz_rows`` (active frontier rows) sizes the push phase. Without the
    pull gate, the pull phases are frontier-independent by construction
    (the whole table is scanned every level — that level-invariance was
    the roofline finding ISSUE 1 acted on). On a pull-gated engine,
    ``active_tiles`` (unsettled GATE_TILE row blocks this level) sizes the
    gated model instead: light-bucket gathers and the state pass scale
    with the active-tile count; the heavy section is all-or-nothing
    (counted fully while any tile is active, zero at 0); the permutation
    gather and the next-frontier zero-init stay full-table (the compiled
    program still writes them full-height), and the settled-mask read adds
    one table scan — the model bills the gate's own overhead so the gated
    entry stays honest.

    Distributed MS engines (``_gather_p > 1``) add an ``exchange`` entry —
    per-level WIRE bytes, not HBM: the dense slab gather and the sliced
    ring rotation both move (P-1) x [rows/P, w] u32 per chip per level
    (dist_msbfs_hybrid; the sparse row-gather rungs move less — and the
    ISSUE 7 delta-encoded id stream less again; this is the dense
    ceiling, the per-branch prices live in
    collectives.sparse_rows_wire_bytes_per_level and the walk's trace
    rows attribute the branch each level actually took). The packed MS
    wire format already carries one bit per (vertex, lane), so ISSUE 5's
    ``wire_pack`` does not change this entry; their HBM phases are the
    single-chip model's, per chip, and are not re-derived here (``hg``
    is absent on those engines).
    """
    from tpu_bfs.parallel.collectives import dense_rows_wire_bytes

    hg, w = getattr(engine, "hg", None), engine.w
    out = {}
    p = int(getattr(engine, "_gather_p", 1))
    if p > 1:
        out["exchange"] = dense_rows_wire_bytes(p, engine._gather_rows_loc, w)
    if hg is None:
        return out
    rows = hg.vt * TILE
    tb = rows * w * 4  # one [rows, w] u32 table
    gated = bool(getattr(engine, "pull_gate", False)) and active_tiles is not None
    at_rows = min(int(active_tiles or 0) * TILE, rows) if gated else rows
    pal = pallas_expand_bytes(
        engine, active_tiles=active_tiles if gated else None
    )
    if pal:
        # Pallas tier (ISSUE 16): per-kernel attribution — the
        # VMEM-resident accumulator drops the fori tier's per-step
        # accumulator round-trip, so the residual bound shrinks to the
        # kernel model. The heavy fold pyramid + pick gather still run
        # in jnp after the kernel.
        res = sum(pal.values())
        if hg.res_heavy and (not gated or at_rows > 0):
            res += 4 * hg.res_num_virtual * w * 4 + hg.res_heavy * w * 4
    else:
        # residual: per light bucket, k fori steps each gathering n rows
        # (n*w*4 read) and accumulating (acc read+write) + index table;
        # the virtual/heavy bucket adds its fold pyramid and pick
        # gathers.
        res = 0
        if hg.res_heavy and (not gated or at_rows > 0):
            m = hg.res_virtual.idx.shape[0]  # rows per virtual gather
            res += hg.kcap * (3 * hg.res_num_virtual * w * 4) + hg.kcap * m * 4
            # fold pyramid: halving read+write chain ~ 2 * 2*num_virtual
            # rows, then the heavy_pick gather back out.
            res += 4 * hg.res_num_virtual * w * 4 + hg.res_heavy * w * 4
        for b in hg.res_light:
            n, k = b.idx.shape
            ne = min(n, at_rows) if gated else n
            res += k * (3 * ne * w * 4) + ne * k * 4
    # permutation back to rank0: read bucket rows + write the rank0 table.
    res += 2 * tb
    out["residual"] = res
    if hg.num_tiles:
        # a_tiles streamed once; each (row,col) tile production reads a
        # 128-row frontier slab column; output written once per row tile.
        # (Ungated even on gated engines — see msbfs_hybrid._make_core.)
        out["dense"] = hg.a_tiles.nbytes + hg.num_tiles * TILE * w * 4 + tb
    if engine.adaptive_push is not None:
        deg_cap = engine.adaptive_push[1]
        nz = int(nz_rows or 0)
        # zero-init of the hit table + per active row: its frontier word
        # row read + deg_cap neighbor rows read-modify-write.
        out["push"] = tb + nz * (1 + 2 * deg_cap) * w * 4
    if gated:
        # Gated state: full-table settled-mask read + next-frontier
        # zero-init, then claim/visited/ripple traffic on active tiles.
        out["state"] = 2 * tb + (
            (3 + 2 * engine.num_planes) * at_rows * w * 4
        )
    else:
        # claim reads hit+vis, writes vis and nxt; ripple reads+writes
        # planes.
        out["state"] = (4 + 2 * engine.num_planes) * tb
    return out


@dataclasses.dataclass
class LevelAttribution:
    level: int
    frontier_rows: int  # active rows entering the level
    took: str  # 'push' (adaptive light level) or 'pull'
    t_full_s: float  # the real fused one-level step
    phases_s: dict  # phase -> seconds (standalone slice)
    bytes_model: dict  # phase -> analytic HBM bytes
    # Unsettled GATE_TILE blocks entering the level (pull-gated engines
    # only; sizes the gated byte model). None when the engine is ungated.
    active_tiles: int | None = None
    # Exchange branch this level's step recorded (distributed MS engines
    # stepping through _core_from — the diff of the chunk-chained
    # per-branch counters; None when unobserved, e.g. the donating TPU
    # step path, which bypasses the recording).
    exchange_branch: int | None = None


def roofline_hybrid(engine, sources, *, peak_gbs: float | None = None,
                    measured_gteps: float | None = None,
                    log=None) -> dict:
    """Attribute a real traversal of ``sources`` level by level.

    Returns a JSON-ready report: per-level attribution, per-phase totals
    with shares and achieved GB/s, the fusion dividend, the named binding
    term, and the peak-bandwidth ceiling implied by the byte model (scaled
    from ``measured_gteps`` when given — pass the timed batch's figure so
    the ceiling is anchored to the same run protocol). ``peak_gbs``
    defaults to the published HBM peak of the default device
    (:func:`device_peaks`)."""
    if peak_gbs is None:
        peak_gbs = device_peaks(jax.devices()[0].device_kind)["hbm_gbs"]
    fns = phase_fns(engine)
    arrs = engine.arrs
    sources = np.asarray(sources)
    # Pull-gated engines: refine the gate's lane mask to this batch (the
    # all-ones default is safe but gates nothing until every lane settles).
    # The phase SLICES stay the ungated forms — for a gated engine the
    # per-level gap between the slice sum and t_full then measures the
    # gate's win directly; the byte model switches to the gated entries.
    note = getattr(engine, "_note_batch_sources", None)
    if note is not None:
        note(sources)
    fw = engine._seed_dev(sources)
    # vis must be a DISTINCT buffer: the donating step would otherwise
    # donate the same seed buffer through two donated parameters, which
    # PJRT rejects at execute time.
    vis = jnp.copy(fw)
    planes = tuple(jnp.zeros_like(fw) for _ in range(engine.num_planes))
    level, alive = 0, True
    cap = engine.max_levels_cap
    row_cap = engine.adaptive_push[0] if engine.adaptive_push else None
    levels: list[LevelAttribution] = []

    def try_timed(call, warm):
        """Phase timing with an OOM seatbelt: a slice whose live set
        doesn't fit next to the standing carry reports None (partial
        attribution beats losing the whole report), anything else
        propagates."""
        try:
            return run_timed(call, warm=warm)
        except Exception as exc:  # noqa: BLE001 — OOM-only degrade
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            if log is not None:
                log(f"phase slice OOM'd; reporting None ({str(exc)[:120]})")
            return None, None

    # One-level fused step. On TPU the carry buffers are DONATED so the
    # step's output can alias them — without donation the old and new
    # carries are simultaneously live (the standing 6 tables twice over)
    # and the stepping OOMs at flagship scale where engine.run fits.
    # Donated inputs are consumed per call, so the usual warm-by-running
    # is impossible; the compile is absorbed via AOT lower().compile()
    # (which executes nothing) and the Compiled object is called once per
    # level.
    raw_step = getattr(engine._core_from, "__wrapped__", None)
    donating = raw_step is not None and jax.default_backend() == "tpu"
    step_fn = (
        jax.jit(raw_step, donate_argnums=(1, 2, 3))
        if donating else engine._core_from
    )
    compiled_step = None

    count_rows = jax.jit(
        lambda f: jnp.sum(jnp.any(f[: engine._act] != 0, axis=1)
                          .astype(jnp.int32))
    )
    count_tiles = None
    if getattr(engine, "pull_gate", False):
        from tpu_bfs.algorithms._packed_common import (
            GATE_TILE,
            row_unsettled,
        )

        nt_tiles = engine._table_rows // GATE_TILE

        @jax.jit
        def count_tiles(v, lane_mask):
            need = row_unsettled(v, engine._act, lane_mask)
            blk = jnp.any(
                need[: nt_tiles * GATE_TILE].reshape(nt_tiles, GATE_TILE),
                axis=1,
            )
            return jnp.sum(blk.astype(jnp.int32))

    # Each slice warms on its own FIRST dispatch, not at level 0: the push
    # slice no longer dispatches on pull levels (ADVICE r5 — timing it
    # there ran a row_cap-truncated index table through an nz-trip fori,
    # a million-iteration clamped scatter that could blow the pstage
    # timeout), so its first dispatch can land at any level.
    warmed: set[str] = set()
    # Chunk-chained exchange counters of the previous step (per-level
    # branch attribution below diffs against them).
    prev_counts_walk = None

    def timed_slice(name, call):
        out, t = try_timed(call, name not in warmed)
        warmed.add(name)
        return out, t

    while alive and level < cap:
        warm = level == 0
        nz = int(count_rows(fw))
        at = (
            int(count_tiles(vis, engine._lane_mask_dev))
            if count_tiles is not None
            else None
        )
        took = "pull"
        if "gate" in fns:
            g_nz, g_bad = fns["gate"](arrs, fw)
            if int(g_nz) <= row_cap and not bool(g_bad):
                took = "push"
        phases = {}
        for name in ("residual", "dense", "push"):
            if name not in fns:
                continue
            if name == "push" and took != "push":
                # The fused loop does not run push this level; dispatching
                # the gate-free slice anyway would time an out-of-contract
                # input (see the warmed-set note above).
                continue
            out, t = timed_slice(name, partial(fns[name], arrs, fw))
            del out  # free the [rows, w] hit before the next dispatch
            phases[name] = t
        # State = claim + ripple, timed separately (see phase_fns) on a
        # freshly materialized full hit. The hit materialization itself
        # is the largest slice intermediate — same OOM seatbelt.
        try:
            h = fns["hit"](arrs, fw)
            jax.block_until_ready(h)
        except Exception as exc:  # noqa: BLE001 — OOM-only degrade
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            h = None
            if log is not None:
                log(f"hit materialization OOM'd ({str(exc)[:120]})")
        if h is None:
            cl, t_claim = None, None
        else:
            cl, t_claim = timed_slice("claim", partial(fns["claim"], h, vis))
            del h
        if cl is None:
            phases["state"] = None
        else:
            _nxt, vis2p, _ = cl
            del cl, _nxt
            out, t_rip = timed_slice(
                "ripple", partial(fns["ripple"], planes, vis2p)
            )
            del out, vis2p
            phases["state"] = (
                None if t_rip is None else t_claim + t_rip
            )

        step_args = (
            arrs, fw, vis, planes, jnp.int32(level), jnp.int32(level + 1)
        )
        if donating:
            if compiled_step is None:
                compiled_step = step_fn.lower(*step_args).compile()
            step, step_warm = partial(compiled_step, *step_args), False
        else:
            step, step_warm = partial(step_fn, *step_args), warm
        (fw2, vis2, planes2, lvl2, alive2), t_full = run_timed(
            step, warm=step_warm
        )
        # Which exchange branch did this one-level step take? Without a
        # chain nonce each step RESTARTS the per-branch counters
        # (collectives.chained_prev_counts), so they are usually a
        # one-level one-hot; a chained engine instead accumulates, and
        # the level's branch is the diff against the previous step's
        # counters. The donating TPU path calls the raw core and records
        # nothing — branch stays None.
        step_branch = None
        counts_now = getattr(engine, "last_exchange_level_counts", None)
        if not donating and counts_now is not None:
            counts_now = np.asarray(counts_now)
            if counts_now.sum() == 1:
                step_branch = int(np.argmax(counts_now))
            elif (
                prev_counts_walk is not None
                and prev_counts_walk.shape == counts_now.shape
            ):
                hot = np.flatnonzero(counts_now - prev_counts_walk)
                if len(hot) == 1 and counts_now[hot[0]] > prev_counts_walk[hot[0]]:
                    step_branch = int(hot[0])
            prev_counts_walk = counts_now
        levels.append(LevelAttribution(
            level=level, frontier_rows=nz, took=took, t_full_s=t_full,
            phases_s=phases,
            bytes_model=phase_bytes(engine, nz_rows=nz, active_tiles=at),
            active_tiles=at,
            exchange_branch=step_branch,
        ))
        if log is not None:
            gate_msg = "" if at is None else f"active_tiles={at} "
            log(f"level {level}: rows={nz} took={took} {gate_msg}"
                f"full={t_full*1e3:.1f}ms " + " ".join(
                    f"{k}={v*1e3:.1f}ms" if v is not None else f"{k}=OOM"
                    for k, v in phases.items()))
        fw, vis, planes = fw2, vis2, planes2
        level, alive = int(lvl2), bool(alive2)

    # ---- aggregate ----
    # Attributed time: the phases the fused loop actually runs per level
    # (push levels skip residual+dense; pull levels skip push) + state.
    tot_attr: dict[str, float] = {}
    tot_bytes: dict[str, float] = {}
    t_full_sum = 0.0
    unmeasured = 0  # phase slices that OOM'd next to the standing carry
    for la in levels:
        t_full_sum += la.t_full_s
        names = (["push"] if la.took == "push" else
                 [n for n in ("residual", "dense") if n in la.phases_s])
        for n in names + ["state"]:
            t = la.phases_s.get(n)
            if t is None:
                unmeasured += 1
                continue
            tot_attr[n] = tot_attr.get(n, 0.0) + t
            tot_bytes[n] = tot_bytes.get(n, 0.0) + la.bytes_model.get(n, 0)
    attr_sum = sum(tot_attr.values())
    # Fold the walk into the unified engine-trace contract (ISSUE 6):
    # the roofline drives the level loop one step at a time, so it
    # observes per-level frontier rows and direction directly — richer
    # than the fused loop's own recording. gated_tiles converts the
    # gate's input (active tiles) into the trace's skip count.
    trace_rows = []
    exch_bytes = getattr(engine, "wire_bytes_per_level", None)
    exch_per = [float(x) for x in exch_bytes()] if exch_bytes is not None else None
    exch_each = (
        exch_per[0] if exch_per is not None and len(exch_per) == 1 else None
    )
    # Per-branch labels (cap rungs, ISSUE 7 delta widths) for engines
    # that publish them; the per-level branch came from the step diffs.
    label_hook = getattr(engine, "exchange_branch_labels", None)
    exch_labels = label_hook() if callable(label_hook) else None
    for la in levels:
        gated_tiles = None
        if la.active_tiles is not None:
            from tpu_bfs.algorithms._packed_common import GATE_TILE

            total_tiles = engine._table_rows // GATE_TILE
            gated_tiles = max(total_tiles - la.active_tiles, 0)
        b = la.exchange_branch
        label = (
            exch_labels[b]
            if exch_labels is not None and b is not None
            and b < len(exch_labels) else None
        )
        wire = exch_each
        if b is not None and exch_per is not None and b < len(exch_per):
            wire = exch_per[b]
        trace_rows.append({
            "level": la.level,
            "frontier": la.frontier_rows,
            "direction": (
                "push" if la.took == "push"
                else "pull-gated" if la.active_tiles is not None else "pull"
            ),
            "gated_tiles": gated_tiles,
            "exchange": label,
            "wire_bytes": wire,
        })
    engine.last_run_trace = trace_rows
    # Full degradation (every slice OOM'd) still emits the partial report
    # — per-level t_full and the unmeasured count are real data.
    binding = max(tot_attr, key=tot_attr.get) if tot_attr else None
    total_bytes = sum(tot_bytes.values())
    report = {
        "num_levels": len(levels),
        # Gated engines: the byte model uses the gated entries and the
        # slices stay ungated, so per-level (slice sum - t_full) includes
        # the gate's win; levels[i].active_tiles records the gate's input.
        "pull_gate": bool(getattr(engine, "pull_gate", False)),
        "levels": [dataclasses.asdict(la) for la in levels],
        "t_full_sum_s": t_full_sum,
        "t_attributed_sum_s": attr_sum,
        # slices re-run what the fused loop fuses; the gap is XLA's win.
        "fusion_dividend_s": attr_sum - t_full_sum,
        "phase_share": {n: t / attr_sum for n, t in tot_attr.items()},
        "phase_achieved_gbs": {
            n: (tot_bytes[n] / 1e9) / t if t > 0 else None
            for n, t in tot_attr.items()
        },
        "binding_term": binding,
        "unmeasured_phase_slices": unmeasured,
        # Compact engine-trace form (obs/engine_trace.trace_summary): the
        # same keys bench.py's verdict carries, derived from this walk.
        "trace_summary": _trace_summary(trace_rows, engine),
        "peak_gbs": peak_gbs,
        "hbm_bytes_total": total_bytes,
        # time the whole byte model would take at peak bandwidth.
        "t_at_peak_bw_s": total_bytes / (peak_gbs * 1e9),
    }
    # Expansion-tier attribution (ISSUE 16): which tier ran, and — on the
    # pallas tier — the per-kernel VMEM-resident byte bound of one
    # ungated level with its time at peak bandwidth (the BLEST-style
    # floor the fused kernel chases; compare against the residual
    # phase's achieved figure above).
    report["expand_impl"] = getattr(engine, "expand_impl", "xla")
    pal = pallas_expand_bytes(engine)
    if pal:
        report["expand_kernel_bytes"] = {
            **{k: int(v) for k, v in pal.items()},
            "level_total": int(sum(pal.values())),
        }
        report["expand_kernel_t_at_peak_bw_s"] = (
            sum(pal.values()) / (peak_gbs * 1e9)
        )
    if measured_gteps is not None:
        # The fused batch measured `measured_gteps`; if every attributed
        # phase ran at peak HBM bandwidth, the same byte model implies:
        report["measured_gteps"] = measured_gteps
        report["ceiling_gteps_at_peak_bw"] = (
            measured_gteps * t_full_sum / report["t_at_peak_bw_s"]
            if report["t_at_peak_bw_s"] > 0 else None
        )
    return report
