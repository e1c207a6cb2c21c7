"""Empirical cross-check of the modeled wire-byte accounting against XLA.

Every wire-byte figure the framework prints is modeled (a formula over
branch counts — collectives.py labels them so), because per-level hardware
byte counters don't exist on the CPU mesh and an xprof capture needs the
real chip. This module retires the "trust the formula" caveat a different
way: it parses the COMPILED program's collective instructions (HLO on the
8-virtual-device CPU mesh — the same program XLA runs on TPU, modulo
backend lowering) and re-derives the per-level bytes from the collectives'
own operand shapes. Agreement means the formulas describe what the
compiler actually emits, not what we hoped it would emit.

Conventions (ring collectives over P devices):
- ``collective-permute`` sends its whole operand once per execution.
- ``all-to-all`` with a P-piece tuple operand keeps one piece local and
  sends P-1 — wire bytes = (P-1) x piece bytes.
- scalar ``all-reduce``: the SPARSE models carry a flat +4 bytes for
  their phase-1 pmax scalar (it exists only on that path); the DENSE
  models carry no flat term — their per-level termination psum is
  outside every model's stated scope (exchange traffic) and is reported
  separately here.
- wire dtypes: the unpacked dense ring ships PRED chunks (one BYTE per
  vertex per hop — n result bytes pins the dtype), the unpacked
  allreduce an S32 buffer (four bytes per vertex); ``wire_pack`` ships
  U32 words, 32 vertices/word, on both (``check_packed_exchange``
  asserts the exact /8 and /32 ratios plus an unchanged collective
  instruction count).
"""

from __future__ import annotations

import numpy as np

# The HLO walking core moved to tpu_bfs/analysis/hlo.py (ISSUE 8): the
# shape/byte parsing and collective inventory are shared with the
# static-analysis passes now; this module keeps the wire-byte AUDITS and
# re-exports the core names its tests and clients import from here.
from tpu_bfs.analysis.hlo import (  # noqa: F401 — re-exported API
    Collective,
    hlo_collectives,
    shape_bytes as _shape_bytes,
)


def _lower_1d_loop(eng) -> str:
    """Compiled HLO text of a 1D DistBfsEngine's level loop."""
    import jax.numpy as jnp

    f0, vis0, d0 = eng._init_state(0)
    return (
        eng._loop.lower(
            eng.src, eng.dst, eng.rp, eng._aux, f0, vis0, d0,
            jnp.int32(0), jnp.int32(64),
        )
        .compile()
        .as_text()
    )


def check_1d_sparse(graph, p: int = 8, wire_pack: bool = False) -> dict:
    """1D DistBfsEngine, queue-style sparse exchange: the modeled per-level
    branch bytes (sparse_wire_bytes_per_level) vs the compiled program's
    all-to-all piece sizes and ring-step permutes. ``wire_pack`` audits
    the bit-packed dense fallback (u32 word permutes) against the packed
    model and the recalibrated default cap ladder."""
    from tpu_bfs.parallel.collectives import sparse_wire_bytes_per_level
    from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

    eng = DistBfsEngine(
        graph, make_mesh(p), exchange="sparse", wire_pack=wire_pack
    )
    n = eng.part.vloc
    colls = hlo_collectives(_lower_1d_loop(eng))

    # Sparse branches: each cap's [P, cap] s32 bucket buffer all-to-all
    # keeps the self piece local -> (P-1) * 4c on the wire.
    a2a_wire = sorted(
        {(c.pieces - 1) * (c.result_bytes // c.pieces)
         for c in colls if c.op == "all-to-all"}
    )
    # Dense fallback: unrolled ring reduce-scatter, P-1 permutes of one
    # [n] bool chunk each (one [ceil(n/32)] u32 chunk under wire_pack).
    ring = [c for c in colls if c.op == "collective-permute"]
    ring_wire = sum(c.result_bytes for c in ring)
    scalars = [c for c in colls if c.op == "all-reduce"]

    modeled = sparse_wire_bytes_per_level(
        p, n, eng.sparse_caps, wire_pack=wire_pack
    )
    derived = [w + 4.0 for w in a2a_wire] + [ring_wire + 4.0]
    return {
        "config": (
            f"1D sparse exchange, P={p}, vloc={n}, caps={eng.sparse_caps}, "
            f"wire_pack={wire_pack}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "ring_steps": len(ring),
        "scalar_allreduces": len(scalars),
        "agree": (
            [float(x) for x in modeled] == [float(x) for x in derived]
            and len(ring) == p - 1
        ),
    }


def check_2d(graph, rows: int = 2, cols: int = 4, exchange: str = "ring",
             backend: str = "scan", wire_pack: bool = False) -> dict:
    """2D Dist2DBfsEngine: the modeled per-level bytes (dense_2d_wire_bytes
    — the BASELINE scale-26 config's wire model) vs the compiled loop's
    column all-gather and row reduce-scatter.

    Ring conventions as in the module docstring; ``all-gather`` result
    holds all R pieces, so wire/chip = result - own piece = result*(R-1)/R.
    The 'allreduce' row exchange lowers to one [C*w] s32 all-reduce whose
    bandwidth-optimal wire cost is 2*(C-1)/C x result bytes. Under
    ``wire_pack`` the column gather moves u32[R*ceil(w/32)] words, the
    ring permutes u32[ceil(w/32)] chunks, and the allreduce row exchange
    becomes one all-to-all of per-destination word chunks (keep-own
    convention, as in the 1D packed audit)."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.collectives import dense_2d_wire_bytes, packed_words
    from tpu_bfs.parallel.dist_bfs2d import Dist2DBfsEngine, make_mesh_2d

    eng = Dist2DBfsEngine(
        graph, make_mesh_2d(rows, cols), exchange=exchange, backend=backend,
        wire_pack=wire_pack,
    )
    w = eng.part.w
    nw = packed_words(w)
    f0, vis0, d0 = eng._init_state(0)
    hlo = (
        eng._loop.lower(
            eng.src_g, eng.dst_l, eng.rp, eng._aux, f0, vis0, d0,
            jnp.int32(0), jnp.int32(64),
        )
        .compile()
        .as_text()
    )
    colls = hlo_collectives(hlo)

    # Column exchange: one pred[R*w] (u32[R*nw] packed) all-gather over 'r'.
    ag_result = rows * 4 * nw if wire_pack else rows * w
    col_ags = [
        c for c in colls if c.op == "all-gather" and c.result_bytes == ag_result
    ]
    ag_wire = (rows - 1) * (ag_result // rows) if rows > 1 else 0

    if exchange == "ring":
        # Row exchange: unrolled ring, C-1 permutes of one pred[w]
        # (u32[nw] packed) chunk.
        chunk = 4 * nw if wire_pack else w
        ring = [
            c for c in colls
            if c.op == "collective-permute" and c.result_bytes == chunk
        ]
        row_wire = sum(c.result_bytes for c in ring)
        row_ok = len(ring) == cols - 1
    elif wire_pack:
        # Packed row exchange: one u32[C, nw] all-to-all, keep-own piece.
        a2as = [
            c for c in colls
            if c.op == "all-to-all" and c.result_bytes == 4 * cols * nw
        ]
        row_wire = sum(
            (c.pieces - 1) * (c.result_bytes // c.pieces) for c in a2as
        )
        row_ok = len(a2as) == 1
    else:
        # Row exchange: one s32[C*w] all-reduce (psum) over 'c'.
        big_ars = [
            c for c in colls
            if c.op == "all-reduce" and c.result_bytes == 4 * cols * w
        ]
        row_wire = sum(
            2 * (cols - 1) * c.result_bytes // cols for c in big_ars
        )
        row_ok = len(big_ars) == 1
    scalars = [
        c for c in colls if c.op == "all-reduce" and c.result_bytes == 4
    ]

    modeled = dense_2d_wire_bytes(rows, cols, w, exchange, wire_pack=wire_pack)
    derived = float(ag_wire + row_wire)
    return {
        "config": (
            f"2D {exchange}/{backend}, mesh {rows}x{cols}, w={w}, "
            f"wire_pack={wire_pack}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "column_allgathers": len(col_ags),
        "scalar_allreduces": len(scalars),
        # A 1-row mesh column-exchanges nothing: no all-gather to find.
        "agree": (
            modeled == derived
            and len(col_ags) == (1 if rows > 1 else 0)
            and row_ok
        ),
    }


def check_planned_sparse(graph, p: int = 8, wire_pack: bool = False) -> dict:
    """ISSUE 7 tentpole proof, from the compiled HLO: the exchange
    planner's delta branches ship exactly ``delta_words(cap, b)`` =
    1 + ceil(cap*b/32) uint32 words per destination (one header word +
    the bit-packed deltas), the sieve path adds EXACTLY ONE packed vis
    transfer (a u32[ceil(n/32)] all-gather — nothing else in the 1D loop
    all-gathers), and the whole branch space prices to the model: every
    entry of planned_sparse_wire_bytes_per_level is re-derived from the
    collectives' own operand shapes.

    Collective inventory audited (delta_bits=(8,16), sieve+predict on):
    each (cap rung x {delta8, delta16, plain}) all-to-all appears TWICE —
    once unsieved, once sieved (consumed pairwise, so a program missing a
    sieved rung fails); the dense ring appears THREE times (unsieved
    fallback, sieved fallback, predicted-dense) at P-1 permutes each; the
    measured pmax is ONE s32[2] all-reduce per measure (two instances:
    pre- and post-sieve) — the pair rides one scalar collective, which is
    why measured levels model +8, sieved +16, predicted +0."""
    from tpu_bfs.parallel.collectives import (
        DELTA_BITS_DEFAULT,
        delta_words,
        packed_words,
        planned_sparse_wire_bytes_per_level,
    )
    from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

    delta_bits = DELTA_BITS_DEFAULT
    eng = DistBfsEngine(
        graph, make_mesh(p), exchange="sparse", wire_pack=wire_pack,
        delta_bits=delta_bits, sieve=True, predict=True,
    )
    n = eng.part.vloc
    nw = packed_words(n)
    caps = eng.sparse_caps
    colls = hlo_collectives(_lower_1d_loop(eng))
    pool = list(colls)

    def _take(pred) -> bool:
        for idx, c in enumerate(pool):
            if pred(c):
                del pool[idx]
                return True
        return False

    # Per-rung piece bytes, in branch order (delta widths then plain).
    piece_bytes = []
    for c in sorted(caps):
        piece_bytes += [4 * delta_words(c, b) for b in delta_bits]
        piece_bytes.append(4 * c)
    found_pairs = []
    for piece in piece_bytes:
        # Consume the unsieved AND sieved instance of this rung/encoding.
        got = sum(
            _take(
                lambda a: a.op == "all-to-all"
                and a.pieces == p
                and a.result_bytes == piece * p
            )
            for _ in range(2)
        )
        found_pairs.append(got == 2)
    leftover_a2a = [c for c in pool if c.op == "all-to-all"]
    # The sieve's vis transfer: exactly ONE all-gather in the whole loop.
    ags = [c for c in pool if c.op == "all-gather"]
    sieve_ok = len(ags) == 1 and ags[0].result_bytes == p * 4 * nw
    # Dense ring: three instances (unsieved, sieved, predicted) of P-1
    # permutes each, pred[n] chunks (u32[nw] under wire_pack).
    chunk = 4 * nw if wire_pack else n
    perms = [c for c in pool if c.op == "collective-permute"]
    ring_ok = (
        len(perms) == 3 * (p - 1)
        and all(c.result_bytes == chunk for c in perms)
    )
    # Scalars: two s32[2] pmax pairs (pre/post-sieve measure), plus the
    # 4-byte termination psum and visited-total seed. XLA's all-reduce
    # combiner may fuse independent scalars into one (s32[], s32[])
    # tuple: 8 bytes in two pieces, which is not a pmax pair.
    pairs = [c for c in pool if c.op == "all-reduce" and c.result_bytes == 8
             and c.pieces == 1]
    singles = [c for c in pool if c.op == "all-reduce"
               and c.result_bytes == 4 * c.pieces]

    sparse_wire = [(p - 1) * piece for piece in piece_bytes]
    ring_wire = float((p - 1) * chunk)
    ag_wire = float((p - 1) * 4 * nw)
    derived = (
        [w + 8.0 for w in sparse_wire] + [ring_wire + 8.0]
        + [w + ag_wire + 16.0 for w in sparse_wire]
        + [ring_wire + ag_wire + 16.0] + [ring_wire]
    )
    modeled = planned_sparse_wire_bytes_per_level(
        p, n, caps, delta_bits, wire_pack=wire_pack
    )
    return {
        "config": (
            f"planned sparse exchange, P={p}, vloc={n}, caps={caps}, "
            f"delta_bits={delta_bits}, wire_pack={wire_pack}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "rung_pairs_found": found_pairs,
        "sieve_allgathers": len(ags),
        "ring_permutes": len(perms),
        "pair_pmaxes": len(pairs),
        "scalar_allreduces": len(singles),
        "agree": (
            all(found_pairs)
            and not leftover_a2a
            and sieve_ok
            and ring_ok
            and len(pairs) == 2
            and [float(x) for x in modeled] == [float(x) for x in derived]
        ),
    }


def check_rows_delta(graph, p: int = 8, lanes: int = 64) -> dict:
    """Delta-encoded sparse row gather (ISSUE 7, distributed wide engine —
    the hybrid shares the code path): per rung, the id stream compresses
    to ONE u32[delta_words(cap, b)] all-gather per width (plus the shared
    [cap, w] lane-word gather, which the encoding cannot touch), and the
    whole branch space prices to sparse_rows_wire_bytes_per_level."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.collectives import (
        DELTA_BITS_DEFAULT,
        delta_words,
        sparse_rows_wire_bytes_per_level,
    )
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

    delta_bits = DELTA_BITS_DEFAULT
    eng = DistWideMsBfsEngine(
        graph, make_mesh(p), lanes=lanes, exchange="sparse",
        delta_bits=delta_bits,
    )
    w = eng.w
    rows_loc = eng._gather_rows_loc
    caps = eng.sparse_caps
    fw0 = eng._seed_dev(np.asarray([0]))
    hlo = (
        eng._dist_core.lower(eng.arrs, fw0, jnp.int32(32)).compile().as_text()
    )
    ags = [c for c in hlo_collectives(hlo) if c.op == "all-gather"]
    pool = list(ags)

    def _take(pred) -> bool:
        for idx, a in enumerate(pool):
            if pred(a):
                del pool[idx]
                return True
        return False

    derived = []
    found = []
    for c in sorted(caps):
        vals_b = p * c * 4 * w
        got_vals = _take(lambda a: a.result_bytes == vals_b and a.pieces == 1)
        for b in delta_bits:
            ids_b = p * 4 * delta_words(c, b)
            got = _take(lambda a: a.result_bytes == ids_b and a.pieces == 1)
            found.append(got)
            derived.append(
                None if not (got and got_vals)
                else (ids_b + vals_b) * (p - 1) / p + 8.0
            )
        ids_plain = p * c * 4
        got = _take(lambda a: a.result_bytes == ids_plain and a.pieces == 1)
        found.append(got and got_vals)
        derived.append(
            None if not (got and got_vals)
            else (ids_plain + vals_b) * (p - 1) / p + 8.0
        )
    dense_b = p * rows_loc * 4 * w
    dense_got = _take(lambda a: a.result_bytes == dense_b)
    found.append(dense_got)
    derived.append(dense_b * (p - 1) / p + 8.0 if dense_got else None)

    modeled = sparse_rows_wire_bytes_per_level(
        p, rows_loc, w, caps, delta_bits
    )
    return {
        "config": (
            f"dist-wide delta rows, P={p}, rows_loc={rows_loc}, w={w}, "
            f"caps={caps}, delta_bits={delta_bits}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "all_gathers": len(ags),
        "agree": (
            all(found)
            and [float(x) for x in modeled] == [float(x) for x in derived]
        ),
    }


def check_2d_sparse(graph, rows: int = 2, cols: int = 4) -> dict:
    """2D queue-style ROW exchange (ISSUE 7): the 2D engine's sparse mode
    runs sparse_exchange_or over 'c' — the modeled per-branch bytes
    (column all-gather + sparse rung / ring fallback) vs the compiled
    loop's own collective shapes."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.dist_bfs2d import Dist2DBfsEngine, make_mesh_2d

    eng = Dist2DBfsEngine(
        graph, make_mesh_2d(rows, cols), exchange="sparse"
    )
    w = eng.part.w
    caps = eng.sparse_caps
    f0, vis0, d0 = eng._init_state(0)
    hlo = (
        eng._loop.lower(
            eng.src_g, eng.dst_l, eng.rp, eng._aux, f0, vis0, d0,
            jnp.int32(0), jnp.int32(64),
        )
        .compile()
        .as_text()
    )
    colls = hlo_collectives(hlo)
    col_ags = [
        c for c in colls
        if c.op == "all-gather" and c.result_bytes == rows * w
    ]
    ag_wire = (rows - 1) * w if rows > 1 else 0
    a2a_wire = sorted(
        {(c.pieces - 1) * (c.result_bytes // c.pieces)
         for c in colls if c.op == "all-to-all"}
    )
    ring = [
        c for c in colls
        if c.op == "collective-permute" and c.result_bytes == w
    ]
    derived = [ag_wire + x + 4.0 for x in a2a_wire] + [
        ag_wire + sum(c.result_bytes for c in ring) + 4.0
    ]
    modeled = eng.wire_bytes_per_level()
    return {
        "config": (
            f"2D sparse row exchange, mesh {rows}x{cols}, w={w}, caps={caps}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "column_allgathers": len(col_ags),
        "ring_steps": len(ring),
        "agree": (
            [float(x) for x in modeled] == [float(x) for x in derived]
            and len(col_ags) == (1 if rows > 1 else 0)
            and len(ring) == cols - 1
        ),
    }


def check_rows_sparse(graph, p: int = 8, lanes: int = 64) -> dict:
    """Distributed wide engine, queue-style sparse row gather
    (collectives.sparse_rows_gather, shared with the distributed hybrid):
    the modeled per-branch bytes (sparse_rows_wire_bytes_per_level) vs the
    compiled cap-ladder's all-gather sizes.

    Each sparse rung c gathers (ids s32[c], vals u32[c, w]) from every
    chip; XLA's all-gather combiner may emit them as two array ops or one
    tuple op, so both forms are accepted. Wire/chip = (P-1)/P x gathered
    result bytes, + the 4-byte pmax scalar every branch pays. The dense
    fallback gathers the whole [v_loc, w] slab."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.collectives import sparse_rows_wire_bytes_per_level
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

    eng = DistWideMsBfsEngine(
        graph, make_mesh(p), lanes=lanes, exchange="sparse"
    )
    w = eng.w
    rows_loc = eng._gather_rows_loc
    caps = eng.sparse_caps
    fw0 = eng._seed_dev(np.asarray([0]))
    hlo = (
        eng._dist_core.lower(eng.arrs, fw0, jnp.int32(32)).compile().as_text()
    )
    ags = [c for c in hlo_collectives(hlo) if c.op == "all-gather"]
    pool = list(ags)  # ops are CONSUMED as rungs match (see below)

    def _take(pred) -> bool:
        for i, a in enumerate(pool):
            if pred(a):
                del pool[i]
                return True
        return False

    def rung_result_bytes(ids_b: int, vals_b: int):
        """Gathered result bytes of one rung, from the HLO's own ops —
        separate ids/vals all-gathers or one combined tuple op. Matched
        ops are consumed so size collisions between rungs (cap_j*4 ==
        cap_i*4w, or ids_b == vals_b at w=1) can't let one op vouch for
        two probes — a program genuinely missing a rung must fail."""
        if _take(lambda a: a.result_bytes == ids_b + vals_b and a.pieces == 2):
            return ids_b + vals_b
        if _take(lambda a: a.result_bytes == ids_b and a.pieces == 1):
            if _take(lambda a: a.result_bytes == vals_b and a.pieces == 1):
                return ids_b + vals_b
        return None

    derived = []
    found = []
    for c in sorted(caps):
        got = rung_result_bytes(p * c * 4, p * c * 4 * w)
        found.append(got is not None)
        derived.append(
            None if got is None else got * (p - 1) / p + 4.0
        )
    dense_b = p * rows_loc * 4 * w
    dense_got = _take(lambda a: a.result_bytes == dense_b)
    found.append(dense_got)
    derived.append(dense_b * (p - 1) / p + 4.0 if dense_got else None)

    modeled = sparse_rows_wire_bytes_per_level(p, rows_loc, w, caps)
    return {
        "config": (
            f"dist-wide sparse rows, P={p}, rows_loc={rows_loc}, w={w}, "
            f"caps={caps}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "all_gathers": len(ags),
        "agree": (
            all(found)
            and [float(x) for x in modeled]
            == [float(x) for x in derived]
        ),
    }


def check_minplus_exchange(graph, p: int = 8, lanes: int = 32) -> dict:
    """ISSUE 20 tentpole proof, from the compiled HLO: the (min, +) value
    exchange (collectives.sparse_rows_exchange_min, the delta-stepping
    engines' bucket-close collective) prices exactly like its OR row-gather
    twin with the lane payload reinterpreted — per rung ONE [cap, lanes]
    s32 value all-gather shared across the id encodings, one id all-gather
    per encoding (delta_words(cap, b) u32 words delta-encoded, cap int32s
    plain), ONE s32[2] pmax pair per measured round — and the history
    predictor's armed branch adds EXACTLY one extra dense table all-gather
    (the measurement-free round) and nothing else.

    Three compiles are audited against minplus_rows_wire_bytes_per_level:

    - the planner variant (delta_bits + predict): every branch's modeled
      bytes re-derived from the collectives' own operand shapes, matched
      ops CONSUMED so no op vouches twice, zero leftover all-gathers;
    - the measured variant (predict off) vs the OR counterpart
      (DistWideMsBfsEngine, SAME cap ladder / delta widths / lane count):
      all-gather instruction counts must be EQUAL — generalizing the
      monoid adds no collective;
    - planner vs measured: all-gather count delta must be EXACTLY one
      (the predicted-dense branch's table rebuild).
    """
    import jax.numpy as jnp

    from tpu_bfs.parallel.collectives import (
        DELTA_BITS_DEFAULT,
        delta_words,
        minplus_rows_wire_bytes_per_level,
    )
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine
    from tpu_bfs.parallel.dist_sssp import DistSsspEngine

    delta_bits = DELTA_BITS_DEFAULT
    mesh = make_mesh(p)

    def sssp_ags(predict: bool):
        eng = DistSsspEngine(
            graph, mesh, lanes=lanes, exchange="sparse",
            delta_bits=delta_bits, predict=predict,
        )
        progs = {nm: (fn, args) for nm, fn, args in eng.analysis_programs()}
        fn, args = progs["dist_sssp_core"]
        colls = hlo_collectives(fn.lower(*args).compile().as_text())
        return eng, colls

    eng, colls = sssp_ags(predict=True)
    n = eng.sell.v_loc
    caps = eng.sparse_caps
    pool = [c for c in colls if c.op == "all-gather"]
    n_ags_planner = len(pool)

    def _take(pred) -> bool:
        for idx, a in enumerate(pool):
            if pred(a):
                del pool[idx]
                return True
        return False

    derived = []
    found = []
    for c in sorted(caps):
        # One shared [cap, lanes] s32 value gather per rung, then one id
        # gather per encoding (delta widths in ladder order, then plain).
        vals_b = p * c * 4 * lanes
        got_vals = _take(lambda a: a.result_bytes == vals_b and a.pieces == 1)
        for b in delta_bits:
            ids_b = p * 4 * delta_words(c, b)
            got = _take(lambda a: a.result_bytes == ids_b and a.pieces == 1)
            found.append(got and got_vals)
            derived.append(
                None if not (got and got_vals)
                else (ids_b + vals_b) * (p - 1) / p + 8.0
            )
        ids_plain = p * c * 4
        got = _take(lambda a: a.result_bytes == ids_plain and a.pieces == 1)
        found.append(got and got_vals)
        derived.append(
            None if not (got and got_vals)
            else (ids_plain + vals_b) * (p - 1) / p + 8.0
        )
    # Dense table rebuild: the measured ladder's overflow leaf AND the
    # predictor's measurement-free branch each all-gather every chip's
    # [v_loc, lanes] owned-row slab — two instances, same shape; only the
    # measured one pays the s32[2] pmax.
    dense_b = p * n * 4 * lanes
    for flat in (8.0, 0.0):
        got = _take(lambda a: a.result_bytes == dense_b and a.pieces == 1)
        found.append(got)
        derived.append(dense_b * (p - 1) / p + flat if got else None)
    # The pmax pair (changed-row count + max id gap) rides ONE s32[2]
    # all-reduce; the per-round light-sweep convergence psum is the 4-byte
    # scalar, outside the exchange model by the dense_or convention.
    pairs = [c for c in colls if c.op == "all-reduce" and c.result_bytes == 8]

    modeled = minplus_rows_wire_bytes_per_level(
        p, n, lanes, caps, delta_bits, predict=True
    )

    # Monoid-generalization certificate: same ladder, same encodings, same
    # lane count -> the min exchange compiles to exactly as many
    # all-gathers as the OR row gather (predict off), and arming the
    # predictor adds exactly the one dense rebuild.
    _, colls_meas = sssp_ags(predict=False)
    n_ags_measured = len([c for c in colls_meas if c.op == "all-gather"])
    eng_or = DistWideMsBfsEngine(
        graph, mesh, lanes=lanes, exchange="sparse", delta_bits=delta_bits,
        sparse_caps=caps,
    )
    fw0 = eng_or._seed_dev(np.asarray([0]))
    hlo_or = (
        eng_or._dist_core.lower(eng_or.arrs, fw0, jnp.int32(32))
        .compile().as_text()
    )
    n_ags_or = len([c for c in hlo_collectives(hlo_or) if c.op == "all-gather"])

    return {
        "config": (
            f"min-plus rows exchange, P={p}, v_loc={n}, lanes={lanes}, "
            f"caps={caps}, delta_bits={delta_bits}, predict=True"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "all_gathers": {
            "minplus_planner": n_ags_planner,
            "minplus_measured": n_ags_measured,
            "or_rows": n_ags_or,
        },
        "pair_pmaxes": len(pairs),
        "agree": (
            all(found)
            and not [c for c in pool if c.op == "all-gather"]
            and len(pairs) == 1
            and n_ags_measured == n_ags_or
            and n_ags_planner == n_ags_measured + 1
            and [float(x) for x in modeled] == [float(x) for x in derived]
        ),
    }


def check_packed_exchange(graph, p: int = 8) -> dict:
    """ISSUE 5 tentpole proof, from the compiled HLO: the bit-packed wire
    format moves exactly 1/8 the collective bytes of the pred ring and
    exactly 1/32 the collective operand bytes of the s32 allreduce, with
    an IDENTICAL collective instruction count — packing is pure compute,
    it never adds a collective.

    Compiles the 1D level loop four ways (ring/allreduce x plain/packed)
    and derives everything from the instructions' own shapes:

    - ring: P-1 collective-permutes both ways; plain chunks are pred[n]
      (n result bytes — ONE byte per vertex, pinning the dtype the model
      documents), packed chunks u32[ceil(n/32)]. vloc is 1024-aligned by
      partition_1d, so the /8 ratio is exact, never ceil-rounded.
    - allreduce: ONE collective both ways; plain is an s32[P*n] all-reduce
      (4 bytes per vertex), packed is one u32 all-to-all whose operand is
      P*n/8 bytes — exactly 1/32. (The packed form also sheds the psum's
      all-gather half, so its modeled WIRE bytes, keep-own convention,
      equal the packed ring's — dense_or_wire_bytes says so.)
    """
    from tpu_bfs.parallel.collectives import dense_or_wire_bytes, packed_words
    from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

    mesh = make_mesh(p)
    colls, n = {}, None
    for impl in ("ring", "allreduce"):
        for packed in (False, True):
            eng = DistBfsEngine(graph, mesh, exchange=impl, wire_pack=packed)
            n = eng.part.vloc
            colls[impl, packed] = hlo_collectives(_lower_1d_loop(eng))
    nw = packed_words(n)

    def wire(c: Collective) -> int:
        # Permutes send their operand; an all-to-all keeps its own piece.
        if c.op == "all-to-all":
            return (c.pieces - 1) * (c.result_bytes // c.pieces)
        return c.result_bytes

    ring_plain = [
        c for c in colls["ring", False] if c.op == "collective-permute"
    ]
    ring_packed = [
        c for c in colls["ring", True] if c.op == "collective-permute"
    ]
    # The big exchange all-reduce; the 4-byte scalars are the termination
    # psums, present identically in every variant.
    ar_plain = [
        c for c in colls["allreduce", False]
        if c.op == "all-reduce" and c.result_bytes > 4
    ]
    a2a_packed = [c for c in colls["allreduce", True] if c.op == "all-to-all"]

    ring_bytes = sum(wire(c) for c in ring_plain)
    ring_packed_bytes = sum(wire(c) for c in ring_packed)
    ar_operand = sum(c.result_bytes for c in ar_plain)
    a2a_operand = sum(c.result_bytes for c in a2a_packed)
    counts = {
        (impl, packed): len(cs) for (impl, packed), cs in colls.items()
    }
    modeled = {
        impl: dense_or_wire_bytes(p, n, impl, wire_pack=True)
        for impl in ("ring", "allreduce")
    }
    derived = {
        "ring": float(ring_packed_bytes),
        "allreduce": float(sum(wire(c) for c in a2a_packed)),
    }
    return {
        "config": f"packed vs plain 1D exchange, P={p}, vloc={n}",
        "vloc": n,
        "ring_permute_result_bytes": sorted(
            {c.result_bytes for c in ring_plain}
        )[0] if ring_plain else None,
        "allreduce_operand_bytes": ar_operand,
        "ring_reduction": ring_bytes / ring_packed_bytes
        if ring_packed_bytes else None,
        "allreduce_operand_reduction": ar_operand / a2a_operand
        if a2a_operand else None,
        "collective_counts": {f"{i}/{p_}": c for (i, p_), c in counts.items()},
        "modeled_packed_per_level": modeled,
        "hlo_packed_per_level": derived,
        "agree": (
            len(ring_plain) == len(ring_packed) == p - 1
            and len(ar_plain) == 1
            and len(a2a_packed) == 1
            and counts["ring", True] == counts["ring", False]
            and counts["allreduce", True] == counts["allreduce", False]
            and ring_packed_bytes * 8 == ring_bytes
            and a2a_operand * 32 == ar_operand
            and derived == {k: float(v) for k, v in modeled.items()}
            and ring_packed_bytes == (p - 1) * 4 * nw
        ),
    }


def check_wire_checksum(p: int = 8, words: int = 64) -> dict:
    """ISSUE 15 wire-checksum byte proof, from the compiled HLO: the
    per-hop chunk checksum (integrity/wire.checksummed_ring_or) costs
    EXACTLY one uint32 word — 4 bytes — per chunk per hop, with an
    identical collective instruction count (the fold is pure compute;
    framing never adds a collective). Compiles the checksummed packed
    ring reduce-scatter-OR both ways over the real ``p``-device mesh and
    derives everything from the permutes' own result shapes:

    - both variants emit exactly ``p - 1`` collective-permutes;
    - plain chunks are ``u32[words]`` (4 * words bytes), framed chunks
      ``u32[words + 1]`` — the delta is 4 bytes per hop, total
      ``4 * (p - 1)`` per shard per exchange;
    - the two programs' results are bit-identical on clean wires (the
      OR semantics are untouched; pinned separately in
      tests/test_integrity.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from tpu_bfs.integrity.wire import checksummed_ring_or
    from jax import shard_map

    devs = jax.devices()[:p]
    mesh = Mesh(np.array(devs), ("x",))
    chunks = jnp.zeros((p, p, words), jnp.uint32)

    def lower(wire_check: bool) -> str:
        def body(c):
            out, bad = checksummed_ring_or(
                c[0], "x", wire_check=wire_check
            )
            return out[None], bad[None]

        fn = shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")),
            check_vma=False,
        )
        return jax.jit(fn).lower(chunks).compile().as_text()

    colls = {
        checked: [
            c for c in hlo_collectives(lower(checked))
            if c.op == "collective-permute"
        ]
        for checked in (False, True)
    }
    plain_bytes = sum(c.result_bytes for c in colls[False])
    checked_bytes = sum(c.result_bytes for c in colls[True])
    counts = {
        checked: len(hlo_collectives(lower(checked)))
        for checked in (False, True)
    }
    return {
        "config": f"checksummed packed ring, P={p}, {words} words/chunk",
        "permutes": {c: len(v) for c, v in colls.items()},
        "plain_permute_bytes": plain_bytes,
        "checked_permute_bytes": checked_bytes,
        "checksum_overhead_bytes": checked_bytes - plain_bytes,
        "collective_counts": counts,
        "agree": (
            len(colls[False]) == len(colls[True]) == p - 1
            and counts[True] == counts[False]
            and checked_bytes - plain_bytes == 4 * (p - 1)
            and all(c.result_bytes == 4 * words for c in colls[False])
            and all(c.result_bytes == 4 * (words + 1) for c in colls[True])
        ),
    }


def check_gated_hybrid(graph, p: int = 8, exchange: str = "dense") -> dict:
    """Pull-gated distributed hybrid (ISSUE 1): the gate must move ZERO
    extra collective bytes — its settled mask is chip-resident, and its
    per-level skipped-block counters come back per-chip (a sharded
    [P, L] output summed on host, deliberately not a psum). Proof: compile
    the gated and ungated cores for the same graph/mesh/exchange and
    compare the full multiset of collective instructions (op, result
    bytes, tuple arity) — equality means the gated program's exchange is
    instruction-for-instruction the ungated one's. Works for every
    exchange the engine grows the flag on ('dense', 'sparse', 'sliced')."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

    mesh = make_mesh(p)
    colls = {}
    for gate in (False, True):
        eng = DistHybridMsBfsEngine(
            graph, mesh, exchange=exchange, pull_gate=gate
        )
        args = (eng.arrs, eng._seed_dev(np.asarray([0])), jnp.int32(32))
        if gate:
            args = args + (eng._lane_mask_dev,)
        hlo = eng._dist_core.lower(*args).compile().as_text()
        colls[gate] = sorted(
            (c.op, c.result_bytes, c.pieces) for c in hlo_collectives(hlo)
        )
    return {
        "config": f"gated-vs-ungated dist hybrid, P={p}, exchange={exchange}",
        "ungated_collectives": colls[False],
        "gated_collectives": colls[True],
        "agree": colls[False] == colls[True] and len(colls[False]) > 0,
    }


def check_sliced_hybrid(graph, p: int = 8, lanes: int | None = None) -> dict:
    """Ring-sliced distributed hybrid: the modeled dense-slab bytes
    ((P-1) x [rows_loc, w] u32 per level) vs the compiled rotation's
    permute operand and the engine's own static ring-step count.
    ``lanes`` widens the rows (the model is width-generic; the w=256 arm
    calibrates it at the round-4 single-chip default width)."""
    import jax.numpy as jnp

    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

    kw = {} if lanes is None else {"lanes": lanes}
    eng = DistHybridMsBfsEngine(graph, make_mesh(p), exchange="sliced", **kw)
    rows_loc = eng._gather_rows_loc
    fw0 = eng._seed_dev(np.asarray([0]))
    hlo = (
        eng._dist_core.lower(eng.arrs, fw0, jnp.int32(32)).compile().as_text()
    )
    perms = [
        c for c in hlo_collectives(hlo) if c.op == "collective-permute"
    ]
    slab = rows_loc * eng.w * 4
    # The rotation rides a lax.scan whose trip count is the per-step axis
    # of the step arrays minus the unrotated first step — static, read
    # from the engine's own tables rather than parsed out of the while
    # condition. The GLOBAL array is [P_devices, P_steps, ...] with the
    # device-sharding axis first; inside shard_map each chip scans axis 1.
    # (shape[0] would coincide today only because steps+1 == P.)
    steps = int(eng.arrs["perm"].shape[1]) - 1
    modeled = 0.0 if p == 1 else float((p - 1) * rows_loc * 4 * eng.w)
    derived = float(steps * slab)
    return {
        "config": (
            f"sliced hybrid, P={p}, rows_loc={rows_loc}, w={eng.w}"
        ),
        "modeled_per_level": modeled,
        "hlo_per_level": derived,
        "permute_result_bytes": sorted({c.result_bytes for c in perms}),
        "ring_steps": steps,
        "agree": (
            modeled == derived
            and steps == p - 1
            and all(c.result_bytes == slab for c in perms)
            and len(perms) > 0
        ),
    }
