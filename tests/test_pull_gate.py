"""Frontier-gated pull expansion (ISSUE 1): the gate must be invisible in
every observable output — distances, parents, checkpoints, truncation —
while actually gating (skipped-block counters prove work was skipped), and
the roofline byte model's gated entry must scale with the active-tile
count. Engine-level bit-identity across fuzz shapes (including parents)
lives in test_fuzz_cross_engine.py::test_pull_gate_bit_identical; this
file pins the gate's own machinery. Engines are module-scoped — the suite
has to fit the tier-1 timeout now that the distributed layer runs.
"""

import numpy as np
import pytest

from tpu_bfs.algorithms._packed_common import (
    GATE_TILE,
    host_lane_mask,
)
from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine
from tpu_bfs.graph.generate import rmat_graph
from tpu_bfs.reference import bfs_scipy


@pytest.fixture(scope="module")
def g_rmat():
    return rmat_graph(10, 8, seed=5)


@pytest.fixture(scope="module")
def eng_gated(g_rmat):
    return HybridMsBfsEngine(
        g_rmat, lanes=64, num_planes=4, tile_thr=4, pull_gate=True
    )


@pytest.fixture(scope="module")
def eng_plain(g_rmat):
    return HybridMsBfsEngine(g_rmat, lanes=64, num_planes=4, tile_thr=4)


def _sources(g, n, seed=7):
    rng = np.random.default_rng(seed)
    return rng.choice(np.flatnonzero(g.degrees > 0), size=n, replace=False)


def test_host_lane_mask_covers_exactly_seeded_lanes():
    # 5 lanes, lane 3 isolated (row >= act): its bit must be absent.
    rows = np.asarray([0, 7, 2, 99, 5])
    mask = host_lane_mask(rows, act=50, w=2)
    assert mask.dtype == np.uint32 and mask.shape == (2,)
    assert mask[0] == 0b10111  # lanes 0,1,2,4
    assert mask[1] == 0
    # 33 lanes spill into word 1 (word-major lane map).
    mask = host_lane_mask(np.zeros(33, np.int64), act=1, w=2)
    assert mask[0] == 0xFFFFFFFF and mask[1] == 1


def test_gate_actually_skips_and_counts(g_rmat):
    srcs = _sources(g_rmat, 64)
    eng = WidePackedMsBfsEngine(g_rmat, lanes=64, pull_gate=True)
    res = eng.run(srcs)
    gc = np.asarray(eng.last_gate_level_counts)
    assert gc.shape == (eng.max_levels_cap,)
    # Late levels must skip something on a power-law graph where the
    # batch converges — an all-zero counter means the gate is dead code.
    assert gc.sum() > 0
    # Ungated runs leave no counters.
    plain = WidePackedMsBfsEngine(g_rmat, lanes=64)
    plain.run(srcs)
    assert plain.last_gate_level_counts is None
    for i in (0, 63):
        np.testing.assert_array_equal(
            res.distances_int32(i), bfs_scipy(g_rmat, int(srcs[i]))
        )


def test_gated_checkpoint_relays_to_ungated_engine(g_rmat, eng_gated,
                                                   eng_plain):
    """A checkpoint advanced under the gate finishes bit-identically on an
    ungated engine (and vice versa): the gate must not leak into the
    persisted carry's observable content."""
    srcs = _sources(g_rmat, 16)
    full = eng_plain.run(srcs)
    st = eng_gated.start(srcs)
    st = eng_gated.advance(st, 2)
    st = eng_plain.advance(st)
    res = eng_plain.finish(st)
    for i in range(len(srcs)):
        np.testing.assert_array_equal(
            res.distances_int32(i), full.distances_int32(i)
        )
    # And the mirror relay: start/advance plain, finish gated.
    st = eng_plain.start(srcs)
    st = eng_plain.advance(st, 2)
    st = eng_gated.advance(st)
    res = eng_gated.finish(st)
    for i in range(len(srcs)):
        np.testing.assert_array_equal(
            res.distances_int32(i), full.distances_int32(i)
        )


def test_pull_gate_rejects_adaptive_push(g_rmat):
    with pytest.raises(ValueError, match="cannot combine"):
        WidePackedMsBfsEngine(
            g_rmat, lanes=64, pull_gate=True, adaptive_push=(64, 16)
        )
    with pytest.raises(ValueError, match="cannot combine"):
        HybridMsBfsEngine(
            g_rmat, lanes=64, num_planes=4, pull_gate=True,
            adaptive_push=(64, 16),
        )


def test_phase_bytes_gated_scales_with_active_tiles(eng_gated, eng_plain):
    """ISSUE 1 acceptance: phase_bytes models the gated path, and the
    modeled bytes strictly shrink as the active-tile count falls (while
    active rows < the largest structures)."""
    from tpu_bfs.utils.roofline import phase_bytes

    full_tiles = eng_gated._table_rows // GATE_TILE
    totals = [
        sum(phase_bytes(eng_gated, active_tiles=a).values())
        for a in (full_tiles, full_tiles // 2, 2, 1, 0)
    ]
    assert all(a > b for a, b in zip(totals, totals[1:])), totals
    # The ungated model is frontier-independent and must be unchanged by
    # the engine's flag (active_tiles=None keeps the legacy entries).
    assert phase_bytes(eng_plain) == phase_bytes(eng_plain, nz_rows=None)


def test_roofline_records_active_tiles(g_rmat, eng_gated):
    from tpu_bfs.utils.roofline import roofline_hybrid

    srcs = _sources(g_rmat, 64)
    rep = roofline_hybrid(eng_gated, srcs, peak_gbs=819.0)
    assert rep["pull_gate"] is True
    ats = [la["active_tiles"] for la in rep["levels"]]
    assert all(a is not None and a >= 0 for a in ats)
    # The batch converges, so the tail level must be gating below peak.
    assert ats[-1] < max(ats)


def test_tiled_engine_gate_and_counter(g_rmat):
    from tpu_bfs import validate
    from tpu_bfs.algorithms.bfs_tiled import TiledBfsEngine

    plain = TiledBfsEngine(g_rmat, tile_thr=4)
    gated = TiledBfsEngine(g_rmat, tile_thr=4, pull_gate=True)
    s = int(_sources(g_rmat, 1)[0])
    rp, rg = plain.run(s), gated.run(s)
    np.testing.assert_array_equal(rp.distance, rg.distance)
    validate.certify_bfs(g_rmat, s, rg.distance, rg.parent)
    assert gated.last_gate_skipped_tiles is not None
    assert plain.last_gate_skipped_tiles is None


# Slow lane: ~20s of multi-layout mesh builds; single-chip gate
# equivalence stays in tier-1 via the fuzz arm's pull-gate checks.
@pytest.mark.slow
def test_dist_hybrid_gated_bit_identical():
    """Gather (dense) and ring-sliced layouts, gated vs ungated on the
    same mesh — the sparse exchange shares the gather layout's gated code
    path exactly and is covered by the compile-only wirecheck below."""
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

    g = rmat_graph(9, 10, seed=103)
    srcs = _sources(g, 3)
    mesh = make_mesh(4)
    for exch in ("dense", "sliced"):
        plain = DistHybridMsBfsEngine(g, mesh, tile_thr=4, exchange=exch)
        gated = DistHybridMsBfsEngine(
            g, mesh, tile_thr=4, exchange=exch, pull_gate=True
        )
        rp, rg = plain.run(srcs), gated.run(srcs)
        for i in range(len(srcs)):
            np.testing.assert_array_equal(
                rp.distances_int32(i), rg.distances_int32(i)
            )
        gc = gated.last_gate_level_counts
        assert gc is not None and gc.shape == (gated.max_levels_cap,)


def test_stats_json_gains_gated_tiles(g_rmat):
    from tpu_bfs.utils.stats import level_stats

    srcs = _sources(g_rmat, 32)
    eng = WidePackedMsBfsEngine(g_rmat, lanes=64, pull_gate=True)
    res = eng.run(srcs)
    st = level_stats(
        res.distances_int32(0), g_rmat.degrees,
        gated_tiles=np.asarray(eng.last_gate_level_counts),
    )
    lines = st.json_lines()
    assert all('"gated_tiles"' in line for line in lines)
    # Ungated stats keep the legacy shape — no key churn for consumers.
    st0 = level_stats(res.distances_int32(0), g_rmat.degrees)
    assert all('"gated_tiles"' not in line for line in st0.json_lines())


@pytest.mark.slow
def test_wirecheck_gated_moves_no_extra_collective_bytes():
    """ISSUE 1 acceptance: the gated distributed program's collective
    instruction multiset equals the ungated one's, for every exchange the
    flag grows on (compile-only — no traversal runs). Slow-marked for
    the tier-1 wall clock (the PR 7 planner-proof precedent: six
    dist-hybrid compiles, ~35 s — the single heaviest test in the
    tier) — it still runs in the full `make test` / slow tier, and the
    per-exchange gated bit-identity tests above keep the gate's tier-1
    coverage."""
    from tpu_bfs.utils.wirecheck import check_gated_hybrid

    g = rmat_graph(9, 10, seed=103)
    for exch in ("dense", "sparse", "sliced"):
        r = check_gated_hybrid(g, p=4, exchange=exch)
        assert r["agree"], r
