"""Roofline attribution (utils/roofline.py): the phase slices must measure
the REAL level loop — same expansion specs, no perturbation of the
traversal — and the report must be structurally sound. Perf numbers are
meaningless on CPU; what CI pins is correctness of the instrument:

- stepping via engine._core_from one level at a time reproduces the same
  distances as engine.run (bit-identical planes semantics);
- the slice composition hit = residual | dense equals the fused loop's
  expansion (checked through the final visited table);
- adaptive engines attribute push levels as 'push' exactly when the fused
  loop's gate takes the push branch;
- the byte model covers every attributed phase with positive bytes.
"""

import numpy as np
import pytest

from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
from tpu_bfs.graph.generate import rmat_graph
from tpu_bfs.reference import bfs_scipy
from tpu_bfs.utils.roofline import (
    device_peaks,
    phase_bytes,
    phase_fns,
    roofline_hybrid,
)

# These run on the CPU, which has no published peak: the byte model is
# checked against the v5e figure, given explicitly.
PEAK_GBS = device_peaks("TPU v5 lite")["hbm_gbs"]


@pytest.fixture(scope="module")
def small_graph():
    return rmat_graph(10, 8, seed=5)


@pytest.fixture(scope="module")
def engine(small_graph):
    return HybridMsBfsEngine(small_graph, lanes=64, num_planes=4)


@pytest.fixture(scope="module")
def adaptive_engine(small_graph):
    return HybridMsBfsEngine(
        small_graph, lanes=64, num_planes=4, adaptive_push=(64, 32)
    )


def _sources(g, n, seed=7):
    rng = np.random.default_rng(seed)
    return rng.choice(np.flatnonzero(g.degrees > 0), size=n, replace=False)


def test_report_structure_and_level_parity(small_graph, engine):
    sources = _sources(small_graph, 64)
    res = engine.run(sources)
    report = roofline_hybrid(engine, sources, measured_gteps=1.0,
                             peak_gbs=PEAK_GBS)
    # stepping runs one body per level incl. the final empty-frontier one.
    assert report["num_levels"] in (res.num_levels, res.num_levels + 1)
    assert report["binding_term"] in report["phase_share"]
    assert abs(sum(report["phase_share"].values()) - 1.0) < 1e-9
    assert report["t_attributed_sum_s"] > 0
    assert report["hbm_bytes_total"] > 0
    assert report["t_at_peak_bw_s"] > 0
    assert report["ceiling_gteps_at_peak_bw"] > 0
    for la in report["levels"]:
        assert la["took"] == "pull"  # no adaptive push on this engine
        assert set(la["phases_s"]) >= {"residual", "state"}
        for t in la["phases_s"].values():
            assert t > 0


def test_phase_slices_compose_to_fused_expansion(small_graph, engine):
    """hit = residual | dense must equal what the fused loop expands:
    claim the slice hit against level-0 visited and compare with the
    engine's own one-level advance."""
    import jax.numpy as jnp

    sources = _sources(small_graph, 64)
    fns = phase_fns(engine)
    fw = engine._seed_dev(sources)
    h = fns["hit"](engine.arrs, fw)
    if "dense" in fns:
        h_split = fns["residual"](engine.arrs, fw) | fns["dense"](
            engine.arrs, fw
        )
        np.testing.assert_array_equal(np.asarray(h), np.asarray(h_split))
    planes = tuple(jnp.zeros_like(fw) for _ in range(engine.num_planes))
    _, vis2, _ = fns["claim"](h, fw)
    planes2 = fns["ripple"](planes, vis2)
    fw_f, vis_f, planes_f, _, _ = engine._core_from(
        engine.arrs, fw, fw, planes, jnp.int32(0), jnp.int32(1)
    )
    np.testing.assert_array_equal(np.asarray(vis2), np.asarray(vis_f))
    for a, b in zip(planes2, planes_f):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stepping_does_not_perturb_distances(small_graph, adaptive_engine):
    """End-to-end: run roofline, then compare the engine's distances on
    sampled lanes against the SciPy oracle — the instrument must leave the
    engine reusable and the traversal correct."""
    sources = _sources(small_graph, 64)
    report = roofline_hybrid(adaptive_engine, sources, peak_gbs=PEAK_GBS)
    assert report["num_levels"] >= 1
    res = adaptive_engine.run(sources)
    for i in (0, 31, 63):
        np.testing.assert_array_equal(
            res.distances_int32(i), bfs_scipy(small_graph, int(sources[i]))
        )


def test_adaptive_attribution_matches_gate(small_graph, adaptive_engine):
    """Levels labeled 'push' must be exactly the light levels the fused
    loop's gate takes: frontier rows <= row_cap and no ineligible row."""
    sources = _sources(small_graph, 64)
    report = roofline_hybrid(adaptive_engine, sources, peak_gbs=PEAK_GBS)
    row_cap = adaptive_engine.adaptive_push[0]
    saw_push = False
    for la in report["levels"]:
        if la["took"] == "push":
            saw_push = True
            assert la["frontier_rows"] <= row_cap
            assert "push" in la["phases_s"]
    # a 64-lane batch on a scale-10 graph has light first/last levels
    assert saw_push


def test_byte_model_covers_attributed_phases(small_graph, adaptive_engine):
    b = phase_bytes(adaptive_engine, nz_rows=10)
    assert b["residual"] > 0 and b["state"] > 0 and b["push"] > 0
    if adaptive_engine.hg.num_tiles:
        assert b["dense"] > 0
    # push bytes scale with the active-row count
    assert phase_bytes(adaptive_engine, nz_rows=20)["push"] > b["push"]


@pytest.mark.slow  # two extra engine builds + interpret-mode stepping
def test_pallas_tier_attribution(small_graph):
    """ISSUE 16: on a kernel-tier engine the roofline (a) steps the
    engine's ACTUAL residual slice (distances stay oracle-correct after
    instrumentation), (b) attributes modeled HBM bytes per kernel with
    a consistent level_total, and (c) reports the VMEM-resident bound;
    an XLA-tier engine reports none of it."""
    from tpu_bfs.reference import bfs_scipy
    from tpu_bfs.utils.roofline import pallas_expand_bytes

    sources = _sources(small_graph, 64)
    eng = HybridMsBfsEngine(
        small_graph, lanes=64, num_planes=4, expand_impl="pallas"
    )
    report = roofline_hybrid(eng, sources, measured_gteps=1.0,
                             peak_gbs=PEAK_GBS)
    assert report["expand_impl"] == "pallas"
    kb = report["expand_kernel_bytes"]
    assert kb["level_total"] == sum(
        v for k, v in kb.items() if k != "level_total"
    ) > 0
    assert report["expand_kernel_t_at_peak_bw_s"] > 0
    assert report["hbm_bytes_total"] > 0
    res = eng.run(sources)
    for i in (0, 63):
        np.testing.assert_array_equal(
            res.distances_int32(i), bfs_scipy(small_graph, int(sources[i]))
        )
    # The XLA tier carries no kernel attribution (and the helper is
    # explicitly empty for it — bench keys can never lie about the tier).
    xla = HybridMsBfsEngine(small_graph, lanes=64, num_planes=4)
    assert pallas_expand_bytes(xla) == {}
    assert "expand_kernel_bytes" not in roofline_hybrid(
        xla, sources, peak_gbs=PEAK_GBS)
    # Gated-out tiles cost only their output writes: the all-gated model
    # is strictly below the full one.
    full = sum(pallas_expand_bytes(eng).values())
    dark = sum(pallas_expand_bytes(eng, active_tiles=0).values())
    assert 0 < dark < full


def test_distributed_ms_exchange_entry(small_graph):
    # Distributed MS engines get a per-level WIRE-bytes 'exchange' entry
    # (the dense slab-gather ceiling), priced by the SAME
    # collectives.dense_rows_wire_bytes the engines' exchange accounting
    # uses — one formula, never two copies to drift apart.
    from tpu_bfs.parallel.collectives import dense_rows_wire_bytes
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

    eng = DistWideMsBfsEngine(small_graph, make_mesh(4), lanes=64)
    pb = phase_bytes(eng)
    assert set(pb) == {"exchange"}  # no hg: HBM phases are not re-derived
    assert pb["exchange"] == dense_rows_wire_bytes(
        eng._gather_p, eng._gather_rows_loc, eng.w
    )
    assert pb["exchange"] > 0


def test_peaks_keyed_by_device_kind():
    import jax

    v5e = device_peaks("TPU v5 lite")
    assert v5e == {"hbm_gbs": 819.0, "bf16_tflops": 197.0}
    # The CPU (and any device not in the table) has no peak: an error,
    # never a default.
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks(jax.devices()[0].device_kind)
