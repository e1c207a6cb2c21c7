"""The benchmark's peaks table and its ``tile_spmm`` model."""

from __future__ import annotations

import pytest

import bench_helpers  # noqa: F401  (puts the checkout root on the path)

from benchmark import roofline


def test_v5e_peaks_and_their_source():
    p = roofline.device_peaks("TPU v5 lite")
    assert p == {"hbm_gbs": 819.0, "bf16_tflops": 197.0, "int8_tops": 393.0}
    assert "TPU v5e" in roofline.PEAKS_SOURCE


def test_a_device_not_in_the_table_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.device_peaks("cpu")


@pytest.mark.parametrize("tile_thr", [2, 4])
def test_tile_spmm_bytes_equal_the_programs_dense_term(tile_thr):
    """Today's program prices one dense pass with the same model."""
    from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
    from tpu_bfs.graph.generate import rmat_graph
    from tpu_bfs.utils.roofline import phase_bytes

    eng = HybridMsBfsEngine(rmat_graph(10, 8, seed=5), lanes=64,
                            num_planes=4, tile_thr=tile_thr)
    hg = eng.hg
    assert hg.num_tiles > 0
    shape = {"num_tiles": int(hg.num_tiles), "num_row_tiles": int(hg.vt),
             "w": int(eng.w), "a_tile_bytes": int(hg.a_tiles.nbytes)}
    assert roofline.tile_spmm_bytes(**shape) == phase_bytes(eng)["dense"]


def test_tile_spmm_least_time_names_its_bound():
    shape = {"num_tiles": 1000, "num_row_tiles": 16000, "w": 256,
             "a_tile_bytes": 1000 * 4 * 128 * 4}
    t, bound = roofline.tile_spmm_least_s(shape, "TPU v5 lite")
    by_bytes = roofline.tile_spmm_bytes(**shape) / 819e9
    by_ops = roofline.tile_spmm_ops(**shape) / 393e12
    assert t == max(by_bytes, by_ops)
    assert bound == ("hbm" if by_bytes >= by_ops else "mxu_int8")
    assert roofline.tile_spmm_ops(**shape) == 2 * 1000 * 128 * 128 * 32 * 256
