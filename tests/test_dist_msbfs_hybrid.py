"""Distributed hybrid (MXU tiles + gather residual) MS-BFS on a CPU mesh.

Golden-differential per lane plus cross-engine equality with the single-chip
hybrid; the Pallas kernel runs in interpret mode on the virtual devices.
"""

import numpy as np
import pytest

from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.parallel.dist_msbfs_hybrid import (
    DistHybridMsBfsEngine,
    build_dist_hybrid,
)
from tpu_bfs.reference import bfs_python


def _check_lanes(graph, engine, sources, res=None):
    res = engine.run(np.asarray(sources)) if res is None else res
    for s_idx, src in enumerate(sources):
        golden, _ = bfs_python(graph, int(src))
        np.testing.assert_array_equal(
            res.distances_int32(s_idx), golden,
            err_msg=f"lane {s_idx} source {src}",
        )
    return res


def test_dist_hybrid_split_conserves_edges(random_small):
    hd = build_dist_hybrid(random_small, 4, tile_thr=4)
    sentinel = hd["rows"] - 1
    res_slots = sum(
        int((a != sentinel).sum())
        for k, a in hd["res_arrs"].items()
        if k.startswith(("light", "virtual"))
    )
    dense_bits = int(np.bitwise_count(hd["a_tiles_s"]).sum())
    assert hd["num_dense_edges"] + res_slots == random_small.num_edges
    assert 0 < dense_bits <= hd["num_dense_edges"]


@pytest.mark.parametrize("num_devices", [2, 4])
def test_dist_hybrid_matches_oracle(random_small, num_devices):
    engine = DistHybridMsBfsEngine(
        random_small, make_mesh(num_devices), tile_thr=2
    )
    assert engine.hd["num_tiles"] > 0
    _check_lanes(random_small, engine, [0, 1, 17, 255, 499])


def test_dist_hybrid_pure_residual(random_small):
    engine = DistHybridMsBfsEngine(
        random_small, make_mesh(4), tile_thr=10**6
    )
    assert engine.hd["num_tiles"] == 0
    _check_lanes(random_small, engine, [0, 3, 400])


def test_dist_hybrid_heavy_rows(rmat_small):
    # Threshold high enough that hub rows keep residual edges above kcap:
    # exercises the per-shard virtual-row fold alongside the dense tiles.
    engine = DistHybridMsBfsEngine(
        rmat_small, make_mesh(4), tile_thr=300, kcap=8
    )
    assert engine.hd["num_tiles"] > 0
    assert engine.hd["res_spec"].heavy
    sources = np.flatnonzero(engine.hd["in_degree"] > 0)[:40]
    _check_lanes(rmat_small, engine, sources)


def test_dist_hybrid_matches_single_chip(random_small):
    rng = np.random.default_rng(5)
    sources = rng.integers(0, random_small.num_vertices, 80)
    dist_res = DistHybridMsBfsEngine(
        random_small, make_mesh(8), tile_thr=2
    ).run(sources, time_it=True)
    single_res = HybridMsBfsEngine(random_small, tile_thr=2).run(sources)
    for i in [0, 40, 79]:
        np.testing.assert_array_equal(
            dist_res.distances_int32(i), single_res.distances_int32(i)
        )
    np.testing.assert_array_equal(dist_res.reached, single_res.reached)
    np.testing.assert_array_equal(
        dist_res.edges_traversed, single_res.edges_traversed
    )
    assert dist_res.num_levels == single_res.num_levels
    assert dist_res.teps and dist_res.teps > 0


def test_dist_hybrid_state_is_sharded(random_small):
    # The traversal state (frontier, visited, planes) must be sharded over
    # the mesh, not replicated — the reference's full-per-device allocation
    # (bfs.cu:339-351) is the anti-pattern; per-chip bytes must fall as 1/P.
    from jax.sharding import PartitionSpec

    mesh = make_mesh(8)
    engine = DistHybridMsBfsEngine(random_small, mesh, tile_thr=2)
    rows = engine.hd["rows"]
    fw0 = engine._seed_dev(np.array([0, 7]))
    assert fw0.shape == (rows, engine.w)
    assert fw0.sharding.spec == PartitionSpec("v")
    shard_rows = {s.data.shape[0] for s in fw0.addressable_shards}
    assert shard_rows == {rows // 8}

    res = engine.run(np.array([0, 7]))
    assert res._vis.sharding.spec == PartitionSpec("v")
    for pl in res._planes:
        assert pl.sharding.spec == PartitionSpec("v")
        assert {s.data.shape[0] for s in pl.addressable_shards} == {rows // 8}


def test_dist_hybrid_isolated_source(random_disconnected):
    g = random_disconnected
    iso = np.flatnonzero(g.degrees == 0)
    engine = DistHybridMsBfsEngine(g, make_mesh(2), tile_thr=2)
    assert engine.hd["num_active"] < g.num_vertices
    res = _check_lanes(g, engine, [int(iso[0]), 0])
    assert res.reached[0] == 1 and res.edges_traversed[0] == 0


def test_dist_hybrid_disconnected_and_cap(random_disconnected, line_graph):
    from tpu_bfs.algorithms.msbfs_packed import UNREACHED

    engine = DistHybridMsBfsEngine(
        random_disconnected, make_mesh(2), tile_thr=2
    )
    res = _check_lanes(random_disconnected, engine, [0, 5, 9])
    assert (res.distance_u8_lane(0) == UNREACHED).any()

    deep = DistHybridMsBfsEngine(
        line_graph, make_mesh(2), tile_thr=2, num_planes=5
    )
    with pytest.raises(RuntimeError, match="num_planes"):
        deep.run(np.array([0]))


# Slow lane: the sparse gather's byte model is HLO-proven by wirecheck
# in tier-1 and the wide engine pins the same sparse-vs-dense agreement
# (test_dist_msbfs_wide); this hybrid-engine sweep is the heavier twin.
@pytest.mark.slow
def test_sparse_frontier_gather_matches_dense(rmat_small):
    # Queue-style (rank0 row id + lane words) gather vs the dense slab:
    # identical distances, counters cover every level, fewer modeled bytes.
    srcs = np.array([1, 5, 9, 33])
    mesh = make_mesh(8)
    dense = DistHybridMsBfsEngine(rmat_small, mesh, tile_thr=4)
    sparse = DistHybridMsBfsEngine(
        rmat_small, mesh, tile_thr=4, exchange="sparse"
    )
    rd = dense.run(srcs)
    rs = sparse.run(srcs)
    for i in range(len(srcs)):
        np.testing.assert_array_equal(
            rs.distances_int32(i), rd.distances_int32(i)
        )
    assert sparse.last_exchange_level_counts[:-1].sum() >= 1
    assert sparse.last_exchange_bytes < dense.last_exchange_bytes
    assert (
        sparse.last_exchange_level_counts.sum()
        == dense.last_exchange_level_counts.sum()
    )


# Slow lane: w=256 over two exchanges is ~14s; the width machinery is
# width-agnostic by construction and w<=128 stays covered in tier-1.
@pytest.mark.slow
def test_dist_hybrid_w256_lanes_past_4096(random_small):
    # Width generalization on the sharded engine: w=256 (8192 lanes)
    # through dense tiles + residual + the ring exchange on a 4-device
    # mesh, lanes seeded past word column 128 validated against the
    # oracle. Also covers the sliced (O(A/P)-transient) layout: its
    # rotating accumulator is [rows_loc, w] — width-agnostic by
    # construction, but only a run proves it.
    rng = np.random.default_rng(9)
    sources = rng.integers(0, random_small.num_vertices, size=8192)
    picks = [0, 4095, 4096, 8191]
    for exchange in ("dense", "sliced"):
        engine = DistHybridMsBfsEngine(
            random_small, make_mesh(4), tile_thr=2, lanes=8192,
            exchange=exchange,
        )
        assert engine.w == 256
        res = engine.run(sources)
        for i in picks:
            golden, _ = bfs_python(random_small, int(sources[i]))
            np.testing.assert_array_equal(
                res.distances_int32(i), golden,
                err_msg=f"{exchange} lane {i}",
            )
    with pytest.raises(ValueError):
        DistHybridMsBfsEngine(random_small, make_mesh(4), lanes=6144)


def test_cli_mesh4_hybrid_at_8192_lanes_matches_the_reference():
    """The four-chip deployment's normal path at its width: the CLI's
    ``--multi-source --devices 4 --engine hybrid --lanes 8192 --planes 5``
    on a small Kronecker graph, 4 of the suite's virtual devices. Every
    lane's reached count and the batch's level count against the
    reference, a seeded sample of distance rows (lanes past word column
    128 among them), and the same batch bit for bit on the single-chip
    hybrid at the same width."""
    from tpu_bfs import cli
    from tpu_bfs.reference import bfs_scipy

    rng = np.random.default_rng(26)
    got = {}

    def keep(graph, engine, res):
        got.update(g=graph, engine=engine, res=res)

    spec = "rmat:scale=9,ef=16,seed=1"
    g = cli.load_graph(spec)
    sources = rng.integers(0, g.num_vertices, size=8192)
    argv = [str(sources[0]), spec, "--multi-source",
            ",".join(str(int(s)) for s in sources[1:]), "--skip-cpu",
            "--devices", "4", "--engine", "hybrid", "--lanes", "8192",
            "--planes", "5"]
    assert cli.main(argv, on_result=keep) == 0
    engine, res = got["engine"], got["res"]
    assert isinstance(engine, DistHybridMsBfsEngine)
    assert engine.mesh.devices.size == 4 and engine.w == 256
    assert engine.hd["num_tiles"] > 0  # the MXU tile pass runs
    golden = {int(s): bfs_scipy(got["g"], int(s)) for s in set(sources)}
    reached = {s: int(np.count_nonzero(d < g.num_vertices))
               for s, d in golden.items()}
    np.testing.assert_array_equal(
        res.reached, [reached[int(s)] for s in sources])
    ecc = max(int(d[d < g.num_vertices].max()) for d in golden.values())
    assert res.num_levels == ecc
    lanes = sorted({0, 4095, 4096, 8191,
                    *rng.choice(8192, size=12, replace=False).tolist()})
    assert any(i >= 4096 for i in lanes)
    for i in lanes:
        np.testing.assert_array_equal(
            res.distances_int32(i), golden[int(sources[i])],
            err_msg=f"lane {i}")
    one = HybridMsBfsEngine(got["g"], lanes=8192, num_planes=5).run(sources)
    assert one.num_levels == res.num_levels
    np.testing.assert_array_equal(one.reached, res.reached)
    np.testing.assert_array_equal(one.edges_traversed, res.edges_traversed)
    for i in lanes:
        np.testing.assert_array_equal(one.distances_int32(i),
                                      res.distances_int32(i))


@pytest.mark.parametrize("exchange", ["dense", "sparse"])
def test_fetch_span_exchange_bytes_match_the_engine_accounting(
        random_small, exchange, tmp_path):
    """The ``tpu_bfs.engine.fetch`` span's ``exchange_bytes``, computed on
    the host from static shapes and the batch's level-loop passes, is the
    engine's own deferred accounting (``last_exchange_bytes``: bytes each
    chip receives) for the dense exchange; the sparse exchange, whose
    bytes follow the branch each level took on the device, leaves it out,
    as does a single-chip engine."""
    import glob

    import jax
    from jax.profiler import ProfileData

    engines = [DistHybridMsBfsEngine(random_small, make_mesh(4),
                                     tile_thr=2, exchange=exchange),
               HybridMsBfsEngine(random_small, tile_thr=2)]
    sources = np.arange(64) * 7 % random_small.num_vertices
    for e in engines:
        e.run(sources)  # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for e in engines:
            with jax.profiler.TraceAnnotation(type(e).__name__):
                e.run(sources)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "tpu_bfs.engine.fetch"]
    assert len(spans) == 2
    dist, single = sorted(spans, key=lambda st: "exchange_bytes" in st)[::-1]
    assert "exchange_bytes" not in single
    if exchange == "sparse":
        assert "exchange_bytes" not in dist
        return
    mesh = engines[0]
    assert dist["levels"] >= 2
    assert dist["exchange_bytes"] == mesh.last_exchange_bytes > 0
    # (P - 1) / P of the whole [rows, w] u32 table a chip, each pass.
    rows = mesh.hd["rows"]
    assert dist["exchange_bytes"] == dist["levels"] * 3 * rows // 4 * 4 * 128
