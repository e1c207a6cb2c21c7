"""Answer reductions launched at dispatch (PackedRunProtocol.launch_summaries).

The serve executor enqueues a batch's ``lane_stats`` and ``lane_ecc`` right
behind its core; the result then reads those arrays instead of computing
them at fetch. Same kernels on the same arrays: every answer is identical
to the lazy path's. The blocking ``run`` (CLI and batch path) never
launches them, and never runs ``lane_ecc`` unless ``.ecc`` is read.
"""

import numpy as np
import pytest

from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine
from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine
from tpu_bfs.graph.csr import INF_DIST
from tpu_bfs.graph.generate import rmat_graph
from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine
from tpu_bfs.reference import bfs_python


@pytest.fixture(scope="module")
def rmat9():
    return rmat_graph(9, 8, seed=11)


_ENGINES = {
    "wide": lambda g: WidePackedMsBfsEngine(g, kcap=8),
    "hybrid": lambda g: HybridMsBfsEngine(g, tile_thr=8, kcap=8),
    # The distributed wide engine's results read a chip-major view of
    # the seed table (_src_bits_view): the launched lane_ecc must too.
    "dist_wide": lambda g: DistWideMsBfsEngine(g, make_mesh(4), kcap=8),
}


def _sources(g, n=70):
    rng = np.random.default_rng(7)
    return rng.integers(0, g.num_vertices, n)


@pytest.mark.parametrize("name", sorted(_ENGINES))
def test_launched_summaries_match_lazy_path(rmat9, name):
    engine = _ENGINES[name](rmat9)
    sources = _sources(rmat9)
    lazy = engine.fetch(engine.dispatch(sources))
    pend = engine.dispatch(sources)
    engine.launch_summaries(pend)
    assert pend.stats is not None and pend.ecc is not None
    eager = engine.fetch(pend)
    np.testing.assert_array_equal(eager.reached, lazy.reached)
    np.testing.assert_array_equal(eager.edges_traversed, lazy.edges_traversed)
    np.testing.assert_array_equal(eager.ecc, lazy.ecc)
    assert eager.num_levels == lazy.num_levels
    for i in (0, 31, 32, len(sources) - 1):
        golden, _ = bfs_python(rmat9, int(sources[i]))
        np.testing.assert_array_equal(eager.distances_int32(i), golden)
        np.testing.assert_array_equal(lazy.distances_int32(i), golden)
        assert int(eager.ecc[i]) == int(golden[golden != INF_DIST].max())


def test_launch_without_ecc_leaves_it_lazy(rmat9):
    """``ecc=False`` (a batch whose queries all pull their rows) launches
    lane_stats alone; ``.ecc`` still answers, computed on first read."""
    engine = _ENGINES["wide"](rmat9)
    sources = _sources(rmat9, 40)
    pend = engine.dispatch(sources)
    engine.launch_summaries(pend, ecc=False)
    assert pend.stats is not None and pend.ecc is None
    res = engine.fetch(pend)
    np.testing.assert_array_equal(res.ecc, engine.run(sources).ecc)


@pytest.mark.parametrize("name", ["wide", "hybrid"])
def test_run_never_calls_lane_ecc_unless_read(rmat9, monkeypatch, name):
    engine = _ENGINES[name](rmat9)
    calls = []
    lane_ecc = engine._lane_ecc

    def spy(*args):
        calls.append(1)
        return lane_ecc(*args)

    monkeypatch.setattr(engine, "_lane_ecc", spy)
    res = engine.run(_sources(rmat9, 40))
    assert int(res.reached.sum()) > 0 and res.distances_int32(0) is not None
    assert calls == []
    res.ecc
    res.ecc  # cached: one kernel call
    assert calls == [1]
