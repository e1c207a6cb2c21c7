"""The dataset and the reference: the generator's stream, the graph cache,
the Graph500 numerator and the plain BFS, each against brute force."""

from __future__ import annotations

import collections
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the checkout root on the path)

from benchmark import data

GEN = {"kind": "kronecker", "scale": 9, "edgefactor": 4, "seed": 3,
       "a": 0.57, "b": 0.19, "c": 0.19}


@pytest.fixture
def ds(tmp_path):
    return data.load("tiny", GEN, str(tmp_path), log=lambda m: None)


def _brute_bfs(n, u, v, src):
    adj = collections.defaultdict(set)
    for a, b in zip(u.tolist(), v.tolist()):
        if a != b:
            adj[a].add(b)
            adj[b].add(a)
    dist = np.full(n, -1, np.int64)
    dist[src] = 0
    queue = collections.deque([src])
    while queue:
        x = queue.popleft()
        for y in adj[x]:
            if dist[y] < 0:
                dist[y] = dist[x] + 1
                queue.append(y)
    return dist


def test_generator_is_the_programs_numpy_stream():
    from tpu_bfs.graph.generate import rmat_edges

    u, v = data.kronecker_edges(9, 4, 3, a=0.57, b=0.19, c=0.19)
    pu, pv = rmat_edges(9, 4, seed=3, impl="numpy")
    assert np.array_equal(u, pu) and np.array_equal(v, pv)


def test_cached_load_equals_a_fresh_generation(tmp_path, ds):
    again = data.load("tiny", GEN, str(tmp_path), log=lambda m: None)
    fresh = data.load("tiny", GEN, str(tmp_path / "elsewhere"),
                      log=lambda m: None)
    for name in data._FILES:
        assert np.array_equal(getattr(again, name), getattr(fresh, name))
        assert np.array_equal(getattr(ds, name), getattr(fresh, name))
    cached = os.listdir(os.path.join(str(tmp_path), "graphs"))
    assert len(cached) == 1 and cached[0].startswith("tiny-")


def test_component_edge_numerator_against_brute_force(ds):
    n = ds.num_vertices
    for src in (int(ds.eligible_keys()[0]), int(ds.eligible_keys()[-1]),
                int(np.flatnonzero(np.diff(ds.indptr) == 0)[0])):
        reach = _brute_bfs(n, ds.u, ds.v, src) >= 0
        # Graph500: every input edge tuple with its ends in the component,
        # self loops and repeats included.
        want = int(np.count_nonzero(reach[ds.u] & reach[ds.v]))
        assert int(ds.component_edges([src])[0]) == want
        assert int(ds.component_size([src])[0]) == int(reach.sum())


def test_search_keys_have_an_edge_other_than_a_self_loop(ds):
    keys = set(ds.eligible_keys().tolist())
    ends = set(ds.u[ds.u != ds.v].tolist()) | set(ds.v[ds.u != ds.v].tolist())
    assert keys == ends


@pytest.mark.parametrize("pick", [0, 5, -1])
def test_reference_bfs_against_brute_force(ds, pick):
    src = int(ds.eligible_keys()[pick])
    want = _brute_bfs(ds.num_vertices, ds.u, ds.v, src)
    assert np.array_equal(ds.bfs_levels(src), want)
    cut = ds.bfs_levels(src, max_depth=2)
    assert np.array_equal(cut, np.where(want <= 2, want, -1))
