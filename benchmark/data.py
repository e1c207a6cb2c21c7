"""The dataset of a configuration and the plain reference over it.

The graph is fixed by the configuration (generator, scale, edge factor,
graph seed); ``--seed`` only draws traffic. The generator is this
module's own copy of the Graph500 Kronecker generator in its NumPy form
(A=.57, B=C=.19, D=.05, then a random vertex permutation), so the
dataset does not move when the program's generator does.

Everything derived from the edge list that the benchmark itself needs
is cached once per configuration (under ``<checkout>/benchmark/.cache``
when run by ``benchmark/run.py``):

- the input edge tuples (the dataset: the program's ingestion path,
  ``from_edges``, is run on them in every run, as part of set-up);
- the reference's own symmetric CSR (self loops dropped), built by SciPy
  from the edge tuples and not by the program;
- connected components, their sizes, and the Graph500 edge count of
  each component (input edge tuples with both ends in it, self loops
  and repeats included);
- on first use, a key at the graph's diameter and the diameter itself
  (``deepest_key``).

The reference is a plain breadth-first search by SciPy over that CSR.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

_FILES = ("u", "v", "indptr", "indices", "comp", "comp_size", "comp_edges")


def kronecker_edges(scale: int, edgefactor: int, seed: int, *, a: float,
                    b: float, c: float) -> tuple[np.ndarray, np.ndarray]:
    """``edgefactor << scale`` Kronecker edge tuples, then the vertex
    permutation the Graph500 specification asks for. The same stream as
    the NumPy path of the program's ``rmat_edges`` for the same seed."""
    m = edgefactor << scale
    rng = np.random.default_rng(seed)
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)
    for _ in range(scale):
        u <<= 1
        v <<= 1
        r_u = rng.random(m)
        r_v = rng.random(m)
        u_bit = r_u > ab
        v_bit = np.where(u_bit, r_v > c_norm, r_v > a_norm)
        u |= u_bit
        v |= v_bit
    perm = rng.permutation(1 << scale)
    return perm[u], perm[v]


class Dataset:
    """The edge tuples of one configuration and the reference's tables."""

    def __init__(self, arrays: dict, num_vertices: int, cache_dir=None):
        self.num_vertices = num_vertices
        self.cache_dir = cache_dir
        self.u = arrays["u"]
        self.v = arrays["v"]
        self.indptr = arrays["indptr"]
        self.indices = arrays["indices"]
        self.comp = arrays["comp"]
        self.comp_size = arrays["comp_size"]
        self.comp_edges = arrays["comp_edges"]
        self._csr = None

    @property
    def num_input_edges(self) -> int:
        return len(self.u)

    def eligible_keys(self) -> np.ndarray:
        """Vertices with at least one edge other than a self loop: the
        Graph500 rule for search keys."""
        return np.flatnonzero(np.diff(self.indptr) > 0)

    def component_edges(self, sources) -> np.ndarray:
        """Graph500 numerator per source: input edge tuples in its component."""
        return self.comp_edges[self.comp[np.asarray(sources, dtype=np.int64)]]

    def component_size(self, sources) -> np.ndarray:
        return self.comp_size[self.comp[np.asarray(sources, dtype=np.int64)]]

    def csr(self):
        if self._csr is None:
            from scipy.sparse import csr_matrix

            n = self.num_vertices
            data = np.ones(len(self.indices), dtype=np.int8)
            self._csr = csr_matrix((data, self.indices, self.indptr),
                                   shape=(n, n))
        return self._csr

    def bfs_levels(self, source: int, *, max_depth: int | None = None
                   ) -> np.ndarray:
        """[V] int32 hop distances from ``source``, -1 where unreached.
        ``max_depth`` stops the search after that many levels (the
        control's broken guarantee); None searches to the end."""
        from scipy.sparse.csgraph import breadth_first_order

        order, pred = breadth_first_order(
            self.csr(), int(source), directed=True, return_predecessors=True)
        dist = np.full(self.num_vertices, -1, dtype=np.int32)
        dist[source] = 0
        rest = order[1:]
        via = pred[rest]
        # BFS order lists each vertex after its predecessor, so one pass
        # per level settles the next level.
        level = 0
        while True:
            d = dist[via]
            todo = (d == level) & (dist[rest] < 0)
            if not todo.any() or (max_depth is not None and level >= max_depth):
                break
            dist[rest[todo]] = level + 1
            level += 1
        return dist


    def deepest_key(self) -> tuple[int, int]:
        """(key, diameter): a vertex whose eccentricity is the graph's
        largest, found by the reference's BFS and cached.

        BFS from the vertex of largest degree (the hub) gives each vertex's
        depth ``h``; the vertices at the largest depth ``H`` are searched,
        then those at ``H - 1`` and so on down to a depth ``t``. Any two
        vertices lie at most ``h(u) + h(v)`` apart, so a pair with no
        searched end lies at most ``2 (t - 1)`` apart: once the largest
        eccentricity found reaches that, it is the diameter (of the hub's
        component, which holds the graph's longest paths)."""
        path = (os.path.join(self.cache_dir, "deepest.json")
                if self.cache_dir else None)
        if path and os.path.exists(path):
            with open(path) as f:
                got = json.load(f)
            return got["key"], got["diameter"]
        hub = int(np.argmax(np.diff(self.indptr)))
        h = self.bfs_levels(hub)
        ecc = {}
        depth = int(h.max())
        while True:
            for v in np.flatnonzero(h == depth):
                ecc[int(v)] = int(self.bfs_levels(int(v)).max())
            key = max(ecc, key=lambda v: (ecc[v], -v))
            if ecc[key] >= 2 * (depth - 1):
                break
            depth -= 1  # not pinned down yet: search the next depth too
        if path:
            with open(path + ".tmp", "w") as f:
                json.dump({"key": key, "diameter": ecc[key]}, f)
            os.replace(path + ".tmp", path)
        return key, ecc[key]


def generator_of(config: dict) -> dict:
    """The generator's parameters: the configuration's ``generator`` group
    with its top-level ``scale``."""
    return dict(config["generator"], scale=int(config["scale"]))


def _cache_dir(cache_root: str, name: str, gen: dict) -> str:
    key = hashlib.sha256(json.dumps(gen, sort_keys=True).encode()).hexdigest()
    return os.path.join(cache_root, "graphs", f"{name}-{key[:12]}")


def _build(gen: dict, log) -> tuple[dict, int]:
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    if gen["kind"] != "kronecker":
        raise ValueError(f"unknown generator {gen['kind']!r}")
    n = 1 << gen["scale"]
    t0 = time.perf_counter()
    u, v = kronecker_edges(gen["scale"], gen["edgefactor"], gen["seed"],
                           a=gen["a"], b=gen["b"], c=gen["c"])
    log(f"generated {len(u)} edge tuples in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    keep = u != v
    ends = (np.concatenate([u[keep], v[keep]]),
            np.concatenate([v[keep], u[keep]]))
    sym = coo_matrix((np.ones(len(ends[0]), np.int8), ends),
                     shape=(n, n)).tocsr()
    sym.sum_duplicates()
    indptr = sym.indptr.astype(np.int64)
    indices = sym.indices.astype(np.int32)
    _, comp = connected_components(sym, directed=False)
    comp = comp.astype(np.int32)
    comp_size = np.bincount(comp, minlength=comp.max() + 1).astype(np.int64)
    comp_edges = np.bincount(comp[u], minlength=len(comp_size)).astype(np.int64)
    log(f"reference CSR and {len(comp_size)} components in "
        f"{time.perf_counter() - t0:.3f} s")
    arrays = dict(u=u.astype(np.int32), v=v.astype(np.int32), indptr=indptr,
                  indices=indices, comp=comp, comp_size=comp_size,
                  comp_edges=comp_edges)
    return arrays, n


def load(name: str, gen: dict, cache_root: str, log=print) -> Dataset:
    """The dataset of configuration ``name``: from the cache under
    ``cache_root`` when it holds a complete copy, else generated and
    cached there."""
    d = _cache_dir(cache_root, name, gen)
    meta = os.path.join(d, "meta.json")
    if os.path.exists(meta):
        with open(meta) as f:
            n = json.load(f)["num_vertices"]
        arrays = {k: np.load(os.path.join(d, f"{k}.npy")) for k in _FILES}
        return Dataset(arrays, n, d)
    arrays, n = _build(gen, log)
    os.makedirs(d, exist_ok=True)
    for k in _FILES:
        tmp = os.path.join(d, f"{k}.tmp.npy")
        np.save(tmp, arrays[k])
        os.replace(tmp, os.path.join(d, f"{k}.npy"))
    with open(meta + ".tmp", "w") as f:
        json.dump({"num_vertices": n, "generator": gen}, f)
    os.replace(meta + ".tmp", meta)
    return Dataset(arrays, n, d)
