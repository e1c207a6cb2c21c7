"""The comparison that decides ``correct`` fails a broken timed path.

Each cell runs whole, at its tiny size, past the look for a chip, with the
program broken underneath where its batch results are produced
(``fetch_packed_batch`` for the wide and hybrid engines, the packed
engine's ``fetch``), once for each fault a one-chip cell can have:

- ``unchanged``: the level loop returns its state unchanged (every lane
  reached only its source);
- ``half``: the upper half of each batch's real lanes left out;
- ``altered``: each answer altered where it is produced (the source's
  distance and the reached count off by one);
- ``deeper``: each lane's eccentricity, and so the batch's level count,
  off by one where it is produced, its reached count and distances left
  right.

A fourth, the exchange between chips left out, needs a cell on several
chips; these cells have one.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench_helpers import off_chip, run_cell, tiny_tree  # noqa: F401

from tpu_bfs.algorithms import _packed_common as pc
from tpu_bfs.algorithms import msbfs_packed

INF = np.iinfo(np.int32).max
CELLS = ["g500-s21-batch", "g500-s21-keys64", "g500-s22-serve-nodist"]


def _real_lanes(sources) -> int:
    """Lanes holding a query: the serve path pads with the first source."""
    s = np.asarray(sources)
    differs = np.flatnonzero(s != s[0])
    return int(differs[-1]) + 1 if len(differs) else 1


def _break(fault: str, res):
    n_real = _real_lanes(res.sources)
    srcs = np.asarray(res.sources)
    if fault in ("altered", "deeper"):
        hit = range(n_real)
    elif fault == "half":
        hit = range((n_real + 1) // 2, n_real)
    else:
        hit = range(len(srcs))
    hit = set(hit)
    if not hit:
        return res
    lanes = sorted(hit)
    original = res.distances_int32
    reached = np.array(res.reached, copy=True)
    ecc = np.array(res.ecc, copy=True)
    if fault == "deeper":
        ecc[lanes] += 1
        res.num_levels += 1
    elif fault == "altered":
        reached[lanes] += 1
    else:
        reached[lanes] = 1
        ecc[lanes] = 0
    res.reached = reached
    if isinstance(res, msbfs_packed.PackedBfsResult):
        res.ecc = ecc
    else:
        res._ecc_cache = ecc

    def distances(i):
        d = np.array(original(i), copy=True)
        if i in hit and fault != "deeper":
            if fault == "altered":
                d[srcs[i]] += 1
            else:
                d[:] = INF
                d[srcs[i]] = 0
        return d

    res.distances_int32 = distances
    return res


@pytest.fixture
def broken(monkeypatch):
    def arm(fault: str):
        fetch = pc.fetch_packed_batch
        packed_fetch = msbfs_packed.PackedMsBfsEngine.fetch
        monkeypatch.setattr(pc, "fetch_packed_batch",
                            lambda *a, **k: _break(fault, fetch(*a, **k)))
        monkeypatch.setattr(
            msbfs_packed.PackedMsBfsEngine, "fetch",
            lambda self, *a, **k: _break(fault, packed_fetch(self, *a, **k)))
    return arm


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "deeper"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, tiny_tree, off_chip,
                                            broken):
    broken(fault)
    out = run_cell(off_chip, tiny_tree, cell, seconds=0.5)
    assert out["correct"] is False
    failing = {k for k, c in out["compared"].items()
               if (c["value"] < c["limit"] if c["rule"] == ">="
                   else c["value"] > c["limit"])}
    assert failing, out["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_same_runs_unbroken_are_correct(cell, tiny_tree, off_chip):
    out = run_cell(off_chip, tiny_tree, cell, seconds=0.5)
    assert out["correct"] is True, out["compared"]
