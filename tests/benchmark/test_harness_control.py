"""The control (the reference in the program's place, stopping each search
one level short) comes out not correct in every cell, at tiny size; the
same control runs on the chip at the cells' own sizes through
``benchmark/control.py``."""

from __future__ import annotations

import pytest

from bench_helpers import off_chip, run_cell, tiny_tree  # noqa: F401

from benchmark import control


@pytest.mark.parametrize("cell", ["g500-s21-batch", "g500-s21-keys64",
                                  "g500-s22-serve-nodist"])
def test_the_control_is_not_correct(cell, tiny_tree, off_chip):
    out = run_cell(off_chip, tiny_tree, cell, seconds=0.5,
                   prepare=control.prepare)
    assert out["correct"] is False
    c = out["compared"]
    broken = sum(c.get(k, {"value": 0})["value"] for k in (
        "dist_mismatch_vertices", "ecc_mismatch_lanes", "levels_mismatch"))
    assert broken > 0
    assert c.get("missing_responses", {"value": 0})["value"] == 0


def test_the_truncated_search_drops_exactly_the_last_level(tmp_path):
    from benchmark import data

    ds = data.load("tiny", {"kind": "kronecker", "scale": 8, "edgefactor": 4,
                            "seed": 2, "a": 0.57, "b": 0.19, "c": 0.19},
                   str(tmp_path), log=lambda m: None)
    src = int(ds.eligible_keys()[0])
    full = ds.bfs_levels(src)
    d, levels, reached = control.truncated(ds, src)
    assert levels == full.max() - 1
    assert reached == int((full >= 0).sum() - (full == full.max()).sum())
    assert (d == full)[full < full.max()].all()
