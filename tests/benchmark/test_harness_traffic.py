"""Traffic from a seed: the closed loop's outstanding count and its
window on whole bursts of answers, and the batch window closing on whole
batches."""

from __future__ import annotations

import time
import types

import numpy as np
import pytest

from bench_helpers import REPO, off_chip, run_cell, tiny_tree  # noqa: F401

from benchmark import harness


def _module(kind: str):
    return harness.load_module(f"{REPO}/benchmark/traffic/{kind}.py",
                               f"traffic_{kind}")


class _SlowEngine:
    """Stands in for an engine whose batches take ``dt`` seconds."""

    def __init__(self, dt: float, n: int):
        self.dt = dt
        self.n = n
        self.lanes = 8

    def run(self, keys):
        time.sleep(self.dt)
        return types.SimpleNamespace(
            sources=np.asarray(keys), reached=np.ones(len(keys), np.int64),
            ecc=np.full(len(keys), 3, np.int32), num_levels=3,
            distances_int32=lambda i: np.zeros(self.n, np.int32))


def _batch_driver(seed: int):
    ds = types.SimpleNamespace(
        eligible_keys=lambda: np.arange(100), deepest_key=lambda: (0, 3),
        component_edges=lambda k: np.full(len(k), 10, np.int64))
    cell = types.SimpleNamespace(params={"batch": 8})
    drv = _module("batch").Driver(cell, ds, seed)
    drv.engine = _SlowEngine(0.05, 100)
    return drv


def _null_span(name):
    import contextlib

    return contextlib.nullcontext()


def test_batch_window_closes_on_whole_batches():
    drv = _batch_driver(3)
    drv.window(0.22, _null_span)
    # 0.05 s batches: the fifth is the first to end at or after 0.22 s.
    assert len(drv.batches) == 5
    assert drv.window_s >= 0.22
    assert drv.counters["levels_run"] == 5 * 4
    # Rows are read after the window, from its last batch alone.
    assert drv.rows == [] and drv.last is not None
    last_keys = drv.batches[-1][0]
    drv.finish()
    assert drv.last is None and len(drv.rows) == 6
    assert {s for s, _ in drv.rows} <= set(last_keys.tolist())
    gteps = drv.end_to_end()["batch_gteps"]
    assert gteps == pytest.approx(5 * 8 * 10 / drv.window_s / 1e9)


def test_batch_keys_are_drawn_from_the_seed_and_distinct():
    a, b, c = _batch_driver(5), _batch_driver(5), _batch_driver(6)
    for d in (a, b, c):
        d.window(0.1, _null_span)
    keys = [np.concatenate([b[0] for b in d.batches[:2]]) for d in (a, b, c)]
    assert np.array_equal(keys[0], keys[1])
    assert not np.array_equal(keys[0], keys[2])
    for k, *_ in a.batches:
        assert len(set(k.tolist())) == len(k)


def test_closed_loop_keeps_the_outstanding_count(tiny_tree, off_chip):
    """From set-up to the window's close, requests in flight rise to
    ``outstanding`` and never above it; the window opens and closes at the
    end of a burst of answers and counts the answers between."""
    seen = {}

    def keep(driver):
        seen["d"] = driver

    out = run_cell(off_chip, tiny_tree, "g500-s22-serve-nodist", seconds=1.0,
                   prepare=keep)
    assert out["correct"] is True
    d = seen["d"]
    ids = d.traffic_ids
    events = sorted([(d.sent_t[i], 1) for i in ids]
                    + [(d.responses[i].t, -1) for i in ids])
    depth = np.cumsum([e for _, e in events])
    assert depth.max() == 64  # the tiny cell's outstanding count
    # The loop was full before the window opened.
    assert sum(1 for i in ids if d.responses[i].t <= d.t_open) >= 64
    read = np.array(sorted(r.t for r in d.responses.values()))
    for edge in (d.t_open, d.t_close):
        assert edge in read
        assert not ((read > edge) & (read < edge + d._gap)).any()
    assert d.t_close - d.t_open >= 1.0
    inside = ((read > d.t_open) & (read <= d.t_close)).sum()
    assert out["metrics"]["serve_qps"]["value"] == pytest.approx(
        inside / (d.t_close - d.t_open))
