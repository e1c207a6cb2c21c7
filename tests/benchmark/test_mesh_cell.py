"""The four-chip cell (``g500-s22-4chip-batch``) off chip, and the
per-layer metrics of the sharded level loop (``benchmark/mesh.py``).

- The cell at a tiny size (scale 9, 4096 lanes) runs whole on 4 of the
  suite's virtual CPU devices through the CLI's ``--devices 4`` path and
  reads ``correct: true``, reporting ``batch_gteps`` and ``setup_s``.
- With the exchange between chips left out (each chip expands against
  its own frontier shard only) the same run reads ``correct: false``:
  the fault the one-chip cells of ``test_harness_faults.py`` cannot have.
- The mesh readers read ``mesh4.xplane.pb`` (``record_mesh_trace.py``), a
  TPU trace of the sharded engine on four chips, and read nothing on the
  one-chip traces, which lack the exchange's names.
"""

from __future__ import annotations

import json
import os
import re

import jax.numpy as jnp
import pytest

from bench_helpers import off_chip, run_cell, tiny_tree  # noqa: F401
from test_program_trace import SCOPED, SMALL, _ctx, _reader, trace_root  # noqa: F401

from benchmark import mesh
from benchmark import program_trace as pt

CELL = "g500-s22-4chip-batch"
HERE = os.path.dirname(os.path.abspath(__file__))
MESH4 = os.path.join(HERE, "data", "mesh4.xplane.pb")
#: The distance planes' zero fill, which XLA lifts out of the per-chip
#: body under a name of its own: the one op of the core with no phase.
PLANE_FILL = re.compile(r"^jit\(core\)/shard_map/broadcast\.\d+$")
PHASES = ("level.gather", "level.tiles", "level.claim")
READERS = ("mesh_level_ms", "mesh_exchange_ms", "mesh_exchange_roofline",
           "mesh_imbalance", "mesh_gather_ms", "mesh_tiles_ms",
           "mesh_claim_ms")


@pytest.fixture
def mesh_tree(tiny_tree):
    """The tiny tree with the four-chip cell cut to scale 9 and 4096 lanes
    (the sharded engine's narrowest width), 64 keys a batch."""
    cfg = os.path.join(tiny_tree, "benchmark", "configs",
                       "graph500-s22-4chip.json")
    with open(cfg) as f:
        doc = json.load(f)
    doc["scale"] = 9
    with open(cfg, "w") as f:
        json.dump(doc, f)
    wl = os.path.join(tiny_tree, "benchmark", "workloads", f"{CELL}.json")
    with open(wl) as f:
        doc = json.load(f)
    doc["params"] = dict(doc["params"], batch=64, cli_flags=[
        "--engine", "hybrid", "--lanes", "4096", "--planes", "5"])
    with open(wl, "w") as f:
        json.dump(doc, f)
    return tiny_tree


def test_the_mesh_cell_runs_correct_on_four_devices(mesh_tree, off_chip):
    seen = {}
    out = run_cell(off_chip, mesh_tree, CELL, seconds=0.5,
                   prepare=lambda d: seen.update(d=d))
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"batch_gteps", "setup_s"}
    assert seen["d"].counters["engine"] == "DistHybridMsBfsEngine"


class _NoExchange:
    """``jax.lax`` with the frontier's all-gather left out: each chip sees
    its own shard where the others' should be, and zeros elsewhere."""

    def __init__(self, lax, chips: int):
        self._lax = lax
        self._chips = chips

    def __getattr__(self, name):
        return getattr(self._lax, name)

    def all_gather(self, x, axis_name, **_):
        me = self._lax.axis_index(axis_name)
        return jnp.zeros((self._chips,) + x.shape, x.dtype).at[me].set(x)


def test_the_mesh_cell_without_the_exchange_is_not_correct(
        mesh_tree, off_chip, monkeypatch):
    from tpu_bfs.parallel import dist_msbfs_hybrid as dh

    monkeypatch.setattr(dh, "lax", _NoExchange(dh.lax, 4))
    out = run_cell(off_chip, mesh_tree, CELL, seconds=0.5)
    assert out["correct"] is False
    assert out["compared"]["reached_mismatch_lanes"]["value"] > 0


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("path", [SMALL, SCOPED], ids=["small", "scoped"])
def test_mesh_readers_read_nothing_on_one_chip(name, path, trace_root):
    ctx = _ctx(trace_root, path, levels_run=3)
    assert _reader(name).read(ctx) is None


@pytest.fixture
def mesh_ctx(trace_root):
    """The reader's context for the four-chip trace: its passes are the
    ``levels`` stats (level-loop passes) of its fetch spans."""
    spans = pt.decode(MESH4).in_window(mesh.FETCH_SPAN)
    return _ctx(trace_root, MESH4,
                levels_run=sum(sp.stats["levels"] for sp in spans))


def test_the_four_chip_trace_holds_each_chip_and_phase(mesh_ctx):
    chips = mesh.per_chip(mesh_ctx)
    assert len(chips) == 4
    for c in chips:
        assert {"level.exchange", "level.sync", "level.tiles",
                "level.gather", "level.claim"} <= set(c)
    # Every op of the core's own code is inside ``lanes8192``; the
    # planes' zero fill (``shard_map/broadcast.21``) is not.
    core = [op for op in pt.decode(MESH4).ops
            if op.tf_op.startswith(pt.CORE_PREFIX)]
    fills = {op.tf_op for op in core
             if PLANE_FILL.match(op.tf_op.split(":", 1)[0])}
    assert len(fills) <= 1
    named = [op for op in core if op.tf_op not in fills]
    assert named and {pt.lanes_of(op.tf_op) for op in named} == {8192}


def test_the_fetch_spans_carry_the_dense_exchange_bytes():
    """Each batch's ``exchange_bytes`` is the program's dense model
    (``collectives.dense_rows_wire_bytes``: (P - 1) / P of the whole
    [rows, 256] u32 table a chip) times its level-loop passes, with the
    rows of the recorded graph's sharded tables."""
    from record_mesh_trace import LANES, SCALE

    from tpu_bfs.graph.generate import rmat_graph
    from tpu_bfs.parallel.collectives import dense_rows_wire_bytes
    from tpu_bfs.parallel.dist_msbfs_hybrid import build_dist_hybrid

    rows = build_dist_hybrid(rmat_graph(SCALE, 16, seed=1), 4)["rows"]
    per_pass = dense_rows_wire_bytes(4, rows // 4, LANES // 32)
    assert per_pass == 3 * rows * LANES // 32  # (4 - 1) / 4 x rows x w x 4
    spans = pt.decode(MESH4).in_window(mesh.FETCH_SPAN)
    assert len(spans) == 2
    for sp in spans:
        assert sp.stats["lanes"] == LANES
        assert sp.stats["exchange_bytes"] == sp.stats["levels"] * per_pass


def test_the_mesh_readers_on_the_four_chip_trace(mesh_ctx):
    chips = mesh.per_chip(mesh_ctx)
    n = mesh_ctx.counters["levels_run"]
    level = _reader("mesh_level_ms").read(mesh_ctx)
    assert level == pytest.approx(1e3 * max(sum(c.values()) for c in chips)
                                  / n)
    exchange = _reader("mesh_exchange_ms").read(mesh_ctx)
    assert 0 < exchange < level
    share = _reader("mesh_exchange_roofline").read(mesh_ctx)
    received = mesh.window_exchange_bytes(mesh_ctx)
    assert 0 < share <= 100
    assert share == pytest.approx(
        100 * received / (1600e9 / 8) / (exchange * n / 1e3))
    work = [sum(s for k, s in c.items()
                if k not in ("level.exchange", "level.sync")) for c in chips]
    assert _reader("mesh_imbalance").read(mesh_ctx) == pytest.approx(
        100 * (max(work) / (sum(work) / 4) - 1))
    idle = _reader("device_idle_share").read(mesh_ctx)
    assert 0 <= idle < 100 and mesh_ctx.trace.devices == 4


@pytest.mark.parametrize("scope", PHASES)
def test_the_mesh_phase_readers_on_the_four_chip_trace(mesh_ctx, scope):
    """``mesh_<phase>_ms``: the mean chip's self time in the phase a pass;
    with the exchange and the termination sum they split the mean chip's
    core time, all but the planes' zero fill."""
    chips = mesh.per_chip(mesh_ctx)
    n = mesh_ctx.counters["levels_run"]
    got = _reader(f"mesh_{scope.split('.')[1]}_ms").read(mesh_ctx)
    assert got > 0
    assert got == pytest.approx(
        1e3 * sum(c[scope] for c in chips) / len(chips) / n)
    split = sum(mesh.scope_ms(mesh_ctx, k) for k in
                (*PHASES, "level.exchange", "level.sync"))
    whole = 1e3 * sum(sum(c.values()) for c in chips) / len(chips) / n
    assert split <= whole and split == pytest.approx(whole, rel=0.01)
