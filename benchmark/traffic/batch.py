"""Batch traffic: whole multi-source batches back to back.

Parameters (``params`` of the cell file):

- ``batch``: search keys per batch, distinct, drawn from the seed among
  vertices with an edge (the Graph500 rule);
- ``cli_flags``: flags the cell adds to the configuration's for the
  CLI's ``--multi-source`` engine selection;
- ``pin_deepest_key``: whether every batch holds a key at the graph's
  diameter (``Dataset.deepest_key``) in place of its first drawn key, so
  that every batch runs the diameter's levels whatever the seed (default
  false: with many keys a batch nearly always reaches the common depth);
- ``lane_ecc``: whether every batch's per-lane eccentricities are read in
  the window and a sample of them compared (default false: at 8192 lanes
  the program's on-device reduction does not fit beside a batch's state);
- ``rate_metric``: the end-to-end metric the rate is reported as
  (default ``batch_gteps``), so that cells whose rates spread differently
  keep bounds of their own.

A batch's time follows its level count: with few keys, which seeds
happen to draw a deep key would set the spread, hence the pin.

The engine is the one the CLI's ``--multi-source`` path builds: the
driver calls ``tpu_bfs.cli.main`` with the cell's flags on a one-level
run (which builds and compiles it) and keeps the engine it hands back.
The window then drives ``engine.run(keys)`` for whole batches; the first
batch to end at or after ``--seconds`` closes it. The rate is the
Graph500 edge count of every key's component, summed over the window's
batches, over the window's wall time. Each batch's per-lane ``reached``
counts, its level count and (with ``lane_ecc``) its eccentricities come
back with it, inside the window. The distance rows of ``ROW_LANES``
lanes drawn from the seed come from the last batch only, whose result
outlives the window (one batch's device state is all the chip holds at
the flagship width), read once the window has closed.
"""

from __future__ import annotations

import contextlib
import gc
import io
import time

import numpy as np

from benchmark.harness import Compared, draw_keys, log, program_graph

#: Offset of the warm-up keys' stream: the same keys in every run.
WARM_SEED = 0x5EED
#: Lanes of every batch whose eccentricity is compared with the
#: reference's (with ``lane_ecc``), and lanes of the last batch whose
#: distance rows are: each costs one reference BFS after the window (about
#: a second at scale 21).
ECC_LANES = 2
ROW_LANES = 6


class Driver:
    def __init__(self, cell, dataset, seed: int):
        self.cell = cell
        self.ds = dataset
        self.seed = seed
        self.params = cell.params
        self.batch = int(self.params["batch"])
        self.eligible = dataset.eligible_keys()
        self.pin, self.diameter = dataset.deepest_key()
        if not self.params.get("pin_deepest_key", False):
            self.pin = None
        self.lane_ecc = bool(self.params.get("lane_ecc", False))
        self.counters: dict = {}
        self.batches: list = []  # (keys, reached, levels, ecc or None)
        self.rows: list = []  # (source, distance row) of the last batch
        self.last = None  # the window's last result, until its rows are read
        self.window_s = None
        self.engine = None

    def _draw(self, rng):
        keys = draw_keys(rng, self.eligible, self.batch)
        if self.pin is not None and self.pin not in keys:
            keys[0] = self.pin
        return keys

    # --- set-up -------------------------------------------------------
    def _cli_build(self, graph, keys, compiles):
        """Build the engine through ``tpu_bfs.cli.main`` on a one-level
        run of ``keys``; returns the engine the CLI used."""
        from tpu_bfs import cli

        got = {}
        build_s = []
        make = cli._make_ms_engine

        def timed_make(*a, **k):
            c0, t0 = compiles.seconds, time.perf_counter()
            eng = make(*a, **k)
            build_s.append(time.perf_counter() - t0 - (compiles.seconds - c0))
            return eng

        argv = [str(keys[0]), self.cell.config_name, "--multi-source",
                ",".join(str(int(s)) for s in keys[1:]), "--skip-cpu",
                "--max-levels", "1", *self.cell.config.get("cli_flags", []),
                *self.params.get("cli_flags", [])]
        load = cli.load_graph
        cli.load_graph = lambda spec: graph
        cli._make_ms_engine = timed_make
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                rc = cli.main(argv, on_result=lambda g, e, r: got.update(e=e))
        finally:
            cli.load_graph = load
            cli._make_ms_engine = make
        if rc != 0 or "e" not in got:
            raise RuntimeError(f"tpu_bfs.cli.main returned {rc}: "
                               f"{out.getvalue()[-2000:]}")
        self.counters["engine_build_s"] = sum(build_s)
        return got["e"]

    def setup(self, phases) -> None:
        with phases.phase("graph_ingest"):
            graph = program_graph(self.ds, self.cell.config["ingest"])
        warm = self._draw(np.random.default_rng(WARM_SEED))
        with phases.phase("engine_build_and_compile"):
            self.engine = self._cli_build(graph, warm, phases.compiles)
        self.counters["compile_s"] = phases.compiles.seconds
        self.counters["engine"] = type(self.engine).__name__
        self.counters["lanes"] = int(self.engine.lanes)
        hg = getattr(self.engine, "hg", None)
        if hg is not None and hg.num_tiles:
            self.counters["tile_spmm_shape"] = {
                "num_tiles": int(hg.num_tiles),
                "num_row_tiles": int(hg.vt),
                "w": int(self.engine.w),
                "a_tile_bytes": int(hg.a_tiles.nbytes),
            }
        with phases.phase("warm_up"):
            self.last = self.engine.run(warm)
            self.finish()
            self.rows.clear()
        log(f"[setup] engine {self.counters['engine']}, "
            f"{self.counters['lanes']} lanes")

    # --- the window ---------------------------------------------------
    def window(self, seconds: float, annotate) -> None:
        rng = np.random.default_rng(self.seed)
        t0 = time.perf_counter()
        while True:
            keys = self._draw(rng)
            self.last = None  # one batch's device state at a time
            with annotate("bench.batch"):
                self.last = self.engine.run(keys)
            self.batches.append((
                keys, np.asarray(self.last.reached).copy(),
                int(self.last.num_levels),
                self.last.ecc.copy() if self.lane_ecc else None))
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        # One level-loop pass per BFS level plus the pass that finds the
        # frontier empty.
        self.counters["levels_run"] = sum(b[2] + 1 for b in self.batches)
        self.counters["batches"] = len(self.batches)

    def finish(self) -> None:
        """Read the sampled distance rows of the last batch, then let its
        device state go."""
        res, self.last = self.last, None
        rng = np.random.default_rng([self.seed, 1])
        n = min(ROW_LANES, len(res.sources))
        for i in sorted(rng.choice(len(res.sources), size=n, replace=False)):
            row = np.asarray(res.distances_int32(int(i)))
            self.rows.append((int(res.sources[i]), row))

    def report(self) -> None:
        n = sum(len(b[0]) for b in self.batches)
        levels = [b[2] for b in self.batches]
        log(f"[window] {len(self.batches)} batches, {n} keys, "
            f"{self.window_s:.3f} s, levels per batch {min(levels)} to "
            f"{max(levels)}; {len(self.rows)} distance rows read after it")

    def attempted_failed(self) -> tuple[int, int]:
        return sum(len(b[0]) for b in self.batches), 0

    def end_to_end(self) -> dict:
        edges = sum(int(self.ds.component_edges(b[0]).sum())
                    for b in self.batches)
        name = self.params.get("rate_metric", "batch_gteps")
        return {name: edges / self.window_s / 1e9}

    def release(self) -> None:
        self.engine = self.last = None
        gc.collect()

    # --- correct ------------------------------------------------------
    def compare(self) -> list:
        """Every lane's reached count against its component's size; every
        batch's level count against the diameter (equal to it with the
        pinned key, at most it without); with ``lane_ecc``, the
        eccentricity of ``ECC_LANES`` lanes of every batch drawn from the
        seed and of the pinned key; and the rows read after the window;
        all against the reference's BFS."""
        levels = {}

        def ref(src):
            if src not in levels:
                levels[src] = self.ds.bfs_levels(src)
            return levels[src]

        bad_reached = bad_levels = bad_ecc = checked_ecc = 0
        for j, (keys, reached, lv, ecc) in enumerate(self.batches):
            bad_reached += int(np.count_nonzero(
                reached != self.ds.component_size(keys)))
            bad_levels += int(lv != self.diameter if self.pin is not None
                              else lv > self.diameter)
            if ecc is None:
                continue
            rng = np.random.default_rng([self.seed, 2, j])
            for i in rng.choice(len(keys), size=min(ECC_LANES, len(keys)),
                                replace=False):
                bad_ecc += int(ecc[i] != ref(int(keys[i])).max())
                checked_ecc += 1
            if self.pin is not None:
                bad_ecc += int(ecc[int(np.flatnonzero(keys == self.pin)[0])]
                               != self.diameter)
                checked_ecc += 1
        n = self.ds.num_vertices
        bad_dist = 0
        for src, row in self.rows:
            got = np.where((row < 0) | (row > n), -1, row)
            bad_dist += int(np.count_nonzero(got != ref(src)))
        out = [
            Compared("reached_mismatch_lanes", bad_reached, 0),
            Compared("levels_mismatch_batches", bad_levels, 0),
            Compared("dist_mismatch_vertices", bad_dist, 0),
            Compared("rows_compared", len(self.rows), 1, at_least=True),
        ]
        if self.lane_ecc:
            out[2:2] = [Compared("ecc_mismatch_lanes", bad_ecc, 0),
                        Compared("ecc_lanes_compared", checked_ecc,
                                 len(self.batches), at_least=True)]
        return out
