"""The control: the plain reference put in the program's place, with one
guarantee broken, to show that the comparison deciding ``correct`` fails it.

The configurations state exact hop distances, eccentricities (``levels``)
and component sizes (``reached``). The control answers from the reference
but stops every search one level short of the source's eccentricity, the
shortcut a depth cap would take: the last level's vertices read as
unreached, ``levels`` is one less, and ``reached`` drops the last level's
vertices (batch cells keep the true ``reached`` and break the level
count, the distance rows and the eccentricities of the lanes the
comparison reads, searched when read). The serve
control answers one request at a time on the host, seconds each at the
cell's scale, so it keeps ``CONTROL_OUTSTANDING`` requests in flight
instead of the cell's count: the comparison is per answer.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10

runs the cell's whole harness with the control in the program's place,
one run per seed in this process, and prints each run's compared numbers;
every run has to come out ``correct: false``. It needs the chip only for
the harness's look for one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.dirname(
        os.path.abspath(__file__)):
    sys.path.pop(0)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402

CONTROL_OUTSTANDING = 8


def truncated(ds, source: int):
    """(distances, levels, reached) of a search that stops one level short."""
    d = ds.bfs_levels(source)
    ecc = int(d.max())
    if ecc > 0:
        d[d == ecc] = -1
    return d, max(ecc - 1, 0), int(np.count_nonzero(d >= 0))


class _LazyEcc:
    """Per-lane eccentricities of the truncated search, each searched when
    the comparison reads it."""

    def __init__(self, ds, sources):
        self.ds = ds
        self.sources = sources

    def __len__(self) -> int:
        return len(self.sources)

    def __getitem__(self, i) -> int:
        return truncated(self.ds, int(self.sources[i]))[1]

    def copy(self) -> "_LazyEcc":
        return self

    def max(self) -> int:
        raise NotImplementedError("every lane would be searched")


class _Result:
    def __init__(self, ds, keys):
        self.ds = ds
        self.sources = np.asarray(keys)
        self.reached = ds.component_size(keys)
        self.ecc = _LazyEcc(ds, self.sources)
        # The batch's level count as the truncated search of its first key
        # reports it: one search a batch, which also paces the window.
        self.num_levels = truncated(ds, int(self.sources[0]))[1]

    def distances_int32(self, i: int):
        return truncated(self.ds, int(self.sources[i]))[0]


class ControlEngine:
    """Stands in for the batch engine."""

    def __init__(self, ds, lanes: int):
        self.ds = ds
        self.lanes = lanes

    def run(self, keys):
        return _Result(self.ds, keys)


def _serve_loop(ds, stdin, stdout, stderr, counted) -> None:
    """Answers each JSONL request from the control; prints READY and a
    statsz line a second, as the server does."""
    stderr.write("# READY control\n")
    done = threading.Event()

    def statsz():
        while not done.wait(1.0):
            stderr.write("statsz " + json.dumps({
                "completed": counted[0], "routing": {},
                "padded_lanes_total": 0, "extract_ms_total": 0.0}) + "\n")

    ticker = threading.Thread(target=statsz)
    ticker.start()
    try:
        for line in stdin:
            req = json.loads(line)
            _, levels, reached = truncated(ds, int(req["source"]))
            resp = {"id": req["id"], "source": req["source"], "status": "ok",
                    "levels": levels, "reached": reached}
            stdout.write(json.dumps(resp) + "\n")
            stdout.flush()
            counted[0] += 1
    finally:
        done.set()
        ticker.join()
        stderr.write("statsz " + json.dumps({
            "completed": counted[0], "routing": {}, "padded_lanes_total": 0,
            "extract_ms_total": 0.0}) + "\n")


def prepare(driver) -> None:
    """Put the control in the program's place in ``driver``."""
    ds = driver.ds
    if hasattr(driver, "_cli_build"):
        driver._cli_build = lambda graph, keys, compiles: ControlEngine(
            ds, len(keys))
        return
    from benchmark import serve_client

    # The control answers one request at a time on the host: allow its
    # backlog to drain, and warm nothing up (it compiles nothing).
    driver.drain_s = 600.0
    driver.params = dict(driver.params, outstanding=CONTROL_OUTSTANDING)

    def start(graph) -> None:
        r_in, w_in = os.pipe()
        r_out, w_out = os.pipe()
        driver._req = os.fdopen(w_in, "w", buffering=1)
        driver._resp = r_out
        driver.stderr = serve_client._Stderr()
        driver.warm_burst = 1
        server_in, server_out = os.fdopen(r_in, "r"), os.fdopen(w_out, "w")

        def serve():
            try:
                _serve_loop(ds, server_in, server_out, driver.stderr, [0])
            finally:
                server_out.close()
                server_in.close()

        driver._server = threading.Thread(target=serve, name="control")
        driver._server.start()
        driver._reader = threading.Thread(target=driver._read,
                                          name="bench-reader")
        driver._reader.start()
        driver.stderr.ready.wait(60)

    driver._start_server = start


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    failed_as_due = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run.run(run.parse_args([
            "--workload", args.workload, "--seed", str(seed), "--seconds",
            str(args.seconds), "--trace", "0"]), prepare=prepare,
            t_start=t0)
        failed_as_due &= not res["correct"]
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "compared": res["compared"]}), flush=True)
    harness.log(f"control {'failed on every seed, as due' if failed_as_due else 'PASSED on some seed: the comparison misses it'}")
    return 0 if failed_as_due else 1


if __name__ == "__main__":
    sys.exit(main())
