"""The JSONL query server driven in-process over OS pipes.

``ServeDriver`` starts ``tpu_bfs.serve.frontend.run_server`` (the loop
behind ``tpu-bfs-serve``) on a thread of its own, with the configuration's
flags parsed by the server's own argument parser and the dataset's graph
registered under the configuration's graph key, so the server never
generates it. Requests go down one pipe and responses come back up
another; the client reads what the server has written so far in one go
and stamps each response line with the time of that read.

Every request asks for ``levels`` and ``reached`` without the distance
row (``"want_distances": false``). The traffic kinds subclass the driver
and supply the traffic. Set-up: the graph ingestion, the server's start
(engine builds, ladder warm-up, compile), then a burst as wide as the
server's widest rung and ``WARM_SINGLES`` requests one at a time, so
that every rung of the ladder has served the cell's own requests before
the window. The lone requests' latency is a traversal's time
(``self.lone_s``, the shortest of them).
"""

from __future__ import annotations

import gc
import json
import os
import re
import select
import threading
import time

import numpy as np

from benchmark.harness import Compared, log, program_graph

_ID = re.compile(rb'"id": (\d+)')
_STATUS = re.compile(rb'"status": "(\w+)"')
_LEVELS = re.compile(rb'"levels": (\d+)')
_REACHED = re.compile(rb'"reached": (\d+)')
WARM_SEED = 0x5EED
WARM_SINGLES = 2
DRAIN_S = 60.0  # how long past the window's close a response may come
#: Answered requests whose ``levels`` is compared with the reference's,
#: besides the one with the most: one reference BFS each after the window.
VERIFY_SAMPLES = 6


class _Stderr:
    """The server's stderr: READY and statsz lines are caught, other log
    lines are echoed to ours."""

    def __init__(self):
        self.ready = threading.Event()
        self.statsz: list = []
        self.cond = threading.Condition()
        self._buf = ""

    def write(self, text: str) -> int:
        with self.cond:  # the server writes from several threads
            self._buf += text
            while "\n" in self._buf:
                line, self._buf = self._buf.split("\n", 1)
                if line.startswith("statsz "):
                    self.statsz.append(json.loads(line[len("statsz "):]))
                    self.cond.notify_all()
                    continue
                if "READY" in line:
                    self.ready.set()
                log(f"  server: {line}")
        return len(text)

    def flush(self) -> None:
        pass

    def latest(self, timeout: float = 30.0) -> dict:
        """The newest statsz line (waiting for the first one)."""
        with self.cond:
            if not self.cond.wait_for(lambda: self.statsz, timeout):
                raise TimeoutError(f"no statsz line in {timeout} s")
            return self.statsz[-1]


class Response:
    __slots__ = ("id", "t", "status", "levels", "reached")

    def __init__(self, qid: int, t: float, line: bytes):
        self.id = qid
        self.t = t
        self.status = _STATUS.search(line).group(1).decode()
        lv, rc = _LEVELS.search(line), _REACHED.search(line)
        self.levels = int(lv.group(1)) if lv else None
        self.reached = int(rc.group(1)) if rc else None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class ServeDriver:
    def __init__(self, cell, dataset, seed: int):
        self.cell = cell
        self.ds = dataset
        self.seed = seed
        self.params = cell.params
        self.eligible = dataset.eligible_keys()
        self.counters: dict = {}
        self.sources: dict = {}  # id -> source, every request sent
        self.sent_t: dict = {}  # id -> send time (perf_counter)
        self.responses: dict = {}  # id -> Response
        self.last_t = None  # when the latest response was read
        self._send_lock = threading.Lock()
        self._arrived = threading.Condition(threading.Lock())
        self._next_id = 0
        self.first_id = 0  # the first request of the cell's traffic
        self.window_s = None
        self.drain_s = DRAIN_S

    # --- the server -----------------------------------------------------
    def _start_server(self, graph) -> None:
        from tpu_bfs.serve.frontend import build_arg_parser, run_server
        from tpu_bfs.serve.registry import EngineRegistry

        cfg = self.cell.config
        args = build_arg_parser().parse_args(
            [cfg["graph_key"], *cfg["server_flags"]])
        registry = EngineRegistry()
        registry.add_graph(cfg["graph_key"], graph)
        r_in, w_in = os.pipe()
        r_out, w_out = os.pipe()
        self._req = os.fdopen(w_in, "w", buffering=1)
        server_in = os.fdopen(r_in, "r")
        server_out = os.fdopen(w_out, "w")
        self._resp = r_out
        self.stderr = _Stderr()
        self.rc = None
        self.warm_burst = int(args.lanes)

        def serve():
            try:
                self.rc = run_server(args, stdin=server_in, stdout=server_out,
                                     stderr=self.stderr, registry=registry)
            finally:
                server_out.close()
                server_in.close()

        self._server = threading.Thread(target=serve, name="bench-server")
        self._server.start()
        self._reader = threading.Thread(target=self._read, name="bench-reader")
        self._reader.start()
        while not self.stderr.ready.wait(1.0):
            if not self._server.is_alive():
                raise RuntimeError(f"server exited with {self.rc} before READY")

    def _read(self) -> None:
        buf = b""
        while True:
            chunk = os.read(self._resp, 1 << 20)
            if not chunk:
                return
            t = time.perf_counter()
            *lines, buf = (buf + chunk).split(b"\n")
            for line in lines:
                m = _ID.search(line)
                if m is None:
                    log(f"[client] response without an id: {line[:200]!r}")
                    continue
                r = Response(int(m.group(1)), t, line)
                with self._arrived:
                    self.responses[r.id] = r
                    self.last_t = t
                    self._arrived.notify_all()
                self._on_response(r)
            if not select.select([self._resp], [], [], 0)[0]:
                self._on_caught_up()

    def _on_response(self, r: Response) -> None:
        """Hook for closed-loop traffic: a response was read."""

    def _on_caught_up(self) -> None:
        """Hook for closed-loop traffic: every response the server has
        written so far has been read."""

    def send(self, source: int) -> int:
        return self.send_many([source])[0]

    def send_many(self, sources) -> list:
        """One request per source, written to the server in one write."""
        with self._send_lock:
            ids = list(range(self._next_id, self._next_id + len(sources)))
            self._next_id += len(sources)
            t = time.perf_counter()
            lines = []
            for qid, src in zip(ids, sources):
                self.sources[qid] = int(src)
                self.sent_t[qid] = t
                lines.append(json.dumps({"id": qid, "source": int(src),
                                         "want_distances": False}) + "\n")
            self._req.write("".join(lines))
            self._req.flush()
        return ids

    def wait_for(self, ids, timeout: float) -> bool:
        end = time.monotonic() + timeout
        with self._arrived:
            pending = [i for i in ids if i not in self.responses]
            while pending:
                pending = [i for i in pending if i not in self.responses]
                if not pending:
                    break
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self._arrived.wait(min(left, 1.0))
        return True

    def quiet_after(self, after: float, gap: float, timeout: float) -> float:
        """The read time of the last response of the first burst that ends
        at or after ``after``: a response read at or after ``after``
        followed by ``gap`` seconds with none."""
        end = time.monotonic() + timeout
        with self._arrived:
            while True:
                now = time.perf_counter()
                last = self.last_t
                if last is not None and last >= after:
                    if now - last >= gap:
                        return last
                    wait = gap - (now - last)
                else:
                    wait = max(after - now, 0.0) + gap
                if time.monotonic() > end:
                    raise TimeoutError(f"no burst of responses ended in "
                                       f"{timeout} s")
                self._arrived.wait(wait)

    def close(self) -> None:
        """EOF to the server; it drains, prints its final statsz, exits."""
        self._req.close()
        self._server.join(timeout=self.drain_s + 30)
        self._reader.join(timeout=30)
        os.close(self._resp)
        if self._server.is_alive() or self._reader.is_alive():
            raise RuntimeError("the server did not exit after EOF")

    # --- set-up -----------------------------------------------------------
    def setup(self, phases) -> None:
        with phases.phase("graph_ingest"):
            graph = program_graph(self.ds, self.cell.config["ingest"])
        c0 = phases.compiles.seconds
        t0 = time.perf_counter()
        with phases.phase("service_build_and_compile"):
            self._start_server(graph)
        build = time.perf_counter() - t0
        with phases.phase("warm_up"):
            rng = np.random.default_rng(WARM_SEED)
            ids = [self.send(s) for s in rng.choice(self.eligible,
                                                     self.warm_burst)]
            if not self.wait_for(ids, 300):
                raise RuntimeError("warm-up burst not answered")
            lone = []
            for s in rng.choice(self.eligible, WARM_SINGLES):
                qid = self.send(s)
                if not self.wait_for([qid], 300):
                    raise RuntimeError("warm-up request not answered")
                lone.append(self.responses[qid].t - self.sent_t[qid])
            bad = [i for i in range(self._next_id) if not self.responses[i].ok]
            if bad:
                raise RuntimeError(f"warm-up requests failed: {bad[:5]}")
        self.counters["compile_s"] = phases.compiles.seconds
        self.counters["engine_build_s"] = build - (phases.compiles.seconds - c0)
        self.lone_s = min(lone)
        log(f"[setup] a lone request took {self.lone_s:.3f} s")
        self.first_id = self._next_id

    # --- after the window ---------------------------------------------------
    def finish(self) -> None:
        """Wait for every request of the traffic (up to ``drain_s`` past the
        window's close), then end the server and read its final counters."""
        self.traffic_ids = list(range(self.first_id, self._next_id))
        if not self.wait_for(self.traffic_ids, self.drain_s):
            log(f"[window] responses missing {self.drain_s} s after the close")
        self.close()
        self.final = self.stderr.latest()
        self._window_counters()

    def _window_counters(self) -> None:
        """The server's counters from the statsz line before the window
        (``self.base``) to its final one."""
        a, b = self.base, self.final
        routed = {w: n - a["routing"].get(w, 0) for w, n in b["routing"].items()}
        offered = sum(int(w) * n for w, n in routed.items())
        padded = b["padded_lanes_total"] - a["padded_lanes_total"]
        self.counters["lanes_offered"] = offered
        self.counters["lanes_used"] = offered - padded
        self.counters["completed"] = b["completed"] - a["completed"]
        self.counters["routing"] = routed

    def attempted_failed(self) -> tuple[int, int]:
        ids = self.traffic_ids
        ok = sum(1 for i in ids if i in self.responses and self.responses[i].ok)
        return len(ids), len(ids) - ok

    def release(self) -> None:
        gc.collect()

    # --- correct --------------------------------------------------------------
    def _sample(self, answered: list) -> list:
        """Answered requests whose ``levels`` is compared with the
        reference: ``VERIFY_SAMPLES`` drawn from the seed, and the one with
        the most levels."""
        if not answered:
            return []
        rng = np.random.default_rng([self.seed, 1])
        k = min(VERIFY_SAMPLES, len(answered))
        picked = {answered[i].id for i in rng.choice(len(answered), k,
                                                      replace=False)}
        picked.add(max(answered, key=lambda r: r.levels).id)
        return sorted(picked)

    def compare(self) -> list:
        """Every request of the traffic answered; every answer's ``reached``
        against its component's size; the sample's ``levels`` against the
        reference's eccentricity."""
        ids = self.traffic_ids
        missing = sum(1 for i in ids if i not in self.responses)
        answered = [self.responses[i] for i in ids
                    if i in self.responses and self.responses[i].ok]
        srcs = np.array([self.sources[r.id] for r in answered], dtype=np.int64)
        reached = np.array([r.reached for r in answered], dtype=np.int64)
        bad_reached = int(np.count_nonzero(
            reached != self.ds.component_size(srcs))) if len(srcs) else 0
        sample = self._sample(answered)
        bad_levels = sum(
            int(self.responses[i].levels
                != int(self.ds.bfs_levels(self.sources[i]).max()))
            for i in sample)
        return [
            Compared("missing_responses", missing, 0),
            Compared("reached_mismatch", bad_reached, 0),
            Compared("levels_mismatch", bad_levels, 0),
            Compared("responses_compared", len(sample), 1, at_least=True),
        ]
