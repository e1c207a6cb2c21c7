"""Closed-loop traffic: a fixed number of requests kept outstanding.

Parameters (``params`` of the cell file):

- ``outstanding``: requests in flight at once, from set-up to the
  window's close: each response read sends the next request, and the
  requests owed for what one read of the server's output brought are
  written together, as soon as the client has read all there is.

Sources are drawn from the seed, uniform over vertices with an edge. The
loop starts in set-up, after the warm-up: ``outstanding`` requests are
sent and each answer sends the next. Once every one of the first
``outstanding`` requests has been answered the loop is in its steady
state, and the window opens at the end of the burst holding the last of
those answers. It
closes at the end of the first burst to end at or after ``--seconds``, so
it holds whole bursts. The server answers a batch's queries together and
a batch takes at least a traversal, so a pause of a quarter of the lone
request's traversal (measured in the warm-up) ends a burst.
``serve_qps`` counts the OK responses read inside the window over its
length. The requests still outstanding at its close are waited for and
checked, and not counted.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from benchmark.harness import log
from benchmark.serve_client import ServeDriver

STEADY_TIMEOUT_S = 600.0


class Driver(ServeDriver):
    def setup(self, phases) -> None:
        super().setup(phases)
        with phases.phase("closed_loop_fill"):
            self._rng = np.random.default_rng(self.seed)
            self._rng_lock = threading.Lock()
            self._annotate = lambda name: contextlib.nullcontext()
            self._looping = True
            self._owed = 0
            first = self._send(int(self.params["outstanding"]))
            if not self.wait_for(first, STEADY_TIMEOUT_S):
                raise RuntimeError("the closed loop's first requests were "
                                   "not answered")
            filled = max(self.responses[i].t for i in first)
            self.t_open = self.quiet_after(filled, self._gap, STEADY_TIMEOUT_S)
            self.base = self.stderr.latest()

    @property
    def _gap(self) -> float:
        return self.lone_s / 4

    def _send(self, n: int) -> list:
        with self._rng_lock:
            srcs = self._rng.choice(self.eligible, n)
        with self._annotate("bench.request"):
            return self.send_many(srcs)

    def _on_response(self, r) -> None:
        if getattr(self, "_looping", False) and r.id >= self.first_id:
            self._owed += 1

    def _on_caught_up(self) -> None:
        n, self._owed = getattr(self, "_owed", 0), 0
        if n and self._looping:
            self._send(n)

    def window(self, seconds: float, annotate) -> None:
        self._annotate = annotate
        time.sleep(max(self.t_open + seconds - time.perf_counter(), 0.0))
        self.t_close = self.quiet_after(self.t_open + seconds, self._gap,
                                        STEADY_TIMEOUT_S)
        self._looping = False
        self.window_s = self.t_close - self.t_open

    def in_window(self) -> list:
        """Read times of the OK responses read inside the window."""
        return sorted(r.t for r in list(self.responses.values())
                      if r.ok and self.t_open < r.t <= self.t_close)

    def report(self) -> None:
        times = np.array(self.in_window())
        bursts = np.split(times, np.flatnonzero(np.diff(times) >= self._gap)
                          + 1) if len(times) else []
        log(f"[window] {len(self.traffic_ids)} requests of the loop, "
            f"{len(times)} answered ok inside the window of "
            f"{self.window_s:.3f} s, in {len(bursts)} bursts of "
            f"{[len(b) for b in bursts]}")

    def end_to_end(self) -> dict:
        return {"serve_qps": len(self.in_window()) / self.window_s}
