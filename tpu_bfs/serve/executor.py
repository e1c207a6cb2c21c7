"""Batch execution with failure classification, retry, and OOM degrade.

The serving dispatch path reuses the one transient/deterministic
classifier the whole repo shares (utils/recovery.py): transient infra
errors re-dispatch the SAME batch with capped backoff (the queries are
already coalesced; re-enqueueing them would just re-form the same
batch), OOM hands the queries back to the service for re-admission at a
narrower lane count (floor_lanes halving — the degrade ladder), and
everything else resolves the batch's queries with explicit error
results. Unlike bench.py's retry ladder there is no wall-clock budget:
the server is the long-lived process the budget envelope exists to
protect elsewhere.

The execution is split into PIPELINE HALVES (ISSUE 3): ``dispatch_batch``
launches the device level loop through the engine's async ``dispatch``
entry and returns a :class:`PendingBatch` immediately; ``finish_batch``
blocks on the result and extracts/resolves. The split lets the service
hand completed batches to an extraction worker and keep dispatching —
and because JAX surfaces async-dispatch failures (OOM included) at the
blocking fetch, the SAME classifier runs on both halves: a transient
fetch failure re-dispatches the identical padded batch, an OOM raises
:class:`OomRequeue` from whichever half saw it, and every admitted query
still resolves exactly once.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs
from tpu_bfs.serve.scheduler import (
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    QueryResult,
)
from tpu_bfs.utils.recovery import (
    COUNTERS,
    is_mesh_fault,
    is_oom_failure,
    is_transient_failure,
)


def pad_batch(sources: np.ndarray, lanes: int) -> tuple[np.ndarray, int]:
    """Pad a partial batch to exactly ``lanes`` sources so every dispatch
    reuses ONE compiled shape per ladder width (a variable-length batch
    would retrace the level loop per distinct size). Pad lanes repeat the
    first real source — a valid vertex by construction — and are masked
    out on extract by never being read (lanes [n:) belong to no query).
    With the width ladder the residual waste is bounded: routing already
    picked the narrowest resident width >= n, and what's left shows up in
    the ``padded_lanes_total`` counter."""
    n = len(sources)
    if n > lanes:
        raise ValueError(f"batch of {n} exceeds {lanes} lanes")
    if n == lanes:
        return np.asarray(sources, dtype=np.int64), n
    out = np.empty(lanes, dtype=np.int64)
    out[:n] = sources
    out[n:] = sources[0]
    return out, n


def engine_devices(engine) -> int:
    """The device count an engine's batches span — 1 for the single-chip
    engines, the mesh size for the distributed ones. The breaker and the
    degrade bookkeeping key on (width, devices): a single-chip rung
    tripping must not blackhole the same width on the mesh path (and
    vice versa), because the two are DIFFERENT compiled programs over
    different device sets (ISSUE 11). One definition shared with the
    fault sites' ``devices`` context (faults.mesh_devices) so the
    rank-qualifier semantics and the breaker keys cannot drift."""
    return _faults.mesh_devices(engine)


def breaker_key(width: int, devices: int, kind: str = "bfs") -> tuple:
    """The partition-aware breaker/degrade key: ``(width, devices)``,
    extended with the query kind when non-default (ISSUE 14) — a broken
    sssp rung must not blackhole the same width's bfs engine (different
    compiled programs), while default-kind keys keep the PR 10/11 tuple
    shape existing pins and dashboards read."""
    base = (int(width), int(devices))
    return base if kind == "bfs" else base + (kind,)


class CircuitBreaker:
    """Per-key (dispatch width x device count) circuit breaker over
    DETERMINISTIC batch failures.

    A rung whose every dispatch fails deterministically (wedged device
    state, a compiler bug tripped by one shape) would otherwise burn its
    full retry ladder on every batch routed to it, forever. The breaker
    OPENS after ``threshold`` consecutive deterministic failures at a
    key: the service's router then skips that rung (queries route to the
    next wider one). After ``cooldown_s`` it HALF-OPENS — one probe batch
    is admitted; success closes the breaker, failure re-opens it for
    another cooldown. OOMs never count here (the width-degrade ladder
    already evicts and routes around those); transient failures never
    count (the retry ladder owns them).

    Thread-safe; open transitions bump ``RecoveryCounters.breaker_opens``
    and are visible in statsz (``breaker_open`` / ``breaker_opens``)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, *, threshold: int = 3, cooldown_s: float = 30.0,
                 now=time.monotonic, log=None):
        if threshold < 1:
            raise ValueError(f"breaker threshold must be >= 1, got {threshold}")
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._now = now
        self._log = log or (lambda msg: None)
        self._lock = threading.Lock()
        # key -> [state, consecutive_fails, opened_at]
        self._state: dict = {}  # guarded-by: _lock
        self.opens = 0  # guarded-by: _lock

    def allow(self, key) -> bool:
        """May a batch be routed to ``key`` right now? Open keys refuse
        until the cooldown elapses, then admit exactly one probe."""
        with self._lock:
            st = self._state.get(key)
            if st is None or st[0] == self.CLOSED:
                return True
            # OPEN past the cooldown admits one probe (half-open); a
            # HALF_OPEN whose probe never reported back (lost outside the
            # executor, e.g. a failed engine build) re-admits one more
            # probe per cooldown period — a lost probe must not block the
            # rung forever.
            if self._now() - st[2] >= self.cooldown_s:
                st[0] = self.HALF_OPEN
                st[2] = self._now()
                self._log(f"circuit breaker half-open for width {key}: "
                          f"admitting one probe batch")
                return True
            return False  # open, or half-open with the probe in flight

    def record_success(self, key) -> None:
        with self._lock:
            st = self._state.pop(key, None)
            if st is not None and st[0] != self.CLOSED:
                self._log(f"circuit breaker closed for width {key} "
                          f"(probe batch succeeded)")

    def record_failure(self, key) -> bool:
        """Count one deterministic failure; True when the breaker OPENED
        (first crossing of the threshold, or a failed half-open probe)."""
        with self._lock:
            st = self._state.setdefault(key, [self.CLOSED, 0, 0.0])
            st[1] += 1
            opened = (
                st[0] == self.HALF_OPEN
                or (st[0] == self.CLOSED and st[1] >= self.threshold)
            )
            if opened:
                st[0] = self.OPEN
                st[2] = self._now()
                self.opens += 1
        if opened:
            COUNTERS.bump("breaker_opens")
            self._log(
                f"circuit breaker OPEN for width {key} after {st[1]} "
                f"consecutive deterministic failures (cooldown "
                f"{self.cooldown_s:.1f}s)"
            )
        return opened

    def trip(self, key) -> None:
        """Force-open ``key`` immediately — the integrity tier's
        corruption quarantine (ISSUE 15): a rung whose AUDITED answer
        was provably corrupt must stop taking traffic now, not after
        ``threshold`` more batches of wrong answers. Half-opens on the
        ordinary cooldown timer like any open breaker (the evicted
        rung's rebuild gets its probe batch)."""
        with self._lock:
            st = self._state.setdefault(key, [self.CLOSED, 0, 0.0])
            st[0] = self.OPEN
            st[1] = max(st[1], self.threshold)
            st[2] = self._now()
            self.opens += 1
        COUNTERS.bump("breaker_opens")
        self._log(f"circuit breaker FORCED OPEN for {key} (corruption "
                  f"quarantine; cooldown {self.cooldown_s:.1f}s)")

    def open_keys(self) -> list:
        """Keys currently open/half-open (for statsz)."""
        with self._lock:
            return sorted(
                k for k, st in self._state.items() if st[0] != self.CLOSED
            )


# Batch ordinals are assigned unconditionally (one integer increment):
# the obs layer needs a stable correlation id, and tests that spy on the
# disabled path count obs-layer CALLS, not plain counters.
_BATCH_SEQ = itertools.count(1)


class BatchRequeue(Exception):
    """Base of the internal batch-outcome signals that leave queries
    UNRESOLVED and ride up to the service: re-admission policy (which
    width, which mesh) is the service's call, not the executor's. Both
    pipeline halves close their open spans on any subclass."""

    def __init__(self, queries, cause: BaseException):
        super().__init__(str(cause))
        self.queries = queries
        self.cause = cause


class OomRequeue(BatchRequeue):
    """The batch OOM'd; its queries ride along for the service to
    degrade the lane count and re-admit."""


class MeshFaultRequeue(BatchRequeue):
    """The batch's MESH died under it (device loss / hung collective /
    backend restart — utils/recovery.is_mesh_fault): retrying on the
    same mesh shape would re-dispatch into the same dead collective, so
    the queries ride up for the service to rebuild the ladder one mesh
    rung down (ISSUE 12's failover ladder) and re-admit. ``devices``
    records the mesh span the fault hit."""

    def __init__(self, queries, cause: BaseException, devices: int):
        super().__init__(queries, cause)
        self.devices = devices


class PendingBatch:
    """One dispatched-but-unresolved batch crossing the pipeline handoff.

    Carries everything either half needs: the engine, the admitted
    queries (for exactly-once resolution), the padded source array (so a
    transient fetch failure can re-dispatch the identical batch), the
    async handle, and the retry attempt counter — shared across both
    halves so the retry budget cannot double through the handoff."""

    __slots__ = ("engine", "queries", "n", "padded", "handle", "attempt",
                 "lanes", "bid", "devices", "t_dispatch", "device_ms",
                 "wire_bytes", "kind", "params", "generation",
                 "overlay_epoch", "core_ready", "_on_core_ready",
                 "_core_lock")

    def __init__(self, engine, queries, n: int, padded: np.ndarray,
                 kind: str = "bfs", params: dict | None = None):
        self.engine = engine
        self.queries = list(queries)
        self.n = n
        self.padded = padded
        # The batch's query kind + its batch-uniform dispatch kwargs
        # (ISSUE 14: khop's k, p2p's padded targets) — carried so a
        # transient re-dispatch on either pipeline half replays the
        # identical call.
        self.kind = kind
        self.params = params or {}
        self.handle = None
        self.attempt = 0
        # Recorded at dispatch: the OOM handler clears ``engine`` to drop
        # the device-table reference before a narrower rebuild, but the
        # service still needs the width the failure happened at. In
        # LADDER units: an adapter whose batch capacity differs from its
        # registry width (p2p counts pairs) publishes ``ladder_lanes``
        # so the breaker keys and the OOM-degrade walk stay on the
        # service's width grid.
        self.lanes = getattr(engine, "ladder_lanes", engine.lanes)
        # Mesh span of this batch's engine — half of the partition-aware
        # breaker key, recorded here for the same clears-engine reason.
        self.devices = engine_devices(engine)
        # Dispatch stamp -> fetch-return duration: the batch's device
        # occupancy, the denominator of the per-query GTEPS record.
        self.t_dispatch: float | None = None
        self.device_ms: float | None = None
        # Modeled off-chip bytes the batch's traversal moved (mesh
        # engines; None on single-chip — there is no wire).
        self.wire_bytes: float | None = None
        # Process-wide batch ordinal: the span-correlation id every obs
        # event of this batch (and its queries) carries.
        self.bid = next(_BATCH_SEQ)
        # Graph generation this batch was ADMITTED under (ISSUE 19):
        # stamped by the scheduler inside the flip lock at dispatch, so
        # the stamp always names the generation of the engine tables the
        # batch actually traversed — the staleness auditor's ground
        # truth. Static services leave it 0.
        self.generation = 0
        # Overlay install epoch at the same dispatch point: bumps on
        # table events the generation number cannot see (restage heals,
        # compactions), so the shadow auditor can tell "replayable
        # against the live tables" from "superseded install".
        self.overlay_epoch = 0
        # Set once this batch's core no longer occupies the device: its
        # outputs are ready, it failed, or its fetch half gave it up
        # (watch_core). The scheduler holds partial batches while any
        # dispatched core is unfinished.
        self.core_ready = threading.Event()
        self._core_lock = threading.Lock()
        self._on_core_ready = None  # guarded-by: _core_lock

    def watch_core(self, on_ready) -> None:
        """Call ``on_ready()`` once, when this batch's core has finished
        on the device. Observed, not guessed: a daemon thread blocks in
        the handle's ``levels.block_until_ready()`` (the level loop's
        last output). A handle with no such device value (host values,
        test doubles), or whose value is ready already, counts as
        finished now. :meth:`mark_core_ready` also fires it, so a core
        that never reports back (a watchdog trip) holds nothing past its
        batch's fetch half."""
        with self._core_lock:
            if not self.core_ready.is_set():
                self._on_core_ready = on_ready
                on_ready = None
        if on_ready is not None:
            on_ready()
            return
        levels = getattr(self.handle, "levels", None)
        ready = getattr(levels, "block_until_ready", None)
        is_ready = getattr(levels, "is_ready", None)
        if ready is None or (is_ready is not None and is_ready()):
            self.mark_core_ready()
            return

        def wait():
            try:
                ready()
            except Exception:  # noqa: BLE001 — the fetch half classifies it
                pass
            finally:
                self.mark_core_ready()

        threading.Thread(target=wait, name="bfs-serve-core-wait",
                         daemon=True).start()

    def mark_core_ready(self) -> None:
        """Set :attr:`core_ready` and fire the watcher, once."""
        with self._core_lock:
            if self.core_ready.is_set():
                return
            self.core_ready.set()
            on_ready, self._on_core_ready = self._on_core_ready, None
        if on_ready is not None:
            on_ready()


class _Ready:
    """Degenerate handle for engines exposing only the blocking ``run``
    protocol (test fakes): the whole run happens at dispatch time."""

    __slots__ = ("res",)

    def __init__(self, res):
        self.res = res


class BatchExecutor:
    """Runs coalesced batches through an engine's dispatch/fetch halves."""

    def __init__(self, metrics, *, max_retries: int = 2,
                 backoff_s: float = 0.05, backoff_cap_s: float = 2.0,
                 log=None, sleep=time.sleep, watchdog_s: float = 0.0,
                 breaker: CircuitBreaker | None = None):
        self.metrics = metrics
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self._log = log or (lambda msg: None)
        self._sleep = sleep
        # Dispatch watchdog: > 0 bounds how long the blocking fetch half
        # may run before being CLASSIFIED AS TRANSIENT (the existing
        # retry/rebuild path fires instead of the executor hanging
        # forever on a wedged device). 0 keeps the plain inline fetch.
        self.watchdog_s = watchdog_s
        self.breaker = breaker
        # Every watchdog trip abandons a daemon thread still blocked in
        # engine.fetch, pinning that batch's device handle until the
        # fetch eventually returns. On a permanently wedged device those
        # would accumulate forever; past this cap new watched fetches are
        # REFUSED with a deterministic error (feeding the breaker, which
        # then routes around the rung) instead of abandoning more state.
        self.max_abandoned = 8
        self._abandoned = 0  # guarded-by: _abandon_lock
        self._abandon_lock = threading.Lock()

    # --- pipeline halves --------------------------------------------------

    def dispatch_batch(self, engine, queries) -> PendingBatch | None:
        """Pad and launch ``queries`` (<= engine.lanes of them) as one
        batch WITHOUT blocking on the result. Returns the pending handoff
        (resolve via :meth:`finish_batch`), or None when the batch already
        resolved with deterministic errors. Raises :class:`OomRequeue` on
        a dispatch-time OOM — the only outcome that leaves the queries
        unresolved, because re-admission at a narrower width is the
        service's call, not the executor's."""
        # Deadline re-check at DISPATCH time: batch-forming already
        # expired queued queries, but a query can reach this point again
        # long after that check — an OOM requeue, a breaker reroute, or
        # a mesh-degrade re-admission — and burning chip time on an
        # answer its client stopped waiting for helps nobody.
        now = time.monotonic()
        live = []
        expired = 0
        for q in queries:
            if q.expired(now):
                if q.resolve_status(
                    STATUS_EXPIRED,
                    error="deadline expired before dispatch "
                          "(after requeue/reroute)",
                ):
                    expired += 1
            else:
                live.append(q)
        if expired:
            self.metrics.record_expired(expired)
        if not live:
            return None
        queries = live
        sources = np.asarray([q.source for q in queries], dtype=np.int64)
        padded, n = pad_batch(sources, engine.lanes)
        # Per-kind dispatch kwargs (ISSUE 14): the scheduler only
        # coalesces same-batch-key queries, so the first query's kind
        # and parameters speak for the whole batch. p2p's targets pad
        # exactly like the sources (pad pairs clone pair 0).
        kind = getattr(queries[0], "kind", "bfs")
        from tpu_bfs.workloads import batch_params

        params = batch_params(queries)
        if "targets" in params:
            params["targets"], _ = pad_batch(
                params["targets"], engine.lanes
            )
        pending = PendingBatch(engine, queries, n, padded, kind, params)
        rec = _obs.ACTIVE
        mesh_kw = {"devices": pending.devices} if pending.devices > 1 else {}
        if rec is not None:
            # The batch span opens at dispatch and closes when every
            # query resolved (finish) or the batch failed; every query's
            # own span learns its batch id here. Latest wins: a query
            # requeued out of an OOM'd batch must close naming the batch
            # that actually served it, not the aborted one (the aborted
            # batch's own events still list the query id).
            for q in pending.queries:
                if hasattr(q, "obs_batch"):
                    q.obs_batch = pending.bid
            rec.begin("batch", f"b{pending.bid}",  # span-outlives: finish_batch/_resolve_ok/_classify_failure close it
                      cat="serve.batch",
                      batch=pending.bid, n=n, width=pending.lanes,
                      queries=[q.id for q in pending.queries], **mesh_kw)
        # The admission-to-dispatch wait, summed over the batch's queries
        # from stamps already taken (``now`` above, each ``t_submit``),
        # is computed only while a profiler records.
        with _obs.host_span("tpu_bfs.serve.dispatch", lambda: {
            "width": pending.lanes, "n": n,
            "wait_ms_sum": 1e3 * sum(now - q.t_submit for q in queries),
        }, span_id=f"b{pending.bid}", cat="serve.batch", batch=pending.bid,
                width=pending.lanes, **mesh_kw) as span:
            while True:
                try:
                    if _faults.ACTIVE is not None:
                        # Chaos-harness injection site: engine-agnostic
                        # (the _packed_common dispatch/fetch sites cover
                        # real engines; this one also covers test
                        # doubles).
                        _faults.ACTIVE.hit("serve_batch",
                                           lanes=pending.lanes, n=pending.n)
                    self._launch(engine, pending)
                    span.set(batch=pending.bid, attempt=pending.attempt)
                    return pending
                except Exception as exc:  # noqa: BLE001 — gated by the classifier
                    try:
                        retry = self._classify_failure(pending, exc)
                    except BatchRequeue as brq:
                        # The OOM/mesh-fault rides up to the service's
                        # requeue ladder (the classifier already ended
                        # the batch span; the dispatch span ends as it
                        # leaves).
                        span.set(batch=pending.bid,
                                 **({"oom": True}
                                    if isinstance(brq, OomRequeue)
                                    else {"mesh_fault": True}))
                        raise
                    if not retry:
                        span.set(batch=pending.bid, failed=True)
                        break
        if rec is not None:
            rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                    batch=pending.bid, failed=True)
        return None

    def finish_batch(self, pending: PendingBatch) -> None:
        """Block on a dispatched batch and resolve every query exactly
        once. Transient fetch failures re-dispatch the same padded batch
        (the handle is dead once its fetch raised); OOM raises
        :class:`OomRequeue` exactly as the dispatch half does."""
        engine = pending.engine
        with _obs.host_span("tpu_bfs.serve.fetch", lambda: {
            "width": pending.lanes, "n": pending.n,
        }, span_id=f"b{pending.bid}", cat="serve.batch", batch=pending.bid,
                n=pending.n) as span:
            while True:
                try:
                    if pending.handle is None:  # re-dispatch after a retry
                        self._launch(engine, pending)
                    res = self._fetch_watched(engine, pending)
                    # The batch's device occupancy — the per-query GTEPS
                    # denominator. Under pipelining, dispatch time
                    # includes the wait behind the previous in-flight
                    # batch (one device stream), so the window is clamped
                    # to start no earlier than the previous batch's
                    # fetch-return on this engine: an approximation of
                    # the true compute window (slightly late on the start
                    # side), but it no longer double-counts the
                    # predecessor's whole runtime.
                    t_done = time.monotonic()
                    if pending.t_dispatch is not None:
                        start = pending.t_dispatch
                        prev_done = engine.__dict__.get(
                            "_serve_prev_fetch_done")
                        if prev_done is not None and prev_done > start:
                            start = prev_done
                        pending.device_ms = (t_done - start) * 1e3
                    engine.__dict__["_serve_prev_fetch_done"] = t_done
                    # Modeled exchange bytes: the READY-only reader —
                    # fetch of batch N must not block on (or wait for)
                    # batch N+1's still-running loop. See
                    # completed_exchange_record for the bounded
                    # adjacent-batch attribution caveat.
                    taker = getattr(engine, "completed_exchange_record",
                                    None)
                    wb = (
                        taker()[1] if taker is not None
                        else getattr(engine, "last_exchange_bytes", None)
                    )
                    pending.wire_bytes = None if wb is None else float(wb)
                    span.set(batch=pending.bid, attempt=pending.attempt)
                    break
                except Exception as exc:  # noqa: BLE001 — gated by the classifier
                    pending.handle = None
                    try:
                        retry = self._classify_failure(pending, exc)
                    except BatchRequeue as brq:
                        # Same discipline as the dispatch half: the fetch
                        # span ends as the OOM/mesh-fault rides up.
                        span.set(batch=pending.bid,
                                 **({"oom": True}
                                    if isinstance(brq, OomRequeue)
                                    else {"mesh_fault": True}))
                        raise
                    if not retry:
                        span.set(batch=pending.bid, failed=True)
                        res = None
                        break
        if res is None:
            rec = _obs.ACTIVE
            if rec is not None:
                rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                        batch=pending.bid, failed=True)
            return
        # The result now owns whatever device state extraction needs; drop
        # the handle's copy so the batch's loop outputs free as soon as
        # the result does.
        pending.handle = None
        self._resolve_ok(pending, res)

    def run_batch(self, engine, queries) -> None:
        """The unpipelined path: dispatch immediately finished."""
        pending = self.dispatch_batch(engine, queries)
        if pending is not None:
            self.finish_batch(pending)

    # --- internals --------------------------------------------------------

    def _launch(self, engine, pending: PendingBatch) -> None:
        """Dispatch the batch's core and, where the engine has the hook,
        its answer reductions right behind it (``launch_summaries``), so
        its answers do not queue behind cores dispatched later. The
        eccentricity reduction is launched only where _extract will read
        it: some query skips its distance row."""
        pending.t_dispatch = time.monotonic()
        pending.handle = self._dispatch(engine, pending.padded,
                                        pending.params)
        launch = getattr(engine, "launch_summaries", None)
        if launch is not None:
            launch(pending.handle, ecc=any(
                not getattr(q, "want_distances", True)
                for q in pending.queries))

    @staticmethod
    def _dispatch(engine, padded, params=None):
        dispatch = getattr(engine, "dispatch", None)
        if dispatch is not None:
            return dispatch(padded, **params) if params else dispatch(padded)
        if params:
            return _Ready(engine.run(padded, time_it=False, **params))
        return _Ready(engine.run(padded, time_it=False))

    @staticmethod
    def _fetch(engine, handle):
        if isinstance(handle, _Ready):
            return handle.res
        return engine.fetch(handle)

    def _fetch_watched(self, engine, pending: PendingBatch):
        """The blocking fetch, under the dispatch watchdog when armed.

        A device computation that exceeds ``watchdog_s`` is CLASSIFIED AS
        TRANSIENT (a DEADLINE_EXCEEDED RuntimeError the shared classifier
        retries), so a wedged device fires the existing re-dispatch path
        instead of hanging the executor forever. The abandoned fetch runs
        on a daemon thread; if it eventually completes, its result is
        discarded — the batch's queries resolve exactly once through
        whichever attempt the retry ladder lands.

        Deliberately one thread PER watched fetch, not a persistent
        worker: after a trip the abandoned fetch may block its thread
        indefinitely, and the retry's fetch must proceed concurrently —
        a single long-lived worker would serialize behind exactly the
        hang the watchdog exists to route around. Thread spawn cost is
        noise next to a device level-loop fetch."""
        if self.watchdog_s <= 0:
            return self._fetch(engine, pending.handle)
        with self._abandon_lock:
            # Captured under the lock: the refusal message reads the count
            # too, and a trip on another thread must not race the read
            # (the lock lint in tpu_bfs/analysis pins the discipline).
            abandoned = self._abandoned
        if abandoned >= self.max_abandoned:
            # Deterministic (no transient marker): resolves the batch's
            # queries with errors and feeds the breaker, instead of
            # abandoning yet another fetch on a wedged device.
            raise RuntimeError(
                f"dispatch watchdog: {abandoned} abandoned fetches "
                f"still running (cap {self.max_abandoned}); refusing to "
                f"watch another fetch on this engine"
            )
        box: list = []
        done = threading.Event()
        state = {"abandoned": False}

        def work(handle=pending.handle):
            try:
                box.append(("ok", self._fetch(engine, handle)))
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                box.append(("err", exc))
            finally:
                # done + the abandoned-count handoff commute under one
                # lock: either the watcher sees done in time, or it marks
                # the thread abandoned and this finally pays it back.
                with self._abandon_lock:
                    if state["abandoned"]:
                        self._abandoned -= 1
                    done.set()

        threading.Thread(
            target=work, name="bfs-serve-fetch", daemon=True
        ).start()
        if not done.wait(self.watchdog_s):
            tripped = False
            with self._abandon_lock:
                if not done.is_set():
                    state["abandoned"] = True
                    self._abandoned += 1
                    tripped = True
            if tripped:
                COUNTERS.bump("watchdog_trips")
                self.metrics.record_watchdog_trip()
                rec = _obs.ACTIVE
                if rec is not None:
                    # Flight-recorder trigger: the trip is exactly the
                    # incident class the ring buffer exists to replay.
                    rec.event("watchdog_trip", cat="serve.batch",
                              batch=pending.bid, n=pending.n,
                              watchdog_s=self.watchdog_s,
                              queries=[q.id for q in pending.queries])
                    rec.flight_dump("watchdog_trip")
                raise RuntimeError(
                    f"DEADLINE_EXCEEDED: dispatch watchdog: a "
                    f"{pending.n}-query batch's device fetch is still "
                    f"running after {self.watchdog_s:.1f}s — classifying "
                    f"as transient"
                )
        kind, val = box[0]
        if kind == "err":
            raise val
        return val

    def _classify_failure(self, pending: PendingBatch, exc) -> bool:
        """The one classifier both halves share. True = retry the batch;
        False = resolved as deterministic errors; OOM raises OomRequeue."""
        rec = _obs.ACTIVE
        if is_oom_failure(exc):
            if rec is not None:
                rec.event("batch_oom", cat="serve.batch", batch=pending.bid,
                          width=pending.lanes,
                          queries=[q.id for q in pending.queries])
                rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                        batch=pending.bid, oom=True)
            raise OomRequeue(list(pending.queries), exc) from exc
        if pending.devices > 1 and is_mesh_fault(exc):
            # A mesh-death marker on a MESH-spanning batch (ISSUE 12):
            # the whole mesh shape is suspect, so an in-place retry
            # would re-dispatch into the same dead collective. Feed the
            # (width, devices) breaker — routing stops offering the dead
            # mesh shape while its probe half-opens — and hand the
            # queries up for the degraded-mesh rebuild. Single-chip
            # batches with the same markers fall through to the plain
            # transient retry below (nothing to degrade).
            err = f"{type(exc).__name__}: {str(exc)[:200]}"
            COUNTERS.bump("mesh_faults")
            self.metrics.record_mesh_fault()
            self._log(
                f"MESH FAULT on a {pending.devices}-device batch "
                f"(width {pending.lanes}): {err} — degrading the mesh"
            )
            if self.breaker is not None:
                self.breaker.record_failure(
                    breaker_key(pending.lanes, pending.devices,
                                pending.kind)
                )
            if rec is not None:
                # Flight-recorder trigger (every mesh-fault firing):
                # the run-up to a slice death is exactly what the ring
                # buffer exists to replay.
                rec.event("mesh_fault", cat="serve.batch",
                          batch=pending.bid, width=pending.lanes,
                          devices=pending.devices, error=err,
                          queries=[q.id for q in pending.queries])
                rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                        batch=pending.bid, mesh_fault=True)
                rec.flight_dump("mesh_fault")
            raise MeshFaultRequeue(
                list(pending.queries), exc, pending.devices
            ) from exc
        if is_transient_failure(exc) and pending.attempt < self.max_retries:
            pending.attempt += 1
            wait = min(self.backoff_s * pending.attempt, self.backoff_cap_s)
            self.metrics.record_retry()
            COUNTERS.bump("transient_retries")
            if rec is not None:
                rec.event("retry", cat="serve.batch", batch=pending.bid,
                          attempt=pending.attempt,
                          error=f"{type(exc).__name__}: {str(exc)[:120]}")
            self._log(
                f"transient failure serving a {pending.n}-query batch "
                f"(attempt {pending.attempt}/{self.max_retries}): "
                f"{type(exc).__name__}: {str(exc)[:200]} — "
                f"retrying in {wait:.2f}s"
            )
            self._sleep(wait)
            return True
        err = f"{type(exc).__name__}: {str(exc)[:300]}"
        self._log(f"batch failed deterministically: {err}")
        if rec is not None:
            rec.event("batch_error", cat="serve.batch", batch=pending.bid,
                      width=pending.lanes, error=err,
                      queries=[q.id for q in pending.queries])
        if self.breaker is not None:
            # Deterministic failures (exhausted transients included) feed
            # the per-(width, devices) breaker so routing stops paying
            # this rung's full retry ladder per batch once it is provably
            # broken — without blackholing the same width on a different
            # mesh span.
            opened = self.breaker.record_failure(
                breaker_key(pending.lanes, pending.devices, pending.kind)
            )
            if opened and rec is not None:
                # Flight-recorder trigger: a rung going provably dark is
                # an incident worth a replayable artifact.
                rec.event("breaker_open", cat="serve.batch",
                          width=pending.lanes, batch=pending.bid)
                rec.flight_dump("breaker_open")
        for q in pending.queries:
            q.resolve_status(STATUS_ERROR, error=err)
        self.metrics.record_errors(pending.n)
        return False

    def _resolve_ok(self, pending: PendingBatch, res) -> None:
        if self.breaker is not None:
            self.breaker.record_success(
                breaker_key(pending.lanes, pending.devices,
                            pending.kind)
            )
        try:
            with _obs.host_span("tpu_bfs.serve.extract", lambda: {
                "width": pending.lanes, "n": pending.n,
            }, span_id=f"b{pending.bid}", cat="serve.batch",
                    batch=pending.bid, n=pending.n) as span:
                self._extract(pending, res, span)
        except Exception:
            # An extraction failure propagates to the service's catch-all
            # (which flight-dumps it); the open batch span must not
            # dangle in the very trace written for that incident (the
            # extract span ended failed as the exception left it).
            rec = _obs.ACTIVE
            if rec is not None:
                rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                        batch=pending.bid, failed=True)
            raise
        rec = _obs.ACTIVE
        if rec is not None:
            rec.end("batch", f"b{pending.bid}", cat="serve.batch",
                    batch=pending.bid, n=pending.n, width=pending.lanes)

    def _extract(self, pending: PendingBatch, res, span) -> None:
        from tpu_bfs.graph.csr import INF_DIST

        queries, n = pending.queries, pending.n
        # Ladder units (ladder_lanes where the adapter's capacity
        # differs): the width responses/metrics report must match the
        # routing histogram's rungs.
        width = pending.lanes
        # The on-device ecc summary is only worth its kernel dispatch when
        # some query skips the distance decode; all-want_distances batches
        # derive levels from the rows they pull anyway.
        ecc = (
            getattr(res, "ecc", None)
            if any(not getattr(q, "want_distances", True) for q in queries)
            else None
        )
        # Per-query traversal record (ISSUE 11): the engines' on-device
        # per-lane edge counts + the batch's device occupancy give each
        # query its GTEPS under the batch time share; mesh engines add
        # their modeled wire bytes, split evenly over the real queries.
        edges_arr = getattr(res, "edges_traversed", None)
        # Kind-specific response fields (ISSUE 14): workload results
        # expose per-query extras (p2p's path, cc's component record,
        # khop's k); the base engines' results have none.
        extras_fn = getattr(res, "extras", None)
        wire_share = (
            pending.wire_bytes / n
            if pending.wire_bytes is not None and n else None
        )
        t_x0 = time.monotonic()
        latencies = []
        # Resolving a query runs its completion callbacks, where the
        # frontend encodes and writes its response line: this loop is the
        # batch's response write (with the distance row pulls of the
        # queries that want rows).
        with _obs.host_span("tpu_bfs.serve.write", lambda: {"n": n}):
            for i, q in enumerate(queries):
                want = getattr(q, "want_distances", True)
                d = None
                if want or ecc is None:
                    # The one per-lane device->host distance pull.
                    # Metadata-only queries skip it entirely when the
                    # engine reduced the summaries on device (ecc — every
                    # packed engine does).
                    d = res.distances_int32(i)
                if ecc is not None:
                    levels = int(ecc[i])
                else:
                    finite = d[d != INF_DIST]
                    levels = int(finite.max()) if finite.size else 0
                extras_i = extras_fn(i) if extras_fn is not None else None
                reached_i = int(res.reached[i])
                if _faults.ACTIVE is not None:
                    # Chaos hook: corrupt_result rules flip one
                    # bit of THIS query's just-extracted answer — the
                    # client-visible corruption every integrity detector
                    # must catch (red-before-green for the audit tier).
                    d, extras_i, reached_i, _fired = (
                        _faults.maybe_corrupt_result(
                            d, extras_i, reached_i, lanes=width,
                            batch=pending.bid,
                        )
                    )
                # Stamp at RESOLVE time, per query: extraction cost is
                # real client-visible latency (the old shared
                # pre-extraction stamp hid it, and hid the pipelining win
                # with it).
                latency_ms = (time.monotonic() - q.t_submit) * 1e3
                q.resolve(QueryResult(
                    id=q.id,
                    source=q.source,
                    status=STATUS_OK,
                    kind=pending.kind,
                    extras=extras_i,
                    distances=d if want else None,
                    levels=levels,
                    reached=reached_i,
                    latency_ms=latency_ms,
                    batch_lanes=n,
                    dispatched_lanes=width,
                    devices=pending.devices,
                    edges=(
                        int(edges_arr[i]) if edges_arr is not None else None
                    ),
                    device_ms=pending.device_ms,
                    wire_bytes=wire_share,
                ))
                latencies.append(latency_ms)
        extract_ms = (time.monotonic() - t_x0) * 1e3
        span.set(batch=pending.bid, extract_ms=round(extract_ms, 3))
        self.metrics.record_batch(n, width, latencies, extract_ms=extract_ms)
