"""In-run failure detection + elastic recovery for checkpointed traversals.

SURVEY.md §5: the reference has no failure story at all — a failed rank
hangs the MPI_Allreduce (bfs_mpi.cu:621) and the whole traversal is lost.
Here the traversal state is an explicit host value (utils/checkpoint.py),
so recovery is a driver-level loop: classify the failure, rebuild the
engine (fresh device buffers + compiled programs), and resume from the
last durable checkpoint — bit-identical to never having failed, because
the while-loop carry IS the state. The same transient/deterministic
classifier guards the benchmark's compile-heavy stages (bench.py).
"""

from __future__ import annotations

import dataclasses
import threading

from tpu_bfs import faults as _faults


@dataclasses.dataclass
class RecoveryCounters:
    """Process-wide retry/degrade visibility (one instance: ``COUNTERS``).

    Until round 6 every retry here and in bench.py was invisible after
    the fact — a run that survived three transient failures and an OOM
    shed reported the same clean output as one that never hiccuped, so
    serve-mode incidents left no post-hoc trace. Every retry path now
    bumps these; the CLI's --stats emits them as a final JSON line and
    bench.py attaches them to its verdict line when any fired."""

    transient_retries: int = 0  # re-attempts after a transient classification
    engine_rebuilds: int = 0  # advance_with_recovery engine reconstructions
    oom_degrades: int = 0  # OOM-driven sheds/lane-halvings (bench + serve)
    watchdog_trips: int = 0  # serve dispatch-watchdog deadline firings
    breaker_opens: int = 0  # serve circuit-breaker open transitions
    requeue_sheds: int = 0  # queries shed at the serve requeue budget
    faults_injected: int = 0  # tpu_bfs/faults.py injections (chaos only)
    mesh_faults: int = 0  # mesh-death classifications (is_mesh_fault fired)
    mesh_degrades: int = 0  # degraded-mesh failover rebuilds (ISSUE 12)
    query_resumes: int = 0  # level-checkpointed mid-query resumes
    quarantines: int = 0  # corruption-audit rung quarantines (ISSUE 15)

    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def bump(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def as_dict(self) -> dict:
        with self._lock:
            return {
                f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name != "_lock"
            }

    def any(self) -> bool:
        return any(self.as_dict().values())

    def reset(self) -> None:
        with self._lock:
            for f in dataclasses.fields(self):
                if f.name != "_lock":
                    setattr(self, f.name, 0)


COUNTERS = RecoveryCounters()

# The jaxlib mesh-death strings (ISSUE 12): a participant dropping out
# of the slice surfaces as DATA_LOSS, a failed "slice health" check, or
# a "Program hung" collective timeout. ONE definition: these feed the transient
# patterns (a mesh fault is retryable infrastructure trouble) AND
# is_mesh_fault, which multi-chip callers consult to degrade the mesh
# instead of re-dispatching into the same dead collective.
MESH_FAULT_MARKERS = (
    "DATA_LOSS",
    "slice health",
    "Program hung",
)


def is_mesh_fault(exc: BaseException) -> bool:
    """True when ``exc`` carries a jaxlib mesh-death marker — the whole
    mesh's collectives are suspect, not just this dispatch. Callers with
    a single-chip engine treat these like any transient (retry in
    place); mesh-spanning callers run the degraded-mesh failover ladder
    (serve/executor.MeshFaultRequeue -> BfsService mesh degrade)."""
    msg = str(exc)
    return any(m in msg for m in MESH_FAULT_MARKERS)


# Substrings that mark an error as plausibly-transient infrastructure
# trouble: transport failures and XLA's INTERNAL/UNAVAILABLE status codes.
# Bare "INTERNAL:" is included because infra errors don't always name
# their transport — the deny-list below catches the known deterministic
# INTERNAL shapes (Mosaic lowering bugs) so those surface on the first
# attempt. A backend that fails to initialize is NOT transient: on a TPU
# host it means libtpu or the chip is unusable (or held by another
# process), and waiting does not fix that — it must fail the run.
TRANSIENT_PATTERNS = (
    "Connection reset",
    "Broken pipe",
    "INTERNAL:",
    "UNAVAILABLE:",
    "DEADLINE_EXCEEDED:",
    *MESH_FAULT_MARKERS,
)

# Out-of-HBM flavors (XLA compile- or run-time). Deterministic — never
# retried — but callers with sheddable optional state (the bench's
# adaptive push table) use this to decide a plain re-run. ONE definition:
# an OOM variant added here is seen by both the transient classifier
# below and the bench's shed fallback.
OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
)


def is_oom_failure(exc: BaseException) -> bool:
    s = str(exc)
    low = s.lower()
    return any(m.lower() in low for m in OOM_MARKERS)


# Deterministic failures that can carry an INTERNAL: status but are bugs,
# not infra blips — retrying them burns minutes before surfacing the real
# error. OOM and shape/lowering errors are never transient.
NON_TRANSIENT_MARKERS = (
    "Unable to initialize backend",
    "Mosaic",
    *OOM_MARKERS,
    "Invalid argument",
)

# Exception type names eligible for retry. Matched by name so the check
# works without importing jax at module import time. Validation failures
# (AssertionError, ValueError) are structurally excluded by this list.
# Plain RuntimeError is eligible because jaxlib surfaces some runtime
# statuses as one — but it still must carry a transient pattern in its
# message, so this framework's own RuntimeErrors (e.g. the plane-cap
# truncation raise, which signals a wrong configuration, not
# infrastructure) are never retried.
TRANSIENT_TYPE_NAMES = (
    "JaxRuntimeError",
    "XlaRuntimeError",
    "InternalError",
    "UnavailableError",
    "DeadlineExceededError",
    "RuntimeError",
)


def is_transient_failure(exc: BaseException) -> bool:
    """True for infrastructure-flavored runtime errors worth retrying —
    never for validation failures or deterministic compiler errors."""
    names = {t.__name__ for t in type(exc).__mro__}
    if not names.intersection(TRANSIENT_TYPE_NAMES):
        return False
    msg = str(exc)
    if any(p in msg for p in NON_TRANSIENT_MARKERS):
        return False
    return any(p in msg for p in TRANSIENT_PATTERNS)


def advance_with_recovery(
    make_engine,
    ckpt,
    *,
    engine=None,
    levels_per_chunk: int | None = None,
    max_level: int | None = None,
    save=None,
    max_restarts: int = 2,
    log=None,
):
    """Drive a checkpointed traversal to completion, surviving transient
    device/compile failures by rebuilding the engine and resuming from the
    last durable state.

    ``make_engine()`` must build a fresh engine over the same graph (the
    failure may have poisoned device buffers or the compile client);
    ``engine`` seeds the first attempt so callers reuse one they already
    built. ``save(ckpt)`` (optional) persists each chunk — the recovery
    point. Non-transient exceptions (wrong answers, OOM, truncation)
    propagate immediately; after ``max_restarts`` rebuilds the transient
    error propagates too. Returns ``(engine, ckpt, restarts)``.
    """
    if engine is None:
        engine = make_engine()
    restarts = 0
    while not ckpt.done and (max_level is None or ckpt.level < max_level):
        levels = levels_per_chunk
        if max_level is not None:
            room = max_level - ckpt.level
            levels = room if levels is None else min(levels, room)
        try:
            if _faults.ACTIVE is not None:
                # Chaos-harness injection site: a transient raised here is
                # handled by exactly the rebuild-and-resume path below —
                # the mechanism the ad-hoc per-test monkeypatches used to
                # approximate (tpu_bfs/faults.py).
                _faults.ACTIVE.hit("advance", level=ckpt.level)
            nxt = engine.advance(ckpt, levels=levels)
        except Exception as exc:  # noqa: BLE001 — gated by the classifier
            if restarts >= max_restarts or not is_transient_failure(exc):
                raise
            restarts += 1
            COUNTERS.bump("transient_retries")
            COUNTERS.bump("engine_rebuilds")
            if log is not None:
                log(
                    f"transient failure at level {ckpt.level} "
                    f"({type(exc).__name__}: {str(exc)[:200]}); rebuilding "
                    f"engine and resuming (restart {restarts}/{max_restarts})"
                )
            # Engine builds are compile-heavy too — the rebuild itself may
            # hit the same blip; keep it inside the restart budget.
            while True:
                try:
                    engine = make_engine()
                    break
                except Exception as exc2:  # noqa: BLE001
                    if restarts >= max_restarts or not is_transient_failure(exc2):
                        raise
                    restarts += 1
                    COUNTERS.bump("transient_retries")
                    COUNTERS.bump("engine_rebuilds")
                    if log is not None:
                        log(
                            f"transient failure rebuilding the engine "
                            f"({type(exc2).__name__}); retrying "
                            f"(restart {restarts}/{max_restarts})"
                        )
            continue
        ckpt = nxt
        if save is not None:
            save(ckpt)
    return engine, ckpt, restarts
