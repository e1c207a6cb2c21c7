"""Benchmark: Graph500-style BFS on a seeded RMAT graph, one real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline target: 10 GTEPS/chip (BASELINE.json north_star). TEPS follows the
Graph500 convention: traversed input edges / per-source time, harmonic mean
over sources. The flagship path is the 8192-lane hybrid MXU+gather
multi-source engine (tpu_bfs/algorithms/msbfs_hybrid.py, round-4 measured
default width): one batch run of N concurrent sources, per-source time =
batch time / N — the metric label says so explicitly.

Env overrides: TPU_BFS_BENCH_SCALE (default 21), TPU_BFS_BENCH_EF (16),
TPU_BFS_BENCH_MODE (hybrid|wide|msbfs|single|single-dopt|single-tiled|
dist|serve|lj-hybrid|lj-single-dopt — the lj-* modes bench the
LiveJournal-shaped stand-in, NONETWORK.md; 'dist' is the 1D distributed
single-source stage over the attached mesh, the ISSUE 5 wire-format A/B
with knobs TPU_BFS_BENCH_DIST_DEVICES (all attached) /
TPU_BFS_BENCH_DIST_EXCHANGE (ring|allreduce|sparse, default ring) /
TPU_BFS_BENCH_WIRE_PACK ("1" bit-packs the exchange to uint32 words —
default OFF until chip-measured, like the pull gate) /
TPU_BFS_BENCH_SPARSE_DELTA / TPU_BFS_BENCH_SPARSE_SIEVE /
TPU_BFS_BENCH_SPARSE_PREDICT (the ISSUE 7 exchange planner on the
sparse exchange — delta-encoded ids, backward visited sieve,
history-predictive selection; all default OFF until chip-measured),
emitting wire_bytes_per_level / wire_level_counts / wire_bytes_total;
'serve' is the closed-loop serve-throughput stage
over tpu_bfs/serve, emitting serve_qps/serve_p99_ms/fill_ratio/
serve_routing/serve_extract_p50_ms with knobs TPU_BFS_BENCH_SERVE_CLIENTS
(64) / TPU_BFS_BENCH_SERVE_QUERIES (8 per client) /
TPU_BFS_BENCH_SERVE_LANES (256, the ladder max) /
TPU_BFS_BENCH_SERVE_LADDER (auto|off|'32,128,...') /
TPU_BFS_BENCH_SERVE_PIPELINE (1) / TPU_BFS_BENCH_SERVE_ENGINE
(wide|hybrid|packed|dist2d) / TPU_BFS_BENCH_SERVE_DEVICES ('' = 1,
'all' = every attached device — distributed serving, ISSUE 11) /
TPU_BFS_BENCH_SERVE_EXCHANGE / TPU_BFS_BENCH_SERVE_PULL_GATE (0) /
TPU_BFS_BENCH_SERVE_RESUME (0 — dist2d level-checkpoint cadence K,
ISSUE 12) / TPU_BFS_BENCH_SERVE_AUDIT_RATE (0 — the online integrity
tier's shadow-audit sampling fraction, ISSUE 15; > 0 also arms the
structural tree checks) / TPU_BFS_BENCH_SERVE_AUDIT_CHECKSUM (0 — wire
checksums on the audited transfers), emitting serve_audits_run /
serve_audit_failures / serve_audit_p50_lag_ms / serve_quarantines /
TPU_BFS_BENCH_SERVE_CACHE (0 — the answer cache, ISSUE 18: '1' = the
64 MB default byte budget, else a raw byte budget) /
TPU_BFS_BENCH_SERVE_LANDMARKS (0 — K landmark distance columns);
either arms a second Zipf(s=1.0) closed loop emitting
serve_cache_hit_rate / serve_landmark_hit_rate / serve_hit_p50_ms /
serve_traversal_p50_ms / TPU_BFS_BENCH_MUTATIONS (0 — dynamic graphs,
ISSUE 19: N streaming edge-update flips applied under a closed loop;
TPU_BFS_BENCH_MUTATIONS_OVERLAY 'DxK' sizes the overlay, default
256x32), emitting serve_flip_p50_ms / serve_overlay_occupancy /
serve_mutation_dropped / TPU_BFS_BENCH_DIST_KINDS (ISSUE 20: every
workload kind over the full mesh — a second wide service with the
(min,+)-capable sparse exchange; per-kind p50 / gteps_hmean /
wire_bytes_per_query plus the modeled labelled wire_bytes_per_level
table land under 'dist_kinds'; knobs TPU_BFS_BENCH_DIST_KINDS_LANES
(32) / TPU_BFS_BENCH_DIST_KINDS_QUERIES (6 per kind)), plus the
PR 5/7 wire knobs; mesh runs add serve_gteps_p50 /
serve_gteps_hmean / serve_wire_bytes_per_query plus the mesh-fault
record serve_mesh_faults/serve_mesh_degrades/serve_query_resumes/
serve_devices_final to the verdict, and
TPU_BFS_BENCH_VALIDATE_MODE=structure swaps the SciPy oracle for
Graph500-style tree-property checks at oracle-infeasible scales),
TPU_BFS_BENCH_LANES (msbfs mode, 512), TPU_BFS_BENCH_MAX_LANES (hybrid/wide
modes, 8192 = the measured default — sweep knob), TPU_BFS_BENCH_SOURCES (single
modes, 8), TPU_BFS_BENCH_VALIDATE (1), TPU_BFS_BENCH_VALIDATE_LANES (4),
TPU_BFS_BENCH_CACHE (.bench_cache), TPU_BFS_BENCH_BUDGET_S (1200 — the
outage envelope's wall-clock budget; 0 disables; on exhaustion the one JSON
line carries value=null plus a machine-readable "error", and the exit code
is nonzero),
TPU_BFS_BENCH_ADAPTIVE (level-adaptive push for the hybrid/wide modes —
default ON at the measured "8192,64"; "rows,deg" overrides, "0"/"off"
disables; BENCHMARKS.md "Level-adaptive expansion"),
TPU_BFS_BENCH_PULL_GATE (frontier-aware pull gate for the hybrid/wide
modes, ISSUE 1 — "1" enables; forces adaptive push off so A/B arms stay
clean; the result JSON gains per-level "gate_level_counts"),
TPU_BFS_BENCH_UNATTENDED ("1" adds SIGINT to the signal envelope's
sigwait set even on a tty; by default only SIGTERM is watched
interactively, so Ctrl-C keeps raising KeyboardInterrupt),
TPU_BFS_BENCH_KCAP / TPU_BFS_BENCH_TILE_THR / TPU_BFS_BENCH_A_BUDGET
(hybrid structure sweep knobs: residual ELL bucket cap, dense-tile edge
threshold, dense-tile byte budget; defaults 64 / 64 / 0.2e9 — the
measured flagship optima),
TPU_BFS_BENCH_OBS (serve mode: arm the telemetry recorder, spec grammar
of tpu_bfs/obs — the verdict gains serve_obs_events/serve_flight_dumps/
serve_trace), TPU_BFS_BENCH_TRACE_OUT (dist + serve modes: write a
Chrome/Perfetto trace-event JSON here; dist mode always emits the "trace"
per-level summary keys — BENCHMARKS.md "Trace summary").
"""

import json
import os
import sys
import threading
import time


def _budget_seconds() -> float:
    """TPU_BFS_BENCH_BUDGET_S as a float — THE one parse of the knob,
    shared by the import-time signal-mask decision and _arm_budget so the
    '<= 0 disables the envelope' rule cannot drift between them (a
    mismatch would block signals with no watcher installed, or vice
    versa). A malformed value reads as the 1200 s default (envelope on);
    _arm_budget logs the complaint once at arm time."""
    try:
        return float(os.environ.get("TPU_BFS_BENCH_BUDGET_S", "1200"))
    except ValueError:
        return 1200.0


def _envelope_signal_set() -> tuple:
    """The signals the outage envelope watches. SIGTERM (the driver's
    kill) always; SIGINT only when stdout is not a tty or
    TPU_BFS_BENCH_UNATTENDED=1 — an interactive Ctrl-C must keep raising
    KeyboardInterrupt with a traceback instead of a verdict line (ADVICE
    r5; previously only the BUDGET_S=0 debug mode preserved that). Empty when TPU_BFS_BENCH_BUDGET_S <= 0, the
    documented interactive debug mode where no signal is intercepted."""
    import signal

    if _budget_seconds() <= 0:
        return ()
    sigs = (signal.SIGTERM,)
    if (
        not sys.stdout.isatty()
        or os.environ.get("TPU_BFS_BENCH_UNATTENDED") == "1"
    ):
        sigs = sigs + (signal.SIGINT,)
    return sigs


# The mask must be blocked BEFORE numpy's import: its BLAS pool threads
# inherit the creating thread's mask at spawn, and the kernel may deliver
# a process-directed SIGTERM to ANY thread that leaves it unblocked — so
# blocking only in _install_signal_envelope (after the numpy import) left
# the envelope armed yet unable to intercept; the signal drills died
# rc=143 deterministically on exactly this. Script path only: under
# pytest, bench imports as a module and the host's mask stays untouched.
_ENVELOPE_SIGS: tuple = ()
if __name__ == "__main__":
    _ENVELOPE_SIGS = _envelope_signal_set()
    if _ENVELOPE_SIGS:
        import signal as _signal

        _signal.pthread_sigmask(_signal.SIG_BLOCK, _ENVELOPE_SIGS)

import numpy as np


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Outage envelope.
#
# The one JSON line must land however a run ends, and a run that did not
# measure must say so: value=null, a machine-readable "error", and a
# NONZERO exit code. Every run carries a wall-clock budget
# (TPU_BFS_BENCH_BUDGET_S, default 1200 s):
#
# - Cooperative path: retry waits derate to the remaining budget, and when
#   a retry cannot fit, BudgetExhausted propagates to main(), which prints
#   the verdict and exits 1.
# - Hard path: a single attempt can block past the budget (a wedged compile
#   or device call; no cooperative check can run). A daemon watchdog timer
#   fires at the deadline, prints the same verdict line, and exits 124
#   (the `timeout` convention).
# - Kill path: if the driver's signal arrives before either, a sigwait
#   watcher thread (_install_signal_envelope) prints the verdict and exits
#   128 + signum — works even while the main thread is pinned inside a C
#   call, where an ordinary Python signal handler could never run.
#
# A lost run never echoes an older number: an echo would read as a
# measurement of a tree it never ran on.
# ---------------------------------------------------------------------------

_DEADLINE: float | None = None  # time.monotonic() deadline, set by main()


class BudgetExhausted(RuntimeError):
    """The wall-clock budget cannot fit another retry; carries the last
    transient error and how long the resource has been unavailable."""

    def __init__(self, cause: BaseException, unavailable_s: float):
        self.cause = cause
        self.unavailable_s = unavailable_s
        super().__init__(
            f"bench budget exhausted after {unavailable_s:.0f}s of "
            f"transient failures; last: {type(cause).__name__}: "
            f"{str(cause)[:300]}"
        )


def _budget_remaining() -> float:
    return float("inf") if _DEADLINE is None else _DEADLINE - time.monotonic()


def _backend_came_up() -> bool:
    """True iff a jax backend finished initializing in this process —
    checked WITHOUT triggering initialization (the watchdog must never
    block on the probe it exists to escape). Best-effort over jax's
    backend registry; an unexpected jax internals change reads as
    'unknown' -> False (the conservative 'unavailable' attribution)."""
    import sys as _sys

    jax_mod = _sys.modules.get("jax")
    if jax_mod is None:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:  # noqa: BLE001 — private API, attribution only
        return False


def _failure_payload(mode: str, error: str) -> dict:
    return {
        "metric": f"BFS harmonic-mean GTEPS (mode={mode}) — run lost",
        "value": None,
        "unit": "GTEPS",
        "vs_baseline": None,
        "error": error,
    }


def _result_log_path() -> str:
    return os.environ.get(
        "TPU_BFS_BENCH_RESULT_LOG",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "bench_results.jsonl"),
    )


# Set (to the would-be exit code) the moment main() has printed its real
# verdict line — fresh result, outage verdict, or deterministic-failure
# verdict. A driver signal landing after that point (e.g. during the
# _log_result append) must exit with THAT outcome, not append a lost-run
# line as the new last line (scripts/has_value.py reads only the last
# line, so a trailing verdict would un-land a landed measurement). The
# print and the assignment happen under _VERDICT_LOCK, which the
# watcher/watchdog also take before emitting their payload — no signal
# can land between main()'s print and the assignment (ADVICE r5).
_FINAL_RC: int | None = None
_VERDICT_LOCK = threading.Lock()


def _print_verdict(payload: dict, rc: int) -> int:
    """main()'s verdict emission: one JSON line + the final-rc record,
    atomically w.r.t. the watcher/watchdog payload paths."""
    global _FINAL_RC
    with _VERDICT_LOCK:
        print(json.dumps(payload))
        _FINAL_RC = rc
    return rc


def _install_signal_envelope(mode: str) -> None:
    """Answer the driver's catchable kill with the structured verdict. An
    ordinary Python signal handler only runs when the main thread reaches
    bytecode — a main thread blocked inside one long C call (a compile, a
    device wait) would die without printing. So instead: the watched set
    (_envelope_signal_set — SIGTERM always, SIGINT only for
    non-tty/unattended runs) is blocked in every thread at module import,
    before numpy can spawn unmasked BLAS threads, and sigwait()ed here in a
    dedicated watcher, which prints the value=null verdict and exits
    128 + signum no matter what the main thread is stuck in. Subprocesses
    unblock the inherited mask (utils/native.py).

    Installed only on the script path (__main__): under pytest, main()
    runs in-process and must not alter the host's signal mask. A no-op
    when _ENVELOPE_SIGS is empty (TPU_BFS_BENCH_BUDGET_S=0, the
    documented interactive debugging mode, where Ctrl-C must keep raising
    KeyboardInterrupt with a traceback instead of a verdict line)."""
    import signal

    sigs = _ENVELOPE_SIGS
    if not sigs:
        return

    def watch() -> None:
        signum = signal.sigwait(sigs)
        with _VERDICT_LOCK:
            if _FINAL_RC is not None:
                os._exit(_FINAL_RC)  # verdict already printed; preserve it
            payload = _failure_payload(
                mode,
                f"killed by {signal.Signals(signum).name} mid-run; "
                f"structured verdict emitted by the signal envelope",
            )
            # stdout may hold a partial line from the main thread; start
            # fresh.
            sys.stdout.write("\n" + json.dumps(payload) + "\n")
            sys.stdout.flush()
            os._exit(128 + signum)

    threading.Thread(target=watch, daemon=True, name="signal-envelope").start()


def _arm_budget(mode: str) -> threading.Timer | None:
    """Set the cooperative deadline and arm the hard watchdog. Returns the
    timer (cancel on success) or None when the budget is disabled."""
    global _DEADLINE
    _DEADLINE = None
    raw = os.environ.get("TPU_BFS_BENCH_BUDGET_S", "1200")
    budget = _budget_seconds()  # the one shared parse (see its docstring)
    try:
        float(raw)
    except ValueError:
        log(f"TPU_BFS_BENCH_BUDGET_S={raw!r} is not a number; using 1200")
    if budget <= 0:  # 0 disables the envelope (e.g. interactive debugging)
        return None
    _DEADLINE = time.monotonic() + budget

    def fire() -> None:
        # Last resort: a single attempt blocked through the whole budget.
        # Attribute honestly — "no backend came up" only when none did; a
        # live backend means the run was healthy but slow, and the verdict
        # must say the BUDGET lost the measurement, not an outage that
        # never happened.
        error = (
            f"wall-clock budget {budget:.0f}s exhausted inside a "
            f"blocking attempt; no backend came up"
        )
        if _backend_came_up():
            error = (
                f"wall-clock budget {budget:.0f}s exhausted mid-run on a "
                f"LIVE backend — measurement lost to the budget, not an "
                f"outage; raise TPU_BFS_BENCH_BUDGET_S"
            )
        with _VERDICT_LOCK:
            if _FINAL_RC is not None:
                os._exit(_FINAL_RC)  # verdict already printed; preserve it
            # stdout may hold a partial line from the main thread; start
            # fresh on our own line.
            sys.stdout.write(
                "\n" + json.dumps(_failure_payload(mode, error)) + "\n"
            )
            sys.stdout.flush()
            os._exit(124)

    timer = threading.Timer(budget, fire)
    timer.daemon = True
    timer.start()
    log(f"outage envelope armed: {budget:.0f}s wall-clock budget")
    return timer


# ---------------------------------------------------------------------------
# Transient-failure retry.
#
# Every compile-heavy stage (engine build, pilot, timed batch, on-chip
# Pallas cross-check) runs under a bounded retry that fires ONLY for
# infrastructure-flavored runtime errors — never for validation failures
# (AssertionError et al. propagate on first occurrence, always) and never
# for a backend that fails to initialize (utils/recovery.py).
# ---------------------------------------------------------------------------

def _is_transient(exc: BaseException) -> bool:
    # The transient/deterministic classifier is shared with the in-run
    # failure-recovery machinery (tpu_bfs/utils/recovery.py) — one
    # definition of "worth retrying" for both the bench and checkpointed
    # traversals. Imported lazily: importing tpu_bfs pulls in jax, and
    # bench.py must stay importable (e.g. for cache regeneration) on hosts
    # where the accelerator stack is broken.
    from tpu_bfs.utils.recovery import is_transient_failure

    return is_transient_failure(exc)


def retry_transient(fn, *args, attempts: int = 3, backoff_s: float = 5.0,
                    label: str = "", **kwargs):
    """Call ``fn(*args, **kwargs)``; on a transient infra error retry up to
    ``attempts`` total tries with linear backoff, logging each retry to
    stderr. Non-transient exceptions (validation failures and backend-init
    failures above all) propagate immediately."""
    # Per-ladder outage clock: unavailable_s spans this ladder's failures.
    # (A nested ladder that exhausts its attempts raises the raw error; the
    # outer ladder then starts its own clock, slightly undercounting the
    # inner ladder's time — an informational loss, never a stale or
    # negative duration across unrelated runs in one process.)
    first_transient = None
    for attempt in range(1, attempts + 1):
        try:
            return fn(*args, **kwargs)
        except BudgetExhausted:
            # From a nested retry ladder: the budget verdict is final —
            # re-classifying it as transient would loop on a spent budget.
            raise
        except Exception as exc:  # noqa: BLE001 — filtered by _is_transient
            if attempt >= attempts or not _is_transient(exc):
                raise
            if first_transient is None:
                first_transient = time.monotonic()
            from tpu_bfs.utils.recovery import COUNTERS

            COUNTERS.bump("transient_retries")
            wait = backoff_s * attempt
            # Outage envelope: a retry only makes sense if the wait AND a
            # meaningful attempt still fit the wall-clock budget. Derate
            # the wait toward the deadline; below the floor, fail fast
            # with the structured verdict instead of being timeout-killed
            # mid-sleep.
            remaining = _budget_remaining()
            min_attempt_s = 10.0  # below this the retry cannot do real work
            if wait + min_attempt_s > remaining:
                derated = remaining - min_attempt_s
                if derated < 1.0:
                    raise BudgetExhausted(
                        exc, time.monotonic() - first_transient
                    ) from exc
                log(
                    f"derating retry wait {wait:.0f}s -> {derated:.0f}s to "
                    f"fit the remaining {remaining:.0f}s budget"
                )
                wait = derated
            log(
                f"transient failure in {label or getattr(fn, '__name__', 'stage')} "
                f"(attempt {attempt}/{attempts}): {type(exc).__name__}: "
                f"{str(exc)[:300]} -- retrying in {wait:.0f}s"
            )
            time.sleep(wait)


def _env_max_lanes(*, default: int) -> int:
    """TPU_BFS_BENCH_MAX_LANES, clamped into the engines' legal range so a
    typo'd env var degrades to a logged clamp instead of crashing the bench
    after a minutes-long engine build (the constructors also validate
    early, but the bench's job is to always emit its one JSON line).

    Clamps to a power-of-two word count: auto sizing can only ever pick
    those, so e.g. 12288 would silently bench at 8192 — better to say so
    up front. Bounded by the stricter of the two engines' caps (both are
    4 * LANES today; min() keeps the bench safe if they ever diverge)."""
    from tpu_bfs.algorithms._packed_common import floor_lanes
    from tpu_bfs.algorithms.msbfs_hybrid import MAX_LANES as HYB_MAX
    from tpu_bfs.algorithms.msbfs_wide import MAX_LANES as WIDE_MAX

    val = os.environ.get("TPU_BFS_BENCH_MAX_LANES", str(default))
    try:
        raw = int(val)
    except ValueError:
        log(f"TPU_BFS_BENCH_MAX_LANES={val!r} is not an integer; "
            f"using {default}")
        return default
    clamped = floor_lanes(min(max(raw, 32), min(HYB_MAX, WIDE_MAX)))
    if clamped != raw:
        log(f"TPU_BFS_BENCH_MAX_LANES={raw} not a reachable width; "
            f"clamped to {clamped}")
    return clamped


def _env_adaptive():
    """TPU_BFS_BENCH_ADAPTIVE -> (rows, deg) or None.

    Default ON at the measured caps (8192, 64): the round-4 chip session
    measured the level-adaptive push at 62.21 GTEPS vs 55.96 plain on the
    8192-lane flagship (oracle-validated at full width). "rows,deg"
    overrides the caps; "0"/"off" disables. A malformed value degrades to
    a logged 'off' (never crash a flagship build mid-bench)."""
    raw = os.environ.get("TPU_BFS_BENCH_ADAPTIVE", "").strip().lower()
    if raw in ("0", "off", "no", "false"):
        log("adaptive push disabled by TPU_BFS_BENCH_ADAPTIVE")
        return None
    if not raw:
        log("adaptive push on (default): row_cap=8192 deg_cap=64")
        return (8192, 64)
    try:
        r, d = (int(t) for t in raw.split(","))
        if r < 1 or d < 1:
            raise ValueError
    except ValueError:
        log(f"TPU_BFS_BENCH_ADAPTIVE={raw!r} must be ROWS,DEG positive "
            f"ints or 0/off; adaptive push off")
        return None
    log(f"adaptive push enabled: row_cap={r} deg_cap={d}")
    return (r, d)


def _env_bool(name: str, what: str, off_word: str) -> bool:
    """Opt-in boolean knob: unset/falsy -> False, logged when enabled,
    malformed values logged and treated as off (a chip session must never
    die on a typo'd env var — it just runs the default arm)."""
    raw = os.environ.get(name, "").strip().lower()
    on = raw in ("1", "on", "yes", "true")
    if on:
        log(f"{what} enabled ({name})")
    elif raw and raw not in ("0", "off", "no", "false"):
        log(f"{name}={raw!r} not a boolean; {off_word} off")
    return on


def _env_pull_gate() -> bool:
    """TPU_BFS_BENCH_PULL_GATE -> bool (default off, matching the engines'
    default until the gate is chip-measured). When on, the adaptive-push
    default is forced off with a log line — the engines reject the
    combination (ISSUE 1: measure the gate against the plain scan)."""
    return _env_bool("TPU_BFS_BENCH_PULL_GATE", "pull gate", "gate")


def _env_expand_impl() -> str:
    """TPU_BFS_BENCH_EXPAND_IMPL -> 'xla' (default) or 'pallas' (the
    fused bucketed-ELL expansion kernel, ISSUE 16 — default off until
    chip-measured, like the pull gate it composes with). Pallas runs are
    bit-identical to xla (fuzz-pinned), so the A/B pair isolates the
    kernel tier's win; malformed values log and run the default tier."""
    raw = os.environ.get("TPU_BFS_BENCH_EXPAND_IMPL", "").strip().lower()
    if not raw or raw == "xla":
        return "xla"
    if raw == "pallas":
        log("pallas expansion tier enabled (TPU_BFS_BENCH_EXPAND_IMPL)")
        return "pallas"
    log(f"TPU_BFS_BENCH_EXPAND_IMPL={raw!r} not one of xla|pallas; "
        f"xla tier")
    return "xla"


def _env_wire_pack() -> bool:
    """TPU_BFS_BENCH_WIRE_PACK -> bool (default off until chip-measured,
    like the pull gate — ISSUE 5). Applies to the dist mode's exchange;
    packed runs are bit-identical to plain (fuzz-pinned), so the A/B pair
    isolates the wire-format win."""
    return _env_bool("TPU_BFS_BENCH_WIRE_PACK", "wire pack", "pack")


def _env_sparse_planner() -> tuple[tuple[int, ...], bool, bool]:
    """The ISSUE 7 exchange-planner knobs (all default off until
    chip-measured, like wire_pack): TPU_BFS_BENCH_SPARSE_DELTA (8/16-bit
    delta-encoded id chunks), TPU_BFS_BENCH_SPARSE_SIEVE (backward
    visited sieve), TPU_BFS_BENCH_SPARSE_PREDICT (history-predictive
    dense selection). They apply to the dist mode's sparse exchange
    (TPU_BFS_BENCH_DIST_EXCHANGE=sparse); planner runs are bit-identical
    to plain sparse (fuzz-pinned), so the A/B stages isolate each
    format's wire win."""
    delta = _env_bool("TPU_BFS_BENCH_SPARSE_DELTA", "sparse delta", "delta")
    sieve = _env_bool("TPU_BFS_BENCH_SPARSE_SIEVE", "visited sieve", "sieve")
    predict = _env_bool(
        "TPU_BFS_BENCH_SPARSE_PREDICT", "exchange predictor", "predictor"
    )
    from tpu_bfs.parallel.collectives import DELTA_BITS_DEFAULT

    return (DELTA_BITS_DEFAULT if delta else (), sieve, predict)


def _is_oom(exc: BaseException) -> bool:
    """Deterministic out-of-HBM flavors (XLA compile- or run-time). Not
    transient — but when the adaptive push table is resident, shedding it
    and re-running plain is a legitimate fallback (see bench_hybrid).
    Lazy import: one marker set shared with the recovery classifier."""
    from tpu_bfs.utils.recovery import is_oom_failure

    return is_oom_failure(exc)


class _ShedRetry(Exception):
    """Internal: raised inside a packed bench's run_once when the adaptive
    configuration cannot be built and the plain re-bench should happen
    (the reason is already logged)."""


def _with_adaptive_shed(run_once, rebench_plain, adaptive, what: str):
    """Run one packed bench attempt; on an OOM (or an explicit _ShedRetry)
    with the push table resident, re-bench plain.

    One shared copy of a subtle dance (bench_hybrid and bench_wide both
    need it): the ENGINE BUILD and the batch both run inside ``run_once``,
    so a RESOURCE_EXHAUSTED raised while transferring the push table — not
    just one raised mid-batch — reaches the shed; and the plain re-bench
    runs AFTER the except block, when the raised frames (which reference
    the OOM'd engine's device tables) have been dropped, so the rebuild
    doesn't have to fit next to the dying engine's allocations. Sizing
    models can't see every XLA temp (the round-4 LJ run OOM'd at
    16.22G/15.75G with the table resident); the shed costs ~10% measured,
    an rc=1 loses the number entirely."""
    try:
        return run_once()
    except _ShedRetry:
        pass  # reason already logged at the raise site
    except Exception as exc:  # noqa: BLE001 — OOM-shed fallback only
        if adaptive is None or not _is_oom(exc):
            raise
        log(f"{what}+adaptive OOM ({str(exc)[:200]}); shedding the push "
            f"table and re-benching plain")
    from tpu_bfs.utils.recovery import COUNTERS

    COUNTERS.bump("oom_degrades")
    return rebench_plain()


def load_graph(scale: int, ef: int):
    """Seeded RMAT graph, cached as npz so repeated bench runs skip the
    ~1 min/2^20-vertex generation cost."""
    from tpu_bfs.graph.csr import Graph
    from tpu_bfs.graph.generate import rmat_graph

    from tpu_bfs.utils.native import ensure_built, has_rmat

    ensure_built(log=log)

    # Probe the generator symbol itself, not just that the library loads: a
    # stale prebuilt .so plus a failed make would otherwise crash the bench
    # inside rmat_graph(impl='native') instead of falling back.
    impl = "native" if has_rmat() else "numpy"
    cache_dir = os.environ.get("TPU_BFS_BENCH_CACHE", ".bench_cache")
    # The two generator impls are different streams; tag the cache so a
    # numpy-generated graph is never reused as a "native" one or vice versa.
    tag = "" if impl == "numpy" else f"_{impl}"
    path = os.path.join(cache_dir, f"rmat_s{scale}_ef{ef}_seed1{tag}.npz")
    t0 = time.perf_counter()
    if os.path.exists(path):
        z = np.load(path)
        g = Graph(
            row_ptr=z["row_ptr"],
            col_idx=z["col_idx"],
            num_input_edges=int(z["num_input_edges"]),
            undirected=True,
        )
        log(f"rmat scale={scale} ef={ef} [{impl}]: cached load {time.perf_counter()-t0:.1f}s")
        return g
    g = rmat_graph(scale, ef, seed=1, impl=impl)
    log(
        f"rmat scale={scale} ef={ef} [{impl}]: V={g.num_vertices} "
        f"slots={g.num_edges} gen={time.perf_counter()-t0:.1f}s"
    )
    try:
        os.makedirs(cache_dir, exist_ok=True)
        np.savez(
            path,
            row_ptr=g.row_ptr,
            col_idx=g.col_idx,
            num_input_edges=g.num_input_edges,
        )
    except OSError as exc:  # cache is best-effort
        log(f"cache write skipped: {exc}")
    return g


def _validate_tile_spmm_compiled(engine) -> None:
    """Compiled-vs-interpret cross-check of the Pallas MXU kernel on the
    REAL graph's bit-packed tiles (a random frontier over the densest
    row-tiles' production operands; prefix size TPU_BFS_BENCH_SPMM_TILES,
    default 64 row-tiles). CI only ever runs tile_spmm in interpret mode
    on CPU (tests/test_tile_spmm.py); this is the on-hardware guard
    against Mosaic layout divergence, run on every TPU bench alongside the
    end-to-end lane validation."""
    import jax
    import numpy as np

    from tpu_bfs.ops.tile_spmm import tile_spmm

    platform = jax.default_backend()
    if platform != "tpu":
        if platform == "cpu" and jax.config.jax_platforms == "cpu":
            return  # explicitly on the CPU: interpret mode is all there is
        raise RuntimeError(
            f"the on-chip tile_spmm check needs a TPU; the backend is "
            f"{platform!r} (set JAX_PLATFORMS=cpu to run on the CPU)"
        )
    if not getattr(engine.hg, "num_tiles", 0):
        return
    hg = engine.hg
    t0 = time.perf_counter()
    # Row-tile prefix (TPU_BFS_BENCH_SPMM_TILES, default 16): rank order
    # puts the densest rows first, so even a small prefix covers a big
    # slice of the tile population (64 row-tiles still hold 43k of
    # scale-21's 98k tiles — but interpret mode prices them at 2-5 min
    # under chip contention, too slow for every bench run) — raise it for
    # a deep audit.
    nrt = min(int(os.environ.get("TPU_BFS_BENCH_SPMM_TILES", "16")), hg.vt)
    end = int(hg.row_start[nrt])
    if end == 0:
        return
    row_start = hg.row_start[: nrt + 1]
    rng = np.random.default_rng(11)
    fw = rng.integers(0, 2**32, size=(hg.vt * 128, engine.w), dtype=np.uint32)
    args = (row_start, hg.col_tile[:end], hg.a_tiles[:end], fw)
    out_c = np.asarray(
        retry_transient(
            tile_spmm, *args, num_row_tiles=nrt, w=engine.w, interpret=False,
            label="tile_spmm compiled check",
        )
    )
    out_i = np.asarray(
        tile_spmm(*args, num_row_tiles=nrt, w=engine.w, interpret=True)
    )
    np.testing.assert_array_equal(out_c, out_i)
    log(
        f"tile_spmm compiled==interpret on {end} production tiles "
        f"({nrt} row-tiles) in {time.perf_counter()-t0:.1f}s"
    )


def lj_impl() -> str:
    """Which edge-stream generator the LJ stand-in uses on this machine.

    The native and numpy RMAT builders are different deterministic streams;
    pinning the choice per-machine and RECORDING it (cache filenames, .mtx
    comment, metric description) keeps the lj-* numbers attributable —
    cross-machine runs compare like with like or say why not."""
    from tpu_bfs.utils.native import has_rmat

    return "native" if has_rmat() else "numpy"


def load_graph_lj():
    """The LiveJournal-shaped stand-in (NONETWORK.md): generate once, write
    the 1.0 GiB .mtx, ingest through the native loader path, cache the CSR.
    This is the reproducible entry point behind BENCHMARKS.md's
    "LiveJournal-shaped stand-in" table (TPU_BFS_BENCH_MODE=lj-hybrid /
    lj-single-dopt)."""
    from tpu_bfs.graph.generate import LJ_E, LJ_V, lj_standin_edges, write_mtx
    from tpu_bfs.graph.io import load_edge_list, load_npz, save_npz
    from tpu_bfs.utils.native import ensure_built

    ensure_built(log=log)
    impl = lj_impl()
    cache_dir = os.environ.get("TPU_BFS_BENCH_CACHE", ".bench_cache")
    os.makedirs(cache_dir, exist_ok=True)
    mtx = os.path.join(cache_dir, f"soc-LiveJournal1-standin-{impl}.mtx")
    npz = os.path.join(cache_dir, f"lj_standin_csr_{impl}.npz")
    # Pre-suffix caches (impl="auto" era) are NOT adopted: auto resolved
    # per-run, so a legacy file's stream is unattributable (a then-broken
    # native build would have silently produced numpy data). One
    # regeneration buys correctly-labeled numbers.
    if os.path.exists(npz):
        t0 = time.perf_counter()
        g = load_npz(npz)
        log(f"LJ stand-in [{impl}]: cached CSR load {time.perf_counter()-t0:.1f}s")
        return g
    if not os.path.exists(mtx):
        t0 = time.perf_counter()
        u, v = lj_standin_edges(seed=1, impl=impl)
        log(f"LJ stand-in gen [{impl}] {time.perf_counter()-t0:.1f}s: "
            f"{len(u)} directed edges")
        t0 = time.perf_counter()
        write_mtx(mtx, u, v, LJ_V,
                  comment="synthetic soc-LiveJournal1 stand-in (see "
                          f"NONETWORK.md; {impl} edge stream, seed=1)")
        log(f"write {mtx} {time.perf_counter()-t0:.1f}s "
            f"({os.path.getsize(mtx)/2**30:.2f} GiB)")
        del u, v
    t0 = time.perf_counter()
    g = load_edge_list(mtx)
    log(f"ingest via native .mtx path {time.perf_counter()-t0:.1f}s: "
        f"V={g.num_vertices} slots={g.num_edges} input={g.num_input_edges}")
    assert g.num_vertices == LJ_V and g.num_input_edges == LJ_E
    try:
        save_npz(npz, g)
    except OSError as exc:
        log(f"CSR cache write skipped: {exc}")
    return g


def _bench_batch_packed(g, graph_desc, engine, in_degree, build_log: str, label: str) -> dict:
    """Shared protocol of the wide packed-batch benches: hub pilot (doubles as
    compile warm-up), search keys from the hub's traversable component
    (Graph500 samples among degree>=1 vertices), one timed batch, N-lane
    SciPy validation (TPU_BFS_BENCH_VALIDATE_LANES, default 4, spread
    across the word/bit lane space) + compiled-vs-interpret Pallas check."""
    from tpu_bfs.algorithms.msbfs_packed import UNREACHED

    do_validate = os.environ.get("TPU_BFS_BENCH_VALIDATE", "1") == "1"
    lanes = engine.lanes
    log(build_log)

    t0 = time.perf_counter()
    hub = int(np.argmax(in_degree))  # original-id order
    pilot = retry_transient(engine.run, np.array([hub]), label="pilot run")
    traversable = np.flatnonzero(pilot.distance_u8_lane(0) != UNREACHED)
    del pilot  # frees device-resident planes before the batch
    log(
        f"pilot+compile {time.perf_counter()-t0:.1f}s: traversable "
        f"{len(traversable)}/{g.num_vertices}"
    )
    rng = np.random.default_rng(7)
    sources = rng.choice(traversable, size=lanes, replace=len(traversable) < lanes)

    res = retry_transient(engine.run, sources, time_it=True, label="timed batch")
    gteps = res.teps / 1e9
    log(
        f"batch {res.elapsed_s*1e3:.1f}ms, {lanes} sources, levels="
        f"{res.num_levels}, per-src {res.elapsed_s/lanes*1e3:.3f}ms, "
        f"hmean GTEPS={gteps:.3f}"
    )

    if do_validate:
        from tpu_bfs.reference import bfs_scipy

        t0 = time.perf_counter()
        nv = int(os.environ.get("TPU_BFS_BENCH_VALIDATE_LANES", "4"))
        # First/mid/last lanes always checked, plus nv evenly spread picks
        # (deduplicated, never truncated): every word-column region of the
        # packed tables — including the last word's high bits — contains a
        # validated lane, so a localized lane-map/Mosaic layout bug shows.
        picks = sorted(
            {0, lanes // 2, lanes - 1}
            | {int(x) for x in np.linspace(0, lanes - 1, nv).round()}
        )
        for i in picks:
            expected = bfs_scipy(g, int(sources[i]))
            np.testing.assert_array_equal(res.distances_int32(i), expected)
        log(f"validated {len(picks)} lanes {picks} in {time.perf_counter()-t0:.1f}s")
        if hasattr(engine, "hg"):
            _validate_tile_spmm_compiled(engine)

    result = {
        "metric": (
            f"BFS harmonic-mean per-source GTEPS ({lanes}-source {label} "
            f"MS-BFS batch), {graph_desc}, 1 chip"
        ),
        "value": round(gteps, 4),
        "unit": "GTEPS",
        "vs_baseline": round(gteps / 10.0, 4),
    }
    gc = getattr(engine, "last_gate_level_counts", None)
    if gc is not None:
        # Per-level skipped blocks of the timed batch (ISSUE 1 acceptance:
        # gated-tile counts in the stats JSON) — extra keys are ignored by
        # scripts/has_value.py, which reads only "value"/"stale".
        result["gate_level_counts"] = [
            int(x) for x in np.asarray(gc)[: res.num_levels + 1]
        ]
    if getattr(engine, "expand_impl", "xla") != "xla":
        # Kernel-tier verdict keys (ISSUE 16): which tier ran, the
        # per-kernel VMEM-resident byte bound of one ungated level
        # (ops/ell_expand.ell_expand_hbm_bytes), and per-level modeled
        # kernel bytes. Gated runs scale each level by its skipped-tile
        # count, assuming skips distribute across kernels in proportion
        # to their tile counts (the counter is bucket-aggregated); the
        # floor is the all-skipped identity-write cost.
        from tpu_bfs.utils.roofline import pallas_expand_bytes

        result["expand_impl"] = engine.expand_impl
        pal = pallas_expand_bytes(engine)
        full = sum(pal.values())
        result["expand_kernel_bytes"] = {
            **{k: int(v) for k, v in pal.items()},
            "level_total": int(full),
        }
        levels = res.num_levels + 1
        gcl = result.get("gate_level_counts")
        if gcl:
            zero = sum(pallas_expand_bytes(engine, active_tiles=0).values())
            from tpu_bfs.ops.ell_expand import TILE as KTILE

            nb_tot = sum(
                int(t.shape[-1]) // KTILE
                for n, t in engine.arrs.items()
                if n.endswith("_gt") and "_w" not in n
            )
            save = (full - zero) / max(nb_tot, 1)
            result["expand_kernel_bytes_per_level"] = [
                int(max(full - s * save, zero)) for s in gcl
            ]
        else:
            result["expand_kernel_bytes_per_level"] = [int(full)] * levels
    return result


def bench_hybrid(g, scale: int, ef: int, graph_desc: str | None = None,
                 _shed_adaptive: bool = False) -> dict:
    """Flagship: hybrid MXU+gather MS-BFS (msbfs_hybrid.py), default width
    8192 lanes (the round-4 measured optimum; auto sizing walks down).

    Falls back to the gather-only wide engine when the graph's packed state
    cannot fit 4096 lanes next to the dense tiles (the Pallas kernel needs
    w % 128 == 0, so 4096 lanes is its minimum width). ``_shed_adaptive``
    is the internal OOM-fallback flag: a re-bench with the push table
    dropped (parameter, not env mutation — the shed must not leak into
    later runs in the same process)."""
    from tpu_bfs.algorithms._packed_common import auto_lanes, auto_planes
    from tpu_bfs.algorithms.msbfs_hybrid import (
        DEFAULT_MAX_LANES,
        LANES,
        HybridMsBfsEngine,
        LanesDontFitError,
    )
    from tpu_bfs.graph.ell import rank_vertices

    # Hybrid structure sweep knobs, all defaulting to the measured
    # flagship optima (BENCHMARKS.md): TPU_BFS_BENCH_KCAP (residual ELL
    # bucket cap, 64), TPU_BFS_BENCH_TILE_THR (dense-tile edge threshold,
    # 64), TPU_BFS_BENCH_A_BUDGET (dense-tile byte budget, 0.2e9). A
    # malformed value degrades to the default, logged. Parsed BEFORE the
    # wide-fallback pre-check so a lowered tile budget also lowers the
    # pre-check's fixed-resident estimate (engine selection must see the
    # same numbers the build will).
    kw = {}
    for env, ctor_kw, conv in (
        ("TPU_BFS_BENCH_KCAP", "kcap", int),
        ("TPU_BFS_BENCH_TILE_THR", "tile_thr", int),
        ("TPU_BFS_BENCH_A_BUDGET", "a_budget_bytes", lambda v: int(float(v))),
    ):
        raw = os.environ.get(env, "")
        if raw:
            try:
                kw[ctor_kw] = max(1, conv(raw))
                log(f"{ctor_kw}={kw[ctor_kw]}")
            except (ValueError, OverflowError):  # int(float('inf')) raises
                log(f"{env}={raw!r} not a usable number; default {ctor_kw}")

    # Cheap pre-check with conservative fixed-resident estimates, so a graph
    # that clearly cannot fit 4096 lanes skips the minutes-long hybrid build.
    # Mirrors the engine's own sizing: tables cover only non-isolated rows,
    # and the plane count adapts (5 preferred, 4 buys one more scale step).
    src, dst = g.coo
    _, num_active, _, _ = rank_vertices(src, dst, g.num_vertices)
    rows = (-(-(num_active + 1) // 128)) * 128
    # Residual-slot estimate: the dense tiles absorb roughly half the edge
    # mass on power-law graphs (53% measured at scale 21), and the engine's
    # own sizing counts only residual slots — an all-edges estimate here
    # wrongly forced the wide fallback on graphs that fit (the LJ stand-in).
    fixed = kw.get("a_budget_bytes", int(0.2e9)) + int(g.num_edges * 4.4 * 0.5)
    planes = auto_planes(rows, fixed_bytes=fixed)
    est = auto_lanes(rows, planes, fixed_bytes=fixed)
    if est < LANES:
        log(f"hybrid needs {LANES} lanes, only {est} fit; using wide engine")
        return bench_wide(g, scale, ef, graph_desc)

    t0 = time.perf_counter()
    # TPU_BFS_BENCH_MAX_LANES (default 8192 = DEFAULT_MAX_LANES, the
    # round-4 measured optimum — 55.96 vs 45.68 GTEPS at 4096): width
    # sweep knob. Auto sizing may still settle narrower when the wider
    # state does not fit next to the tiles; whatever width is chosen
    # appears in the metric label via engine.lanes.
    max_lanes = _env_max_lanes(default=DEFAULT_MAX_LANES)
    expand_impl = _env_expand_impl()
    if expand_impl != "xla":
        kw["expand_impl"] = expand_impl
    pull_gate = _env_pull_gate()
    if pull_gate:
        kw["pull_gate"] = True
        log("adaptive push off (pull gate active — A/B arms stay clean)")
        adaptive = None
    else:
        # Level-adaptive push, default ON at the measured caps (see
        # _env_adaptive; TPU_BFS_BENCH_ADAPTIVE=0 disables, "rows,deg"
        # re-tunes); results stay oracle-validated either way.
        adaptive = None if _shed_adaptive else _env_adaptive()
        if adaptive is not None:
            kw["adaptive_push"] = adaptive

    def run_once():
        try:
            engine = retry_transient(HybridMsBfsEngine, g,
                                     max_lanes=max_lanes,
                                     label="hybrid engine build", **kw)
        except LanesDontFitError as exc:
            if adaptive is not None:
                # The push table is ~act*deg_cap*4 B of resident state; on
                # graphs near the HBM edge (the LJ stand-in) it can push
                # the hybrid under its 4096-lane minimum. Dropping the
                # push pass costs ~10% (62.2 -> 56.0 measured); dropping
                # the MXU path for the wide engine costs ~2x — so shed
                # adaptive FIRST.
                log(f"hybrid+adaptive doesn't fit ({exc}); retrying "
                    f"hybrid without the push table")
                raise _ShedRetry from None
            log(f"hybrid unavailable ({exc}); falling back to wide engine")
            return bench_wide(g, scale, ef, graph_desc)
        hg = engine.hg
        return _bench_batch_packed(
            g, graph_desc or f"RMAT scale-{scale} ef={ef}", engine,
            hg.in_degree,
            f"engine build {time.perf_counter()-t0:.1f}s: tiles={hg.num_tiles} "
            f"dense={hg.num_dense_edges/max(g.num_edges,1)*100:.1f}% "
            f"a_mem={hg.a_tiles.nbytes/2**30:.2f}GiB",
            "hybrid MXU+gather"
            + ("" if adaptive is None else "+adaptive-push")
            + ("+pull-gate" if pull_gate else "")
            + ("+pallas-expand" if expand_impl != "xla" else ""),
        )

    return _with_adaptive_shed(
        run_once,
        lambda: bench_hybrid(g, scale, ef, graph_desc, _shed_adaptive=True),
        adaptive,
        "hybrid",
    )


def bench_wide(g, scale: int, ef: int, graph_desc: str | None = None,
               _shed_adaptive: bool = False) -> dict:
    """Wide packed MS-BFS, gather-only (msbfs_wide.py); default width 8192
    lanes like the hybrid. ``_shed_adaptive`` as in bench_hybrid."""
    from tpu_bfs.algorithms._packed_common import PackedStateDoesntFitError
    from tpu_bfs.algorithms.msbfs_wide import (
        DEFAULT_MAX_LANES as WIDE_DEFAULT_MAX_LANES,
        WidePackedMsBfsEngine,
    )

    t0 = time.perf_counter()
    max_lanes = _env_max_lanes(default=WIDE_DEFAULT_MAX_LANES)
    expand_impl = _env_expand_impl()
    pull_gate = _env_pull_gate()
    if pull_gate:
        log("adaptive push off (pull gate active — A/B arms stay clean)")
        adaptive, kw = None, {"pull_gate": True}
    else:
        adaptive = None if _shed_adaptive else _env_adaptive()
        kw = {} if adaptive is None else {"adaptive_push": adaptive}
    if expand_impl != "xla":
        kw["expand_impl"] = expand_impl

    def run_once():
        try:
            engine = retry_transient(WidePackedMsBfsEngine, g,
                                     max_lanes=max_lanes,
                                     label="wide engine build", **kw)
        except PackedStateDoesntFitError as exc:
            # The round-5 sizing-time raise replaces the old delayed
            # runtime OOM; the shed ladder must still get its chance when
            # the push table is what tipped the budget.
            if adaptive is not None:
                log(f"wide+adaptive doesn't fit ({exc}); retrying without "
                    f"the push table")
                raise _ShedRetry from None
            raise
        ell = engine.ell
        return _bench_batch_packed(
            g, graph_desc or f"RMAT scale-{scale} ef={ef}", engine,
            ell.in_degree,
            f"engine build {time.perf_counter()-t0:.1f}s: slots={ell.total_slots} "
            f"(x{ell.total_slots/max(g.num_edges,1):.2f}) heavy={ell.num_heavy}",
            "wide packed"
            + ("" if adaptive is None else "+adaptive-push")
            + ("+pull-gate" if pull_gate else "")
            + ("+pallas-expand" if expand_impl != "xla" else ""),
        )

    return _with_adaptive_shed(
        run_once,
        lambda: bench_wide(g, scale, ef, graph_desc, _shed_adaptive=True),
        adaptive,
        "wide",
    )


def bench_msbfs(g, scale: int, ef: int) -> dict:
    from tpu_bfs.algorithms.msbfs_packed import UNREACHED, PackedMsBfsEngine

    lanes = int(os.environ.get("TPU_BFS_BENCH_LANES", "512"))
    do_validate = os.environ.get("TPU_BFS_BENCH_VALIDATE", "1") == "1"

    t0 = time.perf_counter()
    engine = retry_transient(PackedMsBfsEngine, g, lanes=lanes,
                             label="msbfs engine build")
    ell = engine.ell
    log(
        f"ell build {time.perf_counter()-t0:.1f}s: slots={ell.total_slots} "
        f"(x{ell.total_slots/max(g.num_edges,1):.2f}) heavy={ell.num_heavy}"
    )

    # Graph500 samples search keys among vertices with degree >= 1; RMAT at
    # this sparsity leaves a fringe of tiny components that would dominate a
    # harmonic mean under shared batch time, so sample keys from the
    # traversable component of the max-degree hub (found by a pilot run that
    # doubles as the compile warm-up).
    t0 = time.perf_counter()
    hub = int(np.argmax(ell.in_degree))
    pilot = retry_transient(engine.run, np.array([hub]), label="pilot run")
    traversable = np.flatnonzero(pilot.distance_u8[0] != UNREACHED)
    log(
        f"pilot+compile {time.perf_counter()-t0:.1f}s: traversable "
        f"{len(traversable)}/{g.num_vertices}"
    )
    rng = np.random.default_rng(7)
    sources = rng.choice(traversable, size=lanes, replace=len(traversable) < lanes)

    res = retry_transient(engine.run, sources, time_it=True, label="timed batch")
    gteps = res.teps / 1e9
    log(
        f"batch {res.elapsed_s*1e3:.1f}ms, {lanes} sources, levels<= "
        f"{res.num_levels}, per-src {res.elapsed_s/lanes*1e3:.3f}ms, "
        f"hmean GTEPS={gteps:.3f}"
    )

    if do_validate:
        from tpu_bfs.reference import bfs_scipy

        t0 = time.perf_counter()
        for i in [0, lanes // 2]:
            expected = bfs_scipy(g, int(sources[i]))
            np.testing.assert_array_equal(res.distances_int32(i), expected)
        log(f"validated 2 lanes in {time.perf_counter()-t0:.1f}s")

    return {
        "metric": (
            f"BFS harmonic-mean per-source GTEPS ({lanes}-source packed "
            f"MS-BFS batch), RMAT scale-{scale} ef={ef}, 1 chip"
        ),
        "value": round(gteps, 4),
        "unit": "GTEPS",
        "vs_baseline": round(gteps / 10.0, 4),
    }


def bench_single(g, scale: int, ef: int, backend: str = "scan",
                 graph_desc: str | None = None) -> dict:
    """Single-stream one-source-at-a-time BFS — the shape of the
    reference's live path (queueBfs, bfs.cu:134-165). 'single-dopt' runs
    the direction-optimizing backend; 'single-tiled' the dense-tile bitset
    engine (bfs_tiled.py, the best measured single-stream). NB:
    single-stream BFS on TPU is gather-bound (~13 ns/edge -> ~0.9 s per
    O(E) level at scale 21); the batched engines are the TPU-idiomatic
    execution model (BENCHMARKS.md "Single-stream" section)."""
    n_sources = int(os.environ.get("TPU_BFS_BENCH_SOURCES", "8"))
    do_validate = os.environ.get("TPU_BFS_BENCH_VALIDATE", "1") == "1"
    if backend == "tiled":
        from tpu_bfs.algorithms.bfs_tiled import TiledBfsEngine

        engine = retry_transient(TiledBfsEngine, g,
                                 label="tiled engine build")
    else:
        from tpu_bfs.algorithms.bfs import BfsEngine

        engine = retry_transient(BfsEngine, g, backend=backend,
                                 label="single engine build")
    rng = np.random.default_rng(7)
    candidates = np.flatnonzero(g.degrees > 0)
    sources = rng.choice(candidates, size=n_sources, replace=False)
    warm = retry_transient(engine.run, int(sources[0]), with_parents=False,
                           label="single warm-up")  # warm-up/compile
    if do_validate:
        from tpu_bfs import validate
        from tpu_bfs.reference import bfs_scipy

        validate.check_distances(warm.distance, bfs_scipy(g, int(sources[0])))
        log(f"validated src={int(sources[0])}")
    teps = []
    for s in sources:
        res = retry_transient(engine.run, int(s), with_parents=False,
                              time_it=True, label=f"single src={int(s)}")
        teps.append(res.teps)
        log(
            f"src={int(s)} t={res.elapsed_s*1e3:.2f}ms levels={res.num_levels} "
            f"GTEPS={res.teps/1e9:.3f}"
        )
    gteps = len(teps) / sum(1.0 / t for t in teps) / 1e9
    return {
        "metric": (
            f"BFS harmonic-mean GTEPS (single-stream, {backend} backend), "
            f"{graph_desc or f'RMAT scale-{scale} ef={ef}'}, 1 chip"
        ),
        "value": round(gteps, 4),
        "unit": "GTEPS",
        "vs_baseline": round(gteps / 10.0, 4),
    }


def bench_dist(g, scale: int, ef: int, graph_desc: str | None = None) -> dict:
    """Multi-device 1D-partition single-source BFS (TPU_BFS_BENCH_MODE=
    dist) — the wire-format A/B stage (ISSUES 5 + 7). Knobs:
    TPU_BFS_BENCH_DIST_DEVICES (device count, default all attached),
    TPU_BFS_BENCH_DIST_EXCHANGE (ring|allreduce|sparse, default ring),
    TPU_BFS_BENCH_WIRE_PACK (uint32 word packing, default OFF until
    chip-measured — like the pull gate), TPU_BFS_BENCH_SPARSE_DELTA /
    TPU_BFS_BENCH_SPARSE_SIEVE / TPU_BFS_BENCH_SPARSE_PREDICT (the
    exchange planner's three pieces, sparse exchange only, all default
    OFF until chip-measured), TPU_BFS_BENCH_SOURCES (8).

    The verdict carries the modeled per-level exchange price list
    (``wire_bytes_per_level``, one entry per exchange branch — ascending
    sparse caps then dense), the exact per-branch level counts summed over
    the timed sources (``wire_level_counts``) and the total modeled bytes
    one chip moved (``wire_bytes_total``) — the keys BENCHMARKS.md's
    "Exchange bytes" table is fed from, and the figures
    utils/wirecheck.check_packed_exchange pins to the compiled HLO. On a
    1-device attachment the exchange moves nothing and the wire keys are
    zero (the A/B then only measures pack/unpack compute overhead)."""
    from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

    n_sources = int(os.environ.get("TPU_BFS_BENCH_SOURCES", "8"))
    exchange = os.environ.get("TPU_BFS_BENCH_DIST_EXCHANGE", "ring")
    ndev_raw = os.environ.get("TPU_BFS_BENCH_DIST_DEVICES", "").strip()
    ndev = int(ndev_raw) if ndev_raw else None
    wire_pack = _env_wire_pack()
    delta_bits, sieve, predict = _env_sparse_planner()
    if exchange != "sparse" and (delta_bits or sieve or predict):
        log("sparse planner knobs need TPU_BFS_BENCH_DIST_EXCHANGE=sparse; "
            f"ignored on exchange={exchange!r}")
        delta_bits, sieve, predict = (), False, False
    do_validate = os.environ.get("TPU_BFS_BENCH_VALIDATE", "1") == "1"

    t0 = time.perf_counter()
    engine = retry_transient(
        DistBfsEngine, g, make_mesh(ndev), exchange=exchange,
        wire_pack=wire_pack, delta_bits=delta_bits, sieve=sieve,
        predict=predict, label="dist engine build",
    )
    per_level = [float(x) for x in engine.wire_bytes_per_level()]
    log(f"dist engine build {time.perf_counter()-t0:.1f}s: P={engine.p} "
        f"vloc={engine.part.vloc} exchange={exchange} "
        f"wire_pack={'on' if wire_pack else 'off'} "
        f"delta={list(delta_bits) or 'off'} "
        f"sieve={'on' if sieve else 'off'} "
        f"predict={'on' if predict else 'off'} bytes/level={per_level}")
    rng = np.random.default_rng(7)
    candidates = np.flatnonzero(g.degrees > 0)
    sources = rng.choice(candidates, size=n_sources, replace=False)
    warm = retry_transient(engine.run, int(sources[0]), with_parents=False,
                           label="dist warm-up")
    if do_validate:
        from tpu_bfs import validate
        from tpu_bfs.reference import bfs_scipy

        validate.check_distances(warm.distance, bfs_scipy(g, int(sources[0])))
        log(f"validated src={int(sources[0])}")
    teps = []
    counts = np.zeros(len(per_level), dtype=np.int64)
    total_bytes = 0.0
    for s in sources:
        res = retry_transient(engine.run, int(s), with_parents=False,
                              time_it=True, label=f"dist src={int(s)}")
        teps.append(res.teps)
        counts = counts + np.asarray(engine.last_exchange_level_counts)
        total_bytes += float(engine.last_exchange_bytes)
        log(f"src={int(s)} t={res.elapsed_s*1e3:.2f}ms levels="
            f"{res.num_levels} GTEPS={res.teps/1e9:.3f} "
            f"wire={engine.last_exchange_bytes:.0f}B")
    gteps = len(teps) / sum(1.0 / t for t in teps) / 1e9
    # Per-level engine trace of the LAST timed source (the unified
    # contract of tpu_bfs/obs/engine_trace; BENCHMARKS.md "Trace
    # summary") — the wire_* keys above already aggregate all sources.
    from tpu_bfs.obs.engine_trace import trace_summary

    trace_out = os.environ.get("TPU_BFS_BENCH_TRACE_OUT", "").strip()
    if trace_out:
        from tpu_bfs.obs.exporters import write_perfetto

        try:
            write_perfetto(
                [], trace_out,
                level_traces=[(f"dist-1d/p{engine.p}",
                               engine.last_run_trace or [])],
                meta={"tool": "tpu-bfs-bench", "mode": "dist",
                      "exchange": exchange, "devices": engine.p},
            )
            log(f"trace written -> {trace_out}")
        except OSError as exc:
            # A bad TPU_BFS_BENCH_TRACE_OUT path must not cost the run's
            # verdict (the timed work is already done).
            log(f"trace write failed ({exc!r})")
    return {
        "metric": (
            f"BFS harmonic-mean GTEPS (1D distributed, P={engine.p}, "
            f"{exchange} exchange, wire-pack "
            f"{'on' if wire_pack else 'off'}), "
            f"{graph_desc or f'RMAT scale-{scale} ef={ef}'}"
        ),
        "value": round(gteps, 4),
        "unit": "GTEPS",
        "vs_baseline": None,
        "wire_pack": wire_pack,
        "wire_exchange": exchange,
        "wire_devices": engine.p,
        "wire_sparse_delta": list(delta_bits),
        "wire_sparse_sieve": sieve,
        "wire_sparse_predict": predict,
        "wire_branch_labels": engine.exchange_branch_labels(),
        "wire_bytes_per_level": per_level,
        "wire_level_counts": [int(x) for x in counts],
        "wire_bytes_total": total_bytes,
        "trace": trace_summary(engine.last_run_trace, engine),
    }


def bench_serve(g, scale: int, ef: int, graph_desc: str | None = None) -> dict:
    """Closed-loop serve-throughput stage (TPU_BFS_BENCH_MODE=serve):
    N client threads (TPU_BFS_BENCH_SERVE_CLIENTS, default 64) drive the
    in-process BfsService — the lane-batching query server (tpu_bfs/serve)
    — each submitting its next query the moment the previous one resolves,
    until TPU_BFS_BENCH_SERVE_QUERIES (default 8 per client) complete.
    The JSON line's value is serve QPS; serve_p99_ms / serve_p50_ms /
    fill_ratio (vs DISPATCHED width) / serve_routing (the width ladder's
    per-width batch histogram) ride along (the serving latency/throughput
    record the one-shot GTEPS metric cannot express).
    TPU_BFS_BENCH_SERVE_LANES (default 256) sets the MAX batch width —
    smaller than the flagship's 8192 because a serving batch only ever
    carries the queries that are actually waiting;
    TPU_BFS_BENCH_SERVE_LADDER ('auto' default, 'off', or an explicit
    '32,128,...' list) sets the adaptive-width ladder and
    TPU_BFS_BENCH_SERVE_PIPELINE=0 disables the pipelined extraction —
    together they are the adaptive-vs-fixed A/B axes
    (scripts/chip_session.sh serve stages). Validation:
    TPU_BFS_BENCH_VALIDATE_LANES responses re-checked against the SciPy
    oracle."""
    from tpu_bfs.algorithms._packed_common import floor_lanes
    from tpu_bfs.serve import BfsService

    clients = max(1, int(os.environ.get("TPU_BFS_BENCH_SERVE_CLIENTS", "64")))
    per_client = max(1, int(os.environ.get("TPU_BFS_BENCH_SERVE_QUERIES", "8")))
    lanes = floor_lanes(
        max(32, int(os.environ.get("TPU_BFS_BENCH_SERVE_LANES", "256")))
    )
    ladder = os.environ.get("TPU_BFS_BENCH_SERVE_LADDER", "auto")
    pipeline = os.environ.get("TPU_BFS_BENCH_SERVE_PIPELINE", "1") == "1"
    engine = os.environ.get("TPU_BFS_BENCH_SERVE_ENGINE", "wide")
    do_validate = os.environ.get("TPU_BFS_BENCH_VALIDATE", "1") == "1"
    # Distributed serving (ISSUE 11): TPU_BFS_BENCH_SERVE_DEVICES shards
    # the serving engines over the mesh ('all' = every attached device);
    # TPU_BFS_BENCH_SERVE_ENGINE grows 'dist2d' (the 2D edge partition —
    # the paper's scale-26 baseline config), TPU_BFS_BENCH_SERVE_EXCHANGE
    # picks the exchange family, TPU_BFS_BENCH_SERVE_PULL_GATE gates the
    # dist-hybrid pull expansion, and the PR 5/7 wire knobs
    # (TPU_BFS_BENCH_WIRE_PACK / TPU_BFS_BENCH_SPARSE_*) apply to the
    # serve path exactly as to the dist mode. The verdict then carries
    # per-query GTEPS (p50 + harmonic mean under the batch time share)
    # and modeled wire bytes per query — the Graph500 scale-26 stage's
    # record (BENCHMARKS.md "Distributed serving").
    ndev_raw = os.environ.get("TPU_BFS_BENCH_SERVE_DEVICES", "").strip()
    if ndev_raw == "all":
        import jax

        devices = len(jax.devices())
    else:
        devices = int(ndev_raw) if ndev_raw else 1
    serve_exchange = os.environ.get("TPU_BFS_BENCH_SERVE_EXCHANGE",
                                    "").strip()
    serve_pull_gate = os.environ.get("TPU_BFS_BENCH_SERVE_PULL_GATE",
                                     "0") == "1"
    # One knob for the serve and batch arms (ISSUE 16): the kernel tier
    # is a program-key axis, so preheat stores keep tiers separate.
    serve_expand_impl = _env_expand_impl()
    if serve_expand_impl != "xla" and engine in ("packed", "dist2d"):
        # Drop, don't die (the registry's validate would reject): the
        # kernel tier fuses the wide/hybrid engines' ELL pull loop only.
        log("pallas expansion tier applies to the wide/hybrid serve "
            f"engines only; ignored on engine={engine!r}")
        serve_expand_impl = "xla"
    if devices > 1:
        wire_pack = _env_wire_pack()
        delta_bits, sieve, predict = _env_sparse_planner()
        if serve_exchange != "sparse" and (delta_bits or sieve or predict):
            log("sparse planner knobs need TPU_BFS_BENCH_SERVE_EXCHANGE="
                f"sparse; ignored on exchange={serve_exchange!r}")
            delta_bits, sieve, predict = (), False, False
        if engine != "dist2d" and (sieve or predict):
            # Valid on the dist mode's 1D planner but only the 2D engine
            # runs the full planner on the serve path (the MS row
            # gathers take delta only) — drop, don't die, so a knob set
            # reused from a dist sweep degrades gracefully.
            log("sieve/predict apply to the dist2d serve engine only; "
                f"ignored on engine={engine!r}")
            sieve, predict = False, False
    else:
        wire_pack, delta_bits, sieve, predict = False, (), False, False
    # Scale-26-class graphs are too big for the SciPy oracle; 'structure'
    # validates the Graph500 way instead — BFS-tree properties checked
    # directly on the answer (source at distance 0, every input edge's
    # endpoint distances within 1, README "Distributed serving").
    validate_mode = os.environ.get("TPU_BFS_BENCH_VALIDATE_MODE", "oracle")
    watchdog_ms = float(os.environ.get("TPU_BFS_BENCH_SERVE_WATCHDOG_MS",
                                       "0") or 0)
    # Chaos arm (scripts/chip_session.sh chaos-s20): a deterministic fault
    # schedule (tpu_bfs/faults.py) injected into the serving hot path; the
    # closed loop must still answer every query correctly, and the
    # recovery/fault counters ride the JSON line. Armed AFTER the service
    # is up (below) so bounded budgets land on measured serving
    # dispatches, not on engine warm-up.
    fault_spec = os.environ.get("TPU_BFS_BENCH_FAULTS", "").strip()
    fault_sched = None
    # Telemetry arm (TPU_BFS_BENCH_OBS, spec grammar of tpu_bfs/obs):
    # armed BEFORE the service so registry build/warm spans land in the
    # trace; the verdict then carries the obs event census and — with
    # TPU_BFS_BENCH_TRACE_OUT — a Perfetto JSON of the whole stage.
    obs_spec = os.environ.get("TPU_BFS_BENCH_OBS", "").strip()
    trace_out = os.environ.get("TPU_BFS_BENCH_TRACE_OUT", "").strip()
    recorder = None
    if obs_spec or trace_out:
        from tpu_bfs import obs as obs_mod

        # Same arming contract as the CLI surfaces (obs.arm_for_run): an
        # explicit spec wins, a falsy spec disarms, and TRACE_OUT alone
        # arms a default recorder — the documented dist+serve TRACE_OUT
        # support must not silently depend on TPU_BFS_BENCH_OBS.
        recorder = obs_mod.arm_for_run(obs_spec or None, trace_out)
        if recorder is not None:
            log("obs recorder armed"
                + (f" (spec {obs_spec!r})" if obs_spec else " (trace-out)"))

    # Cold-start vs preheat A/B (ISSUE 9): TPU_BFS_BENCH_AOT_DIR points
    # at an artifact store; the cold service's warmed programs are
    # exported there after the closed loop, then a SECOND service spins
    # up preheating from the store — serve_cold_start_s vs
    # serve_preheat_s land side by side in one verdict.
    aot_dir = os.environ.get("TPU_BFS_BENCH_AOT_DIR", "").strip()

    # Mesh fault tolerance (ISSUE 12): TPU_BFS_BENCH_SERVE_RESUME arms
    # the dist2d engine's level-checkpointed resume (snapshot cadence K);
    # a device_lost injected via TPU_BFS_BENCH_FAULTS then exercises the
    # degraded-mesh failover + resume path on chip, with the
    # serve_mesh_faults/serve_mesh_degrades/serve_query_resumes verdict
    # keys recording what fired.
    resume_levels = int(os.environ.get("TPU_BFS_BENCH_SERVE_RESUME",
                                       "0") or 0)
    if resume_levels and engine != "dist2d":
        log("level-checkpointed resume applies to the dist2d serve "
            f"engine only; ignored on engine={engine!r}")
        resume_levels = 0
    # Online integrity tier (ISSUE 15): AUDIT_RATE samples that fraction
    # of resolved queries for shadow re-execution on a disjoint rung
    # (and arms the structural tree checks); AUDIT_CHECKSUM adds the
    # wire-checksum verification on the audited transfers. The verdict
    # then carries the audit counters — the <5% p50 bar at rate 0.1 is
    # the chip-session integrity stage's acceptance line.
    audit_rate = float(os.environ.get("TPU_BFS_BENCH_SERVE_AUDIT_RATE",
                                      "0") or 0)
    audit_checksum = os.environ.get("TPU_BFS_BENCH_SERVE_AUDIT_CHECKSUM",
                                    "0") == "1"
    # Answer tier (ISSUE 18): TPU_BFS_BENCH_SERVE_CACHE arms the result
    # cache ('1' = the 64 MB default budget, any other value = a raw
    # byte budget) and TPU_BFS_BENCH_SERVE_LANDMARKS the K-column
    # landmark index; armed, a second ZIPFIAN closed loop (s=1.0 over
    # the degree-ranked hot set — the traffic shape the tier exists
    # for) runs after the uniform loop and the verdict gains
    # serve_cache_hit_rate / serve_landmark_hit_rate plus the split
    # hit-vs-traversal p50s.
    cache_raw = os.environ.get("TPU_BFS_BENCH_SERVE_CACHE", "0").strip()
    cache_bytes = 0
    if cache_raw and cache_raw != "0":
        cache_bytes = (64 << 20) if cache_raw == "1" else int(cache_raw)
    landmark_k = int(os.environ.get("TPU_BFS_BENCH_SERVE_LANDMARKS",
                                    "0") or 0)
    # Dynamic graphs (ISSUE 19): TPU_BFS_BENCH_MUTATIONS=N applies N
    # streaming edge-update flips under a dedicated closed loop after
    # the uniform stage; TPU_BFS_BENCH_MUTATIONS_OVERLAY ('DxK',
    # default 256x32) sizes the bounded delta overlay. The verdict
    # gains serve_flip_p50_ms / serve_overlay_occupancy /
    # serve_mutation_dropped (the zero-dropped-queries acceptance).
    mutations_n = int(os.environ.get("TPU_BFS_BENCH_MUTATIONS", "0") or 0)
    overlay_cap = ()
    if mutations_n > 0:
        if engine != "wide" or devices > 1 or serve_pull_gate:
            # Drop, don't die (registry validate would reject): the
            # overlay rides the single-chip wide substrate only.
            log("mutation soak needs the single-chip wide engine "
                f"without pull_gate; ignored on engine={engine!r} "
                f"devices={devices}")
            mutations_n = 0
        else:
            cap_raw = os.environ.get("TPU_BFS_BENCH_MUTATIONS_OVERLAY",
                                     "256x32")
            rows_s, _, ko_s = cap_raw.partition("x")
            overlay_cap = (int(rows_s), int(ko_s))
    svc_kw = dict(
        cache_bytes=cache_bytes, landmarks=landmark_k,
        engine=engine, lanes=lanes, planes=8,
        devices=devices, exchange=serve_exchange, wire_pack=wire_pack,
        delta_bits=delta_bits, sieve=sieve, predict=predict,
        pull_gate=serve_pull_gate, expand_impl=serve_expand_impl,
        resume_levels=resume_levels,
        audit_rate=audit_rate,
        audit_structural=audit_rate > 0 or audit_checksum,
        audit_checksum=audit_checksum,
        width_ladder=ladder, pipeline=pipeline,
        linger_ms=2.0, queue_cap=max(1024, 2 * clients),
        watchdog_ms=watchdog_ms, log=log,
        **({"dynamic": overlay_cap} if mutations_n else {}),
    )
    t0 = time.perf_counter()
    service = retry_transient(
        BfsService, g, label="serve engine build", **svc_kw
    )
    cold_start_s = time.perf_counter() - t0
    log(f"service up in {cold_start_s:.1f}s: engine={engine} "
        f"lanes={lanes} devices={devices} "
        f"exchange={serve_exchange or 'default'} "
        f"wire_pack={'on' if wire_pack else 'off'} "
        f"ladder={service.width_ladder} pipeline={pipeline} "
        f"clients={clients} queries={clients * per_client}")
    if fault_spec:
        from tpu_bfs import faults as faults_mod

        fault_sched = faults_mod.arm_from_spec(fault_spec)
        log(f"fault schedule armed: {fault_sched.to_spec()}")

    rng = np.random.default_rng(7)
    candidates = np.flatnonzero(g.degrees > 0)
    picks = rng.choice(
        candidates, size=(clients, per_client),
        replace=clients * per_client > len(candidates),
    )
    results = [None] * clients
    errs = []

    def client(ci: int) -> None:
        got = []
        try:
            for s in picks[ci]:
                got.append(service.query(int(s), timeout=600.0))
        except Exception as exc:  # noqa: BLE001 — surfaced after join
            errs.append(exc)
        results[ci] = got

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    if errs:
        raise errs[0]
    flat = [r for per in results for r in per]
    bad = [r for r in flat if not r.ok]
    if bad:
        raise RuntimeError(
            f"{len(bad)}/{len(flat)} serve queries failed; first: "
            f"{bad[0].status}: {bad[0].error}"
        )
    if audit_rate > 0 or audit_checksum:
        # Audit-counter barrier: the background shadow replays must
        # land before the snapshot or the verdict under-reports them.
        if not service.flush_audits(300.0):
            log("WARNING: audit flush timed out; audit keys may be low")
    snap = service.statsz()
    qps = len(flat) / elapsed
    log(f"{len(flat)} queries in {elapsed:.2f}s: qps={qps:.1f} "
        f"p50={snap['p50_ms']}ms p99={snap['p99_ms']}ms "
        f"fill={snap['fill_ratio']} batches={snap['batches']}")

    if do_validate:
        t0 = time.perf_counter()
        nv = max(1, int(os.environ.get("TPU_BFS_BENCH_VALIDATE_LANES", "4")))
        picks_v = flat[:: max(1, len(flat) // nv)][:nv]
        if validate_mode == "structure":
            from tpu_bfs import validate as _validate
            from tpu_bfs.graph.csr import INF_DIST

            for r in picks_v:
                if int(r.distances[r.source]) != 0:
                    raise _validate.ValidationError(
                        f"source {r.source} not at distance 0"
                    )
                _validate.check_edge_levels(g, r.distances)
                if int((r.distances != INF_DIST).sum()) != r.reached:
                    raise _validate.ValidationError(
                        f"reached count mismatch for source {r.source}"
                    )
        else:
            from tpu_bfs.reference import bfs_scipy

            for r in picks_v:
                np.testing.assert_array_equal(
                    r.distances, bfs_scipy(g, r.source)
                )
        log(f"validated {nv} serve responses ({validate_mode}) in "
            f"{time.perf_counter()-t0:.1f}s")

    # Mixed-kind workload stage (ISSUE 14): TPU_BFS_BENCH_SERVE_KINDS
    # ('all' / '1', or an explicit 'bfs,sssp,cc,khop,p2p' list) drives a
    # second closed loop of interleaved query kinds through a
    # single-chip wide service with the kind axis enabled (the mesh
    # forms have their own stage: TPU_BFS_BENCH_DIST_KINDS below). The
    # graph gains the deterministic weight plane in-place (same
    # topology, weights are a pure hash of the endpoints) so sssp is
    # servable; per-kind p50/p99/counts land under the 'serve_kinds'
    # verdict key.
    kinds_keys: dict = {}
    kinds_raw = os.environ.get("TPU_BFS_BENCH_SERVE_KINDS", "").strip()
    if kinds_raw:
        import dataclasses as _dc

        from tpu_bfs.graph.generate import edge_weights
        from tpu_bfs.workloads import supported_kinds

        gk = g
        if gk.weights is None:
            src, dst = gk.coo
            gk = _dc.replace(
                gk, weights=edge_weights(src, dst, seed=1, wmax=8)
            )
        avail = supported_kinds("wide", 1, gk)
        want_kinds = (
            avail if kinds_raw.lower() in ("1", "all")
            else tuple(
                k for k in kinds_raw.replace(",", " ").split()
            )
        )
        bad_kinds = [k for k in want_kinds if k not in avail]
        if bad_kinds:
            raise RuntimeError(
                f"TPU_BFS_BENCH_SERVE_KINDS names unservable kinds "
                f"{bad_kinds} (servable: {avail})"
            )
        kinds_lanes = min(lanes, 256)
        ksvc = retry_transient(
            BfsService, gk, label="serve kinds engine build",
            engine="wide", lanes=kinds_lanes, planes=8,
            width_ladder=ladder, pipeline=pipeline, linger_ms=2.0,
            queue_cap=max(1024, 2 * clients), kinds=want_kinds, log=log,
        )
        try:
            kq = rng.choice(candidates, size=(clients, per_client),
                            replace=clients * per_client > len(candidates))
            tgt = rng.choice(candidates, size=(clients, per_client))
            kres: list = [None] * clients
            kerrs: list = []

            def kind_client(ci: int) -> None:
                got = []
                try:
                    for j, s in enumerate(kq[ci]):
                        kind = want_kinds[(ci + j) % len(want_kinds)]
                        got.append((kind, ksvc.query(
                            int(s), kind=kind,
                            k=3 if kind == "khop" else None,
                            target=(int(tgt[ci][j])
                                    if kind == "p2p" else None),
                            timeout=600.0,
                        )))
                except Exception as exc:  # noqa: BLE001 — joined below
                    kerrs.append(exc)
                kres[ci] = got

            kthreads = [
                threading.Thread(target=kind_client, args=(i,), daemon=True)
                for i in range(clients)
            ]
            t0 = time.perf_counter()
            for t in kthreads:
                t.start()
            for t in kthreads:
                t.join()
            kind_elapsed = time.perf_counter() - t0
            if kerrs:
                raise kerrs[0]
            kflat = [kr for per in kres if per for kr in per]
            kbad = [r for _k, r in kflat if not r.ok]
            if kbad:
                raise RuntimeError(
                    f"{len(kbad)}/{len(kflat)} mixed-kind queries failed; "
                    f"first: {kbad[0].status}: {kbad[0].error}"
                )
            per_kind: dict = {}
            for kind, r in kflat:
                per_kind.setdefault(kind, []).append(r.latency_ms)
            kinds_keys = {
                "serve_kinds": {
                    kind: {
                        "count": len(ls),
                        "p50_ms": round(float(np.percentile(ls, 50)), 2),
                        "p99_ms": round(float(np.percentile(ls, 99)), 2),
                    }
                    for kind, ls in sorted(per_kind.items())
                },
                "serve_kinds_qps": round(len(kflat) / kind_elapsed, 2),
            }
            log("mixed-kind stage: " + " ".join(
                f"{k}:p50={v['p50_ms']}ms/p99={v['p99_ms']}ms"
                for k, v in kinds_keys["serve_kinds"].items()
            ) + f" qps={kinds_keys['serve_kinds_qps']}")
        finally:
            ksvc.close()

    # Distributed-kind stage (ISSUE 20): TPU_BFS_BENCH_DIST_KINDS
    # ('all' / '1', or an explicit kind list) serves every workload kind
    # over the FULL mesh — a second wide service with devices > 1 and
    # the (min, +)-capable sparse exchange, so sssp rides the sharded
    # delta-stepping tiles, cc the dist min-label fold, khop/p2p the
    # dist cores' protocol. Per-kind keys land under 'dist_kinds':
    # latency p50, harmonic-mean GTEPS (from the batch device-time
    # share), measured wire bytes per query, and the MODELED
    # wire_bytes_per_level table of the serving engine's exchange
    # branches (labelled) — the figures BENCHMARKS.md "Exchange bytes"
    # quotes per kind.
    dkinds_keys: dict = {}
    dkinds_raw = os.environ.get("TPU_BFS_BENCH_DIST_KINDS", "").strip()
    if dkinds_raw:
        import dataclasses as _dc

        import jax as _jax

        from tpu_bfs.graph.generate import edge_weights
        from tpu_bfs.workloads import supported_kinds

        dkn = devices if devices > 1 else len(_jax.devices())
        if dkn < 2:
            raise RuntimeError(
                "TPU_BFS_BENCH_DIST_KINDS needs a mesh: attach devices "
                "or set XLA_FLAGS=--xla_force_host_platform_device_count=8"
            )
        gk = g
        if gk.weights is None:
            src, dst = gk.coo
            gk = _dc.replace(
                gk, weights=edge_weights(src, dst, seed=1, wmax=8)
            )
        avail = supported_kinds("wide", dkn, gk)
        dk_kinds = (
            avail if dkinds_raw.lower() in ("1", "all")
            else tuple(dkinds_raw.replace(",", " ").split())
        )
        bad_kinds = [k for k in dk_kinds if k not in avail]
        if bad_kinds:
            raise RuntimeError(
                f"TPU_BFS_BENCH_DIST_KINDS names unservable kinds "
                f"{bad_kinds} (servable on the {dkn}-device mesh: {avail})"
            )
        dk_lanes = int(os.environ.get("TPU_BFS_BENCH_DIST_KINDS_LANES",
                                      "32"))
        dk_q = max(2, int(os.environ.get("TPU_BFS_BENCH_DIST_KINDS_QUERIES",
                                         "6")))
        dsvc = retry_transient(
            BfsService, gk, label="dist kinds engine build",
            engine="wide", lanes=dk_lanes, devices=dkn,
            exchange="sparse", delta_bits=(8, 16),
            width_ladder="off", pipeline=pipeline, linger_ms=2.0,
            kinds=dk_kinds, log=log,
        )
        try:
            dq = rng.choice(candidates, size=len(dk_kinds) * dk_q,
                            replace=len(dk_kinds) * dk_q > len(candidates))
            dtgt = rng.choice(candidates, size=len(dk_kinds) * dk_q)
            per_kind_res: dict = {k: [] for k in dk_kinds}
            t0 = time.perf_counter()
            for j, s in enumerate(dq):
                kind = dk_kinds[j % len(dk_kinds)]
                r = dsvc.query(
                    int(s), kind=kind,
                    k=3 if kind == "khop" else None,
                    target=int(dtgt[j]) if kind == "p2p" else None,
                    timeout=600.0,
                )
                if not r.ok:
                    raise RuntimeError(
                        f"dist-kind {kind} query failed: {r.status}: "
                        f"{r.error}"
                    )
                per_kind_res[kind].append(r)
            dk_elapsed = time.perf_counter() - t0
            # The modeled per-branch wire table of each kind's serving
            # engine: sssp's mesh form IS the dist engine; cc/khop/p2p
            # adapters delegate to their base substrate
            # (ExchangeRecordDelegate).
            wire_models: dict = {}
            for spec, eng in dsvc._registry.resident_engines():
                fn = getattr(eng, "wire_bytes_per_level", None)
                per = fn() if fn is not None else None
                if per is None:
                    continue
                labs = getattr(eng, "exchange_branch_labels",
                               lambda: None)()
                wire_models[spec.kind] = {
                    "wire_bytes_per_level": [
                        round(float(x), 1) for x in per
                    ],
                    **({"exchange_branches": list(labs)}
                       if labs else {}),
                }
            per_kind: dict = {}
            for kind, rs in sorted(per_kind_res.items()):
                lat = [r.latency_ms for r in rs]
                gvals = [r.gteps for r in rs if r.gteps]
                wires = [r.wire_bytes for r in rs
                         if r.wire_bytes is not None]
                row = {
                    "count": len(rs),
                    "p50_ms": round(float(np.percentile(lat, 50)), 2),
                }
                if gvals:
                    # 6 significant digits — CPU-mesh figures are ~1e-5
                    # GTEPS and round(x, 4) would flatten them to 0.
                    row["gteps_hmean"] = float(
                        f"{len(gvals) / sum(1.0 / t for t in gvals):.6g}")
                if wires:
                    row["wire_bytes_per_query"] = round(
                        sum(wires) / len(wires), 1)
                row.update(wire_models.get(kind, {}))
                per_kind[kind] = row
            dkinds_keys = {
                "dist_kinds": per_kind,
                "dist_kinds_devices": dkn,
                "dist_kinds_qps": round(len(dq) / dk_elapsed, 2),
            }
            log(f"dist-kind stage ({dkn} devices): " + " ".join(
                f"{k}:p50={v['p50_ms']}ms"
                + (f"/gteps={v['gteps_hmean']}" if "gteps_hmean" in v
                   else "")
                for k, v in per_kind.items()
            ) + f" qps={dkinds_keys['dist_kinds_qps']}")
        finally:
            dsvc.close()

    # Zipfian answer-tier stage (ISSUE 18): with the cache and/or the
    # landmark index armed, drive a second closed loop whose sources
    # follow a Zipf(s=1.0) law over the degree-ranked hot set (rank 1 =
    # the highest-degree vertex = the first landmark) — the skewed
    # traffic the answer tier exists for. bfs repeats must resolve from
    # the cache (or collapse into an in-flight leader); p2p queries
    # sourced at the hubs resolve exactly from the landmark columns.
    # The verdict splits hit vs traversal latency client-side.
    cache_keys: dict = {}
    if cache_bytes or landmark_k:
        zn = int(min(len(candidates), 256))
        order = np.argsort(-g.degrees[candidates], kind="stable")
        universe = candidates[order[:zn]]
        pz = 1.0 / np.arange(1, zn + 1, dtype=np.float64)
        pz /= pz.sum()
        zs = rng.choice(universe, size=(clients, per_client), p=pz)
        zt = rng.choice(universe, size=(clients, per_client), p=pz)
        do_p2p = landmark_k > 0 and "p2p" in service.kinds
        snap0 = service.statsz()
        zres: list = [None] * clients
        zerrs: list = []

        def zipf_client(ci: int) -> None:
            got = []
            try:
                for j, s in enumerate(zs[ci]):
                    if do_p2p and j % 4 == 3:
                        got.append(service.query(
                            int(s), kind="p2p", target=int(zt[ci][j]),
                            timeout=600.0,
                        ))
                    else:
                        got.append(service.query(int(s), timeout=600.0))
            except Exception as exc:  # noqa: BLE001 — joined below
                zerrs.append(exc)
            zres[ci] = got

        zthreads = [
            threading.Thread(target=zipf_client, args=(i,), daemon=True)
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in zthreads:
            t.start()
        for t in zthreads:
            t.join()
        zipf_elapsed = time.perf_counter() - t0
        if zerrs:
            raise zerrs[0]
        zflat = [r for per in zres if per for r in per]
        zbad = [r for r in zflat if not r.ok]
        if zbad:
            raise RuntimeError(
                f"{len(zbad)}/{len(zflat)} Zipfian queries failed; "
                f"first: {zbad[0].status}: {zbad[0].error}"
            )
        snap2 = service.statsz()

        def zdelta(key: str) -> int:
            return int(snap2.get(key, 0)) - int(snap0.get(key, 0))

        hit_lat = [
            r.latency_ms for r in zflat
            if (r.extras or {}).get("cache_hit")
            or (r.extras or {}).get("landmark")
        ]
        trav_lat = [
            r.latency_ms for r in zflat
            if not ((r.extras or {}).get("cache_hit")
                    or (r.extras or {}).get("landmark"))
        ]
        cache_resolved = zdelta("cache_hits") + zdelta(
            "single_flight_collapses")
        lm_resolved = zdelta("landmark_exact")
        cache_keys = {
            "serve_zipf_queries": len(zflat),
            "serve_zipf_qps": round(len(zflat) / zipf_elapsed, 2),
            "serve_cache_hit_rate": round(cache_resolved / len(zflat), 4),
            "serve_landmark_hit_rate": round(lm_resolved / len(zflat), 4),
            "serve_cache_bytes": snap2["cache_bytes"],
            "serve_cache_evictions": snap2["cache_evictions"],
            "serve_single_flight_collapses": snap2[
                "single_flight_collapses"],
            "serve_cache_quarantines": snap2["cache_quarantines"],
        }
        if hit_lat:
            cache_keys["serve_hit_p50_ms"] = round(
                float(np.percentile(hit_lat, 50)), 4)
        if trav_lat:
            cache_keys["serve_traversal_p50_ms"] = round(
                float(np.percentile(trav_lat, 50)), 3)
        if snap2.get("landmarks"):
            cache_keys["serve_landmarks_k"] = snap2["landmarks"]["k"]
            cache_keys["serve_landmark_warm_ms"] = snap2["landmarks"][
                "warm_ms"]
        log(
            f"zipf stage: {len(zflat)} queries "
            f"cache_hit_rate={cache_keys['serve_cache_hit_rate']} "
            f"landmark_hit_rate={cache_keys['serve_landmark_hit_rate']} "
            f"hit_p50={cache_keys.get('serve_hit_p50_ms')}ms "
            f"traversal_p50={cache_keys.get('serve_traversal_p50_ms')}ms"
        )

    # Mutation soak (ISSUE 19): N generation flips applied while a
    # closed loop keeps querying — every response must resolve ok
    # across the flips, and each flip's latency is measured at the
    # mutation caller (the atomic between-batches hand-off price).
    mut_keys: dict = {}
    if mutations_n > 0:
        rows_cap, ko_cap = overlay_cap
        # v1 overlay limit: an override row carries a vertex's FULL
        # current adjacency, so only vertices whose degree clears the
        # slot capacity are mutable — and isolated vertices have no
        # base table row to override at all. Distinct endpoints per
        # flip keep every touched row within ko across the whole soak.
        mutable = np.flatnonzero(
            (g.degrees > 0) & (g.degrees <= ko_cap - 2)
        )
        if len(mutable) < 2 * mutations_n:
            log(f"only {len(mutable)} vertices mutable under ko={ko_cap}; "
                f"capping mutation soak at {len(mutable) // 2} flips")
            mutations_n = len(mutable) // 2
    if mutations_n > 0:
        mrng = np.random.default_rng(23)
        ends = mrng.choice(mutable, size=(mutations_n, 2), replace=False)
        m_clients = min(clients, 16)
        picks_m = rng.choice(candidates, size=(m_clients, 64),
                             replace=True)
        stop = threading.Event()
        mflat: list = []
        merrs: list = []

        def mut_client(ci: int) -> None:
            got = []
            try:
                i = 0
                while not stop.is_set():
                    got.append(service.query(
                        int(picks_m[ci][i % picks_m.shape[1]]),
                        timeout=600.0))
                    i += 1
            except Exception as exc:  # noqa: BLE001 — surfaced after join
                merrs.append(exc)
            mflat.extend(got)

        mthreads = [
            threading.Thread(target=mut_client, args=(i,), daemon=True)
            for i in range(m_clients)
        ]
        flip_lat: list = []
        occupancy = 0
        for t in mthreads:
            t.start()
        try:
            for u, v in ends:
                out = service.apply_edge_updates(add=[(int(u), int(v))])
                flip_lat.append(out["flip_ms"])
                occupancy = max(occupancy, out["overlay_rows"])
                time.sleep(0.05)  # let queries land between flips
        finally:
            stop.set()
            for t in mthreads:
                t.join()
        if merrs:
            raise merrs[0]
        dropped = sum(1 for r in mflat if not r.ok)
        dmeta = service.statsz().get("dynamic", {})
        mut_keys = {
            "serve_mutation_flips": len(flip_lat),
            "serve_flip_p50_ms": round(
                float(np.percentile(flip_lat, 50)), 3),
            "serve_flip_max_ms": round(float(max(flip_lat)), 3),
            "serve_overlay_occupancy": round(occupancy / rows_cap, 4),
            "serve_mutation_queries": len(mflat),
            "serve_mutation_dropped": dropped,
            "serve_generation_final": dmeta.get("generation"),
            "serve_compactions": dmeta.get("compactions", 0),
        }
        log(f"mutation soak: {len(flip_lat)} flips under {len(mflat)} "
            f"queries, flip_p50={mut_keys['serve_flip_p50_ms']}ms "
            f"occupancy={mut_keys['serve_overlay_occupancy']} "
            f"dropped={dropped}")
        if dropped:
            raise RuntimeError(
                f"{dropped}/{len(mflat)} queries dropped across "
                f"{len(flip_lat)} generation flips"
            )

    aot_keys: dict = {}
    if aot_dir:
        # Export from the warmed service BEFORE closing it, then time a
        # fresh preheated bring-up from the store (same in-process graph
        # object, so the registry keys line up) and sanity-serve one
        # query through the adopted executables.
        from tpu_bfs.utils.aot import ArtifactStore

        try:
            store = ArtifactStore(aot_dir, log=log)
            t0 = time.perf_counter()
            exported = service.export_aot(store)
            log(f"aot export -> {aot_dir}: {exported['programs']} programs "
                f"from {exported['engines']} engines in "
                f"{time.perf_counter()-t0:.1f}s")
        finally:
            # A disk-full/permission failure mid-export must not leak the
            # warmed service (live worker threads hang interpreter exit).
            service.close()
        t0 = time.perf_counter()
        pre = retry_transient(
            BfsService, g, aot_dir=aot_dir, label="serve preheat",
            **svc_kw,
        )
        try:
            preheat_s = time.perf_counter() - t0
            r = pre.query(int(picks[0][0]), timeout=600.0)
            counts = pre._registry.aot_store.counts()
        finally:
            pre.close()
        log(f"preheat up in {preheat_s:.1f}s (cold {cold_start_s:.1f}s): "
            f"hits={counts['aot_hits']} fallbacks={counts['aot_fallbacks']} "
            f"query={'ok' if r.ok else r.status}")
        if not r.ok:
            raise RuntimeError(
                f"preheated service failed its sanity query: {r.status}: "
                f"{r.error}"
            )
        aot_keys = {
            "serve_preheat_s": round(preheat_s, 2),
            "aot_hits": counts["aot_hits"],
            "aot_fallbacks": counts["aot_fallbacks"],
        }
    else:
        service.close()

    obs_keys: dict = {}
    if recorder is not None:
        from tpu_bfs.obs.engine_trace import trace_summary

        level_traces = [
            (f"{spec.engine}/w{spec.lanes}"
             + (f"/d{spec.devices}" if spec.devices > 1 else ""),
             eng.last_run_trace)
            for spec, eng in service._registry.resident_engines()
            if getattr(eng, "last_run_trace", None)
        ]
        obs_keys = {
            "serve_obs_events": recorder.counts_by_name(),
            "serve_flight_dumps": len(recorder.dumps),
        }
        if level_traces:
            # The widest rung's trace (the batch shape the closed loop
            # mostly ran) stands in for "the" serve engine trace.
            label, trace = max(
                level_traces,
                key=lambda lt: int(
                    lt[0].rsplit("/w", 1)[1].split("/", 1)[0]
                ),
            )
            obs_keys["serve_trace"] = trace_summary(trace)
            obs_keys["serve_trace_engine"] = label
        if trace_out:
            from tpu_bfs.obs.exporters import write_perfetto

            try:
                write_perfetto(
                    recorder.snapshot(), trace_out, t0=recorder.t0,
                    level_traces=level_traces,
                    meta={"tool": "tpu-bfs-bench", "mode": "serve"},
                )
                log(f"trace written -> {trace_out}")
            except OSError as exc:
                # A bad TPU_BFS_BENCH_TRACE_OUT path must not cost the
                # run's verdict (the timed work is already done).
                log(f"trace write failed ({exc!r})")

    # Per-query traversal-rate record (ISSUE 11): mesh-served responses
    # carry edges + the batch device time, so each query prices as GTEPS
    # under the batch time share; p50 and the harmonic mean land in the
    # verdict next to modeled wire bytes per query.
    dist_keys: dict = {}
    if devices > 1:
        gteps = sorted(r.gteps for r in flat if r.gteps)
        wires = [r.wire_bytes for r in flat if r.wire_bytes is not None]
        dist_keys = {
            "serve_devices": devices,
            "serve_exchange": serve_exchange or "default",
            "serve_wire_pack": wire_pack,
            "serve_pull_gate": serve_pull_gate,
            "serve_sparse_delta": list(delta_bits),
            "serve_sparse_sieve": sieve,
            "serve_sparse_predict": predict,
        }
        if gteps:
            # 6 significant digits (CPU-mesh figures are ~1e-5 GTEPS and
            # must not round to 0; chip figures keep full precision).
            dist_keys["serve_gteps_p50"] = float(
                f"{gteps[len(gteps) // 2]:.6g}")
            dist_keys["serve_gteps_hmean"] = float(
                f"{len(gteps) / sum(1.0 / t for t in gteps):.6g}")
        if wires:
            dist_keys["serve_wire_bytes_per_query"] = round(
                sum(wires) / len(wires), 1)
            dist_keys["serve_wire_bytes_total"] = round(sum(wires), 1)
        log("dist serve record: "
            + " ".join(f"{k}={v}" for k, v in dist_keys.items()))

    # Modeled per-rung HBM peaks over the service's ACTUAL width ladder
    # (ISSUE 13 pass 5's ladder budget model; pure arithmetic, CPU-safe):
    # the verdict records what each resident rung is modeled to occupy
    # and whether the ladder is strictly monotone in width — the
    # precondition the OOM halving and mesh-degrade walks rest on.
    from tpu_bfs.analysis.memory import (
        check_ladder_entries,
        model_spec_peak_bytes,
    )

    hbm_entries = [
        (
            int(w),
            model_spec_peak_bytes(
                engine, int(w), planes=8, devices=devices,
                num_vertices=g.num_vertices, num_edges=g.num_edges,
            )["total_bytes"],
        )
        for w in snap["ladder"]
    ]
    hbm_monotone = not check_ladder_entries("serve", hbm_entries)
    log("hbm model: " + " ".join(
        f"w{w}={b/1e9:.2f}GB" for w, b in hbm_entries
    ) + f" monotone={hbm_monotone}")

    chips = f"{devices} chips" if devices > 1 else "1 chip"
    return {
        "metric": (
            f"BFS serve throughput ({clients} closed-loop clients, "
            f"{lanes}-max-lane {engine} batches, ladder="
            f"{'-'.join(str(w) for w in snap['ladder'])}, "
            f"pipeline={'on' if pipeline else 'off'}, tpu_bfs/serve), "
            f"{graph_desc or f'RMAT scale-{scale} ef={ef}'}, {chips}"
        ),
        "value": round(qps, 2),
        "unit": "qps",
        "vs_baseline": None,
        "serve_qps": round(qps, 2),
        "serve_p50_ms": snap["p50_ms"],
        "serve_p99_ms": snap["p99_ms"],
        "fill_ratio": snap["fill_ratio"],
        "serve_routing": snap["routing"],
        "serve_extract_p50_ms": snap["extract_p50_ms"],
        "serve_padded_lanes": snap["padded_lanes_total"],
        "serve_pipeline": pipeline,
        "serve_retries": snap["retries"],
        "serve_sheds": snap["rejected"],
        # Robustness counters (chaos harness / serve hardening): OOM
        # degrades, watchdog firings, breaker opens, requeue-budget sheds
        # — plus the per-kind injected-fault audit when a schedule ran.
        "serve_oom_degrades": snap["oom_degrades"],
        "serve_watchdog_trips": snap["watchdog_trips"],
        "serve_breaker_opens": snap["breaker_opens"],
        "serve_requeue_shed": snap["requeue_shed"],
        # Mesh fault tolerance (ISSUE 12): mesh-death classifications,
        # degraded-mesh failover rebuilds, and level-checkpointed
        # mid-query resumes — plus the device count the stage ENDED on
        # (< the configured mesh means a degrade happened and held).
        "serve_mesh_faults": snap["mesh_faults"],
        "serve_mesh_degrades": snap["mesh_degrades"],
        "serve_query_resumes": snap.get("query_resumes", 0),
        "serve_devices_final": snap.get("devices", devices),
        # Online integrity tier (ISSUE 15): audits completed, confirmed
        # corruption findings, audit lag behind resolve, and rung
        # quarantines (all zero when the tier is disarmed).
        "serve_audits_run": snap["audits_run"],
        "serve_audit_failures": snap["audit_failures"],
        "serve_audit_p50_lag_ms": snap["audit_p50_lag_ms"],
        "serve_quarantines": snap["quarantines"],
        # Cold-start record (ISSUE 9): always emitted; the preheat side
        # (serve_preheat_s + aot hit/fallback audit) rides along when
        # TPU_BFS_BENCH_AOT_DIR armed the A/B.
        "serve_cold_start_s": round(cold_start_s, 2),
        # Kernel tier (ISSUE 16): which expansion tier the packed MS
        # engines served with (a program-key axis of the AOT store).
        "serve_expand_impl": serve_expand_impl,
        # Static HBM budget (ISSUE 13): modeled peak bytes per resident
        # ladder rung + the strict-monotonicity verdict the degrade
        # ladders depend on (BENCHMARKS.md "Serve HBM model").
        "serve_hbm_model_bytes": {str(w): b for w, b in hbm_entries},
        "serve_hbm_ladder_monotone": hbm_monotone,
        **dist_keys,
        **kinds_keys,
        **dkinds_keys,
        **cache_keys,
        **mut_keys,
        **aot_keys,
        **({"serve_faults": fault_sched.counts()} if fault_sched else {}),
        **obs_keys,
    }


def _log_result(result: dict, mode: str) -> None:
    """Append every landed measurement to a durable in-repo log
    (TPU_BFS_BENCH_RESULT_LOG, default bench_results.jsonl at the repo
    root; empty disables). The official record is the driver's captured
    stdout — but numbers landed by opportunistic sessions between driver
    windows (scripts/chip_session.sh) live only in gitignored caches, and
    a measurement that survived a 5-hour outage should not depend on a
    human reading a log file before the round snapshot. Best-effort."""
    path = _result_log_path()
    if not path:
        return
    try:
        line = dict(result, mode=mode, utc=time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    except OSError as exc:
        log(f"result log append skipped: {exc}")


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache; shared resolution lives in
    tpu_bfs/utils/compile_cache.py (also used by scripts/width_probe.py).
    Lazy import, like the other tpu_bfs uses in this file."""
    from tpu_bfs.utils.compile_cache import enable_compile_cache

    enable_compile_cache(log=log)


def main() -> int:
    scale = int(os.environ.get("TPU_BFS_BENCH_SCALE", "21"))
    ef = int(os.environ.get("TPU_BFS_BENCH_EF", "16"))
    mode = os.environ.get("TPU_BFS_BENCH_MODE", "hybrid")
    # Reset the printed-verdict flag: main() runs repeatedly in one pytest
    # process, and a stale 0 would let this run's watchdog exit silently.
    globals()["_FINAL_RC"] = None
    _enable_compile_cache()
    watchdog = _arm_budget(mode)
    hang = float(os.environ.get("TPU_BFS_BENCH_SELFTEST_HANG_S", "0") or 0)
    if hang > 0:
        # Envelope self-test hook (tests/test_bench_envelope.py and manual
        # `timeout` drills): simulate a run pinned inside a blocking
        # attempt — the watchdog or the signal envelope must produce the
        # one JSON line — without needing a held chip.
        log(f"selftest hang {hang:.0f}s")
        time.sleep(hang)
    try:
        g = load_graph_lj() if mode.startswith("lj-") else load_graph(scale, ef)
        from functools import partial

        lj_desc = "soc-LiveJournal1-shaped stand-in (NONETWORK.md)"
        if mode.startswith("lj-"):
            # Attribute the edge stream: native and numpy RMAT are different
            # deterministic streams (ADVICE r2), so the metric says which one.
            lj_desc = f"{lj_desc[:-1]}; {lj_impl()} stream)"
        fn = {
            "hybrid": bench_hybrid,
            "wide": bench_wide,
            "msbfs": bench_msbfs,
            "single": bench_single,
            "single-dopt": partial(bench_single, backend="dopt"),
            "single-tiled": partial(bench_single, backend="tiled"),
            "dist": bench_dist,
            "serve": bench_serve,
            "lj-hybrid": partial(bench_hybrid, graph_desc=lj_desc),
            "lj-single-dopt": partial(bench_single, backend="dopt", graph_desc=lj_desc),
            "lj-single-tiled": partial(bench_single, backend="tiled", graph_desc=lj_desc),
        }[mode]
        # Outer safety net: if a transient error escapes the per-stage
        # retries (e.g. fired while materializing results between stages),
        # one full re-run is still cheaper than losing the round's number.
        # Validation failures are not retryable and propagate immediately.
        try:
            result = retry_transient(fn, g, scale, ef, attempts=2,
                                     backoff_s=15.0, label=f"bench mode={mode}")
        except BudgetExhausted as exc:
            # The structured verdict: value=null + an attributable error,
            # and a nonzero exit — a run that measured nothing must not
            # read as a success. Disarm the watchdog BEFORE printing: the
            # cooperative verdict fires with seconds left on the budget,
            # and a stalled stdout pipe must not let fire() corrupt the
            # half-written JSON line.
            if watchdog is not None:
                watchdog.cancel()
            log(str(exc))
            return _print_verdict(_failure_payload(
                mode,
                f"device unavailable for {exc.unavailable_s:.0f}s "
                f"(last: {type(exc.cause).__name__}: {str(exc.cause)[:200]})",
            ), 1)
        except Exception as exc:  # noqa: BLE001 — one-JSON-line contract
            # Deterministic failures (a sizing bug OOMing at runtime, a
            # validation mismatch) must still leave one parseable JSON
            # line — the round-4 lj-hybrid run died rc=1 with only a
            # traceback.
            if watchdog is not None:
                watchdog.cancel()
            import traceback

            traceback.print_exc()
            return _print_verdict(_failure_payload(
                mode, f"{type(exc).__name__}: {str(exc)[:300]}"
            ), 1)
        if watchdog is not None:
            watchdog.cancel()
        from tpu_bfs.utils.recovery import COUNTERS

        if COUNTERS.any():
            # Post-hoc incident visibility (round-6 satellite): a number
            # that survived retries/OOM degrades says so in its own JSON
            # line. Extra keys are ignored by scripts/has_value.py.
            result["recovery"] = COUNTERS.as_dict()
        _print_verdict(result, 0)
        _log_result(result, mode)
        return 0
    finally:
        # Always disarm, whatever raised — a leaked timer would os._exit a
        # later run in the same process (e.g. the pytest session driving
        # bench.main()), and a stale deadline would make later retries
        # spuriously exhaust.
        if watchdog is not None:
            watchdog.cancel()
        globals()["_DEADLINE"] = None


if __name__ == "__main__":
    _install_signal_envelope(os.environ.get("TPU_BFS_BENCH_MODE", "hybrid"))
    sys.exit(main())
