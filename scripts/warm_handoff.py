"""Warm engine handoff: replace a serving process without ever serving
from a cold one (ISSUE 9).

Starts a SUCCESSOR server (normally ``tpu-bfs-serve ... --preheat DIR``
over a store the old server populated with ``--export-aot DIR``), waits
for its READY line — every ladder rung warmed, artifacts adopted — and
only THEN SIGTERMs the old server, whose graceful drain (PR 4) flushes
in-flight batches and resolves queued queries. If the successor dies or
never reports ready, the old server is left untouched and the driver
exits non-zero: the fleet keeps serving from the warm process.

Usage::

    python scripts/warm_handoff.py --old-pid PID \
        [--ready-timeout S] [--term-wait S] -- <successor argv...>

``--old-pid 0`` skips the SIGTERM (first bring-up: just gate on READY).

A TPU chip belongs to one process at a time: a successor started on the
chip the old server still holds would fail or hang inside libtpu. So the
driver refuses, before starting anything, when the successor could open
a chip the old server holds (``TPU_VISIBLE_CHIPS`` of both; unset means
every chip). Pin the successor to a free chip, or stop the old server
first. Processes forced off the TPU (``JAX_PLATFORMS`` without ``tpu``)
hold no chip.
The driver's stdin/stdout pass through to the successor, so a fleet
manager (or the preheat smoke) can pipe traffic straight into the new
process. Prints one JSON line (value = seconds to ready) on success.
"""

import argparse
import errno
import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

READY_MARKER = "# READY"


def log(msg: str) -> None:
    print(f"[warm-handoff] {msg}", file=sys.stderr, flush=True)


def pid_alive(pid: int) -> bool:
    # A drained server whose parent hasn't reaped it yet is a zombie:
    # os.kill(pid, 0) still succeeds there, so consult the process state
    # where /proc exists (the smoke holds the old server as an unreaped
    # child for exactly this window).
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
        return state != "Z"
    except (OSError, IndexError):
        pass
    try:
        os.kill(pid, 0)
    except OSError as exc:
        return exc.errno == errno.EPERM
    return True


ALL_CHIPS = None  # a process with no TPU_VISIBLE_CHIPS opens every chip


def chips_of(env: dict):
    """The TPU chips a process with environment ``env`` may open: an
    empty set when it is forced off the TPU, ALL_CHIPS when unpinned,
    else the chip ids of TPU_VISIBLE_CHIPS."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return frozenset()
    visible = env.get("TPU_VISIBLE_CHIPS")
    if visible is None:
        return ALL_CHIPS
    return frozenset(int(c) for c in visible.split(",") if c.strip())


def chips_overlap(a, b) -> bool:
    if a == frozenset() or b == frozenset():
        return False
    return a is ALL_CHIPS or b is ALL_CHIPS or bool(a & b)


def process_env(pid: int) -> dict | None:
    """The environment a live process was started with (None when it
    cannot be read)."""
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            raw = f.read()
    except OSError:
        return None
    pairs = (item.partition(b"=") for item in raw.split(b"\0") if item)
    return {k.decode(errors="replace"): v.decode(errors="replace")
            for k, _, v in pairs}


def host_chip_count() -> int:
    """TPU chips on this host, counted from their device files — never
    through JAX, which would take a chip in this process."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="drain the old server only after the new one is ready"
    )
    ap.add_argument("--old-pid", type=int, required=True,
                    help="PID of the serving process to drain once the "
                    "successor is ready (0 = none: first bring-up)")
    ap.add_argument("--ready-timeout", type=float, default=600.0,
                    help="seconds to wait for the successor's READY line "
                    "before giving up (default 600)")
    ap.add_argument("--term-wait", type=float, default=60.0,
                    help="seconds to wait for the old server to exit "
                    "after SIGTERM (0 = don't wait; default 60)")
    ap.add_argument("successor", nargs=argparse.REMAINDER,
                    help="successor server argv (prefix with --)")
    args = ap.parse_args(argv)
    succ = args.successor
    if succ and succ[0] == "--":
        succ = succ[1:]
    if not succ:
        ap.error("no successor argv given (append: -- <server argv...>)")
    if args.old_pid and not pid_alive(args.old_pid):
        log(f"old pid {args.old_pid} is not alive; treating as first "
            f"bring-up")
        args.old_pid = 0
    if args.old_pid:
        old_env = process_env(args.old_pid)
        old_chips = ALL_CHIPS if old_env is None else chips_of(old_env)
        if chips_overlap(old_chips, chips_of(dict(os.environ))):
            log(f"refusing: the successor could open a TPU chip that old "
                f"server pid {args.old_pid} holds (old TPU_VISIBLE_CHIPS="
                f"{None if old_env is None else old_env.get('TPU_VISIBLE_CHIPS')}"
                f", successor TPU_VISIBLE_CHIPS="
                f"{os.environ.get('TPU_VISIBLE_CHIPS')}); a chip serves one "
                f"process — pin the successor to a free chip or stop the "
                f"old server first")
            return 2

    t0 = time.perf_counter()
    log(f"starting successor: {' '.join(succ)}")
    # stderr is piped so the READY line can be watched; every line is
    # forwarded, so the successor's logs still reach the operator.
    proc = subprocess.Popen(succ, stderr=subprocess.PIPE, text=True)

    ready = threading.Event()

    def watch_stderr() -> None:
        for line in proc.stderr:
            sys.stderr.write(line)
            sys.stderr.flush()
            if READY_MARKER in line:
                ready.set()

    watcher = threading.Thread(target=watch_stderr, daemon=True)
    watcher.start()

    deadline = time.monotonic() + args.ready_timeout
    while not ready.is_set():
        if proc.poll() is not None:
            log(f"successor exited rc={proc.returncode} before READY; "
                f"old server untouched")
            return 1
        if time.monotonic() >= deadline:
            log(f"successor not READY within {args.ready_timeout:.0f}s; "
                f"terminating it — old server untouched")
            proc.terminate()
            return 1
        ready.wait(0.2)
    ready_s = time.perf_counter() - t0
    log(f"successor READY in {ready_s:.2f}s")

    drained = None
    if args.old_pid:
        log(f"SIGTERM -> old server pid {args.old_pid} (graceful drain)")
        try:
            os.kill(args.old_pid, signal.SIGTERM)
        except OSError as exc:
            log(f"SIGTERM failed ({exc!r})")
            return 1
        if args.term_wait > 0:
            stop = time.monotonic() + args.term_wait
            while pid_alive(args.old_pid) and time.monotonic() < stop:
                time.sleep(0.2)
            drained = not pid_alive(args.old_pid)
            log("old server exited" if drained
                else f"old server still alive after {args.term_wait:.0f}s "
                     f"(drain may still be flushing)")

    # Hand the foreground to the successor: the driver lives until the
    # new server exits, so pipelines (smoke, systemd-style supervisors)
    # see one continuous process tree. The handoff JSON is printed LAST,
    # after the successor's protocol stream has closed, so a stage
    # driver's tail-line value gate reads it cleanly.
    rc = proc.wait()
    print(json.dumps({
        "metric": "warm handoff: successor ready-to-serve seconds "
                  "(old server drained only after)",
        "value": round(ready_s, 3),
        "unit": "s",
        "old_pid": args.old_pid,
        "old_drained": drained,
        "successor_rc": rc,
    }), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
