"""The bench's outage envelope: the one JSON line must land however a
run ends, and a run that measured nothing must say so — value=null, an
attributable "error", and a NONZERO exit. A lost run never echoes an
older durable-log number.

Three layers, each pinned here:
- payload: every lost-run verdict is the value=null failure payload;
- watchdog: TPU_BFS_BENCH_BUDGET_S (default 1200) fires from a daemon
  thread even while the main thread is pinned in a blocking attempt,
  and exits 124;
- signal envelope: SIGTERM/SIGINT are sigwait()ed by a watcher thread
  and answered with the structured verdict + exit 128 + signum.

The watchdog and signal layers are exercised end-to-end in subprocesses
(the signal mask and os._exit must not touch the pytest process), pinned
inside a blocking sleep via the TPU_BFS_BENCH_SELFTEST_HANG_S hook.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seed_log(path, mode="hybrid", value=62.33, utc="2026-07-31T12:26:17Z"):
    entries = [
        {"metric": "other-mode entry", "value": 1.0, "unit": "GTEPS",
         "vs_baseline": 0.1, "mode": "wide", "utc": "2026-07-30T00:00:00Z"},
        {"metric": "older matching entry", "value": 41.0, "unit": "GTEPS",
         "vs_baseline": 4.1, "mode": mode, "utc": "2026-07-30T01:00:00Z"},
        {"metric": f"BFS hmean GTEPS (mode={mode})", "value": value,
         "unit": "GTEPS", "vs_baseline": round(value / 10, 4), "mode": mode,
         "utc": utc},
    ]
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")
    return path


# ---------------------------------------------------------------------------
# Lost-run payload (in-process).
# ---------------------------------------------------------------------------

def test_failure_payload_is_null_never_stale():
    p = bench._failure_payload("hybrid", "chip held")
    assert p["value"] is None and p["vs_baseline"] is None
    assert "stale" not in p and "measured_utc" not in p
    assert p["error"] == "chip held" and "hybrid" in p["metric"]


def test_stale_echo_path_is_gone():
    """No code path reads the durable log back into a verdict, and the
    knob that used to gate the echo is gone."""
    import inspect

    assert not hasattr(bench, "_lost_run_payload")
    assert not hasattr(bench, "_last_logged_result")
    assert "TPU_BFS_BENCH_STALE_OK" not in inspect.getsource(bench)


def test_budget_exhausted_verdict_exits_nonzero(tmp_path, monkeypatch,
                                                capsys):
    """The cooperative budget verdict: value=null and rc 1, even with a
    durable log holding an older number for the mode."""
    monkeypatch.setenv("TPU_BFS_BENCH_RESULT_LOG",
                       str(_seed_log(tmp_path / "r.jsonl")))
    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: None)

    def exhausted(*a, **k):
        raise bench.BudgetExhausted(RuntimeError("UNAVAILABLE: x"), 99.0)

    monkeypatch.setattr(bench, "retry_transient", exhausted)
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] is None and "stale" not in out
    assert "unavailable for 99s" in out["error"]


def test_deterministic_failure_never_reads_log(tmp_path, monkeypatch,
                                               capsys):
    monkeypatch.setenv("TPU_BFS_BENCH_RESULT_LOG",
                       str(_seed_log(tmp_path / "r.jsonl")))
    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "hybrid")

    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: None)

    def boom(*a, **k):
        raise RuntimeError("validation failed")

    monkeypatch.setattr(bench, "bench_hybrid", boom)
    assert bench.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] is None and "stale" not in out


def test_has_value_rejects_stale_lines(tmp_path):
    """scripts/has_value.py gates chip-session stages: a stale echo must
    read as 'no value landed' so the stage keeps retrying."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import has_value
    finally:
        sys.path.pop(0)
    fresh = tmp_path / "fresh.json"
    fresh.write_text('{"metric": "m", "value": 62.3, "unit": "GTEPS"}\n')
    assert has_value.main(str(fresh)) == 0
    stale = tmp_path / "stale.json"
    stale.write_text(
        '{"metric": "m", "value": 62.3, "unit": "GTEPS", "stale": true}\n')
    assert has_value.main(str(stale)) == 1
    null = tmp_path / "null.json"
    null.write_text('{"metric": "m", "value": null}\n')
    assert has_value.main(str(null)) == 1


# ---------------------------------------------------------------------------
# End-to-end subprocess drills. Both runs hang in the selftest hook before
# any jax import, so they are fast and never touch an accelerator.
# ---------------------------------------------------------------------------

def _bench_env(tmp_path, **extra):
    env = dict(os.environ)
    env.update(
        TPU_BFS_BENCH_RESULT_LOG=str(_seed_log(tmp_path / "r.jsonl")),
        TPU_BFS_BENCH_MODE="hybrid",
        TPU_BFS_BENCH_SELFTEST_HANG_S="120",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
        **{k: str(v) for k, v in extra.items()},
    )
    return env


def _last_json_line(stdout: str) -> dict:
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    assert lines, f"no JSON line in stdout: {stdout!r}"
    return json.loads(lines[-1])


def test_watchdog_lands_null_json_while_main_thread_blocked(tmp_path):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO, env=_bench_env(tmp_path, TPU_BFS_BENCH_BUDGET_S="3"),
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 124, proc.stderr[-2000:]
    assert time.monotonic() - t0 < 30  # watchdog, not the 120s hang
    out = _last_json_line(proc.stdout)
    assert out["value"] is None and "stale" not in out
    assert "budget" in out["error"]


@pytest.mark.parametrize("signum", [signal.SIGTERM, signal.SIGINT])
def test_signal_envelope_answers_kill_with_verdict(tmp_path, signum):
    """The driver sends a catchable signal while the main thread is
    pinned in a blocking call. The sigwait watcher must print the
    value=null verdict and exit 128 + signum — never die silently, never
    exit 0. Budget 600 (not 0): a budget of 0 is the interactive debug
    mode and deliberately skips the envelope; here it just must not fire
    first."""
    proc = subprocess.Popen(
        [sys.executable, "bench.py"],
        cwd=REPO, env=_bench_env(tmp_path, TPU_BFS_BENCH_BUDGET_S="600"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # Wait for the hang marker so the signal lands mid-"run".
        deadline = time.monotonic() + 30
        marker = ""
        while time.monotonic() < deadline and "selftest hang" not in marker:
            marker += proc.stderr.read(1) or ""
        assert "selftest hang" in marker, marker
        proc.send_signal(signum)
        stdout, stderr = proc.communicate(timeout=30)
    finally:
        proc.kill()
    assert proc.returncode == 128 + signum, stderr[-2000:]
    out = _last_json_line(stdout)
    assert out["value"] is None and "stale" not in out
    assert signal.Signals(signum).name in out["error"]


def test_budget_0_debug_mode_keeps_ctrl_c(tmp_path):
    """TPU_BFS_BENCH_BUDGET_S=0 is the documented interactive debug mode:
    the signal envelope must NOT install, so Ctrl-C still raises
    KeyboardInterrupt with a traceback instead of a verdict line."""
    proc = subprocess.Popen(
        [sys.executable, "bench.py"],
        cwd=REPO, env=_bench_env(tmp_path, TPU_BFS_BENCH_BUDGET_S="0"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.monotonic() + 30
        marker = ""
        while time.monotonic() < deadline and "selftest hang" not in marker:
            marker += proc.stderr.read(1) or ""
        assert "selftest hang" in marker, marker
        proc.send_signal(signal.SIGINT)
        stdout, stderr = proc.communicate(timeout=30)
    finally:
        proc.kill()
    assert proc.returncode != 0  # KeyboardInterrupt, not a 0-exit verdict
    assert "KeyboardInterrupt" in stderr
    assert not [l for l in stdout.splitlines() if l.startswith("{")]


def test_signal_after_printed_verdict_preserves_it(tmp_path, monkeypatch,
                                                   capsys):
    """A signal landing after main() printed its real verdict (e.g. during
    the _log_result append) must exit with THAT outcome — never append a
    lost-run line as the new last line, which would un-land the
    measurement for scripts/has_value.py. (main() resets the flag on entry, so no
    assumption is made about leftovers from earlier in-process runs.)"""
    monkeypatch.setenv("TPU_BFS_BENCH_RESULT_LOG",
                       str(_seed_log(tmp_path / "r.jsonl")))
    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setenv("TPU_BFS_BENCH_SOURCES", "2")
    monkeypatch.setenv("TPU_BFS_BENCH_SCALE", "8")
    from tpu_bfs.graph.generate import random_graph

    monkeypatch.setattr(bench, "load_graph",
                        lambda scale, ef: random_graph(64, 256, seed=3))
    assert bench.main() == 0
    # After a completed run, the flag records the printed verdict's rc:
    # the watcher/watchdog would exit with it instead of a second line.
    assert bench._FINAL_RC == 0


def test_bench_subprocess_smoke_wide(tmp_path):
    """The EXACT driver path (`python bench.py`), end to end in a
    subprocess on CPU: one fresh JSON line with a real value, the durable
    log appended, rc 0 — catches wiring regressions no in-process
    monkeypatched run can (env parsing, signal-envelope install, compile
    cache setup, the __main__ block itself). ~7 s at scale 10."""
    log = tmp_path / "results.jsonl"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
        TPU_BFS_BENCH_SCALE="10", TPU_BFS_BENCH_MODE="wide",
        TPU_BFS_BENCH_CACHE=str(tmp_path / "cache"),
        TPU_BFS_BENCH_RESULT_LOG=str(log),
    )
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = _last_json_line(proc.stdout)
    assert out["value"] is not None and "stale" not in out
    assert out["unit"] == "GTEPS" and "wide" in out["metric"]
    logged = json.loads(log.read_text().strip().splitlines()[-1])
    assert logged["value"] == out["value"] and logged["mode"] == "wide"


def test_budget_default_fits_driver_window():
    """The default budget stays at 1200 s, inside the driver's window."""
    import inspect

    src = inspect.getsource(bench._arm_budget)
    assert '"1200"' in src and "2400" not in src
