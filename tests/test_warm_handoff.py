"""scripts/warm_handoff.py + scripts/fleet_supervisor.py (ISSUE 12
satellite: the handoff driver had no tests; the fleet supervisor
inherits its arms).

The contracts under test, with NO jax server in the loop (tiny stand-in
processes keep the suite fast): zombie-aware pid liveness; a successor
that dies (or never reports READY) leaves the old server UNTOUCHED; the
old server is SIGTERM-drained only AFTER the successor's READY line;
and the supervisor's fleet versions — READY-gated spawn, client-side
requeue of a killed replica's in-flight queries onto a sibling, and
health-gated replacement.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import warm_handoff  # noqa: E402
from fleet_supervisor import FleetSupervisor  # noqa: E402

pytestmark = pytest.mark.serve


def _wait(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


# A stand-in server speaking just enough of the tpu-bfs-serve contract:
# a READY line on stderr, then echo-style JSONL responses on stdout.
FAKE_SERVER = r"""
import json, signal, sys
print("# serving (fake)", file=sys.stderr, flush=True)
print("# READY engine=fake lanes=32 ladder=[32]", file=sys.stderr, flush=True)
signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))
for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    req = json.loads(line)
    print(json.dumps({"id": req.get("id"), "source": req.get("source"),
                      "status": "ok", "levels": 1, "reached": 1}),
          flush=True)
"""


def fake_server_argv():
    return [sys.executable, "-u", "-c", FAKE_SERVER]


# --- pid_alive: zombie-aware liveness ---------------------------------------


def test_pid_alive_zombie_is_dead():
    """A drained-but-unreaped child is a zombie: os.kill(pid, 0) still
    succeeds there, so pid_alive must consult the process STATE."""
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    # Do NOT reap: wait until the process is gone-or-zombie via /proc.
    _wait(lambda: not warm_handoff.pid_alive(child.pid),
          msg="zombie child to read as dead")
    os.kill(child.pid, 0)  # the naive check would still say alive
    child.wait()  # reap
    assert not warm_handoff.pid_alive(child.pid)


def test_pid_alive_live_process():
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(60)"])
    try:
        assert warm_handoff.pid_alive(child.pid)
    finally:
        child.kill()
        child.wait()


# --- warm_handoff: READY gating ---------------------------------------------


def _old_server():
    """A stand-in 'old server' that exits cleanly on SIGTERM. Waits for
    its 'armed' line so a SIGTERM can never beat the handler install."""
    p = subprocess.Popen([
        sys.executable, "-u", "-c",
        "import signal, sys, time;"
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
        "print('armed', flush=True);"
        "time.sleep(600)",
    ], stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "armed"
    return p


def test_successor_death_leaves_old_server_untouched():
    old = _old_server()
    try:
        rc = warm_handoff.main([
            "--old-pid", str(old.pid), "--ready-timeout", "30",
            "--", sys.executable, "-c", "import sys; sys.exit(3)",
        ])
        assert rc == 1
        assert old.poll() is None and warm_handoff.pid_alive(old.pid)
    finally:
        old.kill()
        old.wait()


def test_ready_timeout_leaves_old_server_untouched():
    old = _old_server()
    try:
        rc = warm_handoff.main([
            "--old-pid", str(old.pid), "--ready-timeout", "1",
            "--", sys.executable, "-c", "import time; time.sleep(60)",
        ])
        assert rc == 1
        assert old.poll() is None and warm_handoff.pid_alive(old.pid)
    finally:
        old.kill()
        old.wait()


def test_ready_gated_drain(capsys):
    """The old server is SIGTERMed only after the successor's READY
    line; the driver returns the successor's rc and reports the drain."""
    old = _old_server()
    try:
        rc = warm_handoff.main([
            "--old-pid", str(old.pid), "--term-wait", "30",
            "--", sys.executable, "-c",
            "import sys; print('# READY fake', file=sys.stderr, flush=True)",
        ])
        assert rc == 0
        _wait(lambda: old.poll() is not None, msg="old server drained")
        assert old.returncode == 0  # SIGTERM handler ran: graceful exit
        handoff = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert handoff["old_drained"] is True
        assert handoff["successor_rc"] == 0
    finally:
        if old.poll() is None:
            old.kill()
        old.wait()


# --- fleet supervisor: the inherited arms, fleet-wide -----------------------


def test_fleet_serves_and_restarts_dead_replica():
    """SIGKILL one replica mid-stream: its in-flight queries requeue
    onto the sibling, a replacement spawns READY-gated, and every query
    still answers exactly once."""
    responses = []
    fleet = FleetSupervisor(
        fake_server_argv(), replicas=2, ready_timeout=30.0, term_wait=5.0,
        emit=responses.append, log=lambda m: None,
    ).start()
    try:
        for i in range(4):
            fleet.submit({"id": i, "source": i})
        _wait(lambda: len(responses) >= 4, msg="first wave answered")
        victim = fleet._replicas[0]
        victim.proc.kill()
        _wait(lambda: victim.proc.poll() is not None, msg="victim death")
        for i in range(4, 8):
            fleet.submit({"id": i, "source": i})
        _wait(lambda: len(responses) >= 8, msg="second wave answered")
        # Health-gated replacement: the fleet is back to 2 READY replicas.
        _wait(lambda: len([r for r in fleet._replicas
                           if r.ready.is_set() and r.alive()]) == 2,
              msg="replacement READY")
        assert fleet.restarts == 1
    finally:
        fleet.close()
    assert sorted(r["id"] for r in responses) == list(range(8))
    assert all(r["status"] == "ok" for r in responses)


def test_fleet_requeues_killed_replicas_in_flight():
    """A replica killed with queries IN FLIGHT (it never answered them):
    the supervisor requeues them onto the sibling — exactly-once, no
    silent drops."""
    slow_server = FAKE_SERVER.replace(
        'req = json.loads(line)',
        'req = json.loads(line)\n    import time; time.sleep(0.3)',
    )
    responses = []
    fleet = FleetSupervisor(
        [sys.executable, "-u", "-c", slow_server], replicas=2,
        ready_timeout=30.0, term_wait=5.0, restart=False,
        emit=responses.append, log=lambda m: None,
    ).start()
    try:
        for i in range(6):
            fleet.submit({"id": i, "source": i})
        # Kill one replica while its queries are still pending.
        victim = fleet._replicas[0]
        victim.proc.kill()
        _wait(lambda: len(responses) >= 6, timeout=60.0,
              msg="all queries answered after the kill")
        assert fleet.requeues >= 1
    finally:
        fleet.close()
    assert sorted(r["id"] for r in responses) == list(range(6))
    assert all(r["status"] == "ok" for r in responses)


def test_fleet_drain_timeout_resolves_pending_with_errors():
    """A replica that goes READY but never answers must not strand its
    clients: fail_pending emits an explicit error response per query
    (the never-silent-drops bar), counted in the summary."""
    mute_server = FAKE_SERVER.replace(
        "print(json.dumps(",
        "continue  # wedged: never answers\n    print(json.dumps(",
    )
    responses = []
    fleet = FleetSupervisor(
        [sys.executable, "-u", "-c", mute_server], replicas=1,
        ready_timeout=30.0, restart=False,
        emit=responses.append, log=lambda m: None,
    ).start()
    try:
        fleet.submit({"id": 1, "source": 0})
        assert not fleet.wait_drained(0.5)
        n = fleet.fail_pending("drain timeout")
        assert n == 1 and fleet.summary()["failed"] == 1
        assert responses and responses[0]["status"] == "error"
        assert responses[0]["id"] == 1
        assert fleet.wait_drained(0.1)  # nothing pending anymore
    finally:
        fleet.close()


def test_fleet_refuses_never_ready_binary():
    with pytest.raises(SystemExit, match="not READY"):
        FleetSupervisor(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            replicas=1, ready_timeout=0.5, log=lambda m: None,
        ).start()


def test_fleet_client_id_collisions_across_replicas():
    """Two clients using the same id: the internal wire id keeps them
    distinct and each response carries its own client id back."""
    responses = []
    fleet = FleetSupervisor(
        fake_server_argv(), replicas=2, ready_timeout=30.0,
        emit=responses.append, log=lambda m: None,
    ).start()
    try:
        fleet.submit({"id": "same", "source": 1})
        fleet.submit({"id": "same", "source": 2})
        _wait(lambda: len(responses) == 2, msg="both collided ids answered")
    finally:
        fleet.close()
    assert [r["id"] for r in responses] == ["same", "same"]
    assert sorted(r["source"] for r in responses) == [1, 2]


# --- one process per chip ----------------------------------------------------


def test_chip_sets_from_env():
    chips_of = warm_handoff.chips_of
    assert chips_of({"JAX_PLATFORMS": "cpu"}) == frozenset()
    assert chips_of({}) is warm_handoff.ALL_CHIPS
    assert chips_of({"TPU_VISIBLE_CHIPS": "1,3"}) == {1, 3}
    overlap = warm_handoff.chips_overlap
    assert overlap(warm_handoff.ALL_CHIPS, frozenset({2}))
    assert overlap(frozenset({1, 3}), frozenset({3}))
    assert not overlap(frozenset({0}), frozenset({1}))
    assert not overlap(frozenset(), warm_handoff.ALL_CHIPS)


@pytest.mark.parametrize("succ_chips,refused", [("0", True), ("1", False)])
def test_handoff_refuses_successor_on_held_chip(monkeypatch, succ_chips,
                                                refused):
    """A successor that could open the chip the old server holds is
    refused up front (rc 2, nothing started, old server untouched); one
    pinned to another chip proceeds to the READY gate."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    old = subprocess.Popen(
        [sys.executable, "-u", "-c", "import time; print('up', flush=True);"
         " time.sleep(600)"],
        stdout=subprocess.PIPE, text=True,
        env=dict(env, TPU_VISIBLE_CHIPS="0"),
    )
    try:
        assert old.stdout.readline().strip() == "up"
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", succ_chips)
        rc = warm_handoff.main([
            "--old-pid", str(old.pid), "--ready-timeout", "30",
            "--", sys.executable, "-c", "import sys; sys.exit(3)",
        ])
        # Refused: 2 before the successor runs; allowed: the successor
        # ran and died before READY (1).
        assert rc == (2 if refused else 1)
        assert old.poll() is None and warm_handoff.pid_alive(old.pid)
    finally:
        old.kill()
        old.wait()


def test_fleet_pins_one_chip_per_replica(monkeypatch):
    import fleet_supervisor as fs

    monkeypatch.setattr(fs, "host_chip_count", lambda: 2)
    assert fs.replica_chips(2, env={}) == [0, 1]
    assert fs.replica_chips(1, env={"TPU_VISIBLE_CHIPS": "3"}) == [3]
    assert fs.replica_chips(3, env={"JAX_PLATFORMS": "cpu"}) == [None] * 3
    with pytest.raises(ValueError, match="3 replicas need 3 TPU chips"):
        fs.replica_chips(3, env={})
    pinned = fs.pinned_env(1)
    assert pinned["TPU_VISIBLE_CHIPS"] == "1"
    assert pinned["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert fs.pinned_env(None) == dict(os.environ)
