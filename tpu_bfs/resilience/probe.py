"""Mesh health probe: a tiny all-reduce heartbeat per replica.

A mesh fault is detected where it bites (the serving fetch), but
RECOVERY needs the opposite signal — proof a mesh shape is healthy
again before traffic is routed back onto it. The heartbeat is the
smallest program that exercises the failure mode: one psum of a
replicated scalar across every device of the probed mesh, so a lost
participant, a hung collective, or a restarting backend fails the
probe exactly as it would fail a serving batch's exchange.

``mesh_heartbeat`` is the one-shot form (the degraded service's
``mesh_restore`` gates each promotion on it); :class:`MeshHealthProbe`
is the background prober a long-lived server arms
(``--mesh-probe-interval-s``) so a degraded replica climbs back to the
full mesh without an operator. Both consult the ``probe`` fault site
(tpu_bfs/faults.py), so a chaos schedule can hold a mesh "dead" past
its injected fault and prove the service stays degraded until the
probe clears.
"""

from __future__ import annotations

import threading
import time

from tpu_bfs import faults as _faults

# One compiled heartbeat per device count, reused across probes: the
# probe must stay cheap enough to run on a timer (the first call per
# count pays one tiny compile; after that it is one collective launch).
_HEARTBEATS: dict = {}  # guarded-by: _HB_LOCK
_HB_LOCK = threading.Lock()


def _heartbeat_fn(devices: int):
    with _HB_LOCK:
        fn = _HEARTBEATS.get(devices)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jax import shard_map

    avail = jax.devices()
    if devices > len(avail):
        raise ValueError(
            f"heartbeat over {devices} devices: only {len(avail)} attached"
        )
    mesh = Mesh(np.array(avail[:devices]), ("hb",))

    def local(x):
        return lax.psum(jnp.sum(x), "hb")

    inner = jax.jit(shard_map(
        local, mesh=mesh, in_specs=P("hb"), out_specs=P(), check_vma=False,
    ))
    ones = jax.device_put(
        np.ones(devices, np.int32), NamedSharding(mesh, P("hb"))
    )

    def beat():
        out = inner(ones)
        jax.block_until_ready(out)
        got = int(jnp.asarray(out))
        if got != devices:
            # A psum returning the wrong count means a participant's
            # contribution silently vanished — treat as device loss.
            raise RuntimeError(
                f"DATA_LOSS: mesh heartbeat psum returned {got}, "
                f"expected {devices} (a participant is missing)"
            )

    with _HB_LOCK:
        _HEARTBEATS[devices] = beat
    return beat


def reset_heartbeats() -> None:
    """Drop the compiled heartbeat cache (tests; and after a backend
    restart the old executables' device handles are stale anyway)."""
    with _HB_LOCK:
        _HEARTBEATS.clear()


def mesh_heartbeat(devices: int) -> float:
    """Run one all-reduce heartbeat across ``devices`` devices; returns
    the heartbeat latency in seconds. Raises whatever the collective
    raised on an unhealthy mesh (classify with
    ``utils/recovery.is_mesh_fault`` / ``is_transient_failure``)."""
    if _faults.ACTIVE is not None:
        # Chaos-harness injection site: a mesh kind scheduled at
        # "probe" makes this mesh shape report dead — holding a
        # degraded service off the full mesh until the schedule clears.
        _faults.ACTIVE.hit("probe", devices=devices)
    beat = _heartbeat_fn(devices)
    t0 = time.perf_counter()
    beat()
    return time.perf_counter() - t0


class MeshHealthProbe:
    """Background prober for a degraded service.

    Every ``interval_s`` it asks ``current()`` for the service's live
    device count; when that sits below ``target_devices`` it heartbeats
    the rungs above (widest first) and calls ``on_healthy(devices)``
    for the widest one that answers — the service's ``mesh_restore``
    hook, which rebuilds the ladder there. Probe failures are swallowed
    (the mesh is still dead; that is the expected case) but reported to
    ``log``. Daemon thread; ``stop()`` is idempotent and joins."""

    def __init__(self, target_devices: int, *, interval_s: float,
                 current, on_healthy, log=None):
        if interval_s <= 0:
            raise ValueError(f"interval_s must be > 0, got {interval_s}")
        self.target_devices = int(target_devices)
        self.interval_s = float(interval_s)
        self._current = current
        self._on_healthy = on_healthy
        self._log = log or (lambda msg: None)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="bfs-mesh-probe", daemon=True
        )

    def start(self) -> "MeshHealthProbe":
        self._thread.start()
        return self

    def _rungs_above(self, devices: int) -> list[int]:
        from tpu_bfs.resilience.failover import degrade_ladder

        return [d for d in degrade_ladder(self.target_devices)
                if d > devices]

    def probe_once(self) -> int | None:
        """One probe pass (also the test hook): returns the device count
        promoted to, or None when nothing changed."""
        devices = self._current()
        if devices >= self.target_devices:
            return None
        for d in self._rungs_above(devices):
            try:
                latency = mesh_heartbeat(d)
            except Exception as exc:  # noqa: BLE001 — dead mesh is expected
                self._log(
                    f"mesh probe: {d}-device heartbeat failed "
                    f"({type(exc).__name__}: {str(exc)[:120]})"
                )
                continue
            self._log(
                f"mesh probe: {d}-device heartbeat healthy "
                f"({latency * 1e3:.1f} ms); promoting"
            )
            self._on_healthy(d)
            return d
        return None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.probe_once()
            except Exception as exc:  # noqa: BLE001 — the prober must survive
                self._log(f"mesh probe pass failed ({exc!r})")

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
