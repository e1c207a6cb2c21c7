"""Virtual-device bootstrap: make JAX expose >= n devices on machines with
fewer real accelerators by forcing n virtual XLA CPU devices.

This is how the framework tests multi-chip behavior without a pod — the
capability the reference lacks entirely (its 2-node MPI path,
/root/reference/bfs_mpi.cu:549-643, cannot be exercised without two real
nodes). One copy of the recipe, shared by ``tests/conftest.py`` and
``__graft_entry__.dryrun_multichip``.

The mechanics are delicate because XLA parses ``XLA_FLAGS`` once, at the
first client creation of *any* platform in the process:

- If no backend has been initialized yet, patching ``os.environ`` and
  updating ``jax_platforms`` is sufficient (and cheap — the real-accelerator
  plugin is never touched).
- If a backend was initialized but the flag was already in the environment
  (e.g. a TPU backend probed first), dropping the backend cache makes the
  next CPU client honor the already-parsed flag.
- If the first client was created *before* the flag entered the environment,
  the parsed flag state is stale and nothing in-process can fix it; we raise
  with the exact external recipe instead of letting an undersized mesh make
  distributed code pass vacuously (the reference's own validation sin,
  bfs_mpi.cu:844-846).
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def _patch_flags(n: int) -> None:
    """Ensure XLA_FLAGS requests at least n host-platform devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(m.group(0), f"{_FLAG}={n}")


def ensure_virtual_devices(n: int, *, prefer_real: bool = False) -> None:
    """Make ``jax.devices()`` return >= n devices, virtualizing on CPU.

    With ``prefer_real=True``, an already-sufficient real-device fleet is
    left untouched (the flag append is still done first — harmless, and it
    must precede the device probe to survive in the fallback case).
    Otherwise, or when real devices are too few, the CPU platform is forced
    with n virtual devices. Raises RuntimeError with the external recipe if
    the process consumed XLA_FLAGS before this call.
    """
    _patch_flags(n)
    if not prefer_real:
        # Children this process starts (test servers, smokes) stay off
        # the TPU too: a chip serves one process at a time.
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if not prefer_real:
        # Pre-init this is decisive; post-init it is silently ignored and
        # the clear_backends path below takes over.
        jax.config.update("jax_platforms", "cpu")
    if jax.device_count() >= n and (
        prefer_real or jax.devices()[0].platform == "cpu"
    ):
        return

    import jax.extend.backend as jeb

    jax.config.update("jax_platforms", "cpu")
    jeb.clear_backends()
    if jax.device_count() < n or jax.devices()[0].platform != "cpu":
        raise RuntimeError(
            f"could not bootstrap {n} virtual CPU devices (got "
            f"{jax.devices()}): XLA_FLAGS was consumed before "
            f"ensure_virtual_devices({n}) ran. Call it before any JAX "
            f"backend use, or launch with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS={_FLAG}={n}."
        )
