"""Persistent XLA compilation cache, shared by every entry point: the CLI,
bench.py, the serve registry, chip_smoke.py and the measurement scripts.

Where the cache lives is decided outside the program when it can be. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing. Otherwise the cache sits at one fixed absolute path inside
the checkout, ``<repo root>/.bench_cache/xla_cache``, built from this
file's location and not from the cwd: a directory that moves with the
cwd never hits.

Resolution is ONCE PER PROCESS: every ``EngineRegistry()`` construction
and every entry point calls :func:`enable_compile_cache`; the first
call's outcome (path or unavailable) is cached and later calls return it
silently. ``force=True`` re-resolves (tests that vary the env).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default: <repo root>/.bench_cache/xla_cache.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".bench_cache",
    "xla_cache",
)

# The first call's resolved outcome, kept as a 1-tuple so a resolved
# "unavailable" (None) is distinguishable from "never resolved".
_RESOLVED: tuple | None = None


def reset_resolution() -> None:
    """Forget the cached resolution (tests that vary the env)."""
    global _RESOLVED
    _RESOLVED = None


def enable_compile_cache(log=None, *, force: bool = False) -> str | None:
    """Arm the persistent compile cache; best-effort and idempotent
    (resolved once per process — see module docstring).

    Returns the cache path in use, or None when jax rejected the knob
    (the cache is an optimization, never a dependency).
    """
    global _RESOLVED
    if _RESOLVED is not None and not force:
        return _RESOLVED[0]
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        # JAX's own handling: it read the variable at import.
        if log:
            log(f"persistent compile cache: {from_env} (from {ENV_VAR})")
        _RESOLVED = (from_env,)
        return from_env
    try:
        os.makedirs(DEFAULT_DIR, exist_ok=True)
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        if log:
            log(f"persistent compile cache: {DEFAULT_DIR}")
        _RESOLVED = (DEFAULT_DIR,)
        return DEFAULT_DIR
    except Exception as exc:  # noqa: BLE001 — the cache is an optimization
        if log:
            log(f"compile cache unavailable ({exc!r}); continuing without")
        _RESOLVED = (None,)
        return None
