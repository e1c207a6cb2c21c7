"""The outage envelope's jax-internals assumptions, pinned (VERDICT r5
weak #6).

bench._backend_came_up attributes a blown budget to "no backend came up"
vs "live backend, budget too small" by reading
``jax._src.xla_bridge._backends`` WITHOUT triggering initialization, and
degrades to the conservative False on any internals change. That
degradation is silent by design at runtime — so a jax bump that moves the
registry must break HERE, loudly, instead of quietly turning every budget
verdict into a phantom outage. Same deal for the sigwait watcher's
subprocess contract (utils/native.py unblocks the inherited mask) and the
virtual-device bootstrap's backend-cache clear
(jax.extend.backend.clear_backends).
"""

import signal

import jax

import bench


def test_xla_bridge_backends_registry_exists():
    """The private registry _backend_came_up reads must exist and be a
    dict — the exact shape bench probes (bool(xla_bridge._backends))."""
    from jax._src import xla_bridge

    assert hasattr(xla_bridge, "_backends")
    assert isinstance(xla_bridge._backends, dict)


def test_backend_came_up_true_after_init():
    """After jax initializes (the test session forces CPU devices), the
    probe must say so — False here means every budget exhaustion on a
    LIVE backend would be misattributed to an outage."""
    jax.devices()
    from jax._src import xla_bridge

    assert xla_bridge._backends, "registry empty after jax.devices()"
    assert bench._backend_came_up() is True


def test_backend_probe_never_initializes():
    """_backend_came_up must read sys.modules, never import jax itself:
    the watchdog calls it precisely when an init is wedged. Source-level
    pin — the function must consult sys.modules before touching jax."""
    import inspect

    src = inspect.getsource(bench._backend_came_up)
    assert "modules.get" in src and "import jax\n" not in src


def test_clear_backends_entrypoint_exists():
    """utils/virtual_mesh.ensure_virtual_devices re-creates the CPU client
    with the forced device count through
    jax.extend.backend.clear_backends; its disappearance must fail here,
    not as an undersized mesh."""
    import jax.extend.backend as jax_backend

    assert callable(jax_backend.clear_backends)


def test_sigwait_watcher_signal_assumptions():
    """The signal envelope blocks then sigwait()s its set from a
    non-main thread; both primitives must exist with the semantics the
    watcher assumes (pthread_sigmask accepts SIG_BLOCK from any thread,
    sigwait takes an iterable of signals)."""
    assert callable(signal.pthread_sigmask) and callable(signal.sigwait)
    # Reading the current mask is side-effect free and validates the
    # (how, mask) calling convention the envelope uses.
    cur = signal.pthread_sigmask(signal.SIG_BLOCK, ())
    assert isinstance(cur, set)
