"""Unified telemetry: span tracing, per-level engine traces, exporters,
and the flight recorder (ISSUE 6).

One ACTIVE-guard discipline, copied from :mod:`tpu_bfs.faults`: every
production instrumentation site is a single module-attribute check
(``if obs.ACTIVE is not None``) against a global that stays ``None``
unless a recorder was explicitly armed — via ``--obs``/``--trace-out``
(CLI and serve), the ``TPU_BFS_OBS`` env var, or :func:`arm` in tests.
The un-armed hot path pays one attribute read per site and allocates
nothing (tests/test_obs.py pins that with a spy counter, mirroring the
faults determinism tests).

What the armed recorder collects:

- **spans/events** (:class:`~tpu_bfs.obs.recorder.Recorder`): a
  thread-safe ring buffer of ``time.monotonic``-stamped records wired
  through the full serve lifecycle (admit -> enqueue -> coalesce ->
  dispatch -> fetch -> extract -> resolve, plus registry build/warm and
  every retry/degrade/shed), keyed so each query id's chain carries its
  batch id, width rung, and attempt history;
- **per-level engine traces** (:mod:`~tpu_bfs.obs.engine_trace`): the
  packed dispatch/fetch halves and the distributed engines expose
  ``last_run_trace`` — per BFS level: frontier population, push/pull
  direction, gated-tile skips, cap-ladder exchange choice, and modeled
  wire bytes priced from ``wire_bytes_per_level()``;
- **flight recorder**: the ring buffer auto-dumps its last
  ``window_s`` seconds to a timestamped JSONL file on watchdog trip,
  breaker open, requeue shed, uncaught executor error, or SIGTERM
  drain — every chaos-harness failure becomes a replayable artifact;
- **exporters** (:mod:`~tpu_bfs.obs.exporters`): Chrome/Perfetto
  trace-event JSON (``--trace-out``), Prometheus-style text
  (``/metricz`` via ``BfsService.metricz`` and ``--metricz-out``), and
  plain JSONL.

Names in the profiler's own trace (``jax.profiler``: the CLI's
``--profile-dir``, serve's ``--xprof-dir``), the same trace the device's
ops land in:

- :class:`scope` names a phase of a jitted program (a
  ``jax.named_scope``, as a ``with`` block or a decorator): it lands in
  each compiled op's ``op_name`` metadata, and only there
  (tests/test_obs_scopes.py holds the compiled programs equal with and
  without it). The level loop's phases are
  ``level.tiles``, ``level.gather``, ``level.heavy``, ``level.push`` and
  ``level.claim``, and on a mesh ``level.exchange`` (the frontier's
  exchange between chips) and ``level.sync`` (the termination sum over
  chips); a batch's set-up and reductions ``batch.seed`` and
  ``batch.stats``; an engine's whole core ``lanes<W>``.
- :func:`host_span` names one host step per batch (``tpu_bfs.engine.*``,
  ``tpu_bfs.serve.*``), or one set-up step (``tpu_bfs.setup.*``,
  ``tpu_bfs.graph.compact``), as a ``jax.profiler.TraceAnnotation`` on
  the profiler's clock, with stats computed only while a profiler
  records; where a recorder is armed the same call records its
  begin/end pair. With neither on it returns a shared no-op span.

Spec grammar (``--obs`` / ``TPU_BFS_OBS``)::

    spec  := "1" | "true" | "0" | "off" | kv ("," kv)*
    kv    := "capacity=" INT | "window=" FLOAT (seconds)
           | "dump_dir=" PATH | "max_dumps=" INT

Example: ``TPU_BFS_OBS=dump_dir=/tmp/flightrec,window=60``. Falsy
values (``0``/``false``/``off``/``no``) leave telemetry DISARMED — a
fleet-standard disable value must never kill the process (the same
never-die-on-an-env-knob rule bench._env_bool keeps).
"""

from __future__ import annotations

import contextlib
import os

from tpu_bfs.obs.recorder import Recorder

__all__ = [
    "ACTIVE",
    "ENV_VAR",
    "Recorder",
    "arm",
    "arm_for_run",
    "arm_from_env",
    "arm_from_spec",
    "arm_from_spec_or_env",
    "disarm",
    "host_span",
    "scope",
]

# THE guard production sites check: None (the default) keeps every
# instrumentation site a single attribute test with no further work.
ACTIVE: Recorder | None = None

ENV_VAR = "TPU_BFS_OBS"

_TRUTHY = ("1", "true", "on", "yes")
_FALSY = ("0", "false", "off", "no")


def _parse_spec(spec: str) -> dict:
    spec = spec.strip()
    kw: dict = {}
    if not spec or spec.lower() in _TRUTHY:
        return kw
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        k, eq, v = item.partition("=")
        k = k.strip()
        try:
            if k == "capacity":
                kw["capacity"] = int(v)
            elif k == "window":
                kw["window_s"] = float(v)
            elif k == "dump_dir":
                kw["dump_dir"] = v.strip()
            elif k == "max_dumps":
                kw["max_dumps"] = int(v)
            else:
                raise ValueError
        except ValueError:
            raise ValueError(
                f"bad obs spec item {item!r} (capacity=INT, window=FLOAT, "
                f"dump_dir=PATH, max_dumps=INT)"
            ) from None
        if not eq:
            raise ValueError(f"obs spec item {item!r} must be key=value")
    return kw


def arm(recorder: Recorder | None = None, **kw) -> Recorder:
    """Install ``recorder`` (or a fresh one built from ``kw``) as the
    process-wide ACTIVE recorder. Idempotent-friendly: re-arming replaces
    the previous recorder (its events are dropped with it)."""
    global ACTIVE
    ACTIVE = recorder if recorder is not None else Recorder(**kw)
    return ACTIVE


def arm_from_spec(spec: str) -> Recorder | None:
    """Arm from one spec string; an explicitly-falsy spec (``0``,
    ``false``, ``off``, ``no``) returns None WITHOUT arming — and, via
    arm_from_spec_or_env, without falling through to the env var (an
    explicit ``--obs 0`` overrides a fleet-set TPU_BFS_OBS)."""
    if spec.strip().lower() in _FALSY:
        return None
    return arm(**_parse_spec(spec))


def arm_from_env(env: str = ENV_VAR) -> Recorder | None:
    spec = os.environ.get(env, "").strip()
    return arm_from_spec(spec) if spec else None


def arm_from_spec_or_env(spec: str | None, env: str = ENV_VAR) -> Recorder | None:
    """The entry points' shared precedence (same contract as
    faults.arm_from_spec_or_env): an explicit ``--obs`` spec wins over the
    environment variable; neither set = stay disarmed."""
    return arm_from_spec(spec) if spec is not None else arm_from_env(env)


def arm_for_run(spec: str | None, trace_out: str | None = None,
                env: str = ENV_VAR) -> Recorder | None:
    """The shared entry-point arming (cli.py and serve): an explicit
    ``--obs`` spec wins, else the env var; ``--trace-out`` needs a
    recorder, so it arms one with defaults when nothing else did."""
    rec = arm_from_spec_or_env(spec, env)
    if rec is None and trace_out:
        rec = arm()
    return rec


def disarm() -> None:
    global ACTIVE
    ACTIVE = None


class scope(contextlib.ContextDecorator):
    """``jax.named_scope(name)``, for use inside jitted code, as a
    ``with`` block or a decorator: the phase name reaches each compiled
    op's ``op_name`` metadata (the trace's ``tf_op``) and changes nothing
    else of the program. Each use opens a fresh named scope, so one
    decorated function may be traced from several threads at once."""

    __slots__ = ("name", "_cm")

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self):
        return scope(self.name)

    def __enter__(self):
        import jax

        self._cm = jax.named_scope(self.name)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)


_annotation = None  # jax.profiler.TraceAnnotation, bound on first use


def _profiler_annotation():
    """The annotation class while a profiler records, else None."""
    global _annotation
    ann = _annotation
    if ann is None:
        from jax.profiler import TraceAnnotation as ann

        _annotation = ann
    return ann if ann.is_enabled() else None


class _NullSpan:
    """What :func:`host_span` returns with no profiler and no recorder."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        """End arguments for the recorder's span: none to keep here."""


_NULL_SPAN = _NullSpan()


class _HostSpan:
    __slots__ = ("_ann", "_stats", "_rec", "_name", "_id", "_cat", "_args",
                 "_end")

    def __init__(self, ann, stats, rec, name, span_id, cat, args):
        self._ann = ann
        self._stats = stats
        self._rec = rec
        self._name = name
        self._id = span_id
        self._cat = cat
        self._args = args
        self._end = {}

    def __enter__(self):
        if self._rec is not None:
            self._rec.begin(self._name.rsplit(".", 1)[-1], self._id,
                            cat=self._cat, **self._args)
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def set(self, **args) -> None:
        """End arguments for the recorder's span (its outcome)."""
        self._end.update(args)

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            if self._stats is not None and exc_type is None:
                self._ann.set_metadata(**self._stats())
            self._ann.__exit__(exc_type, exc, tb)
        if self._rec is not None:
            if exc_type is not None:
                self._end.setdefault("failed", True)
            self._rec.end(self._name.rsplit(".", 1)[-1], self._id,
                          cat=self._cat, **self._end)
        return False


def host_span(name: str, stats=None, *, span_id: str | None = None,
              cat: str = "span", **args):
    """One host step (a batch's, or a set-up step) as a span.

    - While a profiler records: a ``jax.profiler.TraceAnnotation`` named
      ``name`` (``tpu_bfs.<layer>.<step>``), on the profiler's clock in
      the same trace as the device's ops. ``stats`` is a callable
      returning its keyword stats; it is called once, as the span ends
      without an exception, so it may read what the step found, and only
      while a profiler records.
    - Where a recorder is armed and ``span_id`` is given: the recorder's
      begin/end pair over the same interval, named by the last part of
      ``name`` (``tpu_bfs.serve.dispatch`` -> ``dispatch``), begun with
      ``args`` and ended with what :meth:`set` was given (plus
      ``failed=True`` when an exception leaves the span).

    With neither on it returns one shared no-op span: the caller pays an
    ``is_enabled`` check and an attribute read, and ``stats`` is never
    called."""
    ann = _profiler_annotation()
    rec = ACTIVE if span_id is not None else None
    if ann is None and rec is None:
        return _NULL_SPAN
    return _HostSpan(None if ann is None else ann(name),
                     None if ann is None else stats, rec, name, span_id, cat,
                     args)
