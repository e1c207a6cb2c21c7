"""Ahead-of-time program artifacts: ``jax.export``-serialized engine
executables keyed like the serve registry (ISSUE 9).

The XLA disk cache (utils/compile_cache.py) only shortcuts the backend
compile; a fresh process still pays Python tracing, lowering, and cache
lookup per program — seconds each, for every rung of a serve width
ladder. This module serializes the LOWERED programs themselves: a warmed
server exports every program its engines expose (``export_programs()``,
the ISSUE 8 ``analysis_programs`` inventory discipline), and a successor
process deserializes and INSTALLS them over the same attributes
(``adopt_programs()``) instead of re-tracing — `tpu-bfs-serve --preheat`
reaches ready-to-serve with zero engine compiles.

Artifacts are defensive by construction:

- **keyed like the registry** — ``(graph_key, engine, lanes, planes,
  pull_gate, devices)`` plus the program name, so an artifact can never
  be installed on an engine shape it wasn't exported from;
- **environment-fingerprinted** — jax version, backend, device
  kind/count; a stale fingerprint (upgraded jax, different chip) falls
  back to JIT instead of mis-deserializing, without quarantining (the
  artifact may be valid for the fleet it was built on);
- **CRC-verified** — the checkpoint-style payload CRC32 (PR 4); a
  corrupt file is quarantined (renamed ``.corrupt``) and the load falls
  back to JIT. The ``corrupt_aot`` fault kind (tpu_bfs/faults.py,
  ``aot_load`` site) drives this arm deterministically in chaos runs.

Counter semantics: ``hits`` counts validated artifact reads,
``fallbacks`` counts loads that fell back to JIT (missing / stale /
corrupt / undeserializable), ``runtime_fallbacks`` counts adopted-call
invocations whose arguments didn't match the exported signature (e.g. a
narrower one-shot batch) and ran the original jit instead, ``exports``
counts programs written. Cross-process reuse needs a STABLE graph key
(a path or generator spec); an in-process ``graph@<id>`` key only
round-trips within one process.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import struct
import threading

import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs

MAGIC = b"TBFSAOT1"
FORMAT = 1

# Program names every packed serving engine exports (the dist engines
# export their fused "dist_core" instead of "core"); "core" (or
# "dist_core") is the expensive one — the level loop — and is what
# ArtifactStore.probe keys readiness on.
CORE_NAMES = ("core", "dist_core")


class AotProgramProtocol:
    """AOT export/adopt hooks (ISSUE 9) — the serving analog of the
    ISSUE 8 ``analysis_programs`` inventory.

    Engines implement ``export_programs() -> [(name, attr, fn,
    example_args), ...]``: every compiled program the serving path
    dispatches, the engine attribute it lives on, the jitted callable,
    and ``jax.ShapeDtypeStruct`` (or concrete) example arguments —
    exactly what ``jax.export.export(fn)(*args)`` needs.
    ``adopt_programs`` installs prepared callables (deserialized
    executables wrapped by :class:`AdoptedProgram`) over those
    attributes, so a preheated process dispatches without ever tracing
    or lowering the originals."""

    _aot_adopted: tuple = ()

    def export_programs(self):
        raise NotImplementedError(
            f"{type(self).__name__} has no AOT program inventory"
        )

    def adopt_programs(self, programs: dict) -> list:
        """Install ``programs[name]`` over each inventory attribute;
        names absent from ``programs`` keep their JIT entry (partial
        stores degrade per-program, never whole-engine). Returns the
        adopted names (also kept on ``_aot_adopted`` for the analysis
        retrace sentinel and the preheat smoke)."""
        adopted = []
        for name, attr, _fn, _args in self.export_programs():
            call = programs.get(name)
            if call is not None:
                setattr(self, attr, call)
                adopted.append(name)
        self._aot_adopted = tuple(adopted)
        return adopted


class AotArtifactError(ValueError):
    """Base: an artifact that cannot serve this process."""


class CorruptAotArtifact(AotArtifactError):
    """Bad magic / torn header / payload CRC mismatch — quarantined."""


class StaleAotArtifact(AotArtifactError):
    """Environment fingerprint mismatch — fallback, NOT quarantined."""


def env_fingerprint() -> dict:
    """Everything that must match for a serialized executable to be
    safe to install here: jax version, backend, device kind and count.
    ``format`` versions the artifact layout itself."""
    import jax

    devs = jax.devices()
    return {
        "format": FORMAT,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": len(devs),
    }


def program_key(spec) -> dict:
    """Canonical store key from an EngineSpec-like (dataclass, mapping)
    — the registry's own key axes, nothing else.

    The mesh-exchange axes (exchange/wire_pack/delta_bits/sieve/predict/
    mesh_shape, ISSUE 11) enter the key ONLY when non-default: each
    reshapes the compiled collective program, so two exchange configs
    must never alias one artifact — while default-config keys (and their
    digests, hence on-disk filenames) stay byte-identical to the PR 9
    layout, so existing single-chip stores remain adoptable."""
    if dataclasses.is_dataclass(spec):
        spec = dataclasses.asdict(spec)
    key = {
        "graph_key": str(spec["graph_key"]),
        "engine": str(spec["engine"]),
        "lanes": int(spec["lanes"]),
        "planes": int(spec["planes"]),
        "pull_gate": bool(spec.get("pull_gate", False)),
        "devices": int(spec.get("devices", 1)),
    }
    if spec.get("exchange"):
        key["exchange"] = str(spec["exchange"])
    if spec.get("wire_pack"):
        key["wire_pack"] = True
    if spec.get("delta_bits"):
        key["delta_bits"] = [int(b) for b in spec["delta_bits"]]
    if spec.get("sieve"):
        key["sieve"] = True
    if spec.get("predict"):
        key["predict"] = True
    if spec.get("mesh_shape"):
        key["mesh_shape"] = [int(x) for x in spec["mesh_shape"]]
    if spec.get("kind", "bfs") != "bfs":
        # The workload-kind axis (ISSUE 14): per-kind engines compile
        # different programs (SSSP's min-plus tiles, khop's clamped
        # loop shares the base core but its residency must not alias a
        # bfs rung's artifacts). Non-default only, so every existing
        # single-chip store stays adoptable byte-for-byte.
        key["kind"] = str(spec["kind"])
    if spec.get("expand_impl", "xla") != "xla":
        # The kernel-tier axis (ISSUE 16): expand_impl='pallas' compiles
        # the fused ell_expand kernel over the padded gt tables — a
        # different program than the fori tier. Non-default only, so
        # xla-tier stores keep their PR 9 digests.
        key["expand_impl"] = str(spec["expand_impl"])
    if spec.get("overlay"):
        # The dynamic-graph axis (ISSUE 19): an overlay engine's core
        # carries the delta fold over (rows, kcap) tables — a different
        # program per capacity, never aliasing the static core. The
        # GENERATION deliberately stays out: flips swap table values
        # under one compiled program, so every generation adopts the
        # same artifact.
        key["overlay"] = [int(x) for x in spec["overlay"]]
    return key


def _key_digest(key: dict) -> str:
    blob = json.dumps(key, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


def _crc32(payload: bytes) -> int:
    import zlib

    return zlib.crc32(payload) & 0xFFFFFFFF


class ArtifactStore:
    """One directory of fingerprinted, CRC-checked program artifacts.

    File layout: ``MAGIC + u32 header_len + header_json + payload``.
    The header carries the registry key, program name, environment
    fingerprint, and the payload CRC32; the payload is the
    ``jax.export`` serialization. Writes are atomic (tmp + rename),
    like every durable artifact in this repo (utils/checkpoint.py).
    """

    def __init__(self, root, *, log=None):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self._log = log or (lambda msg: None)
        self._lock = threading.Lock()
        self.hits = 0  # guarded-by: _lock
        self.fallbacks = 0  # guarded-by: _lock
        self.runtime_fallbacks = 0  # guarded-by: _lock
        self.exports = 0  # guarded-by: _lock

    # --- bookkeeping ------------------------------------------------------

    def _bump(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def counts(self) -> dict:
        """The bench/statsz keys (BENCHMARKS.md "Cold start")."""
        with self._lock:
            return {
                "aot_hits": self.hits,
                "aot_fallbacks": self.fallbacks,
                "aot_runtime_fallbacks": self.runtime_fallbacks,
                "aot_exports": self.exports,
            }

    # --- paths ------------------------------------------------------------

    def path_for(self, key: dict, name: str) -> str:
        key = program_key(key)
        tag = (
            f"{key['engine']}-l{key['lanes']}-p{key['planes']}"
            f"{'-pg' if key['pull_gate'] else ''}-d{key['devices']}"
        )
        return os.path.join(
            self.root, f"{tag}-{name}-{_key_digest(key)}.aot"
        )

    def _quarantine(self, path: str, reason: str) -> None:
        qpath = path + ".corrupt"
        try:
            os.replace(path, qpath)
        except OSError:
            qpath = "<unmovable>"
        self._log(
            f"aot artifact corrupt ({reason}): {path} quarantined as "
            f"{qpath}; falling back to JIT"
        )

    # --- write ------------------------------------------------------------

    def put(self, key: dict, name: str, payload: bytes, *,
            donate_argnums=()) -> str:
        """Atomically write one program artifact; returns its path.

        ``donate_argnums`` records the program's buffer-donation contract
        (ISSUE 13): ``jax.export`` does not carry donation through
        deserialization, so the adopting wrapper re-applies it from the
        header — an adopted resume core aliases its carry exactly like
        the original. Written only when non-empty, so donation-free
        artifacts stay byte-identical to the PR 9 layout."""
        key = program_key(key)
        meta = {
            "key": key,
            "name": name,
            "fingerprint": env_fingerprint(),
            "payload_crc32": _crc32(payload),
        }
        if donate_argnums:
            meta["donate_argnums"] = [int(i) for i in donate_argnums]
        header = json.dumps(meta, sort_keys=True).encode()
        path = self.path_for(key, name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(MAGIC)
            f.write(struct.pack("<I", len(header)))
            f.write(header)
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._bump("exports")
        return path

    # --- read -------------------------------------------------------------

    def _read_header(self, path: str):
        """(meta, payload_offset); raises CorruptAotArtifact on any
        structural damage."""
        with open(path, "rb") as f:
            head = f.read(len(MAGIC) + 4)
            if len(head) < len(MAGIC) + 4 or head[: len(MAGIC)] != MAGIC:
                raise CorruptAotArtifact(f"bad magic in {path}")
            (hlen,) = struct.unpack("<I", head[len(MAGIC):])
            raw = f.read(hlen)
        if len(raw) < hlen:
            raise CorruptAotArtifact(f"torn header in {path}")
        try:
            meta = json.loads(raw)
        except ValueError as exc:
            raise CorruptAotArtifact(
                f"unparsable header in {path}: {exc}"
            ) from None
        return meta, len(MAGIC) + 4 + hlen

    def _validate(self, meta: dict, key: dict, name: str) -> None:
        if meta.get("key") != key or meta.get("name") != name:
            raise StaleAotArtifact(
                f"artifact keyed {meta.get('key')}/{meta.get('name')}, "
                f"wanted {key}/{name}"
            )
        fp = env_fingerprint()
        if meta.get("fingerprint") != fp:
            raise StaleAotArtifact(
                f"environment fingerprint {meta.get('fingerprint')} != "
                f"current {fp}"
            )

    def probe(self, key: dict, name: str | None = None) -> bool:
        """Read-only readiness check: does a fingerprint-current core
        artifact with an INTACT payload exist? The registry names its
        build span ``engine_adopt`` vs ``engine_build`` off this, and
        the span name is the no-compile signal the preheat smoke
        asserts — so the probe verifies the payload CRC too (a valid
        header over a torn/rotted payload must read as NOT adoptable,
        not as a phantom adoption). Side-effect free: no quarantine, no
        counter — :meth:`get` takes the consequential actions."""
        key = program_key(key)
        names = CORE_NAMES if name is None else (name,)
        for n in names:
            path = self.path_for(key, n)
            if not os.path.exists(path):
                continue
            try:
                meta, off = self._read_header(path)
                self._validate(meta, key, n)
                with open(path, "rb") as f:
                    f.seek(off)
                    payload = f.read()
                if _crc32(payload) != meta.get("payload_crc32"):
                    continue
                return True
            except (AotArtifactError, OSError):
                continue
        return False

    def get(self, key: dict, name: str, *, with_meta: bool = False):
        """The validated payload (or ``(payload, meta)`` under
        ``with_meta``), or None with the degrade applied: missing/stale
        -> fallback counted; corrupt -> quarantined + fallback counted.
        Never raises on a bad artifact — the JIT path always serves."""
        key = program_key(key)
        path = self.path_for(key, name)
        if not os.path.exists(path):
            self._bump("fallbacks")
            return None
        try:
            meta, off = self._read_header(path)
            self._validate(meta, key, name)
            with open(path, "rb") as f:
                f.seek(off)
                payload = f.read()
            if _faults.ACTIVE is not None:
                # Chaos-harness injection site (tpu_bfs/faults.py):
                # corrupt_aot flips one payload byte in memory so the CRC
                # check below fires deterministically; raising kinds
                # surface here like a real storage-layer failure.
                _faults.ACTIVE.hit("aot_load", name=name, lanes=key["lanes"])
                payload = _faults.maybe_corrupt_payload(
                    payload, name=name, lanes=key["lanes"]
                )
            if _crc32(payload) != meta.get("payload_crc32"):
                raise CorruptAotArtifact("payload CRC32 mismatch")
        except CorruptAotArtifact as exc:
            self._quarantine(path, str(exc))
            self._bump("fallbacks")
            return None
        except StaleAotArtifact as exc:
            self._log(f"aot artifact stale ({exc}); falling back to JIT")
            self._bump("fallbacks")
            return None
        except (OSError, RuntimeError) as exc:
            # Includes injected transients: a flaky artifact read must
            # degrade to JIT, never kill a preheat.
            self._log(f"aot artifact load failed ({exc!r}); falling back "
                      f"to JIT")
            self._bump("fallbacks")
            return None
        self._bump("hits")
        return (payload, meta) if with_meta else payload


class AdoptedProgram:
    """A deserialized AOT executable standing in for an engine's jit
    entry.

    Calls whose argument shapes match the exported signature run the
    deserialized program (under one ``jax.jit`` wrapper, so repeated
    dispatch is cached exactly like the original pjit entry); anything
    else — a narrower one-shot batch, a resume entry — falls back to the
    ORIGINAL jit function, so correctness never depends on the artifact.
    Exposes ``_cache_size`` like a pjit function, so the analysis trace
    sentinel (PR 8 pass 2, analysis/transfer.py) covers adopted engines
    without per-engine plumbing.
    """

    def __init__(self, name: str, exported, original, store=None,
                 donate_argnums=()):
        import jax

        self.name = name
        self._exported = exported
        # Export-side consumers reach through the wrapper for the
        # original traceable (re-exporting from an adopted server).
        self._aot_original = original
        self._store = store
        # Donation re-applied from the artifact header (ISSUE 13):
        # jax.export strips it, and an adopted resume core that copies
        # its carry would double the residency the donation removed.
        self._donate_argnums = tuple(donate_argnums)
        self._jit = (
            jax.jit(exported.call, donate_argnums=self._donate_argnums)
            if self._donate_argnums else jax.jit(exported.call)
        )
        self._in_shapes = tuple(tuple(a.shape) for a in exported.in_avals)
        self.calls = 0
        self.fallback_calls = 0

    def _matches(self, args) -> bool:
        import jax

        leaves = jax.tree_util.tree_leaves(args)
        if len(leaves) != len(self._in_shapes):
            return False
        for leaf, shape in zip(leaves, self._in_shapes):
            if tuple(np.shape(leaf)) != shape:
                return False
        return True

    def __call__(self, *args):
        if not self._matches(args):
            self.fallback_calls += 1
            if self._store is not None:
                self._store._bump("runtime_fallbacks")
            return self._aot_original(*args)
        self.calls += 1
        return self._jit(*args)

    def _cache_size(self) -> int:
        size = getattr(self._jit, "_cache_size", None)
        return size() if callable(size) else 0


def export_available() -> bool:
    try:
        from jax import export as _  # noqa: F401

        return True
    except ImportError:
        return False


def export_engine_programs(engine, spec, store: ArtifactStore, *,
                           log=None) -> list:
    """Export every program in ``engine.export_programs()`` into the
    store under the registry key for ``spec``. Per-program failures
    (e.g. an exporter that cannot handle a sharded core on this jax)
    log and skip — the store holds what it can, the JIT path serves the
    rest. Returns the exported names."""
    from jax import export as jexp

    log = log or (lambda msg: None)
    if not hasattr(engine, "export_programs"):
        # Workload adapters (ISSUE 14) carry no AOT inventory (their
        # base substrate's programs export under the kind="bfs" key;
        # the adapters' own state — weighted tiles, cached CC index —
        # is data, not programs): nothing to export, JIT serves.
        return []
    key = program_key(spec)
    done = []
    for name, _attr, fn, args in engine.export_programs():
        # Re-exporting from an adopted engine must serialize the
        # original traceable, not the wrapper.
        fn = getattr(fn, "_aot_original", fn)
        with _obs.host_span(
            "tpu_bfs.setup.aot_export",
            span_id=f"{key['engine']}-w{key['lanes']}-{name}",
            cat="aot", program=name, width=key["lanes"],
        ):
            try:
                exported = jexp.export(fn)(*args)
                store.put(
                    key, name, exported.serialize(),
                    donate_argnums=getattr(fn, "_donate_argnums", ()),
                )
            except Exception as exc:  # noqa: BLE001 — per-program degrade
                log(f"aot export of {name!r} failed "
                    f"({type(exc).__name__}: {str(exc)[:160]}); skipped")
                continue
        done.append(name)
    return done


def adopt_engine_programs(engine, spec, store: ArtifactStore, *,
                          log=None) -> list:
    """Load, deserialize, and INSTALL the store's programs over the
    engine's jit entries (``engine.adopt_programs``). Missing/stale/
    corrupt artifacts are skipped (the store counts the fallback and
    the engine keeps its JIT entry for that program). Returns the
    adopted names."""
    from jax import export as jexp

    log = log or (lambda msg: None)
    if not hasattr(engine, "export_programs"):
        return []  # workload adapter: no inventory, JIT serves (above)
    key = program_key(spec)
    programs = {}
    for name, _attr, fn, _args in engine.export_programs():
        with _obs.host_span(
            "tpu_bfs.setup.aot_load",
            span_id=f"{key['engine']}-w{key['lanes']}-{name}",
            cat="aot", program=name, width=key["lanes"],
        ):
            got = store.get(key, name, with_meta=True)
            if got is None:
                continue
            payload, meta = got
            try:
                exported = jexp.deserialize(payload)
            except Exception as exc:  # noqa: BLE001 — CRC-clean but unloadable
                store._quarantine(
                    store.path_for(key, name),
                    f"deserialize failed: {type(exc).__name__}: "
                    f"{str(exc)[:160]}",
                )
                store._bump("fallbacks")
                continue
        programs[name] = AdoptedProgram(
            name, exported, fn, store=store,
            donate_argnums=meta.get("donate_argnums", ()),
        )
    adopted = engine.adopt_programs(programs)
    if adopted:
        log(f"aot adopted {adopted} for {key['engine']}/w{key['lanes']}")
    return adopted
