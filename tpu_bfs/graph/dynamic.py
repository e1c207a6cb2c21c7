"""Dynamic graphs: streaming edge updates over the frozen ELL base
(ISSUE 19).

Every engine's tiled-ELL tables are immutable — rebuilding them per edge
update would cost an ELL build plus an XLA compile per batch. This
module adds the two-layer representation the serve tier mutates through:

- the **base**: the immutable ELL generation every engine compiled over
  (untouched by updates);
- a **bounded dense delta overlay**: up to ``rows`` mutated rank-rows of
  up to ``kcap`` neighbor slots each, uploaded as fixed-shape device
  tables (``ov_rows``/``ov_idx``/``ov_override`` + the ``ov_w`` weights
  plane for sssp), which the expansion tiers fold in AFTER the base
  expansion: an *augment* row OR's (min's) its added neighbors into the
  base row's output, an *override* row REPLACES the base row's output
  with its full current neighbor list — the only sound encoding of a
  removal, since an OR/min contribution cannot be subtracted.

Fixed shapes are the point: a mutation batch swaps table VALUES under
the engines' already-compiled cores (one atomic ``arrs`` dict rebind,
no recompile, no dispatch stall). The overlay is bounded; when a batch
would exceed it — or touch a vertex the base ranked inactive (no table
row exists to override) — the mutation forces a COMPACTION: the overlay
folds into a new base generation persisted through the PR 4 atomic-save
+ payload-CRC machinery (:class:`GenerationStore`), engines rebuild over
the verified artifact, and the overlay empties. A crash mid-compaction
leaves the previous generation's files and ``CURRENT`` pointer intact;
a corrupt new generation is quarantined ``.corrupt`` at load and
serving rolls back to base + overlay.

``generation`` bumps on EVERY applied mutation batch — it is the serve
tier's cache/landmark invalidation key (answercache keys carry it;
landmark columns recompute on flip), not a compaction counter.
Compaction itself is answer-neutral: it rebases the representation
without changing the graph the queries see.

Correctness contract of the fold (tested bit-identical against a
from-scratch rebuild in tests/test_dynamic.py and the fuzz arm):

- overlay neighbor ids are RANKS of the base ranking (graph/ell.py
  ``rank_vertices`` — a pure function of the base edge set, shared by
  every engine over the same base), all ``< num_active``;
- pad rows carry ``row = act`` (the engines' all-identity sentinel row)
  with override=1 and all-sentinel neighbor slots, so a pad row folds
  to the combine identity and scatters identity back into the sentinel
  row — a self-healing no-op;
- real overlay rows are unique (host-side guarantee), so the scatter's
  only duplicate targets are pad rows writing identical identity values.

Scope (v1): undirected bases, single-chip engines (the wide substrate
bfs/cc/khop ride plus SsspEngine). The mesh generalization follows the
partitioned tiles (Buluç & Madduri, arXiv:1104.4518, stays the overlay
partition reference). ``pull_gate``/``adaptive_push`` do not compose
with an overlay (their push/gate passes would miss overlay edges) and
raise at engine construction.
"""

from __future__ import annotations

import os
import threading
import zlib

import numpy as np

from tpu_bfs import faults as _faults
from tpu_bfs import obs as _obs
from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import rank_vertices

#: Overlay table keys every folding engine consumes ("or" kinds).
OVERLAY_KEYS = ("ov_rows", "ov_idx", "ov_override")
#: The sssp engine additionally consumes the versioned weights plane
#: (it derives its own light plane ``ov_wl`` from ``ov_w`` and delta).
WEIGHTED_OVERLAY_KEYS = OVERLAY_KEYS + ("ov_w",)

#: Default overlay capacity: (mutated rows, neighbor slots per row).
DEFAULT_CAPACITY = (256, 16)


class OverlayCapacityError(RuntimeError):
    """A mutation batch does not fit the bounded overlay (too many dirty
    rows, a row past ``kcap`` slots, or a base-inactive vertex touched):
    the caller must compact into a new base generation and retry."""


def empty_overlay_tables(capacity, act: int, *, weighted: bool = False):
    """All-pad host tables for an engine built with an overlay but no
    mutations yet: every row targets the sentinel row ``act`` with
    override=1 and all-sentinel slots — the fold computes the combine
    identity and writes it back into the row that is already identity."""
    rows, kcap = int(capacity[0]), int(capacity[1])
    out = {
        "ov_rows": np.full(rows, act, np.int32),
        "ov_idx": np.full((rows, kcap), act, np.int32),
        "ov_override": np.ones(rows, np.int32),
    }
    if weighted:
        # Pad weight 0: the slot gathers the all-INF sentinel row and
        # INF + 0 absorbs under min.
        out["ov_w"] = np.zeros((rows, kcap), np.int32)
    return out


def overlay_crc32(tables: dict) -> int:
    """CRC32 over the staged overlay tables (the PR 4 payload-CRC rule
    applied pre-upload): computed when the host stages a mutation batch,
    re-verified just before the device swap, so a corruption in between
    (the ``corrupt_overlay`` chaos kind, or a real host-memory flip) is
    caught before any engine folds a torn table."""
    crc = 0
    for name in sorted(tables):
        arr = np.ascontiguousarray(tables[name])
        crc = zlib.crc32(
            f"{name}:{arr.dtype.str}:{arr.shape}".encode(), crc
        )
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc


def make_overlay_fold(expand, *, op: str, weights_key: str | None = None):
    """Wrap a bucketed-ELL ``expand(arrs, fw) -> [rows, w]`` (either
    tier — the fold is a jnp epilogue over the expansion output, outside
    any Pallas kernel, exactly like the heavy fold pyramid) with the
    overlay fold:

    - gather the base output at the overlay rows;
    - override rows replace it with the combine identity;
    - fold the overlay neighbor slots (``op='or'``: OR of frontier rows;
      ``op='minplus'``: min of ``dist[nbr] + w`` over the ``weights_key``
      plane);
    - scatter ``combine(current, folded)`` back into the overlay rows.

    Pad rows (sentinel row, override=1, all-sentinel slots) compute the
    identity and write it into the already-identity sentinel row."""
    import jax
    import jax.numpy as jnp

    if op not in ("or", "minplus"):
        raise ValueError(f"op must be 'or' or 'minplus', got {op!r}")
    if op == "minplus" and not weights_key:
        raise ValueError("op='minplus' needs a weights_key plane")

    def folded(arrs, fw):
        base = expand(arrs, fw)
        rows = arrs["ov_rows"]  # [D]
        idx = arrs["ov_idx"]  # [D, ko]
        ovr = arrs["ov_override"].astype(bool)  # [D]
        ko = idx.shape[1]
        if op == "or":
            ident = jnp.zeros((idx.shape[0], base.shape[1]), base.dtype)

            def body(kk, acc):
                return acc | fw[idx[:, kk]]

        else:
            from tpu_bfs.workloads.sssp import INF_W

            wts = arrs[weights_key]  # [D, ko]
            ident = jnp.full(
                (idx.shape[0], base.shape[1]), INF_W, jnp.int32
            )

            def body(kk, acc):
                return jnp.minimum(acc, fw[idx[:, kk]] + wts[:, kk][:, None])

        add = jax.lax.fori_loop(0, ko, body, ident)
        cur = jnp.where(ovr[:, None], ident, base[rows])
        if op == "or":
            merged = cur | add
        else:
            merged = jnp.minimum(cur, add)
        return base.at[rows].set(merged)

    return folded


class DynamicGraph:
    """Host-side truth of a mutating graph: the immutable base plus the
    current overlay rows, with ``apply`` staging bounded device tables
    and ``compact`` folding them into a new persisted base generation.

    Thread-safe: the serve tier applies mutation batches from request
    threads while the staleness auditor materializes oracles; one lock
    guards the host state."""

    def __init__(self, graph: Graph, *, capacity=DEFAULT_CAPACITY,
                 log=None):
        if not graph.undirected:
            raise ValueError(
                "dynamic graphs support undirected bases (v1): the "
                "overlay encodes symmetric row updates; a directed "
                "in-neighbor overlay needs the reverse-CSR plumbing"
            )
        rows, kcap = int(capacity[0]), int(capacity[1])
        if rows < 1 or kcap < 1:
            raise ValueError(
                f"overlay capacity must be >= (1, 1), got {capacity}"
            )
        self.capacity = (rows, kcap)
        self.log = log or (lambda msg: None)
        self.generation = 0
        self.compactions = 0
        self._lock = threading.RLock()
        with self._lock:
            self._set_base(graph)

    # --- base bookkeeping -------------------------------------------------

    def _set_base(self, graph: Graph) -> None:  # requires-lock: _lock
        self.base = graph
        src, dst = graph.coo
        _, self._act, _, self._rank = rank_vertices(
            src, dst, graph.num_vertices
        )
        self._cur: dict = {}  # dirty vertex -> {neighbor: weight}
        self._edges_delta = 0
        self._graph_cache = graph

    def _base_row(self, v: int) -> dict:
        """Canonical base neighbor map of ``v``: parallel slots collapse
        to their minimum weight (combine-idempotent, so the collapsed
        row answers identically under OR and min-plus)."""
        g = self.base
        lo, hi = int(g.row_ptr[v]), int(g.row_ptr[v + 1])
        nbrs = g.col_idx[lo:hi]
        if g.weights is None:
            return {int(n): 1 for n in nbrs}
        row: dict = {}
        wts = g.weights[lo:hi]
        for n, w in zip(nbrs.tolist(), wts.tolist()):
            n = int(n)
            if n not in row or w < row[n]:
                row[n] = int(w)
        return row

    def _row(self, v: int) -> dict:  # requires-lock: _lock
        row = self._cur.get(v)
        if row is None:
            row = self._cur[v] = self._base_row(v)
        return row

    @property
    def weighted(self) -> bool:
        return self.base.weights is not None

    def overlay_rows_used(self) -> int:
        with self._lock:
            return sum(
                1 for v in self._cur if self._cur[v] != self._base_row(v)
            )

    # --- mutation ---------------------------------------------------------

    def apply(self, add=(), remove=()):
        """Apply one mutation batch to the host truth and stage the full
        overlay device tables. ``add`` items are ``(u, v)`` or
        ``(u, v, w)``; ``remove`` items are ``(u, v)`` (all parallel
        slots of the pair go). Adding an existing edge with a new weight
        re-weights it. Returns ``(tables, stats)``; bumps ``generation``
        — the serve flip key. Raises :class:`OverlayCapacityError`
        WITHOUT mutating anything when the batch needs a compaction
        first (the caller compacts and re-applies)."""
        with self._lock:
            staged = self._stage(add, remove)
            tables, used = self._build_tables(staged)
            # Commit only after staging fit: host truth and the staged
            # tables flip together or not at all.
            self._cur = staged
            self.generation += 1
            self._graph_cache = None
            stats = {
                "generation": self.generation,
                "overlay_rows": used,
                "capacity": self.capacity,
            }
            return tables, stats

    def _stage(self, add, remove) -> dict:  # requires-lock: _lock
        n = self.base.num_vertices
        staged = {v: dict(row) for v, row in self._cur.items()}

        def row_of(v):
            row = staged.get(v)
            if row is None:
                row = staged[v] = self._base_row(v)
            return row

        def check_active(v):
            v = int(v)
            if not (0 <= v < n):
                raise ValueError(f"vertex {v} out of range [0, {n})")
            if self._rank[v] >= self._act:
                raise OverlayCapacityError(
                    f"vertex {v} is inactive in the base ranking (no "
                    f"table row to override) — compaction required"
                )
            return v

        for edge in add:
            u, v = check_active(edge[0]), check_active(edge[1])
            w = int(edge[2]) if len(edge) > 2 else 1
            if w < 1:
                raise ValueError(f"edge weight must be >= 1, got {w}")
            if self.weighted:
                if row_of(u).get(v) != w:
                    row_of(u)[v] = w
                    row_of(v)[u] = w
                    self._edges_delta += 1
            else:
                if v not in row_of(u):
                    row_of(u)[v] = 1
                    row_of(v)[u] = 1
                    self._edges_delta += 1
        for edge in remove:
            u, v = check_active(edge[0]), check_active(edge[1])
            if v in row_of(u):
                row_of(u).pop(v, None)
                row_of(v).pop(u, None)
                self._edges_delta -= 1
        # Drop rows that reverted to their base content.
        return {
            v: row for v, row in staged.items()
            if row != self._base_row(v)
        }

    def _build_tables(self, cur: dict):  # requires-lock: _lock
        rows_cap, kcap = self.capacity
        act = self._act
        weighted = self.weighted
        tables = empty_overlay_tables(
            self.capacity, act, weighted=weighted
        )
        used = 0
        for v, row in sorted(cur.items()):
            base_row = self._base_row(v)
            if row == base_row:
                continue
            added = {n: w for n, w in row.items()
                     if base_row.get(n) != w}
            removed = any(n not in row for n in base_row)
            override = removed or any(
                n in base_row and base_row[n] != w
                for n, w in added.items()
            )
            slots = row if override else added
            if len(slots) > kcap:
                raise OverlayCapacityError(
                    f"vertex {v} needs {len(slots)} overlay slots "
                    f"(kcap={kcap}) — compaction required"
                )
            if used >= rows_cap:
                raise OverlayCapacityError(
                    f"mutation set needs more than {rows_cap} overlay "
                    f"rows — compaction required"
                )
            tables["ov_rows"][used] = self._rank[v]
            tables["ov_override"][used] = 1 if override else 0
            for j, (nbr, w) in enumerate(sorted(slots.items())):
                tables["ov_idx"][used, j] = self._rank[nbr]
                if weighted:
                    tables["ov_w"][used, j] = w
            used += 1
        return tables, used

    # --- the from-scratch oracle -----------------------------------------

    def materialize(self) -> Graph:
        """The current graph as an immutable :class:`Graph` — what a
        from-scratch rebuild would serve. The fuzz/oracle bit-identical
        bar compares engine answers against engines built over THIS."""
        with self._lock:
            if self._graph_cache is not None:
                return self._graph_cache
            g = self.base
            src_parts = []
            dst_parts = []
            wts_parts = [] if self.weighted else None
            dirty = set(self._cur)
            # Untouched rows stream straight from the base CSR slots.
            keep = np.ones(len(g.col_idx), dtype=bool)
            for v in dirty:
                keep[int(g.row_ptr[v]):int(g.row_ptr[v + 1])] = False
            row_ids = np.repeat(
                np.arange(g.num_vertices, dtype=np.int64),
                np.diff(g.row_ptr),
            )
            src_parts.append(row_ids[keep])
            dst_parts.append(g.col_idx[keep].astype(np.int64))
            if wts_parts is not None:
                wts_parts.append(g.weights[keep])
            for v in sorted(dirty):
                row = self._cur[v]
                if not row:
                    continue
                nbrs = np.fromiter(sorted(row), dtype=np.int64)
                src_parts.append(np.full(len(nbrs), v, np.int64))
                dst_parts.append(nbrs)
                if wts_parts is not None:
                    wts_parts.append(np.asarray(
                        [row[int(n)] for n in nbrs], np.int32
                    ))
            from tpu_bfs.graph.io import build_csr

            out = build_csr(
                np.concatenate(src_parts),
                np.concatenate(dst_parts),
                g.num_vertices,
                num_input_edges=max(
                    g.num_input_edges + self._edges_delta, 0
                ),
                undirected=True,
                weights=(np.concatenate(wts_parts)
                         if wts_parts is not None else None),
            )
            self._graph_cache = out
            return out

    # --- compaction -------------------------------------------------------

    def compact(self, store: "GenerationStore") -> Graph:
        """Fold the overlay into a new persisted base generation:
        materialize -> atomic CRC save -> load-verified -> adopt as base
        (overlay empties). The ``CURRENT`` pointer only advances after
        the reloaded artifact verified, so every failure mode rolls
        back: a crash (or the raising ``compaction_crash`` chaos kind at
        the ``compact`` site) before the pointer leaves the previous
        generation intact, and a corrupt new generation quarantines
        ``.corrupt`` at load (CorruptCheckpointError) with the pointer
        still on the old files. The caller keeps serving base + overlay
        on any raise. Returns the VERIFIED loaded graph — engines must
        rebuild from the artifact that proved round-trippable, not the
        in-memory twin."""
        with self._lock:
            gen_id = store.next_generation_id()
            g = self.materialize()
            with _obs.host_span("tpu_bfs.graph.compact", span_id=f"gen{gen_id}",
                                cat="graph.dynamic", generation=gen_id):
                path = store.save(gen_id, g)
                if _faults.ACTIVE is not None:
                    # Chaos site (ISSUE 19): compaction_crash raises
                    # HERE — after the new generation's files hit disk,
                    # before CURRENT advances — the exact window a real
                    # compactor crash leaves behind.
                    _faults.ACTIVE.hit("compact", generation=gen_id)
                loaded = store.load(gen_id)  # raises CorruptCheckpointError
                store.set_current(gen_id)
            self.compactions += 1
            self._set_base(loaded)
            self._graph_cache = loaded
            self.log(
                f"compacted into generation artifact {path} "
                f"(gen_id={gen_id}, V={loaded.num_vertices}, "
                f"E={loaded.num_edges})"
            )
            return loaded

    def overlay_tables(self):
        """Re-stage the CURRENT overlay from host truth (no generation
        bump) — the recovery path after a staged-table corruption was
        caught by :func:`overlay_crc32`, and the torn-flip self-heal."""
        with self._lock:
            tables, _used = self._build_tables(self._cur)
            return tables


class GenerationStore:
    """On-disk base generations through the PR 4 checkpoint machinery:
    ``gen_NNNN.npz`` written by ``_atomic_savez`` (tmp + fsync + rename,
    payload CRC embedded, ``ckpt_save`` fault site inside), loaded by
    ``_load_npz_verified`` (decode/CRC failures rename ``.corrupt`` and
    raise), with a ``CURRENT`` pointer file replaced atomically LAST —
    the commit point a crash can only land before."""

    def __init__(self, root: str, *, log=None):
        self.root = root
        self.log = log or (lambda msg: None)
        os.makedirs(root, exist_ok=True)

    def _path(self, gen_id: int) -> str:
        return os.path.join(self.root, f"gen_{gen_id:04d}.npz")

    def next_generation_id(self) -> int:
        cur = self.current()
        return (cur if cur is not None else 0) + 1

    def save(self, gen_id: int, graph: Graph) -> str:
        from tpu_bfs.utils.checkpoint import _atomic_savez

        path = self._path(gen_id)
        arrays = {
            "row_ptr": np.asarray(graph.row_ptr),
            "col_idx": np.asarray(graph.col_idx),
            "meta": np.asarray(
                [graph.num_input_edges, int(graph.undirected)], np.int64
            ),
        }
        if graph.weights is not None:
            arrays["weights"] = np.asarray(graph.weights)
        _atomic_savez(path, **arrays)
        return path

    def load(self, gen_id: int) -> Graph:
        from tpu_bfs.utils.checkpoint import _load_npz_verified

        arrays = _load_npz_verified(self._path(gen_id))
        meta = arrays["meta"]
        return Graph(
            row_ptr=np.asarray(arrays["row_ptr"]),
            col_idx=np.asarray(arrays["col_idx"]),
            num_input_edges=int(meta[0]),
            undirected=bool(meta[1]),
            weights=(np.asarray(arrays["weights"])
                     if "weights" in arrays else None),
        )

    def set_current(self, gen_id: int) -> None:
        """Advance the commit pointer — atomically, and only ever AFTER
        the generation's payload verified (the caller's contract)."""
        tmp = os.path.join(self.root, ".CURRENT.tmp")
        with open(tmp, "w") as f:
            f.write(f"{gen_id}\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.root, "CURRENT"))

    def current(self) -> int | None:
        try:
            with open(os.path.join(self.root, "CURRENT")) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    def quarantine_orphans(self) -> list:
        """Crash recovery: a compactor that died after ``save`` but
        before ``set_current`` (the ``compaction_crash`` window) leaves
        generation files NEWER than the commit pointer. They never
        verified round-trippable, so they are renamed ``.corrupt`` (the
        PR 4 quarantine rule) and must never be served; the returned
        paths are what the flight dump names."""
        cur = self.current() or 0
        out = []
        for name in sorted(os.listdir(self.root)):
            if not (name.startswith("gen_") and name.endswith(".npz")):
                continue
            try:
                gen_id = int(name[4:-4])
            except ValueError:
                continue
            if gen_id <= cur:
                continue
            path = os.path.join(self.root, name)
            corrupt = path + ".corrupt"
            try:
                os.replace(path, corrupt)
            except OSError:
                continue
            self.log(
                f"quarantined orphan generation artifact {name} -> "
                f"{corrupt} (newer than the CURRENT pointer: a dead "
                f"compactor's uncommitted write)"
            )
            out.append(corrupt)
        return out
