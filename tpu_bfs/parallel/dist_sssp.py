"""Distributed bucketed delta-stepping SSSP over a 1D/2D device mesh.

The multi-chip form of the workload engine (tpu_bfs/workloads/sssp.py),
built on the same substrate as the distributed wide MS-BFS
(parallel/dist_msbfs_wide.py): the sharded bucketized ELL (round-robin
over degree-sorted rows, so every chip sees the same degree mix) plus a
sharded WEIGHTS plane slot-aligned with it
(graph/ell.build_ell_weights_sharded), a replicated rank-order int32
tentative-distance table [v_pad+1, L] (+ the all-INF sentinel row the
pad slots gather), and a per-round value exchange under elementwise min.

Per delta-stepping round each chip relaxes only its OWNED rows through
its ELL+weights shard (the single-chip min-plus expansion runs verbatim
on the local tiles — after shard_map's leading-axis drop the per-shard
arrays have exactly the single-chip key layout), then the mesh rebuilds
the replicated table through one of the (min, +) exchange family
(parallel/collectives.py, ISSUE 20):

- ``ring``: substitute the owned rows into the previous replica and
  ring-reduce-scatter with elementwise min + tiled all-gather;
- ``allreduce``: the same contribution through ``pmin`` — on a 2D mesh
  this factors hierarchically (min over the row axis, then the column
  axis), the 2D partition's two-phase exchange;
- ``sparse``: the queue-style id+value exchange
  (``sparse_rows_exchange_min``) — changed rows ship (id, int32 distance
  row) pairs under the same cap ladder / delta id codec as the OR row
  gather, with optional history prediction (``predict=True``) skipping
  the measurement pmax on confidently-dense rounds.

The delta-stepping control flow is the single-chip loop with its two
scalar decisions made mesh-uniform: the light-sweep convergence test is
one psum per round (the only collective beyond the exchange — the
post-exchange ``changed``/``unsettled`` tests read the REPLICATED table,
so they cost nothing, exactly like the OR engines' gathered-frontier
termination); the bucket close runs under a `lax.cond` whose predicate
every chip shares, so the exchange stays outside the cond and the
collectives stay matched. Round count and the distance table are
bit-identical to the single-chip engine (fuzz-pinned).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_bfs import faults as _faults
from tpu_bfs.algorithms._packed_common import ExpandSpec
from tpu_bfs.graph.csr import Graph
from tpu_bfs.graph.ell import build_ell_sharded, build_ell_weights_sharded
from tpu_bfs.ops.ell_expand import resolve_interpret
from tpu_bfs.parallel.collectives import (
    check_delta_bits,
    default_row_gather_caps,
    dense_min_wire_bytes,
    minplus_rows_branch_count,
    minplus_rows_branch_labels,
    minplus_rows_wire_bytes_per_level,
    normalize_caps,
    ring_reduce_scatter,
    sparse_rows_exchange_min,
)
from jax import shard_map
from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.utils.aot import AotProgramProtocol
from tpu_bfs.workloads.sssp import (
    INF_W,
    SsspBatchResult,
    _check_kernel_ident,
    _make_min_plus_expand,
    _make_summaries,
)

#: Exchange impls of the distributed delta-stepping engine. ``sparse``
#: (and its predictive form) is 1D-only: the queue-style gather is an
#: all-gather over the single partition axis; the 2D mesh exchanges
#: hierarchically through ``allreduce``.
EXCHANGES = ("ring", "allreduce", "sparse")


def _make_dist_sssp_core(
    sell, L: int, mesh: Mesh, exchange: str, sparse_caps, delta_bits,
    delta: int, predict: bool, expand_light, expand_full,
):
    p_count = sell.num_shards
    v_loc = sell.v_loc
    v_pad = sell.v_pad
    axes = tuple(mesh.axis_names)
    nb = (
        minplus_rows_branch_count(sparse_caps, delta_bits, predict=predict)
        if exchange == "sparse" else 1
    )
    delta_i = jnp.int32(delta)

    def psum_all(x):
        for ax in axes:
            x = lax.psum(x, ax)
        return x

    def pmin_all(x):
        for ax in axes:
            x = lax.pmin(x, ax)
        return x

    def chip_fn(arrs, dist0, max_rounds):
        # Block specs keep a leading shard axis of size 1; drop it — the
        # per-shard arrays then carry the single-chip expansion's exact
        # key layout, so _make_min_plus_expand runs on local tiles.
        arrs = {k: a[0] for k, a in arrs.items()}
        if len(axes) == 1:
            p = lax.axis_index(axes[0])
        else:
            p = lax.axis_index(axes[0]) * mesh.shape[axes[1]] + lax.axis_index(
                axes[1]
            )

        def own(full):
            # Global rank r lives on chip r % P at local row r // P.
            return lax.dynamic_index_in_dim(
                full[:v_pad].reshape(v_loc, p_count, L), p, axis=1,
                keepdims=False,
            )

        def contrib_of(new_loc, prev_tbl):
            # The previous replica with this chip's own rows substituted:
            # pmin/ring-min across chips then yields the updated table
            # (new <= prev at own rows; every other chip holds prev there).
            return lax.dynamic_update_index_in_dim(
                prev_tbl.reshape(v_loc, p_count, L), new_loc, p, axis=1
            ).reshape(v_pad, L)

        def dense_gather(new_loc):
            # All chips' owned rows together cover every row with the
            # updated values — one all-gather rebuilds rank order.
            g = lax.all_gather(new_loc, axes[0])  # [P, v_loc, L]
            return g.transpose(1, 0, 2).reshape(v_pad, L)

        def do_exchange(new_loc, prev_tbl, own_prev, prev_biggest, growing):
            if exchange == "sparse":
                return sparse_rows_exchange_min(
                    new_loc, own_prev, prev_tbl, axes[0],
                    caps=sparse_caps, out_rows=v_pad,
                    gid_of=lambda ids: ids * p_count + p,
                    dense_fn=lambda: dense_gather(new_loc),
                    ident=INF_W, delta_bits=delta_bits,
                    gid_of_src=lambda ids, src: ids * p_count + src,
                    predict=predict,
                    prev_biggest=prev_biggest if predict else None,
                    growing=growing if predict else None,
                )
            contrib = contrib_of(new_loc, prev_tbl)
            if exchange == "ring":
                rs = ring_reduce_scatter(contrib, axes[0], p_count, jnp.minimum)
                full = lax.all_gather(rs, axes[0], tiled=True)
            else:
                full = pmin_all(contrib)
            return full, jnp.int32(0), prev_biggest

        def cond(carry):
            _, _, alive, rounds = carry[:4]
            return alive & (rounds < max_rounds)

        def body(carry):
            dist, hi, _, rounds, bcs, pb, pc, ppc = carry
            # Current bucket + settled rows relax out; later buckets mask
            # to INF (the delta-stepping invariant, workloads/sssp.py).
            masked = jnp.where(dist < hi, dist, INF_W)
            own_prev = own(dist)
            new_loc = jnp.minimum(own_prev, expand_light(arrs, masked))
            # The light-sweep convergence test must be mesh-uniform (it
            # gates the close cond): the one per-round scalar psum.
            changed_l = psum_all(
                jnp.any(new_loc < own_prev).astype(jnp.int32)
            ) > 0
            # Bucket stabilized: one relaxation over ALL edges before the
            # bound advances. When changed_l is false new_loc == own_prev
            # globally, so closing over the pre-light ``masked`` equals
            # the single-chip close over the post-light table exactly.
            new2 = lax.cond(
                changed_l,
                lambda: new_loc,
                lambda: jnp.minimum(new_loc, expand_full(arrs, masked)),
            )
            growing = pc > ppc
            full2, branch, biggest = do_exchange(
                new2, dist[:v_pad], own_prev, pb, growing
            )
            bcs = bcs + (jnp.arange(nb, dtype=jnp.int32) == branch)
            # Post-exchange decisions read the REPLICATED table — free of
            # collectives, like the OR engines' gathered-frontier tests.
            prev_tbl = dist[:v_pad]
            changed_rows = jnp.sum(
                jnp.any(full2 < prev_tbl, axis=1).astype(jnp.int32)
            )
            hi2 = jnp.where(changed_l, hi, hi + delta_i)
            unsettled = jnp.any((full2 < INF_W) & (full2 >= hi2))
            dist_next = jnp.concatenate(
                [full2, jnp.full((1, L), INF_W, jnp.int32)]
            )
            return (
                dist_next, hi2, (changed_rows > 0) | unsettled, rounds + 1,
                bcs, biggest, changed_rows, pc,
            )

        dist, _, alive, rounds, bcs, _, _, _ = lax.while_loop(
            cond, body,
            (
                dist0, delta_i, jnp.bool_(True), jnp.int32(0),
                jnp.zeros(nb, jnp.int32), jnp.int32(0), jnp.int32(0),
                jnp.int32(0),
            ),
        )
        return dist, rounds, alive, bcs

    def build(n_arrs):
        shard_spec = P(axes) if len(axes) > 1 else P(axes[0])
        specs = {k: shard_spec for k in n_arrs}
        core = jax.jit(
            shard_map(
                chip_fn,
                mesh=mesh,
                in_specs=(specs, P(), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            )
        )
        device_arrs = {
            k: jax.device_put(v, NamedSharding(mesh, shard_spec))
            for k, v in n_arrs.items()
        }
        return core, device_arrs

    return build


class _DistSsspDispatch:
    """An in-flight distributed SSSP batch (async device references;
    fetch blocks). The dist form additionally carries the exchange
    branch counters — a while-loop output priced at fetch."""

    __slots__ = ("sources", "dist", "rounds", "alive", "bc", "t0")

    def __init__(self, sources, dist, rounds, alive, bc, t0):
        self.sources = sources
        self.dist = dist
        self.rounds = rounds
        self.alive = alive
        self.bc = bc
        self.t0 = t0


class DistSsspEngine(AotProgramProtocol):
    """Multi-chip delta-stepping SSSP: sharded ELL + weights, replicated
    distance table.

    Bit-identical to the single-chip :class:`SsspEngine` (same rounds,
    same distances — fuzz-pinned); per-chip HBM is O(v_pad * 4L) for the
    replicated table plus the chip's edge+weight shard. A 1D mesh takes
    any of :data:`EXCHANGES`; a 2D mesh exchanges hierarchically
    (``allreduce`` over both axes) — its partition benefit is the halved
    per-axis collective span, not a different byte volume."""

    kind = "sssp"

    def __init__(
        self,
        graph: Graph,
        mesh: Mesh | int | None = None,
        *,
        lanes: int = 32,
        kcap: int = 64,
        delta: int = 0,
        max_rounds: int = 4096,
        exchange: str = "ring",
        sparse_caps: int | tuple[int, ...] | None = None,
        delta_bits: tuple[int, ...] = (),
        predict: bool = False,
        expand_impl: str = "xla",
        interpret: bool | None = None,
    ):
        from tpu_bfs.algorithms._packed_common import validate_expand_impl

        validate_expand_impl(expand_impl)
        self.expand_impl = expand_impl
        interpret = resolve_interpret(interpret)
        self._interpret = bool(interpret)
        if not isinstance(graph, Graph):
            raise ValueError(
                "DistSsspEngine needs the host Graph (the weights plane "
                "and result extraction both read it)"
            )
        if graph.weights is None:
            raise ValueError(
                "sssp needs a weighted graph (generate with weights=W or "
                "attach a weights plane)"
            )
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if exchange not in EXCHANGES:
            raise ValueError(
                f"unknown exchange {exchange!r}; have {EXCHANGES}"
            )
        self.mesh = mesh if isinstance(mesh, Mesh) else make_mesh(mesh)
        axes = tuple(self.mesh.axis_names)
        if len(axes) > 1 and exchange != "allreduce":
            raise ValueError(
                f"a 2D mesh exchanges hierarchically — exchange="
                f"'allreduce', not {exchange!r} (the queue-style and ring "
                "forms are defined over the single 1D partition axis)"
            )
        if delta_bits and exchange != "sparse":
            raise ValueError(
                "delta_bits compresses the SPARSE id+value exchange's id "
                f"stream (ISSUE 7); exchange={exchange!r} ships whole "
                "slabs — use exchange='sparse'"
            )
        if predict and exchange != "sparse":
            raise ValueError(
                "predict arms the sparse exchange's history predictor — "
                "use exchange='sparse'"
            )
        p_count = self.mesh.devices.size
        self.sell = build_ell_sharded(graph, p_count, kcap=kcap)
        sell = self.sell
        self.host_graph = graph
        self.lanes = int(lanes)
        self.num_vertices = graph.num_vertices
        self.undirected = graph.undirected
        self.max_rounds = int(max_rounds)
        self._exchange = exchange
        self.predict = bool(predict)
        wmax = int(graph.weights.max()) if len(graph.weights) else 1
        self.wmax = wmax
        if delta <= 0:
            delta = max(1, int(round(float(graph.weights.mean())))) \
                if len(graph.weights) else 1
        self.delta = int(delta)
        # The replicated table is RANK-order (row of vertex v = rank[v]);
        # unlike the packed dist engines there is no chip-major reassembly
        # — the loop's output is already the full replica.
        self._act = sell.v_pad
        self._rank = sell.rank.astype(np.int64)
        self._table_rows = sell.v_pad + 1  # + the all-INF sentinel row
        src, dst = graph.coo
        seen = np.zeros(graph.num_vertices, dtype=bool)
        seen[src] = True
        seen[dst] = True
        self._iso_mask = ~seen

        self.delta_bits = check_delta_bits(delta_bits)
        if sparse_caps is None:
            sparse_caps = default_row_gather_caps(
                sell.v_loc, self.lanes, self.delta_bits
            )
        elif isinstance(sparse_caps, int):
            sparse_caps = (sparse_caps,)
        self.sparse_caps = normalize_caps(sparse_caps)
        self.last_exchange_level_counts: np.ndarray | None = None
        self.last_exchange_bytes: float | None = None

        spec = ExpandSpec(
            kcap=sell.kcap,
            heavy=sell.heavy_per_shard > 0,
            num_virtual=sell.num_virtual,
            fold_steps=sell.fold_steps,
            light_meta=tuple((k, blk.shape[1]) for k, blk in sell.light),
            tail_rows=sell.tail_rows,
        )
        n_arrs = self._build_arrays()
        if expand_impl == "pallas":
            from tpu_bfs.algorithms._packed_common import make_pallas_expand
            from tpu_bfs.ops.ell_expand import validate_kernel_width

            _check_kernel_ident()
            validate_kernel_width(
                self.lanes, self._interpret,
                kernel="dist-sssp expand_impl='pallas'",
            )
            expand_light = make_pallas_expand(
                spec, self.lanes, op="minplus", wsuf="wl",
                interpret=self._interpret,
            )
            expand_full = make_pallas_expand(
                spec, self.lanes, op="minplus", wsuf="w",
                interpret=self._interpret,
            )
        else:
            expand_light = _make_min_plus_expand(spec, self.lanes, "wl")
            expand_full = _make_min_plus_expand(spec, self.lanes, "w")
        build = _make_dist_sssp_core(
            sell, self.lanes, self.mesh, exchange, self.sparse_caps,
            self.delta_bits, self.delta, self.predict, expand_light,
            expand_full,
        )
        self._dist_core, self.arrs = build(n_arrs)
        rows_seed, L = sell.v_pad + 1, self.lanes
        self._seed_k = jax.jit(
            lambda r, c: jnp.full((rows_seed, L), INF_W, jnp.int32)
            .at[r, c]
            .min(jnp.int32(0))
        )
        self._summaries = _make_summaries(sell.v_pad)
        self._warmed = False

    def _build_arrays(self) -> dict:
        """Per-shard expansion arrays, stacked on the shard axis: the
        index slabs exactly as the dist-wide engine builds them, plus the
        sharded weight planes slot-aligned with them (``virtual_w``/
        ``virtual_wl``, ``light{i}_w``/``light{i}_wl`` — after the
        shard-axis drop these are the single-chip min-plus expansion's
        exact keys)."""
        sell = self.sell
        pallas = self.expand_impl == "pallas"
        n_arrs = {}
        if sell.heavy_per_shard > 0:
            n_arrs["virtual_t"] = np.ascontiguousarray(
                sell.virtual.transpose(0, 2, 1)
            )
            n_arrs["fold_pad_map"] = sell.fold_pad_map
            n_arrs["heavy_pick"] = sell.heavy_pick
        for i, (k, blocks) in enumerate(sell.light):
            n_arrs[f"light{i}_t"] = np.ascontiguousarray(
                blocks.transpose(0, 2, 1)
            )
        vw, lw = build_ell_weights_sharded(self.host_graph, sell, pad=0)
        delta = self.delta

        def _weight_planes(prefix, wt):
            # wt: [P, k, n] transposed like the index slabs. Light plane:
            # heavy-edge slots absorb under min; pad slots (weight 0)
            # gather the all-INF sentinel row either way.
            n_arrs[f"{prefix}_w"] = wt
            n_arrs[f"{prefix}_wl"] = np.where(wt <= delta, wt, INF_W).astype(
                np.int32
            )

        if vw is not None:
            _weight_planes(
                "virtual",
                np.ascontiguousarray(vw.transpose(0, 2, 1)).astype(np.int32),
            )
        for i, w in enumerate(lw):
            _weight_planes(
                f"light{i}",
                np.ascontiguousarray(w.transpose(0, 2, 1)).astype(np.int32),
            )
        if pallas:
            from tpu_bfs.graph.ell import pad_gate_blocks

            # Per-shard sentinel-padded whole-block tables (index sentinel
            # = the all-INF row v_pad; weight pad 0 — INF + 0 stays the
            # min identity), stacked on the shard axis like everything.
            for name in ["virtual_t"] if sell.heavy_per_shard > 0 else []:
                n_arrs["virtual_gt"] = np.stack([
                    pad_gate_blocks(n_arrs[name][p], sell.v_pad)
                    for p in range(sell.num_shards)
                ])
            for i in range(len(sell.light)):
                n_arrs[f"light{i}_gt"] = np.stack([
                    pad_gate_blocks(n_arrs[f"light{i}_t"][p], sell.v_pad)
                    for p in range(sell.num_shards)
                ])
            for prefix in (
                ["virtual"] if sell.heavy_per_shard > 0 else []
            ) + [f"light{i}" for i in range(len(sell.light))]:
                for suf in ("w", "wl"):
                    n_arrs[f"{prefix}_{suf}_gt"] = np.stack([
                        pad_gate_blocks(n_arrs[f"{prefix}_{suf}"][p], 0)
                        for p in range(sell.num_shards)
                    ])
        return n_arrs

    def wire_bytes_per_level(self) -> list[float]:
        """Modeled off-chip bytes per round per exchange branch,
        index-aligned with the dispatched loop's branch counters."""
        p = self.sell.num_shards
        if self._exchange == "sparse":
            return minplus_rows_wire_bytes_per_level(
                p, self.sell.v_loc, self.lanes, self.sparse_caps,
                self.delta_bits, predict=self.predict,
            )
        return [dense_min_wire_bytes(p, self.sell.v_loc, self.lanes)]

    def exchange_branch_labels(self) -> list[str]:
        if self._exchange == "sparse":
            return minplus_rows_branch_labels(
                self.sparse_caps, self.delta_bits, predict=self.predict
            )
        return ["dense"]

    def _iso_of(self, sources: np.ndarray):
        # Every vertex has a row here, so results are already correct;
        # the mask only labels the extras symmetric with the single-chip
        # engine's row-less isolated sources.
        return self._iso_mask[np.asarray(sources, np.int64)]

    def _seed_dev(self, sources: np.ndarray):
        rows = self._rank[np.asarray(sources, dtype=np.int64)].astype(np.int32)
        lanes_idx = np.arange(len(sources), dtype=np.int32)
        return self._seed_k(jnp.asarray(rows), jnp.asarray(lanes_idx))

    def dispatch(self, sources, **_ignored) -> _DistSsspDispatch:
        if _faults.ACTIVE is not None:
            # Chaos-harness injection site: the same workload site as the
            # single-chip engine (tpu_bfs/faults.py).
            _faults.ACTIVE.hit("sssp_dispatch", lanes=self.lanes)
        sources = np.asarray(sources, dtype=np.int64)
        if sources.ndim != 1 or not (1 <= len(sources) <= self.lanes):
            raise ValueError(
                f"need 1..{self.lanes} sources, got {sources.shape}"
            )
        if sources.min() < 0 or sources.max() >= self.num_vertices:
            raise ValueError("source out of range")
        dist0 = self._seed_dev(sources)
        t0 = time.perf_counter()
        dist, rounds, alive, bc = self._dist_core(
            self.arrs, dist0, jnp.int32(self.max_rounds)
        )
        return _DistSsspDispatch(sources, dist, rounds, alive, bc, t0)

    def fetch(self, pend: _DistSsspDispatch, *, check_cap: bool = True,
              time_it: bool = False) -> SsspBatchResult:
        if _faults.ACTIVE is not None:
            _faults.ACTIVE.hit("sssp_fetch", lanes=self.lanes)
        rounds = int(pend.rounds)  # blocks until the loop finishes
        elapsed = (time.perf_counter() - pend.t0) if time_it else None
        self._warmed = True
        if check_cap and bool(pend.alive):
            raise RuntimeError(
                f"sssp still relaxing after {rounds} rounds "
                f"(max_rounds={self.max_rounds}) — raise max_rounds or "
                f"delta for this graph"
            )
        # Exchange accounting: the loop finished (rounds read), so the
        # counters are ready — price them with the (min, +) byte model.
        counts = np.asarray(pend.bc)
        self.last_exchange_level_counts = counts
        self.last_exchange_bytes = float(
            np.dot(counts, self.wire_bytes_per_level())
        )
        reached, ecc = self._summaries(pend.dist)
        iso = self._iso_of(pend.sources)
        return SsspBatchResult(
            self, pend.sources, pend.dist, rounds, reached, ecc,
            iso if iso.any() else None, elapsed_s=elapsed,
        )

    def run(self, sources, *, time_it: bool = False, check_cap: bool = True,
            **_ignored) -> SsspBatchResult:
        if time_it and not self._warmed:
            int(self.dispatch(sources).rounds)
        return self.fetch(
            self.dispatch(sources), check_cap=check_cap, time_it=time_it
        )

    def analysis_programs(self):
        """Static-analyzer hook (tpu_bfs/analysis): the sharded
        delta-stepping loop whose min-exchange branch uniformity the
        taint pass proves (plus the replicated summaries reduction). The
        seed table is pre-replicated — per-batch seed movement is
        inherent to dispatch, so the transfer guard watches the LOOP."""
        rep = NamedSharding(self.mesh, P())
        dist0 = jax.device_put(self._seed_dev(np.asarray([0])), rep)
        ml = jax.device_put(jnp.int32(64), rep)
        return [
            ("dist_sssp_core", self._dist_core, (self.arrs, dist0, ml)),
            ("sssp_summaries", self._summaries, (dist0,)),
        ]

    def export_programs(self):
        """AOT inventory (ISSUE 9; utils/aot.py): the sharded
        delta-stepping core — the multi-chip compile a preheat skips."""
        return [
            ("dist_sssp_core", "_dist_core", fn, args)
            for name, fn, args in self.analysis_programs()
            if name == "dist_sssp_core"
        ]
