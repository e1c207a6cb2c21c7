"""Command-line entrypoint.

The reference CLI is ``./a.out <srcVertex> <graphfile>`` (README.md:13), whose
main() runs: load graph -> CPU golden BFS -> GPU BFS -> validate -> print
timings (bfs.cu:783-823). This CLI keeps that exact flow and argument order,
with runtime (not compile-time) configuration of device count, algorithm
backend, and exchange — the reference hardwires DeviceNum at compile time
(bfs.cu:19).

Graph sources: a file path, or generator specs ``rmat:scale=20,ef=16,seed=1``
/ ``random:n=100000,m=1000000,seed=12345`` (the capability of readGraph's
generator mode, bfs.cu:892-907).

Usage:
    python -m tpu_bfs.cli 2 graph.txt
    python -m tpu_bfs.cli 0 rmat:scale=18 --devices 1 --stats

Sibling entry points: ``tpu-bfs-serve`` (the query server),
``tpu-bfs-graph500`` (the Graph500 harness), and ``tpu-bfs-analyze``
(static verification of every distributed exchange program + the serve
tier — `make analyze`; run it before any multi-chip session, it proves
the branch-selection uniformity a real mesh deadlocks without).
"""

from __future__ import annotations

import argparse
import sys
import time


def _parse_spec(spec: str):
    kind, _, rest = spec.partition(":")
    kw = {}
    if rest:
        for item in rest.split(","):
            k, _, v = item.partition("=")
            kw[k.strip()] = int(v)
    return kind, kw


def load_graph(spec: str):
    from tpu_bfs.graph import generate, io

    if spec.startswith("rmat:") or spec == "rmat":
        _, kw = _parse_spec(spec)
        return generate.rmat_graph(
            kw.get("scale", 16),
            kw.get("ef", 16),
            seed=kw.get("seed", 1),
            # weights=W attaches the deterministic per-edge weight plane
            # (ISSUE 14: the sssp serving kind needs it).
            weights=kw.get("weights") or None,
        )
    if spec.startswith("random:"):
        _, kw = _parse_spec(spec)
        return generate.random_graph(
            kw.get("n", 1024), kw.get("m", 8192), seed=kw.get("seed", 12345),
            weights=kw.get("weights") or None,
        )
    if spec == "-":
        return io.read_stdin()
    return io.load_edge_list(spec)


def _maybe_profile(profile_dir):
    """jax.profiler trace context, or a no-op when no dir is given."""
    import contextlib

    if not profile_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(profile_dir)


def _arm_obs(args):
    """Arm the telemetry recorder (tpu_bfs/obs) for a one-shot run —
    the shared ``--obs``-wins / ``--trace-out``-implies precedence."""
    from tpu_bfs import obs as obs_mod

    rec = obs_mod.arm_for_run(getattr(args, "obs", None),
                              getattr(args, "trace_out", None))
    if rec is not None:
        print(f"[obs] telemetry recorder armed (flight window "
              f"{rec.window_s:.0f}s, dump dir {rec.dump_dir!r})",
              file=sys.stderr)
    return rec


def _finish_obs(args, engine, label: str) -> None:
    """One-shot run epilogue: --stats prints the engine-trace summary
    line, --trace-out writes the Perfetto JSON (recorder span stream +
    the engine's per-level trace as its own track)."""
    import json

    from tpu_bfs import obs as obs_mod

    trace = getattr(engine, "last_run_trace", None)
    if args.stats and trace:
        from tpu_bfs.obs.engine_trace import trace_summary

        # Same stable-prefix-plus-JSON shape as the statsz/recovery
        # lines: grep "^trace " and parse the rest.
        print("trace " + json.dumps(trace_summary(trace, engine)))
    rec = obs_mod.ACTIVE
    if getattr(args, "trace_out", None) and rec is not None:
        from tpu_bfs.obs.exporters import write_perfetto

        write_perfetto(
            rec.snapshot(), args.trace_out, t0=rec.t0,
            level_traces=[(label, trace)] if trace else [],
            meta={"tool": "tpu-bfs-cli", "graph": args.graph},
        )
        print(f"[obs] trace written -> {args.trace_out}", file=sys.stderr)


def _make_ms_engine(args, g, n_sources: int):
    """Select the multi-source engine for --multi-source / --engine.

    Default (no --engine): size to the workload — the 512-lane packed engine
    for small batches (lane tables scale with lane count; 254-level depth
    cap), the hybrid flagship (8192-lane default cap since the round-4
    hardware sweep; auto sizing walks down when the state doesn't fit) once
    the batch is big enough to fill its packed rows. With --devices N the sharded-state distributed
    engines run instead (hybrid flagship by default, '--engine wide' for
    gather-only) — the reference reaches every capability from its one
    binary (README.md:13,22); so does this one.
    """
    engine = args.engine
    planes = args.planes if args.planes is not None else 5
    # --lanes: explicit batch width (w = lanes/32 packed words per row).
    # None -> each engine's own default/auto sizing (single-chip cap 8192
    # since round 4; distributed default 4096 — the scale-26 budget's row
    # width; msbfs_wide/msbfs_hybrid MAX_LANES bounds both). Validated
    # here so flag misuse gets the CLI's clean SystemExit, not an engine
    # traceback (engines apply their own stricter constraints on top, e.g.
    # whole 4096-lane steps for the dense kernel on TPU).
    if args.lanes is not None:
        from tpu_bfs.algorithms.msbfs_wide import MAX_LANES

        if args.lanes % 32 or not (32 <= args.lanes <= MAX_LANES):
            raise SystemExit(
                f"--lanes must be a multiple of 32 in [32, {MAX_LANES}], "
                f"got {args.lanes}"
            )
    lanes_kw = {} if args.lanes is None else {"lanes": args.lanes}
    if args.pull_gate:
        lanes_kw["pull_gate"] = True
    if args.expand_impl != "xla":
        lanes_kw["expand_impl"] = args.expand_impl
    if args.devices > 1 and args.wire_pack:
        # The packed MS engines' wire format is already one bit per
        # (vertex, lane); the flag is accepted for knob uniformity and
        # recorded (a validated no-op — see the engines' docstrings).
        lanes_kw["wire_pack"] = True
    if args.devices > 1 and args.sparse_delta:
        # Sparse row gather: the id stream delta-encodes (ISSUE 7); the
        # lane-word payload is already bit-packed.
        from tpu_bfs.parallel.collectives import DELTA_BITS_DEFAULT

        lanes_kw["delta_bits"] = DELTA_BITS_DEFAULT
    if args.devices > 1:
        if engine == "packed":
            raise SystemExit(
                "--engine packed is single-device; use --engine hybrid or "
                "wide with --devices"
            )
        # The distributed MS engines exchange frontier words by ring
        # collectives: 'dense' (always-full bitmap) or 'sparse' (two-phase
        # queue-style). The single-source-only exchanges map: ring (the
        # default) -> dense; allreduce has no packed analog.
        if args.exchange == "allreduce":
            raise SystemExit(
                "--exchange allreduce applies to single-source --devices "
                "runs; the packed engines exchange 'ring' (dense), "
                "'sparse', or 'sliced' (hybrid)"
            )
        exchange = (
            args.exchange if args.exchange in ("sparse", "sliced") else "dense"
        )
        from tpu_bfs.parallel.dist_bfs import make_mesh

        mesh = make_mesh(args.devices)
        if engine == "wide":
            if exchange == "sliced":
                raise SystemExit(
                    "--exchange sliced is a hybrid-engine layout (ring-"
                    "rotated expansion over dense tiles + pair ELL); use "
                    "--engine hybrid"
                )
            if args.pull_gate:
                raise SystemExit(
                    "--pull-gate on a mesh runs through the distributed "
                    "hybrid engine; drop --engine wide"
                )
            from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

            return DistWideMsBfsEngine(
                g, mesh, num_planes=planes, exchange=exchange, **lanes_kw
            )
        from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

        return DistHybridMsBfsEngine(
            g, mesh, num_planes=planes, exchange=exchange, **lanes_kw
        )
    if engine is None:
        engine = "packed" if n_sources <= 512 else "hybrid"
        if engine == "packed" and (args.ckpt or args.resume):
            # Checkpointing needs resumable packed state (wide/hybrid).
            engine = "wide"
        if engine == "packed" and (args.pull_gate or args.expand_impl != "xla"):
            # The gate and the kernel tier live in the wide/hybrid
            # machinery only.
            engine = "hybrid"
    if engine == "packed":
        from tpu_bfs.algorithms.msbfs_packed import PackedMsBfsEngine

        if args.pull_gate:
            raise SystemExit(
                "--pull-gate applies to the wide/hybrid engines (the "
                "512-lane packed engine keeps no settled-mask state); use "
                "--engine wide or hybrid"
            )
        if args.expand_impl != "xla":
            raise SystemExit(
                "--expand-impl pallas applies to the wide/hybrid engines "
                "(the 512-lane packed engine runs no bucketed-ELL pull "
                "loop); use --engine wide or hybrid"
            )
        lanes = (
            args.lanes
            if args.lanes is not None
            else max(32, -(-n_sources // 32) * 32)
        )
        return PackedMsBfsEngine(g, lanes=lanes)
    if args.adaptive_push:
        if g.num_input_edges < 10_000:
            # Measured: 0.35x on a 240-vertex path graph (BENCHMARKS.md
            # "Level-adaptive expansion") — the push pass wins by skipping
            # the full-table scan, and tiny tables cost nothing to scan.
            print(
                f"WARNING: --adaptive-push on a tiny graph "
                f"({g.num_input_edges} edges < 1e4) usually LOSES (0.35x "
                f"measured on a 240-vertex path graph); it pays off when "
                f"light levels skip a large table scan.",
                file=sys.stderr,
                flush=True,
            )
        lanes_kw = dict(lanes_kw, adaptive_push=args.adaptive_push)
    if engine == "wide":
        from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine

        return WidePackedMsBfsEngine(g, num_planes=planes, **lanes_kw)
    from tpu_bfs.algorithms.msbfs_hybrid import HybridMsBfsEngine

    return HybridMsBfsEngine(g, num_planes=planes, **lanes_kw)


def _run_multi_source(args, g, golden, on_result=None) -> int:
    """--multi-source path: <source> plus the listed keys, one packed batch."""
    import numpy as np

    from tpu_bfs import validate
    from tpu_bfs.utils.stats import level_stats

    try:
        extra = [int(t) for t in args.multi_source.split(",") if t.strip()]
    except ValueError:
        raise SystemExit(f"--multi-source must be comma-separated ints, got "
                         f"{args.multi_source!r}")
    sources = np.asarray([args.source] + extra)
    resume_st = None
    if args.resume:
        # Packed-batch resume: the checkpoint carries the whole batch's
        # sources; the command-line list is ignored in its favor.
        from tpu_bfs.utils import checkpoint as ck

        try:
            resume_st = ck.load_packed_checkpoint(args.resume)
        except ValueError as exc:
            # e.g. a single-source checkpoint resumed with --multi-source.
            raise SystemExit(f"--resume: {exc}")
        sources = resume_st.sources
        if args.lanes is None:
            # Rebuild the engine at the CHECKPOINT's width, not today's
            # default: the default moved 4096 -> 8192 lanes in round 4,
            # and a width mismatch is (correctly) rejected downstream —
            # without this, resuming a pre-round-4 checkpoint would demand
            # a manual --lanes. An explicit --lanes still wins (and a
            # mismatch still gets the descriptive rejection).
            args.lanes = int(resume_st.frontier.shape[1]) * 32
        print(f"resumed {len(sources)} sources at level {resume_st.level} "
              f"({args.lanes} lanes)")
        if golden is None and not args.skip_cpu:
            from tpu_bfs.reference import bfs_golden

            golden = bfs_golden(g, int(sources[0]))
    bad = sources[(sources < 0) | (sources >= g.num_vertices)]
    if len(bad):
        raise SystemExit(
            f"--multi-source vertices {bad.tolist()} out of range "
            f"[0, {g.num_vertices})"
        )
    from tpu_bfs import obs as obs_mod

    with obs_mod.maybe_span("engine_build", "cli", cat="cli",
                            lanes=args.lanes, engine=args.engine):
        engine = _make_ms_engine(args, g, len(sources))
    aot_store = aot_spec = None
    if args.aot:
        # One-shot AOT (ISSUE 9): adopt this engine's programs from the
        # store when a previous run exported them (the compile-skipping
        # preheat), and export them back after the run either way.
        from tpu_bfs.utils import aot as aot_mod

        def aot_log(msg):
            print(f"[aot] {msg}", file=sys.stderr, flush=True)

        aot_store = aot_mod.ArtifactStore(args.aot, log=aot_log)
        aot_spec = {
            "graph_key": args.graph,
            "engine": type(engine).__name__,
            "lanes": engine.lanes,
            "planes": getattr(engine, "num_planes", 8),
            "pull_gate": bool(getattr(engine, "pull_gate", False)),
            "devices": args.devices,
        }
        adopted = aot_mod.adopt_engine_programs(
            engine, aot_spec, aot_store, log=aot_log
        )
        if not adopted:
            aot_log(f"no adoptable artifacts in {args.aot}; running JIT "
                    f"(the store is populated after this run)")
    res = None
    if args.ckpt or args.resume:
        # Chunked batch traversal with durable packed state
        # (tpu_bfs/utils/checkpoint.py::PackedCheckpoint): resume continues
        # bit-identically to an uninterrupted batch run, and transient
        # device/compile failures mid-run rebuild the engine and resume
        # from the last chunk (utils/recovery.py).
        from tpu_bfs.utils import checkpoint as ck
        from tpu_bfs.utils.recovery import advance_with_recovery

        st = resume_st if resume_st is not None else engine.start(sources)
        save = None
        if args.ckpt:
            def save(c):
                ck.save_packed_checkpoint(args.ckpt, c)
                print(f"checkpoint @ level {c.level} -> {args.ckpt}")
        try:
            engine, st, _ = advance_with_recovery(
                lambda: _make_ms_engine(args, g, len(sources)), st,
                engine=engine,
                levels_per_chunk=max(1, args.ckpt_every) if args.ckpt else None,
                max_level=args.max_levels,
                save=save,
                log=lambda m: print(f"[recovery] {m}"),
            )
        except RuntimeError as exc:
            if "truncated" not in str(exc):
                raise
            raise SystemExit(
                f"{exc}\nhint: restart with --planes 8 (depth 254); a "
                "checkpoint's plane count is fixed at start, so existing "
                "checkpoints from this run cannot be resumed deeper"
            )
        res = engine.finish(st)
    else:
        try:
            for rep in range(max(1, args.repeat)):
                rec = obs_mod.ACTIVE
                if rec is not None:
                    rec.begin("run", "cli", cat="cli", rep=rep,
                              sources=len(sources))
                try:
                    with _maybe_profile(args.profile_dir):
                        res = engine.run(
                            sources,
                            max_levels=args.max_levels if args.max_levels is not None else 254,
                            time_it=True,
                        )
                finally:
                    # finally, not success-path: a handled truncation
                    # must not leave the span dangling in the trace.
                    if rec is not None:
                        rec.end("run", "cli", cat="cli", rep=rep,
                                levels=None if res is None else res.num_levels)
        except RuntimeError as exc:
            if "truncated" not in str(exc):
                raise
            alt = "" if args.devices > 1 else " or --engine packed"
            raise SystemExit(
                f"{exc}\nhint: rerun with --planes 8 (depth 254){alt}"
            )
    if aot_store is not None:
        # Export AFTER the run: the engine is warmed, and an engine
        # rebuilt mid-run by the recovery path still exports its final
        # (serving) programs. Adopted entries re-export their originals.
        from tpu_bfs.utils import aot as aot_mod

        names = aot_mod.export_engine_programs(
            engine, aot_spec, aot_store,
            log=lambda m: print(f"[aot] {m}", file=sys.stderr, flush=True),
        )
        print(f"[aot] exported {len(names)} programs -> {args.aot}",
              file=sys.stderr, flush=True)
    if res.elapsed_s is not None:
        print(f"Elapsed time in milliseconds (device): "
              f"{res.elapsed_s * 1e3:.3f} ({len(sources)} sources)")
    for i, s in enumerate(sources):
        print(f"source {int(s)}: reached {int(res.reached[i])} vertices, "
              f"traversed edges {int(res.edges_traversed[i])}")
    if res.teps:
        print(f"Harmonic-mean GTEPS/source: {res.teps / 1e9:.4f}")
    if args.stats:
        gated_counts = getattr(engine, "last_gate_level_counts", None)
        if gated_counts is not None:
            # Trim the cap-length counter array to the BATCH's level count
            # (not lane 0's eccentricity — level_stats keeps the deeper
            # levels other lanes ran, where the gate skips the most).
            gated_counts = np.asarray(gated_counts)[: res.num_levels + 1]
        stats = level_stats(
            res.distances_int32(0), g.degrees, gated_tiles=gated_counts
        )
        for line in stats.json_lines():
            print(line)
        from tpu_bfs.utils.stats import recovery_stats_line

        rline = recovery_stats_line()
        if rline:
            # Post-hoc incident visibility: retries/rebuilds/OOM degrades
            # that fired this process (utils/recovery.COUNTERS).
            print(rline)
    if args.certify:
        # Oracle-free certificate for the primary lane (see the
        # single-source path); no CPU golden run at any scale. The message
        # is qualified: like the golden path, only lane 0 is checked.
        validate.certify_bfs(
            g, int(sources[0]), res.distances_int32(0), res.parents_int32(0)
        )
        print(f"Output certified (oracle-free, lane 0 of {len(sources)})")
    elif golden is not None:
        validate.check_distances(res.distances_int32(0), golden)
        if not args.no_parents:
            # Also validate the engine-emitted BFS tree for the primary
            # lane — the check the reference could never run on its parent
            # output (bfs.cu:940; checkOutput compares distances only).
            validate.check_parents(
                g, int(sources[0]), res.distances_int32(0),
                res.parents_int32(0),
            )
        print("Output OK")
    if args.save_dist:
        np.save(args.save_dist, np.stack([
            res.distances_int32(i) for i in range(len(sources))
        ]))
    if args.save_parent:
        # Bulk export: the batched device min-key scan when the engine can
        # serve it (one expansion pass per 128 lanes, single-chip or
        # distributed — parent_scan.py), host scatter-min otherwise; peak
        # host memory stays near the one output array either way.
        out = np.empty((len(sources), g.num_vertices), np.int32)
        np.save(args.save_parent, res.parents_into(out))
    _finish_obs(args, engine, type(engine).__name__)
    if on_result is not None:
        on_result(g, engine, res)
    return 0


def main(argv=None, *, on_result=None) -> int:
    """Run the CLI on ``argv``. In-process callers may pass
    ``on_result(graph, engine, result)``, called once after the run's own
    validation — how a caller checks more than the CLI prints (e.g. more
    lanes of a batch) without saving the whole result to disk."""
    ap = argparse.ArgumentParser(
        prog="tpu_bfs",
        description="TPU-native distributed BFS (capabilities of Distributed-CUDA-BFS).",
    )
    ap.add_argument("source", type=int, help="source vertex (reference argv[1])")
    ap.add_argument(
        "graph",
        help="graph file path, '-' for stdin, or generator spec "
        "(rmat:scale=20,ef=16 | random:n=...,m=...) (reference argv[2])",
    )
    ap.add_argument("--devices", type=int, default=1,
                    help="device count; >1 uses the distributed engine (default 1)")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="2D mesh shape (e.g. 2x4): uses the 2D edge partition "
                    "engine instead of the 1D vertex partition")
    ap.add_argument("--backend", default="scan",
                    choices=["scan", "segment", "scatter", "delta", "dopt",
                             "tiled"],
                    help="frontier-expansion backend ('dopt' = direction-"
                    "optimizing top-down/bottom-up switch; works single-"
                    "device, --devices N, and --mesh RxC; 'delta' and "
                    "'tiled' are single-device only — 'tiled' adds the "
                    "dense-tile bitset pass, the fastest measured "
                    "single-stream)")
    ap.add_argument("--exchange", default="ring",
                    choices=["ring", "allreduce", "sparse", "sliced"],
                    help="multi-device frontier exchange implementation "
                    "('sparse' = two-phase queue-style id exchange with "
                    "dense-bitmap fallback; 1D --devices meshes). With "
                    "--multi-source, 'ring' maps to the packed engines' "
                    "dense word exchange; 'sliced' (hybrid engine only) is "
                    "the ring-rotation expansion with O(A/P) transients")
    ap.add_argument("--max-levels", type=int, default=None)
    ap.add_argument("--skip-cpu", action="store_true",
                    help="skip the CPU golden run + validation (reference always validates, bfs.cu:798-815)")
    ap.add_argument("--certify", action="store_true",
                    help="validate with the oracle-free BFS certificate "
                    "(two O(E) host passes, validate.certify_bfs) instead "
                    "of the CPU golden rerun — feasible at scales where "
                    "the sequential run is not; implies --skip-cpu")
    ap.add_argument("--no-parents", action="store_true")
    ap.add_argument("--stats", action="store_true", help="print per-level JSON stats")
    ap.add_argument("--repeat", type=int, default=1, help="timed repetitions")
    ap.add_argument("--save-dist", default=None, help="save distances to .npy")
    ap.add_argument("--save-parent", default=None, help="save parents to .npy")
    ap.add_argument("--multi-source", default=None, metavar="V1,V2,...",
                    help="run these sources concurrently with <source> via a "
                    "bit-packed multi-source engine; --devices N shards "
                    "state over the mesh (DistHybrid/DistWide engines)")
    ap.add_argument("--engine", default=None,
                    choices=["hybrid", "wide", "packed"],
                    help="--multi-source engine: 'hybrid' = MXU dense "
                    "tiles + gathers (flagship; 8192-lane default cap), "
                    "'wide' = gather-only (same widths), 'packed' = "
                    "512-lane (254-level depth cap; "
                    "single-device). Default: 'packed' for <=512 sources, "
                    "else 'hybrid'; with --devices N always the sharded "
                    "hybrid unless 'wide' is chosen")
    ap.add_argument("--planes", type=int, default=None, metavar="P",
                    choices=range(1, 9),
                    help="bit-plane count for the wide/hybrid engines; caps "
                    "traversal depth at 2**P levels (default 5)")
    ap.add_argument("--lanes", type=int, default=None, metavar="N",
                    help="packed batch width for --multi-source engines "
                    "(default: engine auto sizing — single-chip cap 8192, "
                    "distributed 4096; wider rows trade proportionally "
                    "more HBM for more concurrent sources. NB on TPU, "
                    "widths below 4096 pad to the same physical tables)")
    ap.add_argument("--wire-pack", action="store_true",
                    help="bit-pack the boolean frontier exchanges to uint32 "
                    "words, 32 vertices/word (experimental, default off "
                    "until chip-measured): 1D --devices ring/allreduce/"
                    "sparse-fallback and both 2D --mesh collectives ship "
                    "1 bit per vertex instead of 1-4 bytes, bit-identical "
                    "results (utils/wirecheck.check_packed_exchange proves "
                    "the byte ratios from the compiled HLO). The "
                    "--multi-source packed engines already exchange "
                    "bit-packed lane words; there the flag is a recorded "
                    "no-op")
    ap.add_argument("--sparse-delta", action="store_true",
                    help="delta-encode the sparse exchange's id buffers "
                    "(ISSUE 7; experimental, default off until "
                    "chip-measured): first-id + fixed-width 8/16-bit "
                    "bit-packed deltas in uint32 words instead of 4-byte "
                    "ids, width picked per level by the same mesh-uniform "
                    "pmax discipline as the cap rungs. Needs --exchange "
                    "sparse on a multi-device run; with --multi-source it "
                    "compresses the sparse row gather's id stream. "
                    "Bit-identical results (fuzz-pinned); "
                    "utils/wirecheck proves the byte ratios from the "
                    "compiled HLO (make wirecheck)")
    ap.add_argument("--sparse-sieve", action="store_true",
                    help="visited sieve for the sparse exchange (ISSUE 7, "
                    "experimental): on high-reuse levels each receiver's "
                    "packed vis chunk ships backward once (1 bit/vertex) "
                    "so senders drop already-visited ids before "
                    "compaction — taken only when the modeled id savings "
                    "beat the transfer's own ~vloc/8 cost. Single-source "
                    "--devices/--mesh runs with --exchange sparse")
    ap.add_argument("--sparse-predict", action="store_true",
                    help="history-predictive exchange selection (ISSUE 7, "
                    "experimental): confidently-dense mid-BFS levels "
                    "(previous biggest above every cap, frontier still "
                    "growing) skip the per-level pmax entirely, "
                    "direction-optimizing style. Single-source "
                    "--devices/--mesh runs with --exchange sparse")
    ap.add_argument("--pull-gate", action="store_true",
                    help="frontier-aware pull expansion (experimental, "
                    "default off): settled rows' bucket blocks, state "
                    "tiles, and (single-source 'tiled') dense-tile passes "
                    "are skipped per level, bit-identical to the plain "
                    "scan. Applies to --multi-source wide/hybrid engines "
                    "(single device or --devices N hybrid) and --backend "
                    "tiled; --stats adds per-level gated_tiles counts")
    ap.add_argument("--expand-impl", default="xla",
                    choices=("xla", "pallas"),
                    help="pull-expansion tier for the packed MS engines "
                    "(default xla): 'xla' keeps the fori-loop gather the "
                    "compiler fuses; 'pallas' runs the fused bucketed-ELL "
                    "kernel (ops/ell_expand) — double-buffered index-slab "
                    "DMA, VMEM-resident accumulator, one HBM write per "
                    "128-row tile per level, settled-mask gating inside "
                    "the kernel under --pull-gate. Bit-identical output; "
                    "--multi-source wide/hybrid engines (single device or "
                    "--devices N)")
    ap.add_argument("--adaptive-push", default=None, metavar="ROWS,DEG",
                    help="experimental level-adaptive expansion for "
                    "--engine wide|hybrid (single device): levels with "
                    "<= ROWS active rows, all with out-degree <= DEG, "
                    "take a push-style pass instead of the full ELL/tile "
                    "scan (BENCHMARKS.md 'Level-adaptive expansion')")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of the timed run here")
    ap.add_argument("--ckpt", default=None, metavar="PATH",
                    help="checkpoint the traversal state to PATH (npz "
                    "format) every --ckpt-every levels (single-source "
                    "modes and single-device --multi-source batches)")
    ap.add_argument("--ckpt-every", type=int, default=4, metavar="N",
                    help="levels per checkpoint chunk (default 4)")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume a traversal from a checkpoint written by "
                    "--ckpt (overrides <source> with the saved one)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm a deterministic fault-injection schedule "
                    "(chaos testing, tpu_bfs/faults.py), e.g. "
                    "'seed=7:transient@advance:n=1,corrupt_ckpt:n=1'; "
                    "default: the TPU_BFS_FAULTS env var, else disabled. "
                    "Injected faults exercise the real recovery paths; "
                    "--stats surfaces the counters")
    ap.add_argument("--obs", default=None, metavar="SPEC", nargs="?",
                    const="1",
                    help="arm the telemetry recorder (tpu_bfs/obs): span "
                    "tracing, per-level engine traces, and the flight "
                    "recorder. SPEC e.g. 'dump_dir=/tmp/fr,window=60'; "
                    "bare --obs uses defaults; default: the TPU_BFS_OBS "
                    "env var, else disabled. --stats adds the engine "
                    "trace-summary line")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the "
                    "run here (host spans + a per-level engine-trace "
                    "track: frontier count, direction, gated tiles, "
                    "exchange choice, modeled wire bytes; implies --obs)")
    ap.add_argument("--aot", default=None, metavar="DIR",
                    help="AOT artifact store (utils/aot): install this "
                    "run's engine programs from DIR when exported there "
                    "before (skipping trace/lower/compile), and export "
                    "them back after the run — the one-shot analog of "
                    "tpu-bfs-serve --preheat/--export-aot (multi-source "
                    "packed engines; stale/corrupt artifacts fall back "
                    "to JIT)")
    args = ap.parse_args(argv)
    from tpu_bfs import faults as faults_mod

    sched = faults_mod.arm_from_spec_or_env(args.faults)
    if sched is not None:
        print(f"[faults] schedule armed: {sched.to_spec()}", file=sys.stderr)
    recorder = _arm_obs(args)
    if args.aot is not None and not args.multi_source:
        ap.error("--aot pairs with --multi-source (the packed MS engines "
                 "are the AOT-exportable family; single-source engines "
                 "compile in seconds)")
    if args.adaptive_push is not None:
        if (
            args.engine not in ("wide", "hybrid")
            or args.devices > 1
            or not args.multi_source
        ):
            ap.error("--adaptive-push pairs with --multi-source --engine "
                     "wide|hybrid on a single device")
        try:
            r, d = (int(t) for t in args.adaptive_push.split(","))
            if r < 1 or d < 1:
                raise ValueError
        except ValueError:
            ap.error(f"--adaptive-push must be ROWS,DEG positive ints, got "
                     f"{args.adaptive_push!r}")
        args.adaptive_push = (r, d)
    if args.pull_gate and args.adaptive_push is not None:
        ap.error("--pull-gate and --adaptive-push cannot combine (both "
                 "gate the per-level scan; measure them separately)")
    if args.expand_impl != "xla" and not args.multi_source:
        ap.error("--expand-impl pallas fuses the packed MS engines' "
                 "bucketed-ELL pull expansion; pair it with --multi-source "
                 "(single-source backends run no ELL pull loop)")
    if args.pull_gate and not args.multi_source and (
        args.backend != "tiled" or args.mesh or args.devices > 1
    ):
        ap.error("--pull-gate for single-source runs needs --backend "
                 "tiled on a single device (the other single-source "
                 "backends have no tile pass to gate)")
    if (args.mesh or args.devices > 1) and args.backend in ("delta", "tiled"):
        ap.error(f"--backend {args.backend} is single-device only")
    if args.wire_pack and args.devices == 1 and not args.mesh:
        ap.error("--wire-pack packs multi-device exchanges; add --devices N "
                 "or --mesh RxC (a single chip moves nothing over the wire)")
    if args.sparse_delta or args.sparse_sieve or args.sparse_predict:
        if args.devices == 1 and not args.mesh:
            ap.error("--sparse-delta/--sparse-sieve/--sparse-predict reshape "
                     "multi-device exchanges; add --devices N or --mesh RxC")
        if args.exchange != "sparse":
            ap.error("--sparse-delta/--sparse-sieve/--sparse-predict apply "
                     "to the queue-style id exchange; add --exchange sparse")
    if (args.sparse_sieve or args.sparse_predict) and args.multi_source:
        ap.error("--sparse-sieve/--sparse-predict are single-source "
                 "exchange-planner features (1D --devices or --mesh RxC); "
                 "--multi-source row gathers support --sparse-delta only")
    if args.exchange == "sliced" and not (args.multi_source and args.devices > 1):
        ap.error("--exchange sliced is the packed hybrid engine's ring-"
                 "rotation layout; use it with --multi-source --devices N")
    if args.multi_source and args.mesh:
        ap.error("--multi-source shards 1D (row-tile round-robin); pass "
                 "--devices N instead of a 2D mesh")
    if (args.ckpt or args.resume) and args.multi_source and args.engine == "packed":
        ap.error("--ckpt/--resume with --multi-source needs the wide or "
                 "hybrid engine (the 512-lane packed engine keeps no "
                 "resumable state)")
    if (args.ckpt or args.resume) and (args.repeat > 1 or args.profile_dir):
        ap.error("--repeat/--profile-dir do not apply to checkpointed runs")

    import numpy as np

    from tpu_bfs import validate
    from tpu_bfs.algorithms.bfs import BfsEngine
    from tpu_bfs.utils.compile_cache import enable_compile_cache

    enable_compile_cache(log=lambda m: print(f"[cache] {m}", file=sys.stderr))
    t0 = time.perf_counter()
    from tpu_bfs import obs as obs_mod

    with obs_mod.maybe_span("graph_load", "cli", cat="cli", graph=args.graph):
        g = load_graph(args.graph)
    print(f"Number of vertices {g.num_vertices}")  # reference prints these (bfs.cu:789-790)
    print(f"Number of edges {g.num_edges}")
    print(f"[load] {time.perf_counter() - t0:.3f}s")
    if not (0 <= args.source < g.num_vertices):
        raise SystemExit(
            f"source {args.source} out of range [0, {g.num_vertices})"
        )

    # On --resume the traversal's source comes from the checkpoint; load it
    # before the golden run so the CPU BFS happens once, for the right source.
    # (Multi-source batches resume from a packed checkpoint inside
    # _run_multi_source instead — their golden is computed there.)
    resume_st = None
    if args.resume and not args.multi_source:
        from tpu_bfs.utils import checkpoint as ck

        try:
            resume_st = ck.load_checkpoint(args.resume)
        except ValueError as exc:
            # e.g. a packed-batch checkpoint resumed without --multi-source.
            raise SystemExit(f"--resume: {exc}")
        print(f"resumed source {resume_st.source} at level {resume_st.level}")

    golden = None
    # A resumed multi-source batch learns its sources from the packed
    # checkpoint; _run_multi_source computes the golden itself.
    if args.certify:
        args.skip_cpu = True  # the certificate replaces the golden rerun
    if not args.skip_cpu and not (args.multi_source and args.resume):
        from tpu_bfs.reference import bfs_golden

        t0 = time.perf_counter()
        golden = bfs_golden(
            g, resume_st.source if resume_st is not None else args.source
        )
        # Reference prints CPU elapsed ms (runCpu, bfs.cu:211-219).
        print(f"Elapsed time in milliseconds (CPU): {(time.perf_counter() - t0) * 1e3:.2f}")

    if args.multi_source:
        return _run_multi_source(args, g, golden, on_result)

    def make_engine():
        if args.mesh:
            from tpu_bfs.parallel.dist_bfs2d import Dist2DBfsEngine, make_mesh_2d

            try:
                r, c = (int(t) for t in args.mesh.lower().split("x"))
            except ValueError:
                ap.error(f"--mesh must look like RxC (e.g. 2x4), got {args.mesh!r}")
            from tpu_bfs.parallel.collectives import DELTA_BITS_DEFAULT

            return Dist2DBfsEngine(
                g, make_mesh_2d(r, c), exchange=args.exchange,
                backend=args.backend, wire_pack=args.wire_pack,
                delta_bits=DELTA_BITS_DEFAULT if args.sparse_delta else (),
                sieve=args.sparse_sieve, predict=args.sparse_predict,
            )
        if args.devices > 1:
            from tpu_bfs.parallel.collectives import DELTA_BITS_DEFAULT
            from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh

            return DistBfsEngine(
                g, make_mesh(args.devices), exchange=args.exchange,
                backend=args.backend, wire_pack=args.wire_pack,
                delta_bits=DELTA_BITS_DEFAULT if args.sparse_delta else (),
                sieve=args.sparse_sieve, predict=args.sparse_predict,
            )
        if args.backend == "tiled":
            from tpu_bfs.algorithms.bfs_tiled import TiledBfsEngine

            return TiledBfsEngine(g, pull_gate=args.pull_gate)
        return BfsEngine(g, backend=args.backend)

    with obs_mod.maybe_span("engine_build", "cli", cat="cli",
                            backend=args.backend, devices=args.devices):
        engine = make_engine()

    if args.ckpt or args.resume:
        # Chunked traversal with durable state (tpu_bfs/utils/checkpoint.py):
        # resume continues bit-identically to an uninterrupted run, and a
        # transient device/compile failure mid-run rebuilds the engine and
        # resumes from the last chunk (utils/recovery.py — the reference's
        # failed rank instead hangs the MPI_Allreduce, bfs_mpi.cu:621).
        from tpu_bfs.utils import checkpoint as ck
        from tpu_bfs.utils.recovery import advance_with_recovery

        st = resume_st if resume_st is not None else engine.start(args.source)
        save = None
        if args.ckpt:
            def save(c):
                ck.save_checkpoint(args.ckpt, c)
                print(f"checkpointed at level {c.level}")
        engine, st, _ = advance_with_recovery(
            make_engine, st, engine=engine,
            levels_per_chunk=max(1, args.ckpt_every) if args.ckpt else None,
            max_level=args.max_levels,
            save=save,
            log=lambda m: print(f"[recovery] {m}"),
        )
        res = engine.finish(st, with_parents=not args.no_parents)
    else:
        res = None
        for rep in range(max(1, args.repeat)):
            if recorder is not None:
                recorder.begin("run", "cli", cat="cli", source=args.source,
                               rep=rep)
            try:
                with _maybe_profile(args.profile_dir):
                    res = engine.run(
                        args.source,
                        max_levels=args.max_levels,
                        with_parents=not args.no_parents,
                        time_it=True,
                    )
            finally:
                if recorder is not None:
                    recorder.end(
                        "run", "cli", cat="cli", rep=rep,
                        levels=None if res is None else res.num_levels,
                        reached=None if res is None else res.reached,
                    )
            # Reference prints device elapsed ms (bfs.cu:624-626).
            print(f"Elapsed time in milliseconds (device): {res.elapsed_s * 1e3:.3f}")
    if res.teps:
        print(f"Traversed edges: {res.edges_traversed}  GTEPS: {res.teps / 1e9:.4f}")
    print(f"Reached {res.reached} vertices in {res.num_levels} levels")
    skipped = getattr(engine, "last_gate_skipped_tiles", None)
    if skipped is not None:
        print(f"Pull gate skipped {skipped} dense-tile passes")

    if args.stats:
        from tpu_bfs.utils.stats import level_stats, recovery_stats_line

        for line in level_stats(res.distance, g.degrees).json_lines():
            print(line)
        rline = recovery_stats_line()
        if rline:
            # Retry/OOM-degrade counters, when any fired (post-hoc
            # visibility for checkpointed runs' recovery loops).
            print(rline)

    if args.certify:
        # Oracle-free certificate: parent chains + edge-level property
        # prove the distances exactly (validate.certify_bfs) with two
        # O(E) passes — no sequential rerun, so it works at scales the
        # reference's self-validation (bfs.cu:798-815) can never reach.
        parent = (
            res.parent
            if res.parent is not None
            else validate.min_parent_from_dist(g, res.source, res.distance)
        )
        validate.certify_bfs(g, res.source, res.distance, parent)
        print("Output certified (oracle-free)")
    elif golden is not None:
        # checkOutput analog (bfs.cu:374-384) — but also validates parents,
        # which the reference never does.
        validate.check_distances(res.distance, golden)
        if res.parent is not None:
            validate.check_parents(g, res.source, res.distance, res.parent)
        print("Output OK")

    if args.save_dist:
        np.save(args.save_dist, res.distance)
    if args.save_parent and res.parent is not None:
        np.save(args.save_parent, res.parent)
    _finish_obs(args, engine, type(engine).__name__)
    if on_result is not None:
        on_result(g, engine, res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
