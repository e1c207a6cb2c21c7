"""Chip smoke: the main path end to end on the TPU, in one process.

    python chip_smoke.py            one chip: the hybrid MS-BFS batch
                                    (RMAT scale 21, 8192 sources) and the
                                    serve path (RMAT scale 20, JSONL)
    python chip_smoke.py --chips 4  four chips: the 1D-sharded hybrid batch
                                    and the 2D-mesh single-source run
                                    (RMAT scale 22), against the one-chip
                                    engine and the CPU oracle

Drives the entry points a user calls, in-process: ``tpu_bfs.cli.main``
and ``tpu_bfs.serve.frontend.run_server``. Answers are checked against
the CPU oracle (``tpu_bfs.reference.bfs_scipy``) and the BFS-tree
validator. Set-up facts go on earlier lines; the last line is
``{"ok": true, "device": {...}}``. Any failed phase or wrong answer exits
nonzero without that line, and so does a host where JAX finds no TPU —
before any phase runs. No time printed here is a speed claim.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import inspect
import io
import json
import sys
import time

import numpy as np

from tpu_bfs import cli, validate
from tpu_bfs.graph import generate
from tpu_bfs.reference import bfs_scipy

SEED = 1
_COMPILE_S = [0.0]  # summed /jax/core/compile/* durations


class SmokeFailure(RuntimeError):
    pass


def _expect(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _on_event_duration(event: str, duration: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += duration


@contextlib.contextmanager
def phase(name: str, devices):
    """Wall time, the compile share of it, and device peak memory."""
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    wall, comp = time.perf_counter() - t0, _COMPILE_S[0] - c0
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    print(f"[{name}] wall {wall:.3f} s, of which compile {comp:.3f} s; "
          f"peak_bytes_in_use so far per device {peaks}", flush=True)


def seeded_sources(spec: str, n: int, seed: int = SEED) -> np.ndarray:
    """``n`` distinct seeded sources with at least one edge (the Graph500
    rule: an isolated source traverses nothing)."""
    g = cli.load_graph(spec)
    rng = np.random.default_rng(seed)
    return rng.choice(np.flatnonzero(g.degrees > 0), size=n, replace=False)


def run_cli(argv: list[str]):
    """``cli.main(argv)`` in-process; returns (graph, engine, result). The
    CLI's per-source lines are dropped, the rest is echoed."""
    got = {}
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = cli.main(argv, on_result=lambda g, e, r: got.update(
                g=g, engine=e, res=r))
    finally:
        lines = [ln for ln in out.getvalue().splitlines()
                 if not ln.startswith("source ")]
        for ln in lines:
            print(f"  cli: {ln}", flush=True)
    _expect(rc == 0 and got, f"cli.main({argv[:2]}...) returned {rc}")
    _expect("Output OK" in lines, "the CLI's own lane-0 validation did not pass")
    return got["g"], got["engine"], got["res"]


def run_batch(spec: str, n_sources: int, *extra: str):
    """A hybrid MS-BFS batch of ``n_sources`` distinct seeded sources."""
    sources = seeded_sources(spec, n_sources)
    argv = [str(sources[0]), spec, "--multi-source",
            ",".join(str(s) for s in sources[1:]), "--engine", "hybrid",
            *extra]
    g, engine, res = run_cli(argv)
    _expect(np.array_equal(res.sources, sources), "batch sources reordered")
    return g, engine, res


def spread_lanes(reached: np.ndarray) -> list[int]:
    """First and last lane, and two inner lanes in other 32-lane words
    (each the first lane of its word whose source reaches past itself)."""
    n = len(reached)
    lanes = [0, n - 1]
    for start in (n // 4, n // 2):
        w0 = start // 32 * 32
        word = [i for i in range(w0, min(w0 + 32, n)) if reached[i] > 1]
        lanes.append(word[0] if word else w0)
    return sorted(set(lanes))


def check_lanes(g, res, lanes) -> None:
    for i in lanes:
        validate.check_distances(
            res.distances_int32(i), bfs_scipy(g, int(res.sources[i])))
    validate.check_parents(g, int(res.sources[0]), res.distances_int32(0),
                           res.parents_int32(0))
    print(f"  oracle: lanes {lanes} match bfs_scipy "
          f"(reached {[int(res.reached[i]) for i in lanes]}); lane 0 "
          f"parents form a valid BFS tree", flush=True)


def batch_phase(spec: str = "rmat:scale=21,ef=16,seed=1",
                n_sources: int = 8192) -> None:
    g, engine, res = run_batch(spec, n_sources, "--lanes", str(n_sources))
    _expect(engine.lanes == n_sources,
            f"engine ran {engine.lanes} lanes, wanted {n_sources}")
    lanes = spread_lanes(res.reached)
    _expect(len(lanes) >= 4 or n_sources < 128, f"too few lanes {lanes}")
    check_lanes(g, res, lanes)
    print(f"  batch: {type(engine).__name__}, {engine.lanes} lanes, "
          f"{res.num_levels} levels", flush=True)


def serve_phase(spec: str = "rmat:scale=20,ef=16,seed=1", n_requests: int = 8,
                lanes: int = 256, ladder: str = "64,256") -> None:
    from tpu_bfs.serve.frontend import (
        build_arg_parser,
        decode_distances,
        run_server,
    )
    from tpu_bfs.serve.registry import EngineRegistry

    srcs = seeded_sources(spec, n_requests, SEED + 1)
    reqs = [{"id": i, "source": int(s)} for i, s in enumerate(srcs)]
    reqs[0]["want_distances"] = False
    args = build_arg_parser().parse_args(
        [spec, "--engine", "wide", "--lanes", str(lanes), "--ladder", ladder,
         "--statsz-every", "0"])
    registry = EngineRegistry()
    out, err = io.StringIO(), io.StringIO()
    rc = run_server(
        args, stdin=io.StringIO("".join(json.dumps(r) + "\n" for r in reqs)),
        stdout=out, stderr=err, registry=registry)
    resps = {r["id"]: r for r in map(json.loads, out.getvalue().splitlines())}
    _expect(rc == 0, f"run_server returned {rc}: {err.getvalue()[-2000:]}")
    _expect(sorted(resps) == list(range(n_requests)),
            f"answered {sorted(resps)} of {n_requests} requests")
    bad = {i: r.get("error") for i, r in resps.items() if r["status"] != "ok"}
    _expect(not bad, f"requests not ok: {bad}")
    _expect("distances_npy" not in resps[0], "want_distances=false carried "
            "distances")
    _expect(all("distances_npy" in resps[i] for i in range(1, n_requests)),
            "a distance request came back without distances")
    g = registry.graph(spec)
    for i in (1, n_requests - 1):
        d = decode_distances(resps[i]["distances_npy"])
        validate.check_distances(d, bfs_scipy(g, int(srcs[i])))
    print(f"  serve: {n_requests} requests ok (1 without distances); "
          f"requests 1 and {n_requests - 1} match bfs_scipy; levels "
          f"{[resps[i]['levels'] for i in range(n_requests)]}", flush=True)


def four_chip_phase(devices, spec: str = "rmat:scale=22,ef=16,seed=1",
                    n_sources: int = 4096, lanes: int = 4096) -> None:
    """1D-sharded hybrid batch and 2D-mesh dopt run, each checked against
    the one-chip hybrid engine on the same sources and the oracle."""
    lanes_arg = ("--lanes", str(lanes))
    with phase("dist-batch", devices):
        g, engine, res = run_batch(spec, n_sources, "--devices", "4",
                                   *lanes_arg)
        shard_devs = {
            k: sorted(s.device.id for s in a.addressable_shards)
            for k, a in engine.arrs.items() if hasattr(a, "addressable_shards")
        }
        for k, ids in shard_devs.items():
            _expect(len(ids) == len(set(ids)) == 4,
                    f"{k} shards on devices {ids}, not 4 distinct")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devices]
        _expect(all(b > (1 << 20) for b in in_use),
                f"bytes_in_use per device {in_use}: a device holds nothing")
        print(f"  dist: {type(engine).__name__}; {len(shard_devs)} sharded "
              f"tables, each on 4 distinct devices; bytes_in_use {in_use}",
              flush=True)
        lanes = spread_lanes(res.reached)
        check_lanes(g, res, lanes)
        words = sorted({i // 32 for i in lanes})
        dist = {i: res.distance_u8_lane(i) for w in words
                for i in range(32 * w, 32 * w + 32)}
        reached, edges = res.reached.copy(), res.edges_traversed.copy()
        del engine, res
        gc.collect()
    with phase("one-chip-batch", devices):
        _, engine1, res1 = run_batch(spec, n_sources, *lanes_arg)
        _expect(np.array_equal(res1.reached, reached)
                and np.array_equal(res1.edges_traversed, edges),
                "per-lane reached/edges differ from the one-chip engine")
        diff = [i for i, d in dist.items()
                if not np.array_equal(res1.distance_u8_lane(i), d)]
        _expect(not diff, f"lanes {diff} differ from the one-chip engine")
        print(f"  one-chip: {type(engine1).__name__} bit-identical on all "
              f"{n_sources} lanes' reached/edges and on the {len(dist)} "
              f"lanes of words {words}", flush=True)
        del engine1, res1
        gc.collect()
    with phase("mesh-2x2-dopt", devices):
        src = int(seeded_sources(spec, 1)[0])
        g2, engine2, res2 = run_cli(
            [str(src), spec, "--mesh", "2x2", "--backend", "dopt"])
        validate.check_distances(res2.distance, bfs_scipy(g2, src))
        print(f"  mesh: {type(engine2).__name__} source {src} matches "
              f"bfs_scipy ({res2.reached} reached)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths (default 1)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    devices = devices[: args.chips]
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    from tpu_bfs.utils.compile_cache import enable_compile_cache

    # Each seeded graph is generated once: every CLI call and the serve
    # registry load their spec through cli.load_graph.
    cli.load_graph = functools.lru_cache(maxsize=2)(cli.load_graph)
    print(f"jax {jax.__version__}; devices {jax.devices()}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    impl = inspect.signature(generate.rmat_graph).parameters["impl"].default
    print(f"RMAT stream: {impl} (tpu_bfs.cli.load_graph -> rmat_graph)",
          flush=True)
    try:
        if args.chips == 1:
            with phase("batch", devices):
                batch_phase()
            with phase("serve", devices):
                serve_phase()
        else:
            four_chip_phase(devices)
    except Exception as exc:  # noqa: BLE001 — every failure exits nonzero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
