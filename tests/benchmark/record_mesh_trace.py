"""Record the four-chip trace that ``test_mesh_cell.py`` reads.

    python3 tests/benchmark/record_mesh_trace.py <out.xplane.pb>

Run on a host with four TPU chips: the 1D-sharded hybrid engine (the
four-chip cell's engine, at its 8192 lanes) on a small Kronecker graph,
two batches inside the benchmark's window and batch spans, so the trace
holds the sharded core's scopes (``lanes8192``, ``level.exchange``,
``level.tiles``, ``level.gather``, ``level.claim``, ``level.sync``) on
each chip's device plane and the fetch spans' ``exchange_bytes``. Writes
the ``.xplane.pb`` and prints what ``program_trace`` and the mesh readers
read from it.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

#: Graph scale, lanes, and the batches recorded.
SCALE = 12
LANES = 8192
BATCHES = 2


def main(out: str) -> int:
    import jax

    from benchmark import harness
    from benchmark import program_trace as pt
    from benchmark import trace_reduce as tr
    from tpu_bfs.graph.generate import rmat_graph
    from tpu_bfs.parallel.dist_bfs import make_mesh
    from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < 4:
        print(f"needs four TPU chips, found {devices}", file=sys.stderr)
        return 2
    g = rmat_graph(SCALE, 16, seed=1)
    engine = DistHybridMsBfsEngine(g, make_mesh(4), lanes=LANES)
    rng = np.random.default_rng(26)
    batches = [rng.integers(0, g.num_vertices, size=LANES)
               for _ in range(BATCHES)]
    engine.run(batches[0])  # compiles every program the batches run
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    passes = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for keys in batches:
            with jax.profiler.TraceAnnotation("bench.batch"):
                passes += engine.run(keys).num_levels + 1
    jax.profiler.stop_trace()
    shutil.copy(tr.find_trace(tmp), out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes, {passes} passes")
    ctx = types.SimpleNamespace(
        trace=tr.reduce(tr.load_events(out)), counters={"levels_run": passes},
        device_kind=devices[0].device_kind)
    pt.TRACE_ROOT = os.path.dirname(os.path.abspath(out))
    for name in ("mesh_level_ms", "mesh_exchange_ms",
                 "mesh_exchange_roofline", "mesh_imbalance",
                 "mesh_gather_ms", "mesh_tiles_ms", "mesh_claim_ms",
                 "device_idle_share"):
        reader = harness.load_module(
            os.path.join(harness.BENCH_DIR, "metrics", f"{name}.py"), name)
        print(f"{name}: {reader.read(ctx)}")
    return pt.main([out])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
