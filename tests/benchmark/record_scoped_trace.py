"""Record the scoped trace that ``test_program_trace.py`` reads.

    python3 tests/benchmark/record_scoped_trace.py <out.xplane.pb>

Run on a machine with a TPU: the serve tier's own batch path (an
admission queue, the batch executor, a 64-lane wide engine on a small
graph) inside the benchmark's window span, so the trace holds the level
loop's named scopes (``lanes64``, ``level.gather``, ``level.claim``,
``batch.stats``) on the device and the program's per-batch host spans
(``tpu_bfs.serve.coalesce``, ``tpu_bfs.serve.dispatch``, ...) with their
stats. Writes the ``.xplane.pb`` and prints what ``program_trace`` reads
from it.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

#: Sources of each recorded batch and the batches recorded.
SOURCES = (0, 3, 5, 9, 17, 33, 65, 129, 257)
BATCHES = 2


def main(out: str) -> int:
    import jax

    from benchmark import program_trace as pt
    from benchmark import trace_reduce as tr
    from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine
    from tpu_bfs.graph.generate import rmat_graph
    from tpu_bfs.serve.executor import BatchExecutor
    from tpu_bfs.serve.metrics import ServeMetrics
    from tpu_bfs.serve.scheduler import AdmissionQueue, PendingQuery

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    engine = WidePackedMsBfsEngine(rmat_graph(10, 8, seed=3), lanes=64)
    executor = BatchExecutor(ServeMetrics())
    queue = AdmissionQueue(64)

    def batch():
        for s in SOURCES:
            queue.offer(PendingQuery(s, want_distances=False))
        time.sleep(0.002)  # the queries wait a little before dispatch
        executor.run_batch(engine, queue.next_batch(64, 0.001))

    batch()  # compiles every program the recorded batches run
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(BATCHES):
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(tr.find_trace(tmp), out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return pt.main([out])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
