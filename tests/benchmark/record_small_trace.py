"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 tests/benchmark/record_small_trace.py <out.xplane.pb>

Run on a machine with a TPU: a few short jitted programs inside the
benchmark's own spans (``bench.window``, ``bench.batch``), with host
sleeps between them, so the trace holds device ops, idle gaps and the
spans that label them. Writes the ``.xplane.pb`` and prints what the
reduction reads from it.
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


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce as tr

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def core(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((1024, 1024), jnp.float32)
    core(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.batch"):
                core(x).block_until_ready()
                time.sleep(0.002)
            time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(tr.find_trace(tmp), out)
    shutil.rmtree(tmp)
    s = tr.reduce(tr.load_events(out))
    print(f"{out}: {os.path.getsize(out)} bytes; window {s.window_s} s, "
          f"busy {s.busy_s} s, ops {s.op_s}, modules {s.module_s}, "
          f"gaps {s.gaps}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
