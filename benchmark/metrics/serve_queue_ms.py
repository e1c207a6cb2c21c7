"""serve_queue_ms: milliseconds a query waits from admission to its
batch's dispatch, averaged over the queries dispatched in the traced
window: the sum of the ``wait_ms_sum`` stats of the program's
``tpu_bfs.serve.dispatch`` spans that start inside it over the sum of
their ``n`` (``benchmark/program_trace.py``); nothing where the program
records no such span."""

from benchmark.program_trace import of

SPAN = "tpu_bfs.serve.dispatch"


def read(ctx):
    pt = of(ctx)
    if pt is None:
        return None
    spans = [sp for sp in pt.in_window(SPAN)
             if "n" in sp.stats and "wait_ms_sum" in sp.stats]
    n = sum(sp.stats["n"] for sp in spans)
    if not n:
        return None
    return sum(sp.stats["wait_ms_sum"] for sp in spans) / n
