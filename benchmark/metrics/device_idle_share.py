"""Share of the traced window, in percent, in which the device ran no
operation: 1 - (union of ``XLA Ops`` intervals) / window, averaged over
the chips used (``benchmark/trace_reduce.py``)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
