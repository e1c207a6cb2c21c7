"""tile_spmm_roofline: the least time of the ``tile_spmm`` calls in the
window (``benchmark/roofline.py``: bytes over the HBM peak or MXU int8
operations over its peak, whichever is larger) over their summed device
time, in percent. Kernel events are the ``XLA Ops`` events of the
custom call the kernel's name gives (HLO text ``%tile_spmm[.N] = ...``);
ops that only take its output as an operand do not count."""

import re

from benchmark.roofline import tile_spmm_least_s

KERNEL = re.compile(r"^%tile_spmm(\.\d+)? = ")


def read(ctx):
    shape = ctx.counters.get("tile_spmm_shape")
    if ctx.trace is None or shape is None:
        return None
    names = [k for k in ctx.trace.op_s if KERNEL.match(k)]
    calls = sum(ctx.trace.op_count[k] for k in names)
    spent = sum(ctx.trace.op_s[k] for k in names)
    if not calls or spent <= 0:
        return None
    least, _bound = tile_spmm_least_s(shape, ctx.device_kind)
    return 100.0 * calls * least / spent
