"""serve_narrow_share: share of the device's busy time in the traced
window, in percent, spent in the narrow rung's core (the ops inside the
program's ``lanes64`` scope: the configuration's ladder is 64/256), over
the self time of every device op in the window
(``benchmark/program_trace.py``); nothing where no op carries a
``lanes<W>`` scope."""

from benchmark.program_trace import of

NARROW_LANES = 64


def read(ctx):
    pt = of(ctx)
    if pt is None:
        return None
    by_lanes = pt.lanes_s()
    busy = sum(by_lanes.values())
    if busy <= 0 or set(by_lanes) <= {None}:
        return None
    return 100.0 * by_lanes.get(NARROW_LANES, 0.0) / busy
