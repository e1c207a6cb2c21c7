"""mesh_level_ms: device milliseconds of the sharded core per level-loop
pass on its busiest chip (the slowest chip sets the pace): each chip's
self time of the ``jit(core)`` ops on its own device plane, the largest
over the passes the window's batches ran (``benchmark/mesh.py``); nothing
where the program names no exchange."""

from benchmark.mesh import passes, per_chip


def read(ctx):
    chips, n = per_chip(ctx), passes(ctx)
    if chips is None or not n:
        return None
    return 1e3 * max(sum(c.values()) for c in chips) / n
