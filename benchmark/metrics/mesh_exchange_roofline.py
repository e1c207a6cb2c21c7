"""mesh_exchange_roofline: the least time of the traced window's frontier
exchanges over their measured time, in percent. Least time: the bytes
each chip received (the ``exchange_bytes`` stats of the window's fetch
spans) over the chip's published inter-chip bandwidth; measured: the
self time of the ``level.exchange`` ops, the mean over chips
(``benchmark/mesh.py``). Nothing where the program names no exchange or
records no bytes."""

from benchmark.mesh import EXCHANGE, ici_bytes_per_s, per_chip, \
    window_exchange_bytes


def read(ctx):
    chips, nbytes = per_chip(ctx), window_exchange_bytes(ctx)
    if chips is None or not nbytes:
        return None
    spent = sum(c.get(EXCHANGE, 0.0) for c in chips) / len(chips)
    if spent <= 0:
        return None
    return 100.0 * nbytes / ici_bytes_per_s(ctx.device_kind) / spent
