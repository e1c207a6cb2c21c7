"""mesh_imbalance: how much longer the busiest chip works than the mean
chip, in percent: 100 x (largest / mean - 1) of each chip's self time in
the sharded core outside ``level.exchange`` and ``level.sync``, where a
chip waits for the others (``benchmark/mesh.py``). It shows whether the
round-robin row-tile ownership and the bucket shapes padded to a common
size keep the chips even. Nothing where the program names no exchange."""

from benchmark.mesh import EXCHANGE, SYNC, per_chip


def read(ctx):
    chips = per_chip(ctx)
    if chips is None:
        return None
    work = [sum(s for k, s in c.items() if k not in (EXCHANGE, SYNC))
            for c in chips]
    mean = sum(work) / len(work)
    if mean <= 0:
        return None
    return 100.0 * (max(work) / mean - 1.0)
