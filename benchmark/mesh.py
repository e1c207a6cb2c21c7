"""The sharded level loop on each chip of a mesh, and the least time of its
frontier exchange.

The program's 1D-sharded engine runs one program per chip (``jit(core)``,
a ``shard_map`` of each chip's part) and names its phases as the
single-chip cores do, with two of its own: ``level.exchange`` (the
frontier's all-gather between chips and its reorder) and ``level.sync``
(the termination sum over chips and the loop's count). A chip that
finishes its part of a level early waits inside the next collective, so
the slowest chip sets the pace and a chip's time outside those two
phases is its own work.

Per chip, per level-loop pass: the self time of the core's ops on that
chip's device plane, by innermost scope (``benchmark/program_trace.py``),
over the passes the window's batches ran.

The exchange's least time: the bytes each chip receives in the window's
frontier exchanges over the chip's published inter-chip bandwidth. The
bytes are the ``exchange_bytes`` stats of the program's
``tpu_bfs.engine.fetch`` spans that start in the window, one a batch,
which the program computes from static shapes: the dense exchange
gathers the whole [rows, w] u32 frontier table every pass, so each chip
receives (P - 1) / P x rows x w x 4 bytes a pass. Bandwidth, keyed by ``jax.Device.device_kind``: Google Cloud
documentation, "TPU v5e": 1,600 Gbps of inter-chip interconnect (ICI)
bandwidth per chip. A device that is not in the table is an error.
"""

from __future__ import annotations

from benchmark.program_trace import of, scope_of

EXCHANGE = "level.exchange"
SYNC = "level.sync"
FETCH_SPAN = "tpu_bfs.engine.fetch"

ICI_GBPS = {"TPU v5 lite": 1600.0}  # gigabits per second per chip
ICI_SOURCE = 'Google Cloud documentation, "TPU v5e"'


def ici_bytes_per_s(device_kind: str) -> float:
    try:
        return ICI_GBPS[device_kind] * 1e9 / 8
    except KeyError:
        raise ValueError(f"no published inter-chip bandwidth for device "
                         f"kind {device_kind!r} (known: {sorted(ICI_GBPS)})"
                         ) from None


def per_chip(ctx) -> list | None:
    """[{scope: device seconds}] of the sharded core, one dict a chip, from
    the run's trace; None without a trace, on one chip, or where no core
    op names the exchange (a program without the names)."""
    pt = of(ctx)
    if pt is None:
        return None
    chips: dict = {}
    for op, s in pt.self_times(core_only=True):
        by_scope = chips.setdefault(op.plane, {})
        k = scope_of(op.tf_op)
        by_scope[k] = by_scope.get(k, 0.0) + s
    if len(chips) < 2 or not any(EXCHANGE in c for c in chips.values()):
        return None
    return [chips[p] for p in sorted(chips)]


def passes(ctx) -> int | None:
    return ctx.counters.get("levels_run") if ctx.counters else None


def scope_ms(ctx, scope: str) -> float | None:
    """Device milliseconds a level-loop pass of the sharded core's ops
    whose innermost scope is ``scope``, the mean over chips; None where
    the trace holds no sharded core or the run counted no pass."""
    chips, n = per_chip(ctx), passes(ctx)
    if chips is None or not n:
        return None
    return 1e3 * sum(c.get(scope, 0.0) for c in chips) / len(chips) / n


def window_exchange_bytes(ctx) -> float | None:
    """The ``exchange_bytes`` stats of the fetch spans that start in the
    traced window, summed; None where none carries the stat."""
    pt = of(ctx)
    if pt is None:
        return None
    stats = [sp.stats["exchange_bytes"] for sp in pt.in_window(FETCH_SPAN)
             if "exchange_bytes" in sp.stats]
    return float(sum(stats)) if stats else None
