"""Row-width microbenchmark: is the packed-row gather still latency-bound
past 128 words?

The chained random row-gather + OR (the packed engines' level-loop inner
op) is latency-dominated: the round-4 floor-corrected sweep on v5e
measured 8.41 / 8.24 ns per index at 64- / 128-word rows (flat), and the
earlier biased sweep's 256/512-word points (19.7 / 26.8 ns, carrying a
~+4 ns fence-epilogue bias at reps=3) still showed widening past 128
words costs far less than the lane doubling buys. That slope is why the
engines default to 8192 lanes (w=256) — the end-to-end ground truth is
55.96 vs 45.68 GTEPS on the scale-21 flagship. This probe re-measures
the whole sweep (w in 64..512) with the fence-corrected, floor-subtracted
protocol.

Also times the tile_spmm Pallas kernel per-tile at each legal width
(w % 128 == 0), checks a small prefix against the NumPy reference, and —
when running compiled on a TPU — additionally compares that prefix
compiled-vs-interpret (the bench's Mosaic-divergence guard, at each
probed width).

Usage (real chip): python scripts/width_probe.py
Prints one JSON line per (op, w); ~5-10 min cold, less with the shared
compile cache warm.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# `python scripts/width_probe.py` puts scripts/ (not the repo root) on
# sys.path; the tile_spmm probe imports tpu_bfs and died on that in the
# first chip-session run.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fence(out) -> float:
    """Full-completion fence (shared with the engines' run_timed): a host
    read of a scalar derived from the output — ``block_until_ready`` alone
    once returned early (a 2 GB chained gather "finished" in 36 us). The
    shared implementation warns loudly if that early return ever
    recurs."""
    from tpu_bfs.utils.timing import fence

    return fence(out, warn=True)


def probe_gather(rows: int = 1_250_000, n_idx: int = 1_000_000,
                 chain: int = 8, widths=(64, 128, 256, 512)) -> None:
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    # An INDEPENDENT random permutation per chain step: step k's rows have
    # no relation to step k-1's (a (ix + k) % rows scheme would read the
    # row adjacent to the one just fetched — prefetch/warm-granule effects
    # then bias ns/index by an amount that varies with w, exactly the
    # slope this probe exists to measure). Steps couple only through the
    # OR accumulator — the same dependence structure as the engines' own
    # fori-loop bucket expansion (_packed_common.make_fori_expand).
    idx = jnp.asarray(rng.integers(0, rows, size=(chain, n_idx), dtype=np.int32))
    for w in widths:
        table = jnp.asarray(
            rng.integers(0, 2**32, size=(rows, w), dtype=np.uint32)
        )

        @jax.jit
        def chained(t, ix):
            acc = jnp.zeros((n_idx, t.shape[1]), jnp.uint32)

            def body(k, acc):
                return acc | t[ix[k]]

            return jax.lax.fori_loop(0, chain, body, acc)

        warm = chained(table, idx)
        _fence(warm)  # compile + warm
        # The fence's fixed epilogue (one tiny dispatch + host round-trip)
        # can be the same order as a few reps of the measurement itself; measure it on the already-ready warm output
        # and subtract, and amortize the remainder over more reps — else
        # every ns/index figure carries a ~flat +epilogue/reps bias.
        floor = _fence(warm)
        del warm  # its [n_idx, w] buffer must not sit under the timed loop
        # Bound the reps by in-flight memory, not a constant: every
        # dispatched-but-unconsumed rep holds its [n_idx, w] u32 result on
        # the device next to the table, and 10 queued 1 GB outputs wedged
        # the first w=256 run on the 16 GB chip. Keep table + queued
        # outputs within ~8 GB at every width (floor of 1 rep: noisier at
        # w=512, but a wedge loses the number entirely).
        out_bytes = n_idx * w * 4
        table_bytes = rows * w * 4
        reps = max(1, min(10, int((8e9 - table_bytes) // max(out_bytes, 1))))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = chained(table, idx)
        _fence(out)  # waiting for rep N implies reps 1..N-1 (one stream)
        dt = max(time.perf_counter() - t0 - floor, 1e-9) / reps
        ns_per_index = dt / (n_idx * chain) * 1e9
        print(json.dumps({
            "op": "chained_row_gather_or", "w_words": w, "lanes": 32 * w,
            "rows": rows, "indices": n_idx * chain,
            "ns_per_index": round(ns_per_index, 2),
            "fence_floor_s": round(floor, 4),
            "effective_GBps": round(n_idx * chain * w * 4 / dt / 1e9, 1),
        }), flush=True)  # land each width's line even if a later one wedges
        del table


def probe_tile_spmm(num_row_tiles: int = 256, tiles_per_row: int = 16,
                    widths=(128, 256), interpret: bool | None = None) -> None:
    import jax.numpy as jnp

    from tpu_bfs.ops.ell_expand import resolve_interpret
    from tpu_bfs.ops.tile_spmm import (
        TILE,
        pack_a_tiles,
        tile_spmm,
        tile_spmm_reference,
    )

    rng = np.random.default_rng(2)
    nt = num_row_tiles * tiles_per_row
    a_dense = (rng.random((nt, TILE, TILE)) < 0.05).astype(np.int8)
    a_tiles = pack_a_tiles(a_dense)
    row_start = np.arange(num_row_tiles + 1, dtype=np.int32) * tiles_per_row
    col_tile = rng.integers(0, num_row_tiles, size=nt).astype(np.int32)
    interpret = resolve_interpret(interpret)
    for w in widths:
        fw = rng.integers(
            0, 2**32, size=(num_row_tiles * TILE, w), dtype=np.uint32
        )
        args = (jnp.asarray(row_start), jnp.asarray(col_tile),
                jnp.asarray(a_tiles), jnp.asarray(fw))
        kw = dict(num_row_tiles=num_row_tiles, w=w, interpret=interpret)
        warm = tile_spmm(*args, **kw)
        _fence(warm)  # compile + warm
        floor = _fence(warm)  # fixed fence epilogue, subtracted below
        t0 = time.perf_counter()
        reps = 10
        for _ in range(reps):
            out = tile_spmm(*args, **kw)
        _fence(out)
        dt = max(time.perf_counter() - t0 - floor, 1e-9) / reps
        # Small-prefix correctness: vs the NumPy reference always, and vs
        # interpret mode too when the timed run was compiled (TPU).
        small = 4
        ns = int(row_start[small])
        small_args = (args[0][: small + 1], args[1][:ns], args[2][:ns],
                      args[3])
        ref = tile_spmm_reference(
            row_start[: small + 1], col_tile[:ns], a_tiles[:ns], fw,
            num_row_tiles=small, w=w,
        )
        np.testing.assert_array_equal(
            np.asarray(out)[: small * TILE], ref
        )
        if not interpret:
            out_i = tile_spmm(
                *small_args, num_row_tiles=small, w=w, interpret=True
            )
            np.testing.assert_array_equal(np.asarray(out_i), ref)
        print(json.dumps({
            "op": "tile_spmm", "w_words": w, "lanes": 32 * w,
            "tiles": nt, "us_per_tile": round(dt / nt * 1e6, 3),
            "checked_vs_reference_tiles": ns,
            "compiled_vs_interpret": not interpret,
        }), flush=True)


if __name__ == "__main__":
    import jax

    from tpu_bfs.utils.compile_cache import enable_compile_cache

    # Same persistent compile cache as bench.py (shared helper): each
    # probe attempt otherwise re-pays ~30-40 s of XLA compile per width —
    # chip-window wall-clock an outage-recovery session cannot spare.
    enable_compile_cache(
        log=lambda m: print(f"# {m}", file=sys.stderr, flush=True)
    )

    print(json.dumps({"backend": jax.default_backend(),
                      "devices": len(jax.devices())}), flush=True)
    probe_gather()
    probe_tile_spmm()
    # Completion marker as the LAST line: chip_session's idempotent
    # restart gate (scripts/has_value.py) must distinguish a finished
    # sweep from a partial one killed mid-probe.
    print(json.dumps({"width_probe_complete": True, "value": 1}), flush=True)
