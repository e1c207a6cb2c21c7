"""Published chip peaks and the analytic model of the ``tile_spmm`` kernel.

Peaks are keyed by ``jax.Device.device_kind``. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip. A device that is not in the table is an error.

``tile_spmm`` (the program's ``ops/tile_spmm.py``) expands the frontier
through the dense 128x128 adjacency tiles on the MXU. One call:

- bytes: every bit-packed A tile read once, one 128-row frontier slab
  read per dense tile, and the [row tiles * 128, w] u32 hit table written
  once (the model of the program's ``utils/roofline.phase_bytes``,
  ``dense`` term, copied here);
- operations: an int8 [128 x 128] @ [128 x 32w] multiply-accumulate per
  dense tile, 2 operations per multiply-add, on the int8 MXU peak.

The least time of a call is the larger of bytes over the HBM peak and
operations over the int8 peak; the bound that gives it is named.
"""

from __future__ import annotations

TILE = 128

PEAKS = {
    "TPU v5 lite": {"hbm_gbs": 819.0, "bf16_tflops": 197.0,
                    "int8_tops": 393.0},
}
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'


def device_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def tile_spmm_bytes(*, num_tiles: int, num_row_tiles: int, w: int,
                    a_tile_bytes: int) -> int:
    """HBM bytes of one call over ``num_tiles`` dense tiles."""
    table = num_row_tiles * TILE * w * 4
    return a_tile_bytes + num_tiles * TILE * w * 4 + table


def tile_spmm_ops(*, num_tiles: int, w: int, **_) -> int:
    """MXU operations of one call: 2 * 128 * 128 * 32w per dense tile."""
    return 2 * num_tiles * TILE * TILE * 32 * w


def tile_spmm_least_s(shape: dict, device_kind: str) -> tuple[float, str]:
    """(least seconds of one call, 'hbm' or 'mxu_int8')."""
    peaks = device_peaks(device_kind)
    t_bytes = tile_spmm_bytes(**shape) / (peaks["hbm_gbs"] * 1e9)
    t_ops = tile_spmm_ops(**shape) / (peaks["int8_tops"] * 1e12)
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "mxu_int8")
