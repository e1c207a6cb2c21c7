"""Pallas TPU kernel: fused gated bucketed-ELL expansion (the pull tier).

Every packed engine's hot loop is the bucketed-ELL pull expansion
(_packed_common.make_fori_expand): per bucket, a fori loop of chained
row gathers OR-accumulated (or min-plus for SSSP) into an [n, w] table.
XLA materializes that accumulator in HBM on every fori step — k HBM
round-trips of the full bucket output per level. This kernel is the
ROADMAP item 3 answer (BLEST's recast-the-inner-loop argument, arXiv
2512.21967): one grid step per 128-row output tile that

- applies the PR 1 settled-mask gate INSIDE the kernel: a prefetched
  per-tile need word skips the whole tile's index-slab DMA and row
  gathers, writing the combine identity instead (bit-identical — a
  settled row's claim is empty on every active lane);
- double-buffers the per-slot row-gather DMAs (slab kk+1's HBM reads
  start before slab kk's combine), so gather latency hides behind the
  VPU combine;
- keeps the accumulator resident in VMEM across all k bucket slots and
  writes each row tile's words to HBM exactly once per level instead of
  once per fori step.

The index tables are the gate tier's sentinel-padded whole-block tables
(graph/ell.pad_gate_blocks, [k, nb*128]): the sentinel gathers the
engine's identity row (all-zero for BFS, all-INF for SSSP), so padding
is absorbed by the combine exactly as in the XLA path.

Combine ops (the make_fori_expand combine/identity contract, symbolic
because a kernel cannot close over a jnp callable):

- ``or``       bitwise OR over uint32, identity 0 (BFS frontiers)
- ``min``      minimum over uint32, identity 0xFFFFFFFF (parent keys)
- ``minplus``  min(acc, dist + weight) over int32, identity INF_W
               (SSSP; takes a weight table slot-for-slot with the
               indices, pad slots weight 0 — the sentinel row is INF)

Works under ``interpret=True`` on CPU (the tier-1 and fuzz proof path);
on a real TPU the frontier width must be a multiple of 128 words
(Mosaic's DMA minor-dim tiling — same constraint as ops/tile_spmm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # output rows per grid step == the pull gate's GATE_TILE

#: SSSP "unreached" identity (workloads/sssp.INF_W asserts equality):
#: sums the kernel forms stay < 2**30, far from int32 overflow.
MINPLUS_IDENT = 1 << 29

#: op name -> (identity, table dtype)
KERNEL_OPS = {
    "or": (0, jnp.uint32),
    "min": (0xFFFFFFFF, jnp.uint32),
    "minplus": (MINPLUS_IDENT, jnp.int32),
}


class KernelWidthError(ValueError):
    """A Pallas kernel was asked for a frontier width its DMA tiling
    cannot express on real hardware (legal widths named in the message)."""


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: an explicit choice wins; otherwise True on
    the ``cpu`` platform (the tests' emulation path), False on ``tpu``,
    and an error on any other platform — no silent fallback that would
    run the whole stack through the interpreter and hide the device."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted); "
        f"the default backend is {platform!r}"
    )


def validate_kernel_width(w: int, interpret: bool, *, kernel: str) -> None:
    """Call-boundary width check shared by the Pallas kernels: any
    ``w >= 1`` under ``interpret=True`` (the CPU test path); on a real
    TPU, Mosaic requires every DMA'd frontier slab's minor dimension to
    be 128-aligned, so legal widths are exactly the multiples of 128
    words (4096-lane steps). Fails here with the legal widths named
    instead of deep inside Mosaic lowering."""
    if not isinstance(w, (int, np.integer)) or w < 1:
        raise KernelWidthError(
            f"{kernel}: width must be a positive word count, got {w!r}"
        )
    if not interpret and w % TILE:
        raise KernelWidthError(
            f"{kernel}: w={w} words is not DMA-tileable on TPU — legal "
            f"widths are multiples of {TILE} words ({TILE * 32}-lane "
            f"steps); any width works under interpret=True"
        )


def _ell_expand_kernel(*refs, k: int, w: int, op: str, has_wt: bool):
    """One grid step = one 128-row output tile of one bucket.

    Refs (has_wt inserts wt_ref/wt_buf): need_ref [nb] i32 scalar
    prefetch; gt_ref [k, nb*TILE] i32 and fw_ref [rows, 1, w] stay in HBM;
    out_ref is the [TILE, w] VMEM block; scratch = idx_buf SMEM [k,
    TILE] (slab of row ids — DMA start offsets must be scalar reads),
    (wt_buf VMEM [k, TILE],) row_buf VMEM [2, TILE, 1, w] (double-buffered
    gather landing zone), sems DMA[4] (0 idx slab, 1 wt slab, 2/3 the
    two row slots — each row slot streams TILE same-size copies through
    one semaphore and waits them in issue order)."""
    if has_wt:
        (need_ref, gt_ref, wt_ref, fw_ref, out_ref,
         idx_buf, wt_buf, row_buf, sems) = refs
    else:
        (need_ref, gt_ref, fw_ref, out_ref, idx_buf, row_buf, sems) = refs
        wt_ref = wt_buf = None
    j = pl.program_id(0)
    ident_val, _ = KERNEL_OPS[op]
    dt = out_ref.dtype
    ident = jnp.full((TILE, w), ident_val, dt)

    # Gated-out tile: the identity write is the whole cost — no index
    # DMA, no gathers, no combine (the in-kernel form of the PR 1 skip).
    @pl.when(need_ref[j] == 0)
    def _():
        out_ref[:] = ident

    @pl.when(need_ref[j] != 0)
    def _():
        idx_cp = pltpu.make_async_copy(
            gt_ref.at[:, pl.ds(j * TILE, TILE)], idx_buf, sems.at[0]
        )
        idx_cp.start()
        if has_wt:
            wt_cp = pltpu.make_async_copy(
                wt_ref.at[:, pl.ds(j * TILE, TILE)], wt_buf, sems.at[1]
            )
            wt_cp.start()
            wt_cp.wait()
        idx_cp.wait()

        def row_cp(kk, r, slot):
            # One gathered frontier row: fw[gt[kk, j*TILE + r]] -> the
            # landing slot. Same descriptor rebuilt for start and wait.
            # Rows are sliced along the untiled leading dim of the
            # [rows, 1, w] view: Mosaic refuses a one-row slice of the
            # sublane-tiled dim of a 2D [rows, w] ref.
            return pltpu.make_async_copy(
                fw_ref.at[pl.ds(idx_buf[kk, r], 1)],
                row_buf.at[slot, pl.ds(r, 1)],
                sems.at[2 + slot],
            )

        def start_slab(kk):
            slot = kk % 2

            def sbody(r, carry):
                row_cp(kk, r, slot).start()
                return carry

            jax.lax.fori_loop(0, TILE, sbody, 0)

        def wait_slab(kk):
            slot = kk % 2

            def wbody(r, carry):
                row_cp(kk, r, slot).wait()
                return carry

            jax.lax.fori_loop(0, TILE, wbody, 0)

        out_ref[:] = ident
        start_slab(0)
        # k is static (the bucket's ELL width): unrolling keeps every
        # slot id and weight-column slice static for Mosaic.
        for kk in range(k):
            if kk + 1 < k:
                start_slab(kk + 1)  # hide slab kk+1's gathers behind kk
            wait_slab(kk)
            rows = row_buf[kk % 2].reshape(TILE, w)
            if op == "or":
                out_ref[:] = out_ref[:] | rows
            elif op == "min":
                # Select, not jnp.minimum: Mosaic has no unsigned min
                # (arith.minui) on TPU, but it does have unsigned compare.
                acc = out_ref[:]
                out_ref[:] = jnp.where(rows < acc, rows, acc)
            else:  # minplus: per-output-row weight add, then min
                wcol = wt_buf[kk, :].reshape(TILE, 1)
                out_ref[:] = jnp.minimum(out_ref[:], rows + wcol)


@functools.partial(jax.jit, static_argnames=("w", "op", "interpret"))
def ell_expand(need_blk, gt, fw, wt=None, *, w: int, op: str = "or",
               interpret: bool = False):
    """Gated gather-combine over one bucket's padded ELL table.

    ``gt`` [k, nb*TILE] int32 (pad_gate_blocks layout, sentinel pads),
    ``fw`` [rows, w] (uint32 for or/min, int32 for minplus), ``need_blk``
    [nb] int32 per-output-tile gate (nonzero = compute; pass all-ones
    for an ungated pass), ``wt`` [k, nb*TILE] int32 per-slot weights
    (minplus only). Returns [nb*TILE, w]: row r is
    ``combine_kk fw[gt[kk, r]]`` (+ wt for minplus) where need_blk
    allows, else the op identity."""
    if op not in KERNEL_OPS:
        raise ValueError(f"op must be one of {sorted(KERNEL_OPS)}, got {op!r}")
    validate_kernel_width(w, interpret, kernel="ell_expand")
    k, ncols = gt.shape
    if ncols % TILE:
        raise ValueError(
            f"gt minor dim {ncols} is not a multiple of {TILE} "
            "(use graph/ell.pad_gate_blocks)"
        )
    nb = ncols // TILE
    has_wt = wt is not None
    if (op == "minplus") != has_wt:
        raise ValueError("minplus requires wt; or/min take none")
    _, dt = KERNEL_OPS[op]
    if fw.shape[1] != w or fw.dtype != dt:
        raise ValueError(
            f"fw must be [rows, {w}] {np.dtype(dt).name}, got "
            f"{fw.shape} {fw.dtype}"
        )
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (2 + has_wt)
    scratch = [pltpu.SMEM((k, TILE), jnp.int32)]
    if has_wt:
        scratch.append(pltpu.VMEM((k, TILE), jnp.int32))
    scratch += [
        pltpu.VMEM((2, TILE, 1, w), dt),
        pltpu.SemaphoreType.DMA((4,)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (TILE, w), lambda j, *_: (j, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=scratch,
    )
    fw = fw.reshape(fw.shape[0], 1, w)  # row DMAs slice the leading dim
    args = (need_blk, gt, wt, fw) if has_wt else (need_blk, gt, fw)
    return pl.pallas_call(
        functools.partial(
            _ell_expand_kernel, k=k, w=w, op=op, has_wt=has_wt
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * TILE, w), dt),
        interpret=interpret,
    )(*args)


def ell_expand_reference(need_blk, gt, fw, wt=None, *, w: int,
                         op: str = "or") -> np.ndarray:
    """NumPy oracle for :func:`ell_expand` (tests pin the kernel to it)."""
    need_blk = np.asarray(need_blk)
    gt = np.asarray(gt)
    fw = np.asarray(fw)
    ident_val, dt = KERNEL_OPS[op]
    dt = np.dtype(np.uint32 if dt == jnp.uint32 else np.int32)
    k, ncols = gt.shape
    nb = ncols // TILE
    out = np.full((nb * TILE, w), ident_val, dt)
    for j in range(nb):
        if not need_blk[j]:
            continue
        sl = slice(j * TILE, (j + 1) * TILE)
        acc = np.full((TILE, w), ident_val, dt)
        for kk in range(k):
            rows = fw[gt[kk, sl]]
            if op == "or":
                acc |= rows
            elif op == "min":
                acc = np.minimum(acc, rows)
            else:
                acc = np.minimum(
                    acc, rows + np.asarray(wt)[kk, sl][:, None]
                )
        out[sl] = acc
    return out


def ell_expand_hbm_bytes(k: int, n: int, w: int, *,
                         active_tiles: int | None = None,
                         weighted: bool = False) -> int:
    """Analytic HBM bytes one bucket's kernel pass must move (the
    roofline's per-kernel attribution, utils/roofline.py): per computed
    tile, the index slab ([k, TILE] i32), k*TILE gathered rows of w
    words (+ the weight slab when minplus), and ONE [TILE, w] output
    write — the VMEM-resident bound the kernel is built to meet (the
    XLA fori form writes the accumulator back per slot, k times).
    Gated-out tiles pay only their identity output write."""
    nb = -(-n // TILE)
    at = nb if active_tiles is None else min(active_tiles, nb)
    per_tile = k * TILE * 4 + k * TILE * w * 4 + TILE * w * 4
    if weighted:
        per_tile += k * TILE * 4
    return at * per_tile + (nb - at) * TILE * w * 4
