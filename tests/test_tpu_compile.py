"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

Interpret mode on the CPU cannot see what Mosaic refuses (unaligned
slices, ops without a TPU lowering, scratch that does not fit). These
compile the kernels at their real widths for a v5e chip that is
described, not attached — about two seconds each, no chip time:

- ``tile_spmm`` at w=256 (8192 lanes) with RMAT scale-21 operand shapes:
  16,384 row tiles, ~98k dense tiles;
- ``ell_expand`` at w=256 for ``or``/``min``/``minplus`` with k at the
  hybrid engine's bucket cap (64).

Nothing runs, so nothing here is a result or a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_bfs.ops.ell_expand import TILE, ell_expand
from tpu_bfs.ops.tile_spmm import AW, tile_spmm

W = 256  # 8192 lanes
KCAP = 64  # HybridMsBfsEngine's default bucket cap


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_tile_spmm_w256_scale21(one_chip):
    nr, nt = 16384, 98304

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(
        lambda rs, ct, a, fw: tile_spmm(rs, ct, a, fw, num_row_tiles=nr, w=W),
        S((nr + 1,), jnp.int32), S((nt,), jnp.int32),
        S((nt, AW, TILE), jnp.uint32), S((nr * TILE, W), jnp.uint32),
    )


@pytest.mark.parametrize("op", ["or", "min", "minplus"])
def test_ell_expand_w256_kcap(one_chip, op):
    nb, rows = 2048, 1 << 21
    dt = jnp.int32 if op == "minplus" else jnp.uint32

    def S(shape, d):
        return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)

    args = [S((nb,), jnp.int32), S((KCAP, nb * TILE), jnp.int32),
            S((rows, W), dt)]
    if op == "minplus":
        args.append(S((KCAP, nb * TILE), jnp.int32))

        def fn(need, gt, fw, wt):
            return ell_expand(need, gt, fw, wt, w=W, op=op)
    else:
        def fn(need, gt, fw):
            return ell_expand(need, gt, fw, w=W, op=op)
    _compile(fn, *args)
