"""utils/timing.py: the completion fence and run_timed's floor subtraction.

The fence exists because ``jax.block_until_ready`` once returned early
(round 4: a 2 GB gather chain "finished" in 36 µs); these tests pin the structural contract on any backend — leaf selection
over arbitrary pytrees, per-shard reads, and the epilogue subtraction.
"""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_bfs.utils.timing import fence, run_timed


def test_fence_handles_arbitrary_pytrees():
    # Non-array leaves, empty arrays, and empty trees must not crash the
    # fence (run_timed wraps engine outputs of many shapes).
    assert fence(()) >= 0.0
    assert fence(None) >= 0.0
    assert fence((5, "x", jnp.float32(2.0))) >= 0.0  # scalar jax leaf
    assert fence((np.zeros(0), jnp.arange(3))) >= 0.0  # empty first leaf
    assert fence({"a": jnp.ones((2, 2)), "b": 1}) >= 0.0


def test_fence_reads_every_shard_of_sharded_output():
    # Sharded outputs fence one element per addressable shard — element 0
    # alone only forces the device owning it (review finding, round 4).
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.make_mesh((len(jax.devices()),), ("v",))
    x = jax.device_put(
        jnp.arange(len(jax.devices()) * 4.0),
        NamedSharding(mesh, PartitionSpec("v")),
    )
    y = jax.jit(lambda a: a + 1, out_shardings=NamedSharding(
        mesh, PartitionSpec("v")))(x)
    assert len(y.addressable_shards) == len(jax.devices())
    assert fence(y) >= 0.0


def test_run_timed_subtracts_fence_epilogue():
    # elapsed excludes the fence's fixed epilogue (measured by a second
    # fence on the ready output) and is clamped to a positive epsilon —
    # downstream TEPS math divides by it.
    out, dt = run_timed(lambda: jnp.ones((64, 64)) * 2, warm=True)
    assert float(out[0, 0]) == 2.0
    assert dt > 0.0
    # A no-op-sized computation must not produce a zero or negative time.
    _, dt2 = run_timed(lambda: jnp.float32(1.0), warm=True)
    assert dt2 > 0.0
