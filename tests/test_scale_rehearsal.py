"""Scale rehearsal: the distributed hybrid build path at RMAT scale 18.

The scale-26 plan (BASELINE.json) rests on build_dist_hybrid's host-side
work scaling sanely — round 2 saw the single-chip engine build creep from
36 s to 49-58 s at scale 21, so surprises hide here. This runs the real
path (generate -> build_dist_hybrid -> 8-device sharded engine -> short
traversal -> oracle validation) in a fresh subprocess and asserts measured
wall-time and peak-RSS bounds: scale 18 measures ~2 s build / ~3.4 GiB
peak on this class of host, so the bounds below are ~10-30x headroom —
loose enough for CI contention, tight enough that the regression class
VERDICT r2 #6 worries about (superlinear build blowup) still trips them.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json, resource, time
from tpu_bfs.utils.virtual_mesh import ensure_virtual_devices
ensure_virtual_devices(8)
import numpy as np
from tpu_bfs.graph.generate import rmat_graph

t0 = time.perf_counter()
g = rmat_graph(18, 16, seed=1)
t_gen = time.perf_counter() - t0

from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

t0 = time.perf_counter()
eng = DistHybridMsBfsEngine(g, make_mesh(8))
t_build = time.perf_counter() - t0

hub = int(np.argmax(g.degrees))
t0 = time.perf_counter()
res = eng.run(np.asarray([hub, 1234]))
t_run = time.perf_counter() - t0

from tpu_bfs.reference import bfs_scipy
np.testing.assert_array_equal(res.distances_int32(0), bfs_scipy(g, hub))

print(json.dumps({
    "t_gen": t_gen,
    "t_build": t_build,
    "t_run": t_run,
    "peak_rss_gib": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20,
    "reached_hub": int(res.reached[0]),
    "num_vertices": g.num_vertices,
}))
"""


@pytest.mark.slow
def test_dist_hybrid_build_scale18_bounds():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    stats = json.loads(out.stdout.strip().splitlines()[-1])

    # Host-side engine build: measured ~2 s; 60 s is ~30x headroom, yet a
    # superlinear blowup (the failure mode this rehearses) blows past it.
    assert stats["t_build"] < 60.0, stats
    # Whole-subprocess peak RSS: measured ~3.4 GiB (graph + shards +
    # 8 virtual-device traversal state + XLA compile arena).
    assert stats["peak_rss_gib"] < 10.0, stats
    # The traversal actually traversed: the hub reaches most of the graph.
    assert stats["reached_hub"] > stats["num_vertices"] // 2, stats


# --- sliced arm (VERDICT r3 #5): the scale-26 budget table's binding
# numbers, cross-checked by an executed build instead of arithmetic. ---

_SLICED_SCRIPT = r"""
import json, resource, time
from tpu_bfs.utils.virtual_mesh import ensure_virtual_devices
ensure_virtual_devices(8)
import jax
import jax.numpy as jnp
import numpy as np
from tpu_bfs.graph.generate import rmat_graph
from tpu_bfs.parallel.dist_bfs import make_mesh
from tpu_bfs.parallel.dist_msbfs_hybrid import DistHybridMsBfsEngine

P = 8
t0 = time.perf_counter()
g = rmat_graph(19, 16, seed=1)
t_gen = time.perf_counter() - t0
mesh = make_mesh(P)


def per_device_bytes(arrs):
    tot = {}
    for a in jax.tree_util.tree_leaves(arrs):
        if not hasattr(a, "addressable_shards"):
            continue
        for sh in a.addressable_shards:
            tot[str(sh.device)] = tot.get(str(sh.device), 0) + sh.data.nbytes
    return sorted(tot.values())


def compiled_temp_bytes(eng):
    fw0 = eng._seed_dev(np.asarray([0, 5]))
    c = eng._dist_core.lower(eng.arrs, fw0, jnp.int32(32)).compile()
    return int(c.memory_analysis().temp_size_in_bytes)

# Gather layout first (for the transient comparison), then dropped.
gather = DistHybridMsBfsEngine(g, mesh, exchange="dense")
temp_gather = compiled_temp_bytes(gather)
del gather

t0 = time.perf_counter()
eng = DistHybridMsBfsEngine(g, mesh, exchange="sliced")
t_build = time.perf_counter() - t0
temp_sliced = compiled_temp_bytes(eng)

hub = int(np.argmax(g.degrees))
t0 = time.perf_counter()
res = eng.run(np.asarray([hub, 1234]))
t_run = time.perf_counter() - t0
from tpu_bfs.reference import bfs_scipy
np.testing.assert_array_equal(res.distances_int32(0), bfs_scipy(g, hub))

rows_loc = eng._gather_rows_loc  # the engine's own layout, one source of truth
state_pd = per_device_bytes((res._planes, res._vis, res._src_bits))
struct_pd = per_device_bytes(eng.arrs)
struct_host = sum(
    a.nbytes for a in jax.tree_util.tree_leaves(eng.arrs)
)

print(json.dumps({
    "t_gen": t_gen,
    "t_build": t_build,
    "t_run": t_run,
    "peak_rss_gib": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20,
    "reached_hub": int(res.reached[0]),
    "num_vertices": g.num_vertices,
    "state_per_dev": state_pd,
    "modeled_state_per_dev": (eng.num_planes + 2) * rows_loc * eng.w * 4,
    "struct_per_dev": struct_pd,
    "struct_total": struct_host,
    "temp_sliced": temp_sliced,
    "temp_gather": temp_gather,
}))
"""


@pytest.mark.slow
def test_dist_hybrid_sliced_scale19_memory_budget():
    """Executes the sliced build at RMAT scale 19 on the 8-device mesh and
    asserts the budget table's claims against MEASURED bytes:

    - resident traversal state per chip == (planes + visited + seed) x
      [rows/P, w] u32 — the table's 'distance planes' + 'visited+frontier'
      rows, exact, and identical on every chip (round-robin balance);
    - graph structure (residual ELL + tiles + maps) per chip == total/P,
      exact on every chip — the 1/P scaling the reference forecloses by
      replicating the full graph per device (bfs.cu:346-351);
    - XLA's compiled temp allocation for the sliced level loop is well
      under the gather layout's — the O(A/P)-vs-O(A) expansion-transient
      claim, checked in the compiler's own accounting."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(
        [sys.executable, "-c", _SLICED_SCRIPT],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    stats = json.loads(out.stdout.strip().splitlines()[-1])

    # Build/host bounds: scale 19 measures ~2x the scale-18 arm; bounds
    # keep ~10-20x headroom (two engine builds share the subprocess).
    assert stats["t_build"] < 120.0, stats
    assert stats["peak_rss_gib"] < 16.0, stats
    assert stats["reached_hub"] > stats["num_vertices"] // 2, stats

    # Budget-table formula vs measured device bytes: exact and balanced.
    assert len(stats["state_per_dev"]) == 8, stats
    assert all(
        b == stats["modeled_state_per_dev"] for b in stats["state_per_dev"]
    ), stats
    assert len(stats["struct_per_dev"]) == 8, stats
    assert all(
        b == stats["struct_total"] // 8 for b in stats["struct_per_dev"]
    ), stats

    # The sliced layout's reason to exist: the compiled level loop's temp
    # allocation (all 8 virtual chips in one module) is well under the
    # gather layout's on the same graph.
    assert stats["temp_sliced"] < 0.7 * stats["temp_gather"], stats
