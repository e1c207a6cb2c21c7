"""Bit-packed wire format primitives (ISSUE 5): pack_bits/unpack_bits
round-trip properties — including lengths not divisible by 32, where the
tail word's padding bits must be ZERO so cross-chip word OR combines
exactly as the bools would — and packed-vs-unpacked bit-identity of the
whole ``reduce_scatter_or`` exchange on random masks for p in {1, 2, 4}.

These are the unit-level guarantees under the compiled-HLO byte proof in
tests/test_wirecheck.py::test_packed_exchange_proof: the wirecheck pins
what the packed program MOVES, these pin what it MEANS.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpu_bfs.parallel.collectives import (
    default_sparse_caps,
    pack_bits,
    packed_words,
    reduce_scatter_or,
    sparse_exchange_or,
    unpack_bits,
)
from jax import shard_map
from tpu_bfs.parallel.dist_bfs import make_mesh

# Lengths straddling word boundaries: 1 (single bit), 31/33 (one off a
# boundary), 32/64 (exact), 50/100 (mid-word tails), 1024 (the aligned
# vloc the engines actually ship).
LENGTHS = (1, 31, 32, 33, 50, 64, 100, 1024)


@pytest.mark.parametrize("n", LENGTHS)
def test_pack_roundtrip(n):
    rng = np.random.default_rng(n)
    for density in (0.0, 0.1, 0.5, 1.0):
        m = rng.random(n) < density
        w = np.asarray(pack_bits(jnp.asarray(m)))
        assert w.shape == (packed_words(n),)
        assert w.dtype == np.uint32
        np.testing.assert_array_equal(
            np.asarray(unpack_bits(jnp.asarray(w), n)), m
        )


def test_pack_roundtrip_batched_axes():
    # Only the LAST axis packs; leading axes (lanes, destination chunks)
    # pass through — the [p, n] per-chunk layout the exchange uses.
    rng = np.random.default_rng(5)
    m = rng.random((3, 4, 50)) < 0.4
    w = np.asarray(pack_bits(jnp.asarray(m)))
    assert w.shape == (3, 4, packed_words(50))
    np.testing.assert_array_equal(np.asarray(unpack_bits(jnp.asarray(w), 50)), m)


def test_pack_bit_layout():
    # Vertex 32*j + i lands in bit i of word j — the layout the docstring
    # promises, pinned so a refactor cannot silently flip endianness and
    # still pass the round-trip tests.
    n = 70
    for v in (0, 1, 31, 32, 63, 69):
        m = np.zeros(n, bool)
        m[v] = True
        w = np.asarray(pack_bits(jnp.asarray(m)))
        assert w[v // 32] == np.uint32(1) << (v % 32)
        assert (np.delete(w, v // 32) == 0).all()


@pytest.mark.parametrize("n", [31, 33, 50, 100])
def test_tail_padding_is_zero(n):
    """The tail word's padding bits must be 0 — the OR identity — even for
    the all-ones mask: packed buffers from different chips then combine
    with word OR exactly as the bools would (no tail mask on unpack)."""
    w = np.asarray(pack_bits(jnp.ones(n, bool)))
    tail_bits = n % 32
    assert w[-1] == (np.uint32(1) << tail_bits) - 1  # high bits clear
    assert (w[:-1] == np.uint32(0xFFFFFFFF)).all()
    # And word OR == mask OR through a full pack/combine/unpack cycle.
    rng = np.random.default_rng(n)
    a, b = (rng.random(n) < 0.5 for _ in range(2))
    combined = np.asarray(
        unpack_bits(pack_bits(jnp.asarray(a)) | pack_bits(jnp.asarray(b)), n)
    )
    np.testing.assert_array_equal(combined, a | b)


import functools


@functools.lru_cache(maxsize=None)
def _exchange_fn(p, impl, wire_pack, caps):
    """One jitted exchange per config — reused across the random masks so
    the sweep pays each compile once."""
    mesh = make_mesh(p)

    def local(x):
        if caps is not None:
            hit, _ = sparse_exchange_or(
                x[0], "v", p, caps=caps, wire_pack=wire_pack
            )
            return hit
        return reduce_scatter_or(x[0], "v", p, impl=impl, wire_pack=wire_pack)

    return jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(P("v", None),), out_specs=P("v"),
            check_vma=False,
        )
    )


def _exchange(p, n, mask_pp, impl, wire_pack, caps=None):
    """Run one exchange over a p-device mesh: ``mask_pp`` is the [p, p*n]
    per-chip full-size contribution (row i = chip i's buffer), the return
    the [p*n] owner-ordered OR — what the engines' level loop sees."""
    fn = _exchange_fn(p, impl, wire_pack, caps)
    return np.asarray(fn(jnp.asarray(mask_pp)))


# n=50 keeps a live tail word in every packed chunk; n=64 is the aligned
# control. p=1 pins the degenerate no-wire case.
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("impl", ["ring", "allreduce"])
def test_packed_reduce_scatter_bit_identity(p, impl):
    rng = np.random.default_rng(p * 100 + len(impl))
    for n in (50, 64):
        for density in (0.05, 0.7):
            mask = rng.random((p, p * n)) < density
            plain = _exchange(p, n, mask, impl, wire_pack=False)
            packed = _exchange(p, n, mask, impl, wire_pack=True)
            np.testing.assert_array_equal(packed, plain)
            np.testing.assert_array_equal(plain, mask.any(axis=0))


@pytest.mark.parametrize("p", [2, 4])
def test_packed_sparse_dense_fallback_bit_identity(p):
    # Caps of 1 force the dense fallback on any non-trivial mask, so this
    # exercises sparse_exchange_or's PACKED phase-2b specifically.
    rng = np.random.default_rng(p)
    n = 50
    mask = rng.random((p, p * n)) < 0.5
    plain = _exchange(p, n, mask, "ring", wire_pack=False, caps=(1,))
    packed = _exchange(p, n, mask, "ring", wire_pack=True, caps=(1,))
    np.testing.assert_array_equal(packed, plain)
    np.testing.assert_array_equal(plain, mask.any(axis=0))


def test_default_caps_recalibrated_for_packing():
    """The cap ladder prices ids against the dense fallback it competes
    with: packed dense costs 1/8 the bytes, so the packed rungs must sit
    8x lower (ids only win below vloc/32 entries — vloc/8 packed-dense
    bytes / 4 bytes per id — and the wide rung keeps the same ~2x
    undercut of its dense cost as the unpacked ladder)."""
    vloc = 1 << 16
    plain = default_sparse_caps(vloc)
    packed = default_sparse_caps(vloc, wire_pack=True)
    assert max(packed) == max(plain) // 8
    assert max(packed) <= vloc // 32
    assert min(packed) >= 16


def test_default_caps_recalibrated_for_delta():
    """Delta-encoded ids cost min(delta_bits)/8 bytes per entry instead
    of 4, so the break-even frontier density RISES by that ratio: the
    8-bit ladder sits 4x higher than the plain-id one, and composing
    with wire_pack keeps the two recalibrations independent."""
    vloc = 1 << 16
    plain = default_sparse_caps(vloc)
    delta = default_sparse_caps(vloc, delta_bits=(8, 16))
    assert max(delta) == max(plain) * 4
    packed_delta = default_sparse_caps(vloc, wire_pack=True, delta_bits=(8, 16))
    assert max(packed_delta) == max(plain) // 2  # 1/8 dense x 4 entry


# ---- delta-encoded id chunks (ISSUE 7) ------------------------------------

from tpu_bfs.parallel.collectives import (  # noqa: E402
    delta_decode_ids,
    delta_encode_ids,
    delta_words,
    max_id_gap,
    merge_exchange_counts,
    normalize_caps,
    planned_branch_count,
    planned_branch_labels,
    planned_sparse_exchange_or,
    planned_sparse_wire_bytes_per_level,
)


@pytest.mark.parametrize("bits", [4, 8, 16])
def test_delta_codec_boundary_widths(bits):
    """Round trips at the boundary shapes the satellite names: the empty
    chunk, a single id, the max delta EXACTLY at the bit-width rung
    (2**bits - 1), a full-cap chunk, and ids landing on word boundaries
    of the packed payload."""
    n = 1 << 20  # sentinel; ids stay far below it
    top = (1 << bits) - 1
    cases = [
        [],                      # empty -> all positions decode sentinel
        [5],                     # single id, no deltas
        [0],                     # boundary id zero
        [3, 3 + top],            # max delta exactly at the rung
        list(range(17)),         # full cap at cap=17 below
        [0, top, 2 * top, 3 * top],  # repeated max gaps
        [7, 8, 8 + top],         # min gap next to max gap
    ]
    for ids in cases:
        cap = max(len(ids), 17)
        buf = np.full(cap, n, np.int32)
        buf[: len(ids)] = ids
        words = delta_encode_ids(jnp.asarray(buf)[None, :], n, bits)
        assert words.shape == (1, delta_words(cap, bits))
        dec, valid = delta_decode_ids(words, cap, bits)
        dec, valid = np.asarray(dec)[0], np.asarray(valid)[0]
        m = len(ids)
        if m:
            np.testing.assert_array_equal(dec[:m], ids)
            assert valid[:m].all() and not valid[m:].any()
            # Tail replicates the last id — harmless for OR-scatters,
            # maskable via `valid` for SET-scatters.
            assert (dec[m:] == ids[-1]).all()
        else:
            assert (dec == n).all()


def test_max_id_gap():
    rem = np.zeros((2, 300), bool)
    rem[0, [3, 10, 290]] = True  # gaps 7 and 280
    rem[1, [50]] = True          # single bit: no delta
    assert int(max_id_gap(jnp.asarray(rem))) == 280
    assert int(max_id_gap(jnp.asarray(np.zeros((2, 8), bool)))) == 0


def test_merge_counts_restart_on_branch_space_change():
    """Satellite: a checkpoint resumed under a DIFFERENT exchange config
    (caps/wire_pack/delta changed -> different branch-count length) must
    restart the count, not raise a shape error on ``counts + prev``."""
    prev = np.array([3, 1, 0])  # 4 levels under the old 3-branch layout
    counts = np.zeros(15, np.int64)
    counts[0] = 2
    out = merge_exchange_counts(prev, counts, resumed_level=4)
    np.testing.assert_array_equal(out, counts)  # restarted, no error
    # Same-shape, consistent prev still merges.
    prev_ok = np.array([4, 0, 0])
    out2 = merge_exchange_counts(prev_ok, np.array([1, 2, 0]), resumed_level=4)
    np.testing.assert_array_equal(out2, [5, 2, 0])


def test_cap_ladder_dedupe_branch_stability():
    """Satellite: duplicate caller-provided rungs dedupe everywhere —
    the ladder, the byte models, the branch space — so branch indices
    stay stable and no dead `lax.cond` branches skew the accounting."""
    from tpu_bfs.parallel.collectives import sparse_wire_bytes_per_level

    assert normalize_caps((64, 16, 16, 64)) == (16, 64)
    assert planned_branch_count((16, 16, 64), (8, 16)) == planned_branch_count(
        (16, 64), (8, 16)
    )
    from tpu_bfs.parallel.collectives import rows_gather_branch_labels

    assert rows_gather_branch_labels((16, 16), ()) == ["sparse[16]", "dense"]
    assert sparse_wire_bytes_per_level(
        4, 256, (16, 16, 64)
    ) == sparse_wire_bytes_per_level(4, 256, (16, 64))
    rng = np.random.default_rng(3)
    p, n = 2, 64
    mask = rng.random((p, p * n)) < 0.05
    plain = _exchange(p, n, mask, "ring", wire_pack=False, caps=(16, 64))
    duped = _exchange(p, n, mask, "ring", wire_pack=False, caps=(64, 16, 16))
    np.testing.assert_array_equal(plain, duped)

    # Branch INDICES stay stable after dedupe: the duped ladder selects
    # the same rung position as the clean one, not a dead duplicate.
    def branch_of(caps):
        def local(x):
            return sparse_exchange_or(x[0], "v", p, caps=caps)[1]

        return int(jax.jit(shard_map(
            local, mesh=make_mesh(p), in_specs=(P("v", None),),
            out_specs=P(), check_vma=False,
        ))(jnp.asarray(mask)))

    assert branch_of((16, 64)) == branch_of((64, 16, 16, 64)) == 0


@functools.lru_cache(maxsize=None)
def _planner_fn(p, n, caps, bits, sieve, predict):
    """One jitted planner exchange per config (big-n compile paid once):
    inputs are (mask [p, p*n], visited [p*n], visited_total, prev_biggest,
    growing), output (hit [p*n], branch, biggest)."""
    mesh = make_mesh(p)

    def local(x, vis, vt, pb, gr):
        return planned_sparse_exchange_or(
            x[0], "v", p, caps=caps, delta_bits=bits, sieve=sieve,
            visited=vis, visited_total=vt[0], predict=predict,
            prev_biggest=pb[0], growing=gr[0],
        )

    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("v", None), P("v"), P("v"), P("v"), P("v")),
        out_specs=(P("v"), P(), P()), check_vma=False,
    ))


def _run_planner(p, n, mask, vis, vt, pb=-1, growing=False,
                 caps=(4, 8), bits=(8, 16), sieve=True, predict=True):
    fn = _planner_fn(p, n, caps, bits, sieve, predict)
    h, br, bg = fn(
        jnp.asarray(mask), jnp.asarray(vis),
        jnp.full(p, vt, jnp.int32), jnp.full(p, pb, jnp.int32),
        jnp.full(p, growing, bool),
    )
    return np.asarray(h), int(br), int(bg)


@pytest.mark.slow
def test_planner_branch_selection_at_boundaries():
    """The satellite's exchange-level boundary sweep: max-delta exactly at
    each bit-width rung selects that width, one past it the next, past
    the widest plain ids; cap overflow falls back dense. Every case's hit
    is the plain OR (no sieve interference: visited_total=0).

    This and the two planner tests below share one big-n compile
    (n > 2**16 so a >16-bit gap is constructible) and are slow-marked for
    the tier-1 wall clock; `make wirecheck` runs this file WITHOUT the
    marker filter, so they stay a CI prerequisite of the smoke targets."""
    p, n = 2, 70000  # n > 2**16 so a >16-bit gap is constructible
    vis = np.zeros(p * n, bool)

    def mask_with(ids_remote):
        # Chip 0 contributes ids into chip 1's chunk (remote); chip 1 idle.
        m = np.zeros((p, p * n), bool)
        m[0, [n + i for i in ids_remote]] = True
        return m

    cases = [
        ([10, 10 + 255], 0),            # delta8[4]: gap exactly 255
        ([10, 10 + 256], 1),            # delta16[4]: one past the 8-bit rung
        ([10, 10 + 65535], 1),          # delta16[4]: gap exactly 65535
        ([10, 10 + 65536], 2),          # sparse[4]: past the widest rung
        ([7], 0),                       # single id: no delta at all
        ([0, 1, 2, 3, 4], 3),           # 5 ids: rung 8, tight deltas
        (list(range(0, 18, 2)), 6),     # 9 ids: overflows both caps -> dense
    ]
    for ids, want_branch in cases:
        m = mask_with(ids)
        h, br, _ = _run_planner(p, n, m, vis, vt=0)
        assert br == want_branch, (ids, br, want_branch)
        np.testing.assert_array_equal(h, m.any(axis=0))
    # Empty frontier: nothing on the wire, tightest rung, hit empty.
    h, br, _ = _run_planner(p, n, np.zeros((p, p * n), bool), vis, vt=0)
    assert br == 0
    assert not h.any()


@pytest.mark.slow
def test_planner_sieve_semantics():
    """Sieved levels drop already-visited ids from the wire; the result
    agrees with the plain OR exactly where the claim consumes it
    (~visited positions plus the receiver's own contribution) and never
    invents a hit."""
    p, n = 2, 70000
    rng = np.random.default_rng(11)
    vis = rng.random(p * n) < 0.95
    # A high-reuse level: ~3000 remote contributions, all but 3 already
    # visited at the receiver — pre-sieve the bucket overflows every cap
    # (and the modeled savings clear the vis transfer's cost), post-sieve
    # it collapses onto the tightest rung.
    visited_remote = np.flatnonzero(vis[n:])[:3000] + n
    fresh_remote = np.flatnonzero(~vis[n:])[:3] + n
    m = np.zeros((p, p * n), bool)
    m[0, visited_remote] = True
    m[0, fresh_remote] = True
    vt = int(vis.sum())
    h, br, _ = _run_planner(p, n, m, vis, vt=vt)
    labels = planned_branch_labels((4, 8), (8, 16))
    assert labels[br].startswith("sieved-"), (br, labels[br])
    assert labels[br] != "sieved-dense"  # the sieve reopened a sparse rung
    exp = m.any(axis=0)
    np.testing.assert_array_equal(h & ~vis, exp & ~vis)
    assert not (h & ~exp).any()  # no invented hits
    # With nothing visited the planner must NOT pay the sieve.
    h2, br2, _ = _run_planner(p, n, m, np.zeros(p * n, bool), vt=0)
    assert not labels[br2].startswith("sieved-")
    np.testing.assert_array_equal(h2, exp)


@pytest.mark.slow
def test_planner_history_prediction():
    """A confidently-dense history (previous biggest above every cap and
    a still-growing frontier) takes the dense path WITHOUT measuring —
    branch = dense-predicted — and stays bit-identical; a shrinking
    frontier exits prediction and re-measures."""
    p, n = 2, 70000
    vis = np.zeros(p * n, bool)
    rng = np.random.default_rng(13)
    m = rng.random((p, p * n)) < 0.001
    labels = planned_branch_labels((4, 8), (8, 16))
    h, br, bg = _run_planner(p, n, m, vis, vt=0, pb=10**6, growing=True)
    assert labels[br] == "dense-predicted"
    assert bg == 10**6  # the stale carry survives a predicted level
    np.testing.assert_array_equal(h, m.any(axis=0))
    # Shrinking -> re-measure: same mask lands on a measured branch.
    h2, br2, _ = _run_planner(p, n, m, vis, vt=0, pb=10**6, growing=False)
    assert labels[br2] != "dense-predicted"
    np.testing.assert_array_equal(h2, m.any(axis=0))


def test_planned_wire_model_is_cheaper_on_sparse_levels():
    """The acceptance bar's model side: at serving-scale chunks every
    delta rung undercuts the PR 5 packed-dense baseline by >= 2x, and
    the delta8 rung undercuts the plain-id rung ~4x."""
    from tpu_bfs.parallel.collectives import (
        dense_or_wire_bytes,
        sparse_wire_bytes_per_level,
    )

    p, n = 8, 1 << 20
    caps = (256, 2048)
    per = planned_sparse_wire_bytes_per_level(p, n, caps, (8, 16))
    labels = planned_branch_labels(caps, (8, 16))
    packed_dense = dense_or_wire_bytes(p, n, "ring", wire_pack=True)
    for lbl, bytes_ in zip(labels, per):
        if lbl.startswith("delta"):
            assert bytes_ * 2 <= packed_dense + 4, (lbl, bytes_, packed_dense)
        if lbl.startswith("sieved-delta"):
            # A sieved rung never costs more than its sieved-plain peer
            # (the vis transfer and scalars are shared).
            cap = lbl[lbl.index("["):]
            assert bytes_ <= per[labels.index(f"sieved-sparse{cap}")]
    plain_rung = sparse_wire_bytes_per_level(p, n, caps)[0]
    delta8_rung = per[labels.index("delta8[256]")]
    assert delta8_rung * 3 < plain_rung
