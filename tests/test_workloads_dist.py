"""Semiring exchanges (ISSUE 20): every workload kind on the full mesh.

The fuzz arm: each kind x exchange config runs on the 8-virtual-device
CPU mesh THROUGH THE REGISTRY (the exact engine the serve tier builds)
and must be bit-identical to its single-chip twin — distances AND the
kind extras — with the SciPy oracles (dijkstra, connected_components,
BFS prefixes) pinning both sides. Plus the interleaved mixed-kind serve
composition over one mesh service, unit arms for the (min, +) value
exchange and the sharded weights plane, and the reason-carrying
supported-kinds surface.
"""

import io
import json

import numpy as np
import pytest

from tpu_bfs.graph.csr import INF_DIST
from tpu_bfs.graph.generate import random_graph
from tpu_bfs.reference import bfs_scipy

pytestmark = pytest.mark.serve

P_MESH = 8
SRC = np.array([0, 7, 33, 95, 1, 64], dtype=np.int64)


def _dijkstra_oracle(g, sources):
    """SciPy dijkstra, duplicate edge slots min-folded first."""
    import scipy.sparse as sp
    from scipy.sparse import csgraph

    m = g.to_scipy(weighted=True).tocoo()
    key = m.row.astype(np.int64) * g.num_vertices + m.col
    order = np.lexsort((m.data, key))
    k2, d2 = key[order], m.data[order]
    first = np.ones(len(k2), bool)
    first[1:] = k2[1:] != k2[:-1]
    mm = sp.csr_matrix(
        (d2[first], (k2[first] // g.num_vertices, k2[first] % g.num_vertices)),
        shape=(g.num_vertices, g.num_vertices),
    )
    return csgraph.dijkstra(mm, directed=True, indices=sources)


@pytest.fixture(scope="module")
def wg():
    # The wirecheck calibration shape with the weight plane: small enough
    # that ten mesh compiles fit the tier-1 budget, connected enough that
    # every kind's traversal crosses every shard.
    return random_graph(96, 480, seed=3, weights=5)


@pytest.fixture(scope="module")
def reg(wg):
    from tpu_bfs.serve.registry import EngineRegistry

    registry = EngineRegistry(capacity=24, warm=False)
    key = registry.add_graph("wg", wg)
    return registry, key


def _get(reg, key, **kw):
    from tpu_bfs.serve.registry import EngineSpec

    registry = reg
    return registry.get(EngineSpec(graph_key=key, **kw))


# --- the fuzz matrix: kind x exchange, dist vs single-chip vs oracle --------

# Every kind's mesh forms: sssp sweeps the whole (min, +) exchange family
# (1D ring / allreduce / sparse / planner, 2D hierarchical pmin); the
# bitmap kinds ride the dist-wide OR substrate's dense / sparse / planned
# exchanges, khop also the 2D edge partition.
DIST_KINDS = [
    ("sssp-ring", "sssp", dict(engine="wide", lanes=32, exchange="ring")),
    ("sssp-allreduce", "sssp",
     dict(engine="wide", lanes=32, exchange="allreduce")),
    ("sssp-sparse", "sssp", dict(engine="wide", lanes=32, exchange="sparse")),
    ("sssp-planner", "sssp",
     dict(engine="wide", lanes=32, exchange="sparse", delta_bits=(8, 16),
          predict=True)),
    ("sssp-2d", "sssp", dict(engine="wide", lanes=32, mesh_shape=(2, 4))),
    ("cc-dense", "cc", dict(engine="wide", lanes=64, exchange="dense")),
    ("cc-sparse", "cc", dict(engine="wide", lanes=64, exchange="sparse")),
    ("khop-sparse", "khop",
     dict(engine="wide", lanes=64, exchange="sparse", delta_bits=(8, 16))),
    ("khop-2d", "khop",
     dict(engine="dist2d", lanes=32, exchange="sparse", delta_bits=(8, 16),
          sieve=True, predict=True)),
    ("p2p-sparse", "p2p", dict(engine="wide", lanes=64, exchange="sparse")),
]


@pytest.mark.parametrize(
    "name,kind,kw", DIST_KINDS, ids=[c[0] for c in DIST_KINDS]
)
def test_dist_kinds_bit_identical_to_single_chip(reg, wg, name, kind, kw):
    registry, key = reg
    dist = _get(registry, key, kind=kind, devices=P_MESH, **kw)
    single = _get(
        registry, key, kind=kind, engine="wide", lanes=kw["lanes"]
    )

    if kind == "sssp":
        a, b = single.run(SRC), dist.run(SRC)
        oracle = _dijkstra_oracle(wg, SRC)
        for i in range(len(SRC)):
            d1, d8 = a.distances_int32(i), b.distances_int32(i)
            np.testing.assert_array_equal(d1, d8)
            got = d8.astype(float)
            got[got == INF_DIST] = np.inf
            np.testing.assert_array_equal(got, oracle[i])
            assert int(a.reached[i]) == int(b.reached[i])
            assert int(a.ecc[i]) == int(b.ecc[i])
    elif kind == "cc":
        from scipy.sparse import csgraph

        a, b = single.run(SRC[:3]), dist.run(SRC[:3])
        nc, _ = csgraph.connected_components(wg.to_scipy(), directed=False)
        for i in range(3):
            ea, eb = a.extras(i), b.extras(i)
            assert ea == eb, (name, i, ea, eb)
            assert eb["components"] == nc
        np.testing.assert_array_equal(
            np.asarray(a.reached), np.asarray(b.reached)
        )
    elif kind == "khop":
        a, b = single.run(SRC, k=2), dist.run(SRC, k=2)
        np.testing.assert_array_equal(
            np.asarray(a.reached), np.asarray(b.reached)
        )
        for i, s in enumerate(SRC):
            d = bfs_scipy(wg, int(s))
            want = int(((d != INF_DIST) & (d <= 2)).sum())
            assert int(np.asarray(b.reached)[i]) == want, (name, i)
    else:  # p2p
        tgt = np.array([95, 60, 41, 2, 90, 3], dtype=np.int64)
        a, b = single.run(SRC, targets=tgt), dist.run(SRC, targets=tgt)
        for i in range(len(SRC)):
            ea, eb = a.extras(i), b.extras(i)
            assert ea == eb, (name, i, ea, eb)
            d = bfs_scipy(wg, int(SRC[i]))
            assert eb["distance"] == int(d[tgt[i]]), (name, i)
            path = eb["path"]
            assert path[0] == SRC[i] and path[-1] == tgt[i]
            assert len(path) == eb["distance"] + 1


def test_dist_sssp_wire_accounting_prices_value_branches(reg):
    """The serve-visible byte accounting on the mesh: the min exchange's
    per-round branch counts price against minplus_rows_wire_bytes_per_level
    (value-carrying rungs + the predictor's measurement-free dense) and
    the labels carry the exchange vocabulary breaker/bench keys compose
    on."""
    registry, key = reg
    eng = _get(
        registry, key, kind="sssp", devices=P_MESH, engine="wide", lanes=32,
        exchange="sparse", delta_bits=(8, 16), predict=True,
    )
    per = eng.wire_bytes_per_level()
    labels = eng.exchange_branch_labels()
    assert len(per) == len(labels)
    assert labels[-1] == "dense-predicted"
    eng.run(SRC)
    counts = np.asarray(eng.last_exchange_level_counts, dtype=np.float64)
    assert counts.sum() > 0
    # The accounting the fetch path stamps: total bytes = counts . per.
    assert eng.last_exchange_bytes == float(np.dot(counts, per))


# --- interleaved mixed-kind serving over ONE mesh service -------------------


def test_interleaved_mixed_kind_serve_on_mesh(wg):
    """The composition arm: one 8-device service answers an interleaved
    burst of all five kinds — every response ok, spot-pinned against the
    oracles — through the same scheduler/executor path the JSONL frontend
    drives (kind-aware coalescing never mixes kinds in a mesh batch
    either)."""
    from tpu_bfs.serve import BfsService

    svc = BfsService(
        wg, lanes=32, devices=P_MESH, exchange="sparse",
        delta_bits=(8, 16), width_ladder="off", linger_ms=1.0,
        registry_capacity=8,
    )
    try:
        assert set(svc.kinds) == {"bfs", "sssp", "cc", "khop", "p2p"}
        V = wg.num_vertices
        pend = []
        for i in range(25):
            kind = ("bfs", "sssp", "cc", "khop", "p2p")[i % 5]
            pend.append((kind, i % V, svc.submit(
                i % V, kind=kind,
                k=2 if kind == "khop" else None,
                target=(i + 7) % V if kind == "p2p" else None,
            )))
        res = [(k, s, p.result(timeout=600)) for k, s, p in pend]
        bad = [(k, r.status, r.error) for k, _, r in res if not r.ok]
        assert not bad, bad[:3]
        for kind, s, r in res:
            if kind == "bfs":
                np.testing.assert_array_equal(r.distances, bfs_scipy(wg, s))
            elif kind == "sssp":
                got = r.distances.astype(float)
                got[got == INF_DIST] = np.inf
                np.testing.assert_array_equal(
                    got, _dijkstra_oracle(wg, s)
                )
            elif kind == "khop":
                d = bfs_scipy(wg, s)
                assert r.reached == int(((d != INF_DIST) & (d <= 2)).sum())
            elif kind == "p2p":
                d = bfs_scipy(wg, s)
                assert r.extras["distance"] == int(d[(s + 7) % V])
    finally:
        svc.close()


# --- unit: the (min, +) value exchange --------------------------------------


def _run_exchange_min(prev, new_stacked, *, caps, delta_bits=(),
                      predict=False, prev_biggest=0, growing=False):
    """shard_map harness: blocked ownership (chip q owns global rows
    [q*rows_loc, (q+1)*rows_loc)), replicated prev table, per-chip
    updated own rows; returns (table [p, out_rows, lanes], branch [p],
    biggest [p]) — every chip's replica, so the caller can assert the
    exchange left them identical."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from tpu_bfs.parallel.collectives import sparse_rows_exchange_min
    from jax import shard_map

    p, rows_loc, lanes = new_stacked.shape
    out_rows = p * rows_loc
    mesh = Mesh(np.array(jax.devices()[:p]), ("x",))

    def body(new_l, prev_full):
        new_l = new_l[0]
        q = jax.lax.axis_index("x")
        own_prev = jax.lax.dynamic_slice_in_dim(
            prev_full, q * rows_loc, rows_loc
        )
        table, br, biggest = sparse_rows_exchange_min(
            new_l, own_prev, prev_full, "x", caps=caps, out_rows=out_rows,
            gid_of=lambda ids: ids + q * rows_loc,
            dense_fn=lambda: jax.lax.all_gather(new_l, "x").reshape(
                out_rows, lanes
            ),
            ident=jnp.int32(1 << 20), delta_bits=delta_bits,
            gid_of_src=lambda ids, src: ids + src * rows_loc,
            predict=predict,
            prev_biggest=jnp.int32(prev_biggest) if predict else None,
            growing=jnp.bool_(growing) if predict else None,
        )
        return table[None], br[None], biggest[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("x"), P()),
        out_specs=(P("x"), P("x"), P("x")), check_vma=False,
    )
    t, br, bg = jax.jit(fn)(jnp.asarray(new_stacked), jnp.asarray(prev))
    return np.asarray(t), np.asarray(br), np.asarray(bg)


def test_sparse_rows_exchange_min_unit():
    """Direct harness over the raw collective: sparse rung, delta-encoded
    rung, dense overflow, and the predictor's measurement-free branch all
    produce the same min-merged replica on every chip, with the branch
    ids indexing minplus_rows_branch_labels."""
    from tpu_bfs.parallel.collectives import minplus_rows_branch_labels

    p, rows_loc, lanes = 8, 4, 3
    out_rows = p * rows_loc
    rng = np.random.default_rng(0)
    prev = rng.integers(10, 100, size=(out_rows, lanes)).astype(np.int32)
    new = prev.reshape(p, rows_loc, lanes).copy()
    # Chip q improves one owned row (adjacent local ids -> tiny id gaps,
    # so the delta rung is selectable when armed).
    for q in range(p):
        new[q, q % rows_loc, :] = prev[q * rows_loc + q % rows_loc] - 5
    expected = prev.copy()
    for q in range(p):
        expected[q * rows_loc + q % rows_loc] -= 5

    # 1) sparse rung: one changed row per chip fits cap 2.
    t, br, _ = _run_exchange_min(prev, new, caps=(2,))
    assert (t == expected[None]).all()
    assert (br == 0).all()  # the single rung
    assert minplus_rows_branch_labels((2,), ())[0].startswith("sparse")

    # 2) dense overflow: cap 1 underfits chips with 2+ changed rows.
    new2 = new.copy()
    for q in range(p):
        new2[q, (q + 1) % rows_loc, :] = (
            prev[q * rows_loc + (q + 1) % rows_loc] - 3
        )
    exp2 = expected.copy()
    for q in range(p):
        exp2[q * rows_loc + (q + 1) % rows_loc] -= 3
    t, br, bg = _run_exchange_min(prev, new2, caps=(1,))
    assert (t == exp2[None]).all()
    assert (br == 1).all()  # K*(W+1) with K=1, W=0
    assert (bg == 2).all()  # the measured pmax saw both changed rows

    # 3) delta-encoded rung: 4-bit gaps cover rows_loc=4 local ids.
    t, br, _ = _run_exchange_min(prev, new, caps=(2,), delta_bits=(4,))
    assert (t == expected[None]).all()
    assert (br == 0).all()  # rung 0, delta width 0
    labels = minplus_rows_branch_labels((2,), (4,), predict=True)
    assert labels[-1] == "dense-predicted"

    # 4) predictor armed and confident: dense with NO measurement — the
    # branch is the trailing predicted-dense id and biggest carries the
    # stale prev value through.
    t, br, bg = _run_exchange_min(
        prev, new, caps=(2,), predict=True, prev_biggest=7, growing=True,
    )
    assert (t == expected[None]).all()
    labels_nodelta = minplus_rows_branch_labels((2,), (), predict=True)
    assert labels_nodelta[-1] == "dense-predicted"
    assert (br == len(labels_nodelta) - 1).all()
    assert (bg == 7).all()

    # 5) predictor armed but not confident (shrinking): measured path.
    t, br, bg = _run_exchange_min(
        prev, new, caps=(2,), predict=True, prev_biggest=7, growing=False,
    )
    assert (t == expected[None]).all()
    assert (br == 0).all()
    assert (bg == 1).all()


# --- unit: the sharded weights plane ----------------------------------------


def test_build_ell_weights_sharded_aligns_with_index_slabs(wg):
    """The weights plane replays build_ell_sharded's slicing: every edge
    weight lands in exactly one slot (global multiset equality), pad
    slots are exactly the index slabs' sentinel slots (weight 0 is inert
    under min-plus only because the matching index gathers the all-INF
    row), and the shapes pin to the index tables'."""
    from tpu_bfs.graph.ell import build_ell_sharded, build_ell_weights_sharded

    sell = build_ell_sharded(wg, P_MESH, kcap=64)
    vw, lw = build_ell_weights_sharded(wg, sell)
    nonzero = 0 if vw is None else int((vw != 0).sum())
    all_w = [] if vw is None else [vw[vw != 0].ravel()]
    assert (vw is None) == (sell.virtual is None)
    if vw is not None:
        assert vw.shape == sell.virtual.shape
    assert len(lw) == len(sell.light)
    for (k, idx), w in zip(sell.light, lw):
        assert w.shape == idx.shape and w.shape[-1] == k
        # Pad alignment: zero weight exactly where the index slab points
        # at the sentinel row.
        assert ((w != 0) == (idx != sell.v_pad)).all()
        nonzero += int((w != 0).sum())
        all_w.append(w[w != 0].ravel())
    weights = np.asarray(wg.weights)
    assert nonzero == len(weights)  # one slot per edge, no loss, no dup
    np.testing.assert_array_equal(
        np.sort(np.concatenate(all_w)), np.sort(weights)
    )
    with pytest.raises(ValueError, match="weight"):
        g0 = random_graph(32, 64, seed=1)
        build_ell_weights_sharded(
            g0, build_ell_sharded(g0, P_MESH, kcap=64)
        )


# --- reason-carrying supported kinds + serve errors -------------------------


def test_supported_kinds_carries_reasons():
    from tpu_bfs.workloads import kind_unsupported_reason, supported_kinds

    gu = random_graph(64, 256, seed=5)          # unweighted, undirected
    gd = random_graph(64, 256, seed=5, directed=True)
    gw = random_graph(64, 256, seed=5, weights=3)

    # The mesh no longer drops kinds: same set at 1 and 8 devices.
    assert supported_kinds("wide", 8, gw) == supported_kinds("wide", 1, gw)
    assert set(supported_kinds("wide", 8, gw)) == {
        "bfs", "sssp", "cc", "khop", "p2p"
    }
    # Each refusal names its axis.
    why = kind_unsupported_reason("sssp", "wide", 8, gu)
    assert why and "weight" in why
    why = kind_unsupported_reason("p2p", "wide", 8, gd)
    assert why and "undirected" in why
    why = kind_unsupported_reason("cc", "hybrid", 8, gw)
    assert why and "wide" in why
    why = kind_unsupported_reason("khop", "packed", 8, gw)
    assert why and "single-device" in why
    why = kind_unsupported_reason("pagerank", "wide", 1, gw)
    assert why and "unknown kind" in why
    assert kind_unsupported_reason("khop", "packed", 1, gw) is None


def test_jsonl_unserved_kind_errors_name_why():
    """ISSUE 20 satellite: the JSONL frontend's unknown/unserved-kind
    errors carry the kind_unsupported_reason text — a client learns WHY
    (no weights plane, directed graph), not just that it failed."""
    from tpu_bfs.serve import EngineRegistry
    from tpu_bfs.serve.frontend import build_arg_parser, run_server

    reg = EngineRegistry(capacity=4)
    reg.add_graph("ug", random_graph(96, 480, seed=3))
    reqs = "\n".join([
        json.dumps({"id": 1, "source": 0}),
        json.dumps({"id": 2, "source": 3, "kind": "sssp"}),
        json.dumps({"id": 3, "source": 3, "kind": "pagerank"}),
    ]) + "\n"
    args = build_arg_parser().parse_args(
        ["ug", "--lanes", "32", "--ladder", "off", "--linger-ms", "1",
         "--statsz-every", "0"]
    )
    out, err = io.StringIO(), io.StringIO()
    rc = run_server(args, stdin=io.StringIO(reqs), stdout=out, stderr=err,
                    registry=reg)
    assert rc == 0
    lines = {r["id"]: r for l in out.getvalue().splitlines() if l.strip()
             for r in [json.loads(l)]}
    assert lines[1]["status"] == "ok"
    assert lines[2]["status"] == "error"
    assert "weight" in lines[2]["error"]  # names the blocking axis
    assert lines[3]["status"] == "error"
    assert "unknown kind" in lines[3]["error"]
