"""The online integrity tier (ISSUE 15, tpu_bfs/integrity): wire
checksum codec, sampler determinism, structural detectors, disjoint
shadow-config selection, quarantine escalation, and the end-to-end
corrupt -> detect -> quarantine -> clean-again path on a live service.
"""

import numpy as np
import pytest

from tpu_bfs import faults
from tpu_bfs.graph.csr import INF_DIST
from tpu_bfs.graph.generate import random_graph, rmat_graph
from tpu_bfs.integrity import AuditSampler, IntegrityTier, QuarantineManager
from tpu_bfs.integrity.shadow import ShadowJob, compare_payloads, splitmix32
from tpu_bfs.integrity.structural import StructuralAuditor, StructuralFinding
from tpu_bfs.integrity.wire import (
    append_checksum,
    make_i32_checksum,
    make_words_checksum,
    split_verify,
    words_checksum_np,
)
from tpu_bfs.reference import bfs_scipy
from tpu_bfs.serve import BfsService, EngineRegistry
from tpu_bfs.serve.executor import CircuitBreaker, breaker_key


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


# --- wire checksum codec ----------------------------------------------------


def test_host_and_device_folds_agree():
    rng = np.random.default_rng(5)
    for n in (1, 7, 32, 129):
        words = rng.integers(0, 2**32, size=n, dtype=np.uint32)
        dev = int(make_words_checksum(n)(words))
        host = words_checksum_np(words)
        assert dev == host, n


def test_i32_checksum_matches_host_fold_on_distance_rows():
    dist = np.asarray([0, 1, 2, INF_DIST, 3, INF_DIST], np.int32)
    dev = int(make_i32_checksum(len(dist))(dist))
    assert dev == words_checksum_np(dist)


def test_every_single_bit_flip_changes_the_checksum():
    """The odd-multiplier guarantee, exhaustively: flipping ANY single
    bit of ANY word changes the fold."""
    rng = np.random.default_rng(11)
    words = rng.integers(0, 2**32, size=6, dtype=np.uint32)
    base = words_checksum_np(words)
    for i in range(len(words)):
        for b in range(32):
            flipped = words.copy()
            flipped[i] ^= np.uint32(1 << b)
            assert words_checksum_np(flipped) != base, (i, b)


def test_frame_roundtrip_and_flip_detection():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 2**32, size=16, dtype=np.uint32)
    framed = np.asarray(append_checksum(words))
    payload, ok = split_verify(framed)
    assert bool(ok) and np.array_equal(np.asarray(payload), words)
    for i in (0, 7, 15, 16):  # payload words and the checksum word itself
        bad = framed.copy()
        bad[i] ^= np.uint32(1 << (i % 32))
        _, ok = split_verify(bad)
        assert not bool(ok), i


def test_checksummed_ring_or_semantics_and_byte_model():
    """The checksummed packed ring computes the exact reduce-scatter OR
    (both variants bit-identical) with zero bad hops on a clean wire;
    the HLO byte proof (wirecheck.check_wire_checksum) pins +4 bytes
    per chunk per hop — run here so the codec and the proof travel
    together."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from tpu_bfs.integrity.wire import checksummed_ring_or
    from jax import shard_map
    from tpu_bfs.utils.wirecheck import check_wire_checksum

    p = 8
    if len(jax.devices()) < p:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    rng = np.random.default_rng(0)
    chunks = rng.integers(0, 2**32, size=(p, p, 16), dtype=np.uint32)
    mesh = Mesh(np.array(jax.devices()[:p]), ("x",))
    for wc in (False, True):
        def body(c, wc=wc):
            out, bad = checksummed_ring_or(c[0], "x", wire_check=wc)
            return out[None], bad[None]

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")),
            check_vma=False,
        ))
        out, bad = fn(jnp.asarray(chunks))
        assert np.array_equal(
            np.asarray(out), np.bitwise_or.reduce(chunks, axis=0)
        ), wc
        assert int(np.asarray(bad).sum()) == 0, wc
    proof = check_wire_checksum(p=p, words=16)
    assert proof["agree"], proof
    assert proof["checksum_overhead_bytes"] == 4 * (p - 1)


# --- sampler ----------------------------------------------------------------


def test_sampler_is_deterministic_in_seed_and_sequence():
    a = AuditSampler(0.3, seed=7)
    b = AuditSampler(0.3, seed=7)
    got_a = [a.should_sample() for _ in range(200)]
    got_b = [b.should_sample() for _ in range(200)]
    assert got_a == got_b
    assert got_a == AuditSampler(0.3, seed=7).picks(200)
    # A different seed samples a different subset.
    assert got_a != AuditSampler(0.3, seed=8).picks(200)
    # The fraction lands near the rate (splitmix32 is uniform enough).
    assert 0.15 < sum(got_a) / len(got_a) < 0.45


def test_sampler_edges():
    assert AuditSampler(0.0, seed=1).picks(50) == [False] * 50
    assert AuditSampler(1.0, seed=1).picks(50) == [True] * 50
    with pytest.raises(ValueError):
        AuditSampler(1.5)
    # splitmix32 stays in 32-bit range (the sampler's coin).
    assert all(0 <= splitmix32(x) < 2**32 for x in (0, 1, 2**31, 2**32 - 1))


# --- structural detectors ---------------------------------------------------


def _result(kind="bfs", **kw):
    from tpu_bfs.serve.scheduler import QueryResult

    defaults = dict(id=1, source=0, status="ok", kind=kind)
    defaults.update(kw)
    return QueryResult(**defaults)


def test_structural_bfs_clean_and_corrupt():
    g = random_graph(80, 400, seed=9)
    aud = StructuralAuditor(g)
    dist = bfs_scipy(g, 0)
    reached = int((dist != INF_DIST).sum())
    aud.audit("bfs", _result(distances=dist, reached=reached))  # clean
    # Flip one finite distance's low bit: some edge must now skip a
    # level (or the source check fires) — the corrupt_result shape.
    fin = np.flatnonzero(dist != INF_DIST)
    bad = dist.copy()
    bad[fin[len(fin) // 2]] ^= 1
    with pytest.raises(StructuralFinding):
        aud.audit("bfs", _result(distances=bad, reached=reached))
    # Wrong reached count against a clean row is also a finding.
    with pytest.raises(StructuralFinding):
        aud.audit("bfs", _result(distances=dist, reached=reached + 1))
    # Source not at distance zero.
    off = dist.copy()
    off[0] += 1
    with pytest.raises(StructuralFinding):
        aud.audit("bfs", _result(distances=off, reached=reached))


def test_structural_sssp_relaxation_property():
    from scipy.sparse import csgraph

    # dedup: scipy sums duplicate CSR slots, so a multigraph's parallel
    # edges would reach dijkstra with doubled weights (a wrong oracle).
    g = rmat_graph(7, 8, seed=31, weights=5, dedup=True)
    aud = StructuralAuditor(g)
    d = csgraph.dijkstra(g.to_scipy(weighted=True), indices=0)
    dist = np.where(np.isinf(d), INF_DIST, d).astype(np.int32)
    reached = int((dist != INF_DIST).sum())
    aud.audit("sssp", _result("sssp", distances=dist, reached=reached))
    bad = dist.copy()
    fin = np.flatnonzero((dist != INF_DIST) & (dist > 0))
    bad[fin[0]] += 64  # far past any edge weight: relaxation violated
    with pytest.raises(StructuralFinding):
        aud.audit("sssp", _result("sssp", distances=bad, reached=reached))


def test_structural_p2p_path_checks():
    g = random_graph(60, 600, seed=13)
    aud = StructuralAuditor(g)
    dist = bfs_scipy(g, 0)
    # A real shortest path, walked from the oracle distances.
    t = int(np.flatnonzero(dist == 2)[0])
    mid = next(
        int(v) for v in range(g.num_vertices)
        if dist[v] == 1 and g.has_edge(0, v) and g.has_edge(v, t)
    )
    ok = {"target": t, "met": True, "distance": 2, "path": [0, mid, t]}
    aud.audit("p2p", _result("p2p", extras=ok))
    for mutate in (
        {"distance": 3},  # length disagrees with the path
        {"path": [0, t]},  # skips a hop: (0, t) is not an edge... usually
        {"path": None},  # met without a path
    ):
        bad = {**ok, **mutate}
        if mutate.get("path") == [0, t] and g.has_edge(0, t):
            continue  # dense random graph happened to have the edge
        with pytest.raises(StructuralFinding):
            aud.audit("p2p", _result("p2p", extras=bad))
    # Unmet answers must not carry a path.
    aud.audit("p2p", _result(
        "p2p", extras={"target": t, "met": False, "distance": None,
                       "path": None}))
    with pytest.raises(StructuralFinding):
        aud.audit("p2p", _result(
            "p2p", extras={"target": t, "met": False, "distance": 2,
                           "path": [0, t]}))


def test_structural_cc_and_khop_consistency():
    g = random_graph(50, 200, seed=17)
    aud = StructuralAuditor(g)
    aud.audit("cc", _result(
        "cc", reached=10,
        extras={"component": 3, "component_size": 10, "components": 4}))
    with pytest.raises(StructuralFinding):
        aud.audit("cc", _result(
            "cc", reached=10,
            extras={"component": 3, "component_size": 11, "components": 4}))
    with pytest.raises(StructuralFinding):
        aud.audit("cc", _result(
            "cc", reached=10,
            extras={"component": g.num_vertices, "component_size": 10,
                    "components": 4}))
    aud.audit("khop", _result("khop", reached=5, levels=2, extras={"k": 2}))
    with pytest.raises(StructuralFinding):
        aud.audit("khop", _result("khop", reached=0, levels=2,
                                  extras={"k": 2}))


def test_checksum_mismatch_path():
    """corrupt_wire flips the host copy between the device transfer and
    the host fold: the wire check must read that as corruption."""
    g = random_graph(60, 300, seed=23)
    aud = StructuralAuditor(g, checksum=True)
    dist = bfs_scipy(g, 0)
    reached = int((dist != INF_DIST).sum())
    aud.audit("bfs", _result(distances=dist, reached=reached))  # clean
    faults.arm_from_spec("seed=2:corrupt_wire:n=1")
    with pytest.raises(StructuralFinding, match="wire checksum mismatch"):
        aud.audit("bfs", _result(distances=dist, reached=reached))
    assert faults.ACTIVE.counts()["corrupt_wire"] == 1
    # Budget spent: the next audit is clean again.
    aud.audit("bfs", _result(distances=dist, reached=reached))


# --- shadow compare ---------------------------------------------------------


class _FakeRes:
    def __init__(self, dist=None, reached=0, ecc=0, extras=None):
        self._d = dist
        self.reached = np.asarray([reached])
        self.ecc = np.asarray([ecc])
        self._e = extras

    def distances_int32(self, i):
        return self._d

    def extras(self, i):
        return self._e


def _job(**kw):
    defaults = dict(query_id=1, kind="bfs", source=0, k=None, target=None,
                    width=32, devices=1, distances=None, levels=None,
                    reached=None, extras=None, t_resolved=0.0)
    defaults.update(kw)
    return ShadowJob(**defaults)


def test_compare_payloads_bit_exact_and_batch_safe():
    d = np.asarray([0, 1, 2, INF_DIST], np.int32)
    assert compare_payloads(
        _job(distances=d, reached=3), _FakeRes(dist=d.copy(), reached=3)
    ) is None
    bad = d.copy()
    bad[1] ^= 1
    assert "distance mismatch" in compare_payloads(
        _job(distances=d, reached=3), _FakeRes(dist=bad, reached=3)
    )
    assert "reached mismatch" in compare_payloads(
        _job(reached=3), _FakeRes(reached=4)
    )
    # Batch-dependent extras (sssp round count) never read as corruption.
    assert compare_payloads(
        _job(kind="sssp", extras={"weighted": True, "sssp_rounds": 9}),
        _FakeRes(extras={"weighted": True, "sssp_rounds": 4}),
    ) is None
    # p2p compares met/distance/target only (meet vertex and path are
    # batch-composition-dependent).
    assert compare_payloads(
        _job(kind="p2p", extras={"target": 5, "met": True, "distance": 2,
                                 "path": [0, 3, 5]}),
        _FakeRes(extras={"target": 5, "met": True, "distance": 2,
                         "path": [0, 4, 5]}),
    ) is None
    assert "p2p distance mismatch" in compare_payloads(
        _job(kind="p2p", extras={"target": 5, "met": True, "distance": 2}),
        _FakeRes(extras={"target": 5, "met": True, "distance": 3}),
    )


# --- disjoint shadow-config selection ---------------------------------------


def test_shadow_spec_picks_a_different_rung():
    g = random_graph(96, 480, seed=3)
    svc = BfsService(g, lanes=64, width_ladder="32,64", autostart=False)
    try:
        assert svc._shadow_spec(64, "bfs").lanes == 32
        assert svc._shadow_spec(32, "bfs").lanes == 64
        # Kind rides into the disjoint spec (per-kind residency).
        assert svc._shadow_spec(32, "cc").kind == "cc"
    finally:
        svc.close()


def test_shadow_spec_single_rung_falls_off_ladder():
    g = random_graph(96, 480, seed=3)
    svc = BfsService(g, lanes=64, width_ladder="off", autostart=False)
    try:
        spec = svc._shadow_spec(64, "bfs")
        assert spec.lanes != 64 and spec.lanes % 32 == 0
    finally:
        svc.close()


def test_shadow_spec_mesh_alternates_the_exchange():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device CPU mesh")
    g = random_graph(96, 480, seed=3)
    svc = BfsService(g, lanes=64, devices=8, engine="wide",
                     width_ladder="off", autostart=False)
    try:
        # Single rung on a mesh: the disjoint config is the ALTERNATE
        # exchange family — a different compiled collective program over
        # the same devices.
        spec = svc._shadow_spec(64, "bfs")
        assert spec.devices == 8
        assert spec.exchange == "sparse"
        spec.validate()
    finally:
        svc.close()


# --- quarantine -------------------------------------------------------------


def test_breaker_trip_forces_open_then_half_opens():
    t = [0.0]
    br = CircuitBreaker(threshold=3, cooldown_s=10.0, now=lambda: t[0])
    key = breaker_key(64, 1, "bfs")
    assert br.allow(key)
    br.trip(key)
    assert not br.allow(key)
    assert key in br.open_keys()
    t[0] = 11.0  # past the cooldown: one half-open probe
    assert br.allow(key)
    br.record_success(key)
    assert br.allow(key) and not br.open_keys()


def test_quarantine_escalates_after_repeated_mesh_findings():
    quarantined, escalated = [], []

    class _M:
        def record_quarantine(self):
            pass

    qm = QuarantineManager(
        quarantine_rung=lambda w, k: quarantined.append((w, k)),
        escalate_mesh=lambda d, c: escalated.append(d),
        metrics=_M(), escalate_after=3,
    )
    for i in range(3):
        qm.report(width=64, devices=8, kind="bfs", query_id=i,
                  detail="x", source="shadow")
    assert len(quarantined) == 3
    assert escalated == [8]  # exactly once, at the threshold
    # Single-chip findings quarantine but never escalate.
    for i in range(5):
        qm.report(width=32, devices=1, kind="bfs", query_id=i,
                  detail="x", source="structural")
    assert escalated == [8]


# --- end-to-end on a live service -------------------------------------------


GRAPH = lambda: random_graph(96, 480, seed=3)  # noqa: E731


@pytest.mark.serve
@pytest.mark.chaos
def test_corrupt_result_detected_quarantined_then_clean():
    """The acceptance path: with corrupt_result armed, the audit tier
    catches the corruption (structural AND shadow), quarantines the
    serving rung (eviction + forced-open breaker + recovery counter),
    and every answer served after the quarantine is bit-identical to
    the oracle."""
    from tpu_bfs.utils.recovery import COUNTERS

    g = GRAPH()
    svc = BfsService(g, lanes=64, width_ladder="32,64", linger_ms=1.0,
                     audit_rate=1.0, audit_structural=True)
    q0 = COUNTERS.quarantines
    try:
        faults.arm_from_spec("seed=5:corrupt_result:n=1")
        r = svc.query(0, timeout=120)
        assert r.ok
        assert not np.array_equal(r.distances, bfs_scipy(g, 0))  # corrupted
        assert svc.flush_audits(120)
        snap = svc.statsz()
        assert snap["audit_failures"] >= 1
        assert snap["quarantines"] >= 1
        assert snap["breaker_open"], "corrupt rung's breaker must be open"
        assert COUNTERS.quarantines > q0
        faults.disarm()
        # Post-quarantine: routing avoids the quarantined rung and the
        # answers are oracle-exact again.
        for s in (3, 5, 7):
            r2 = svc.query(s, timeout=120)
            assert r2.ok
            np.testing.assert_array_equal(r2.distances, bfs_scipy(g, s))
        assert svc.flush_audits(120)
        snap2 = svc.statsz()
        assert snap2["audit_failures"] == snap["audit_failures"]
    finally:
        svc.close()


@pytest.mark.serve
def test_clean_soak_zero_false_positives_and_lag_metric():
    g = GRAPH()
    svc = BfsService(g, lanes=64, width_ladder="32,64", linger_ms=1.0,
                     audit_rate=1.0, audit_structural=True,
                     audit_checksum=True)
    try:
        for s in (0, 3, 5, 7, 11):
            assert svc.query(s, timeout=120).ok
        assert svc.flush_audits(120)
        snap = svc.statsz()
        assert snap["audits_run"] >= 5
        assert snap["audit_failures"] == 0
        assert snap["quarantines"] == 0
        assert snap["audit_p50_lag_ms"] is not None
        assert snap["audit"] == {
            "rate": 1.0, "structural": True, "checksum": True,
        }
    finally:
        svc.close()


@pytest.mark.serve
@pytest.mark.chaos
def test_faults_in_the_audit_tier_degrade_to_audit_errors():
    """Chaos targeting the AUDITORS (audit_shadow / audit_structural
    sites): a transient during a shadow replay retries; a deterministic
    failure counts as an audit error — never a corruption finding,
    never a serving failure."""
    g = GRAPH()
    svc = BfsService(g, lanes=32, width_ladder="off", linger_ms=1.0,
                     audit_rate=1.0, audit_structural=True)
    try:
        # One transient at each audit site: the shadow replay's retry
        # absorbs its; the structural audit counts one audit error.
        faults.arm_from_spec(
            "seed=4:transient@audit_shadow:n=1,"
            "transient@audit_structural:n=1"
        )
        r = svc.query(0, timeout=120)
        assert r.ok
        np.testing.assert_array_equal(r.distances, bfs_scipy(g, 0))
        assert svc.flush_audits(120)
        snap = svc.statsz()
        assert snap["audit_failures"] == 0
        assert snap["quarantines"] == 0
        assert snap["audit_errors"] == 1  # the structural site's transient
        assert faults.ACTIVE.counts()["transient"] == 2  # both sites fired
    finally:
        svc.close()


# --- satellite: p2p parent-scanner residency warm-up ------------------------


@pytest.mark.serve
def test_registry_warmup_builds_p2p_parent_scanner(monkeypatch):
    """ROADMAP item 3b: the registry's warm-up builds the cached parent
    scanner, so the FIRST p2p path reconstruction runs the scanner fast
    path — pinned by spying on the host scatter-min, which must never
    be called for a served p2p query."""
    from tpu_bfs.algorithms import _packed_common
    from tpu_bfs.serve.registry import EngineSpec

    g = random_graph(96, 960, seed=19)
    reg = EngineRegistry(capacity=2)
    reg.add_graph("p2p-warm", g)
    eng = reg.get(EngineSpec(graph_key="p2p-warm", kind="p2p", lanes=32))
    scanner = getattr(eng.base, "_parent_scanner_cache", None)
    assert scanner, "warm-up must cache the borrowed parent scanner"

    calls = []
    real = _packed_common.min_parents_lane

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(_packed_common, "min_parents_lane", spy)
    dist = bfs_scipy(g, 0)
    targets = np.flatnonzero(dist == 2)
    if not len(targets):
        pytest.skip("graph has no distance-2 pair")
    res = eng.run(np.asarray([0]), targets=np.asarray([int(targets[0])]))
    ex = res.extras(0)
    assert ex["met"] and ex["distance"] == 2 and len(ex["path"]) == 3
    assert calls == [], "path reconstruction paid the host scatter-min"
