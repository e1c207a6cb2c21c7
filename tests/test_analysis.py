"""The static-analysis layer (tpu_bfs/analysis, ISSUE 8) — fast half.

Unmarked here: the uniformity taint pass (trace-only, no XLA compile),
the AST lock lint, the dtype walk, the baseline mechanics, and every
seeded-violation fixture — the analyzer must fail RED on each planted
defect before its green run on the real tree means anything. The
compile-everything HLO sweeps live in test_analysis_sweep.py behind the
``slow`` marker (the tier-1 budget note in ROADMAP.md); ``make analyze``
runs the full sweep.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_bfs.analysis import Finding, apply_baseline, load_baseline
from tpu_bfs.analysis import dtypes, uniformity
from tpu_bfs.analysis.locks import find_cycles, lint_sources, lint_tree, repo_root
from jax import shard_map


@pytest.fixture(scope="module")
def small_analysis_graph():
    from tpu_bfs.graph.generate import random_graph

    return random_graph(96, 480, seed=3)


def _mesh1d():
    return Mesh(np.array(jax.devices()[:8]), ("v",))


def _mesh2d():
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("r", "c"))


def _smap(body, mesh, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# --- uniformity taint: seeded fixtures --------------------------------------


def test_divergent_branch_scalar_flagged():
    """The tentpole RED case: a cond on a per-chip scalar whose arms
    issue different collective schedules — the deadlock shape."""
    mesh = _mesh1d()

    def bad(x):
        def body(xb):
            m = jnp.max(xb)  # per-chip: NOT pmax'd

            def a(_):
                return lax.psum(xb, "v")

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-divergent", bad, (np.arange(8.0, dtype=np.float32),)
    )
    assert len(rep.findings) == 1, rep.findings
    f = rep.findings[0]
    assert f.pass_name == "uniformity"
    # Actionable: names the site and the missing axis.
    assert "'v'" in f.message and "deadlock" in f.message
    assert "seeded-divergent" in f.where


def test_pmaxed_branch_scalar_certified():
    """Same program with the scalar routed through pmax: clean, and the
    differing-collective branch point is CERTIFIED uniform (the
    certificate the HLO conditional audit consumes)."""
    mesh = _mesh1d()

    def good(x):
        def body(xb):
            m = lax.pmax(jnp.max(xb), "v")

            def a(_):
                return lax.psum(xb, "v")

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-good", good, (np.arange(8.0, dtype=np.float32),)
    )
    assert rep.findings == []
    assert rep.certified_divergent_safe >= 1


def test_collective_free_divergence_is_safe():
    """The dopt shape: per-chip branch choice with collective-free arms
    must NOT be flagged — divergence without communication is legal (and
    is exactly how the direction-optimizing expansion works)."""
    mesh = _mesh1d()

    def dopt_like(x):
        def body(xb):
            m = jnp.sum(xb)  # per-chip scalar

            def a(_):
                return xb * 2

            def b(_):
                return xb + 1

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-dopt", dopt_like, (np.arange(8.0, dtype=np.float32),)
    )
    assert rep.findings == []


def test_axis_granular_uniformity_2d():
    """The 2D planner's exact subtlety: a scalar pmax'd over 'c' only is
    row-uniform — enough for branches whose collectives run over 'c',
    NOT enough for branches communicating over 'r'."""
    mesh = _mesh2d()

    def row_ok(x):
        def body(xb):
            m = lax.pmax(jnp.max(xb), "c")  # uniform over 'c' only

            def a(_):
                return lax.psum(xb, "c")  # communicates over 'c': fine

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P(("r", "c")),), P(("r", "c")))(x)

    rep = uniformity.analyze_program(
        "seeded-2d-ok", row_ok, (np.arange(8.0, dtype=np.float32),)
    )
    assert rep.findings == [] and rep.certified_divergent_safe >= 1

    def row_bad(x):
        def body(xb):
            m = lax.pmax(jnp.max(xb), "c")

            def a(_):
                return lax.psum(xb, "r")  # 'r' collective: rows diverge

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P(("r", "c")),), P(("r", "c")))(x)

    rep = uniformity.analyze_program(
        "seeded-2d-bad", row_bad, (np.arange(8.0, dtype=np.float32),)
    )
    assert len(rep.findings) == 1
    assert "'r'" in rep.findings[0].message


def test_all_to_all_output_is_not_uniform():
    """all_to_all hands each rank a DIFFERENT chunk even from mesh-uniform
    inputs (reduce_scatter likewise) — a branch scalar derived from one
    must be flagged until re-reduced. Guards the taint rule that treats
    these as diverging, not uniformity-preserving."""
    mesh = _mesh1d()

    def bad(x):
        def body(xb):
            g = lax.all_gather(xb, "v", tiled=True)  # uniform over 'v'
            recv = lax.all_to_all(
                g.reshape(8, -1), "v", 0, 0, tiled=True
            )  # per-rank chunks: NOT uniform, despite the uniform input
            m = jnp.max(recv)

            def a(_):
                return lax.psum(xb, "v")

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-a2a", bad, (np.arange(8.0, dtype=np.float32),)
    )
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "'v'" in rep.findings[0].message

    def fixed(x):
        def body(xb):
            g = lax.all_gather(xb, "v", tiled=True)
            recv = lax.all_to_all(g.reshape(8, -1), "v", 0, 0, tiled=True)
            m = lax.pmax(jnp.max(recv), "v")  # re-reduced: uniform again

            def a(_):
                return lax.psum(xb, "v")

            def b(_):
                return xb * 2

            return lax.cond(m > 3, a, b, None)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-a2a-fixed", fixed, (np.arange(8.0, dtype=np.float32),)
    )
    assert rep.findings == [] and rep.certified_divergent_safe >= 1


def test_divergent_while_with_collectives_flagged():
    """A while loop that communicates per iteration under a per-chip trip
    count: ranks run different iteration counts and the collectives
    unpair."""
    mesh = _mesh1d()

    def bad_loop(x):
        def body(xb):
            def cond(st):
                return jnp.sum(st) < 100  # per-chip predicate

            def step(st):
                return st + lax.psum(st, "v")

            return lax.while_loop(cond, step, xb)

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-while", bad_loop, (np.arange(8.0, dtype=np.float32),)
    )
    assert len(rep.findings) == 1
    assert "while" in rep.findings[0].message


def test_uniformity_through_loop_carried_state():
    """The planner's history-prediction shape: a pmax'd scalar carried
    through a while loop stays uniform across iterations — the carry
    fixed point must not decay it to divergent."""
    mesh = _mesh1d()

    def carried(x):
        def body(xb):
            def cond(st):
                acc, u = st
                return u < 100  # uniform carried scalar drives the loop

            def step(st):
                acc, u = st

                def a(_):
                    return lax.psum(acc, "v")

                def b(_):
                    return acc * 2

                acc = lax.cond(u > 3, a, b, None)  # selected by the carry
                return acc, u + lax.pmax(jnp.max(acc), "v")

            acc, _ = lax.while_loop(
                cond, step, (xb, lax.pmax(jnp.max(xb), "v"))
            )
            return acc

        return _smap(body, mesh, (P("v"),), P("v"))(x)

    rep = uniformity.analyze_program(
        "seeded-carried", carried, (np.arange(8.0, dtype=np.float32),)
    )
    assert rep.findings == [], [f.render() for f in rep.findings]
    assert rep.certified_divergent_safe >= 1


# --- uniformity taint: the real planner programs ----------------------------


def test_planner_programs_verify_uniform():
    """ISSUE 8 acceptance (taint half): the richest real branch spaces —
    the 1D exchange planner (delta/sieve/predict: 2B+3 branches) — prove
    clean, with every differing-collective branch point certified by a
    mesh-uniform selection scalar. Trace-only (no XLA compile); the full
    config sweep is slow-marked / `make analyze`."""
    from tpu_bfs.analysis.configs import iter_programs

    for spec in iter_programs(("1d-sparse-planner",)):
        rep = uniformity.analyze_program(spec.name, spec.fn, spec.args)
        assert rep.findings == [], [f.render() for f in rep.findings]
        assert rep.shard_maps >= 1
        if spec.label == "level_loop":
            # The cap/delta/sieve/predict cond ladder is really there and
            # really certified — a trivially-empty walk must not pass.
            assert rep.conds_checked >= 10
            assert rep.certified_divergent_safe >= 10
        # The dtype walk rides the same trace.
        closed = jax.make_jaxpr(spec.fn)(*spec.args)
        assert dtypes.check_jaxpr(spec.name, closed) == []


# --- dtype pass -------------------------------------------------------------


def test_dtype_pass_flags_f64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(np.float64(1.0))
    findings = dtypes.check_jaxpr("seeded-f64", closed)
    assert findings and findings[0].pass_name == "dtype"
    assert "float64" in findings[0].message


def test_hlo_wide_dtype_scan_flags_f64():
    """The compiled-artifact half of the dtype pass: an f64 program's HLO
    must be flagged (result shapes sit RIGHT of the '=' — a scan of the
    instruction name side would be a permanent no-op)."""
    from tpu_bfs.analysis.hlo import wide_dtype_lines

    with jax.enable_x64(True):
        hlo = (
            jax.jit(lambda x: x * 2.0)
            .lower(np.float64(1.0))
            .compile()
            .as_text()
        )
    hits = wide_dtype_lines(hlo)
    assert hits and hits[0]["dtype"] == "f64", hlo[:400]
    clean = jax.jit(lambda x: x * 2.0).lower(np.float32(1.0)).compile()
    assert wide_dtype_lines(clean.as_text()) == []


def test_dtype_pass_flags_i64_widening():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: jnp.cumsum(x.astype(jnp.int64))
        )(np.arange(4, dtype=np.int32))
    findings = dtypes.check_jaxpr("seeded-i64", closed)
    assert findings and "int64" in findings[0].message


def test_dtype_pass_sees_inside_pallas_kernel():
    """ISSUE 16 red-before-green fixture: an f64 seeded INSIDE a pallas
    kernel body — where the walk only reaches through ``pallas_call``'s
    'jaxpr' param, a key the old scan/while/cond-specific key list never
    visited — must be flagged like any other hot-path widening; the same
    kernel without the widening is clean."""
    from jax.experimental import pallas as pl

    def call(body):
        def fn(x):
            return pl.pallas_call(
                body,
                out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
                interpret=True,
            )(x)
        return fn

    def bad(x_ref, o_ref):
        o_ref[:] = (x_ref[:].astype(jnp.float64) * 2.0).astype(jnp.float32)

    def good(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    x = np.ones((8, 8), np.float32)
    with jax.enable_x64(True):
        seeded = jax.make_jaxpr(call(bad))(x)
        clean = jax.make_jaxpr(call(good))(x)
    findings = dtypes.check_jaxpr("seeded-kernel-f64", seeded)
    assert findings and "float64" in findings[0].message
    assert dtypes.check_jaxpr("clean-kernel", clean) == []


# --- transfer pass: seeded host-op fixture ----------------------------------


def test_host_callback_in_loop_flagged():
    """A jax.debug.print left inside a compiled loop lowers to a host
    callback custom-call — per-iteration device->host sync. The HLO scan
    must name it; the clean twin must pass."""
    from tpu_bfs.analysis.transfer import check_hlo_host_ops

    @jax.jit
    def leaky(x, n):
        def body(i, a):
            jax.debug.print("lvl {}", i)
            return a + 1.0

        return lax.fori_loop(0, n, body, x)

    hlo = leaky.lower(jnp.ones(8), jnp.int32(3)).compile().as_text()
    findings = check_hlo_host_ops("seeded-leaky", hlo)
    assert findings, "host callback in a compiled loop must be flagged"
    assert "host" in findings[0].message

    @jax.jit
    def clean(x, n):
        return lax.fori_loop(0, n, lambda i, a: a + 1.0, x)

    hlo = clean.lower(jnp.ones(8), jnp.int32(3)).compile().as_text()
    assert check_hlo_host_ops("seeded-clean", hlo) == []


def test_trace_sentinel_catches_retrace():
    from tpu_bfs.analysis.transfer import TraceSentinel

    @jax.jit
    def f(x):
        return x + 1

    class Holder:
        def __init__(self):
            self.entry = f

    h = Holder()
    f(jnp.ones(4))
    sentinel = TraceSentinel("toy", h)
    sentinel.snapshot()
    f(jnp.ones(4))  # same shape: no retrace
    assert sentinel.check() == []
    f(jnp.ones(5))  # new shape: retrace
    bad = sentinel.check()
    assert bad and bad[0].pass_name == "transfer/retrace"
    assert "retraced" in bad[0].message


# --- lock lint --------------------------------------------------------------


def test_lock_lint_clean_on_tree():
    """The annotated serve/obs tree lints clean, covers a real guarded
    population, and its lock-order graph is the expected acyclic shape."""
    findings, info = lint_tree(repo_root())
    assert findings == [], [f.render() for f in findings]
    assert info["guarded_attrs"] >= 30  # the annotation satellite landed
    assert ("BfsService._lock", "EngineRegistry._lock") in info["edges"]
    assert ("EngineRegistry._lock", "Recorder._lock") in info["edges"]


_UNGUARDED_SRC = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []  # guarded-by: _lock

    def ok(self):
        with self._lock:
            return len(self.items)

    def bad(self):
        return len(self.items)
'''


def test_lock_lint_flags_unguarded_access():
    findings, _ = lint_sources({"fix.py": _UNGUARDED_SRC})
    assert len(findings) == 1
    f = findings[0]
    assert f.where == "fix.py:Box.items@bad"
    assert "guarded-by: _lock" in f.message and "items" in f.message


_REQUIRES_SRC = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0  # guarded-by: _lock

    def _bump(self):  # requires-lock: _lock
        self.n += 1

    def ok(self):
        with self._lock:
            self._bump()

    def bad(self):
        self._bump()
'''


def test_lock_lint_flags_requires_lock_violation():
    findings, _ = lint_sources({"fix.py": _REQUIRES_SRC})
    assert len(findings) == 1
    assert "requires-lock" in findings[0].message
    assert "@bad" in findings[0].where


_CYCLE_SRC = '''
import threading

class A:
    def __init__(self, b):
        self._lock = threading.Lock()
        self.b = B()

    def go(self):
        with self._lock:
            self.b.poke()

class B:
    def __init__(self):
        self._lock = threading.Lock()
        self.a = A(None)

    def poke(self):
        with self._lock:
            pass

    def back(self):
        with self._lock:
            self.a.go()
'''


def test_lock_lint_flags_order_cycle():
    findings, info = lint_sources({"fix.py": _CYCLE_SRC})
    cyc = [f for f in findings if f.where.startswith("lock-order:")]
    assert len(cyc) == 1
    assert "A._lock" in cyc[0].message and "B._lock" in cyc[0].message
    assert ("A._lock", "B._lock") in info["edges"]
    assert ("B._lock", "A._lock") in info["edges"]


_IDIOM_SRC = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.RLock()
        self.items = []  # guarded-by: _lock

    def timed(self):
        if not self._lock.acquire(timeout=0.05):
            return None
        try:
            return list(self.items)
        finally:
            self._lock.release()

    def nested(self):
        with self._lock:
            with self._lock:  # RLock: legal re-entry
                return len(self.items)
'''


def test_lock_lint_accepts_acquire_release_idiom_and_rlock():
    findings, _ = lint_sources({"fix.py": _IDIOM_SRC})
    assert findings == [], [f.render() for f in findings]


_NESTED_FN_SRC = '''
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0  # guarded-by: _lock

    def spawn(self):
        with self._lock:
            def worker():
                self.n += 1  # runs later, on another thread: UNGUARDED
            return worker
'''


def test_lock_lint_nested_function_does_not_inherit_locks():
    findings, _ = lint_sources({"fix.py": _NESTED_FN_SRC})
    assert len(findings) == 1 and "@spawn" in findings[0].where


def test_find_cycles_simple():
    assert find_cycles({("a", "b"), ("b", "a")})
    assert not find_cycles({("a", "b"), ("b", "c")})


# --- baseline mechanics -----------------------------------------------------


def test_baseline_split_and_stale(tmp_path):
    f1 = Finding("locks", "m.py:A.x@f", "msg one")
    f2 = Finding("dtype", "prog:site", "msg two")
    path = tmp_path / "baseline.txt"
    path.write_text(
        "# comment\n\n" + f1.fingerprint + "\nuniformity:gone/never\n"
    )
    base = load_baseline(str(path))
    new, suppressed, stale = apply_baseline([f1, f2], base)
    assert new == [f2]
    assert suppressed == [f1]
    assert stale == {"uniformity:gone/never"}
    assert load_baseline(str(tmp_path / "missing.txt")) == set()


def test_fingerprint_ignores_message():
    a = Finding("locks", "m.py:A.x@f", "one wording")
    b = Finding("locks", "m.py:A.x@f", "another wording")
    assert a.fingerprint == b.fingerprint == "locks:m.py:A.x@f"


# --- wirecheck stays a client of the shared core ----------------------------


def test_wirecheck_reexports_hlo_core():
    from tpu_bfs.analysis import hlo as core
    from tpu_bfs.utils import wirecheck

    assert wirecheck.Collective is core.Collective
    assert wirecheck.hlo_collectives is core.hlo_collectives


# --- memory pass (ISSUE 13, pass 5): donation lint + ladder model -----------


_UNDONATED_CARRY_SRC = '''
import jax
from jax import lax

@jax.jit
def step_loop(tbl, fw, vis):
    def body(st):
        f, v = st
        return f & tbl[0], v | f
    f, v = lax.while_loop(lambda st: st[0].any(), body, (fw, vis))
    return f, v
'''

_DONATED_CARRY_SRC = '''
import jax
from jax import lax
from functools import partial

@partial(jax.jit, donate_argnums=(1, 2))
def step_loop(tbl, fw, vis):
    def body(st):
        f, v = st
        return f & tbl[0], v | f
    f, v = lax.while_loop(lambda st: st[0].any(), body, (fw, vis))
    return f, v
'''

_DEAD_DONATE_SRC = '''
import jax
from functools import partial

@partial(jax.jit, donate_argnums=())
def plain(x):
    return x + 1
'''

_NO_DONATE_ANNOTATED_SRC = '''
import jax
from jax import lax

@jax.jit  # no-donate: the caller re-reads the carry for its probe
def step_loop(tbl, fw, vis):
    def body(st):
        f, v = st
        return f & tbl[0], v | f
    return lax.while_loop(lambda st: st[0].any(), body, (fw, vis))
'''


def test_donation_lint_flags_undonated_carry():
    """The seeded RED case: a jit whose params feed a while_loop carry
    without donate_argnums — double state residency per call."""
    from tpu_bfs.analysis.memory import lint_donation_sources

    findings, info = lint_donation_sources({"fix.py": _UNDONATED_CARRY_SRC})
    assert len(findings) == 1
    assert findings[0].fingerprint == (
        "memory/donation:fix.py:step_loop@undonated-carry"
    )
    assert "donate_argnums" in findings[0].message
    assert info["carry_style"] == 1

    clean, _ = lint_donation_sources({"fix.py": _DONATED_CARRY_SRC})
    assert clean == [], [f.render() for f in clean]


def test_donation_lint_flags_dead_annotation():
    """donate_argnums=() satisfies a grep and donates nothing — the
    bfs.py:31 defect this PR fixes, pinned as a fixture."""
    from tpu_bfs.analysis.memory import lint_donation_sources

    findings, _ = lint_donation_sources({"fix.py": _DEAD_DONATE_SRC})
    assert len(findings) == 1
    assert "dead-annotation" in findings[0].fingerprint
    assert "donates nothing" in findings[0].message


def test_donation_lint_accepts_no_donate_annotation():
    from tpu_bfs.analysis.memory import lint_donation_sources

    findings, info = lint_donation_sources(
        {"fix.py": _NO_DONATE_ANNOTATED_SRC}
    )
    assert findings == [], [f.render() for f in findings]
    assert info["no_donate"] == 1


def test_donation_lint_clean_on_tree():
    """The engine-core modules lint clean AFTER the donations landed:
    the carries it found are donated (bfs core, packed core_from twins,
    both dist loops) or annotated with the documented reason (the
    packed core's fw0-doubles-as-src-bits contract)."""
    from tpu_bfs.analysis.memory import lint_donation_tree

    findings, info = lint_donation_tree(repo_root())
    assert findings == [], [f.render() for f in findings]
    assert info["carry_style"] >= 7  # the loops really are carry-style
    assert info["donating"] >= 4  # bfs core + packed twins + dist loops
    assert info["no_donate"] >= 4  # core/core_from annotations


def test_ladder_model_monotone_for_registry_families():
    """The acceptance check: every EngineSpec family the serve registry
    can build has a modeled ladder strictly monotone in rung width."""
    from tpu_bfs.analysis.memory import check_registry_ladders

    findings, ladders = check_registry_ladders(
        num_vertices=1 << 21, num_edges=1 << 25, device_count=8
    )
    assert findings == [], [f.render() for f in findings]
    # Every registry engine kind appears, single-chip and mesh.
    fams = set(ladders)
    assert {"wide-d1", "packed-d1", "hybrid-d1", "wide-d8", "hybrid-d8",
            "dist2d-d8"} <= fams
    for fam, entries in ladders.items():
        widths = [w for w, _ in entries]
        bytes_ = [b for _, b in entries]
        assert widths == sorted(widths)
        assert bytes_ == sorted(bytes_), fam


def test_non_monotone_two_rung_ladder_flagged():
    """The seeded RED case: two rungs modeling identical (and inverted)
    peaks — the degrade walk would free nothing."""
    from tpu_bfs.analysis.memory import check_ladder_entries

    flat = check_ladder_entries("fam", [(32, 100), (64, 100)])
    assert len(flat) == 1 and "not strictly monotone" in flat[0].message
    inverted = check_ladder_entries("fam", [(32, 200), (64, 100)])
    assert len(inverted) == 1
    assert check_ladder_entries("fam", [(32, 100), (64, 200)]) == []


def test_check_program_donation_red_green():
    """A donating-tagged program whose HLO carries no alias entry is a
    finding (XLA silently drops unusable donations); one whose alias
    landed is a certificate."""
    import functools

    from tpu_bfs.analysis.memory import check_program_donation

    @functools.partial(jax.jit, donate_argnums=(0,))
    def donates(x):
        return x + 1

    donates._donate_argnums = (0,)
    hlo = donates.lower(jnp.ones(8, jnp.int32)).compile().as_text()
    assert check_program_donation("toy", donates, hlo) == []
    # Same tag over an alias-free artifact: the dropped-donation case.
    @jax.jit
    def copies(x):
        return x + 1

    copies._donate_argnums = (0,)
    hlo2 = copies.lower(jnp.ones(8, jnp.int32)).compile().as_text()
    bad = check_program_donation("toy2", copies, hlo2)
    assert bad and "input-output-alias" in bad[0].where


def test_bfs_core_donates_for_real(toy_graph):
    """Satellite 1 pinned at runtime: the single-source core's carry is
    consumed by the call (the donate_argnums=() era kept it alive), and
    chunked resume over the donating loop stays bit-identical."""
    from tpu_bfs.algorithms.bfs import BfsEngine, _bfs_core, bfs

    eng = BfsEngine(toy_graph)
    f0, v0, d0 = eng._init_state(0)
    out = _bfs_core(
        eng.edges, f0, v0, d0, jnp.int32(0), jnp.int32(4),
        backend=eng.backend, caps=eng.caps,
    )
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(f0)  # donated: the buffer is gone
    del out
    # Chunked advance (start/advance to exhaustion) == one-shot run.
    straight = bfs(toy_graph, 3, with_parents=False)
    ckpt = eng.start(3)
    while not ckpt.done:
        ckpt = eng.advance(ckpt, levels=1)
    np.testing.assert_array_equal(
        eng.finish(ckpt, with_parents=False).distance, straight.distance
    )


def test_packed_advance_rides_donating_core(small_analysis_graph):
    """The packed resume path uses the donating twin: chunked advance is
    bit-identical to the uninterrupted run, and the twin really donates
    (fresh carries handed to it are consumed)."""
    from tpu_bfs.algorithms._packed_common import (
        packed_real_to_table,
        start_packed_batch,
    )
    from tpu_bfs.algorithms.msbfs_wide import WidePackedMsBfsEngine

    g = small_analysis_graph
    eng = WidePackedMsBfsEngine(g, lanes=32, num_planes=4)
    assert getattr(eng, "_core_from_donate", None) is not None
    sources = np.arange(32, dtype=np.int64) % g.num_vertices
    res = eng.run(sources)
    ckpt = start_packed_batch(eng, sources)
    from tpu_bfs.algorithms._packed_common import advance_packed_batch
    while ckpt.alive:
        ckpt = advance_packed_batch(eng, ckpt, levels=1)
    from tpu_bfs.algorithms._packed_common import finish_packed_batch
    fin = finish_packed_batch(eng, ckpt)
    for i in (0, 7, 31):
        np.testing.assert_array_equal(
            fin.distances_int32(i), res.distances_int32(i)
        )
    # The twin consumes its carry: a fresh table handed in is deleted.
    fw = packed_real_to_table(
        eng, np.zeros((g.num_vertices, eng.w), np.uint32)
    )
    vis = packed_real_to_table(
        eng, np.zeros((g.num_vertices, eng.w), np.uint32)
    )
    planes = tuple(
        packed_real_to_table(
            eng, np.zeros((g.num_vertices, eng.w), np.uint32)
        )
        for _ in range(eng.num_planes)
    )
    eng._core_from_donate(
        eng.arrs, fw, vis, planes, jnp.int32(0), jnp.int32(1)
    )
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(fw)


# --- lifecycle pass (ISSUE 13, pass 6) --------------------------------------


_DANGLING_SPAN_SRC = '''
class S:
    def f(self, rec, bad):
        rec.begin("dispatch", "b1")
        if bad:
            raise RuntimeError("x")
        rec.end("dispatch", "b1")
'''

_CLOSED_SPAN_SRC = '''
class S:
    def f(self, rec, bad):
        rec.begin("dispatch", "b1")
        if bad:
            rec.end("dispatch", "b1", failed=True)
            raise RuntimeError("x")
        rec.end("dispatch", "b1")
'''

_HANDLER_SPAN_SRC = '''
class S:
    def f(self, rec):
        rec.begin("fetch", "b1")
        try:
            self.work()
            rec.end("fetch", "b1")
        except Exception:
            rec.end("fetch", "b1", failed=True)
            raise
'''

_OUTLIVES_SRC = '''
class S:
    def f(self, rec):
        rec.begin("query", "q1")  # span-outlives: resolve() closes it
        return 1
'''

_LOCK_BRANCH_SRC = '''
class S:
    def f(self, ok):
        self._lock.acquire()
        if ok:
            self._lock.release()
'''

_LOCK_IDIOM_SRC = '''
class S:
    def f(self):
        if not self._lock.acquire(timeout=0.05):
            return None
        try:
            return 1
        finally:
            self._lock.release()
'''

_SNAPSHOT_LEAK_SRC = '''
class C:
    def __init__(self):
        self._resume_cache = ResumeCache(None)

    def save(self, s, ck):
        self._resume_cache.put(s, ck)
'''

_SNAPSHOT_OK_SRC = '''
class C:
    def __init__(self):
        self._resume_cache = ResumeCache(None)

    def save(self, s, ck):
        self._resume_cache.put(s, ck)

    def done(self, s):
        self._resume_cache.drop(s)
'''


def test_lifecycle_flags_dangling_span_across_raise():
    """The PR 6 review class, pinned RED: a span begun, then an explicit
    raise with no end on that path."""
    from tpu_bfs.analysis.lifecycle import check_sources

    findings, _ = check_sources({"fix.py": _DANGLING_SPAN_SRC})
    assert len(findings) == 1
    assert findings[0].fingerprint == "lifecycle:fix.py:S.f@span:dispatch"
    assert "across a raise" in findings[0].message
    clean, _ = check_sources({"fix.py": _CLOSED_SPAN_SRC})
    assert clean == [], [f.render() for f in clean]
    handler, _ = check_sources({"fix.py": _HANDLER_SPAN_SRC})
    assert handler == [], [f.render() for f in handler]


def test_lifecycle_span_outlives_annotation_transfers_ownership():
    from tpu_bfs.analysis.lifecycle import check_sources

    findings, info = check_sources({"fix.py": _OUTLIVES_SRC})
    assert findings == []
    assert info["span_outlives"] == 1


def test_lifecycle_flags_unreleased_lock_branch():
    """The lock half, RED: acquire with a release on one branch only;
    the timeout-acquire/try/finally idiom stays green."""
    from tpu_bfs.analysis.lifecycle import check_sources

    findings, _ = check_sources({"fix.py": _LOCK_BRANCH_SRC})
    assert len(findings) == 1
    assert findings[0].fingerprint == "lifecycle:fix.py:S.f@lock:self._lock"
    clean, _ = check_sources({"fix.py": _LOCK_IDIOM_SRC})
    assert clean == [], [f.render() for f in clean]


def test_lifecycle_flags_snapshot_without_drop():
    """The PR 11 review class, RED: a class that puts resume snapshots
    and never drops any pins ~3x[V] host arrays forever."""
    from tpu_bfs.analysis.lifecycle import check_sources

    findings, _ = check_sources({"fix.py": _SNAPSHOT_LEAK_SRC})
    assert len(findings) == 1
    assert "snapshot" in findings[0].fingerprint
    clean, _ = check_sources({"fix.py": _SNAPSHOT_OK_SRC})
    assert clean == [], [f.render() for f in clean]


def test_lifecycle_clean_on_tree():
    """serve/obs/resilience (+ the 2D serve adapter) verify clean, with
    exactly the two documented cross-function span ownerships (the
    dispatch/fetch/extract spans are ``obs.host_span`` blocks)."""
    from tpu_bfs.analysis.lifecycle import check_tree

    findings, info = check_tree(repo_root())
    assert findings == [], [f.render() for f in findings]
    assert info["span_outlives"] == 2  # query, batch
    assert info["functions"] >= 150


# --- faultcov pass (ISSUE 13, pass 7) ---------------------------------------


def test_faultcov_flags_undeclared_consult():
    """RED: a consultation naming a site the grammar does not declare
    can never fire."""
    from tpu_bfs.analysis.faultcov import check_sources

    prod = {"m.py": 'ACTIVE.hit("nonexistent_site", lanes=4)\n'}
    findings, _ = check_sources(prod, {}, sites=("dispatch",))
    fps = [f.fingerprint for f in findings]
    assert any("undeclared:nonexistent_site" in fp for fp in fps)


def test_faultcov_flags_never_consulted_site():
    from tpu_bfs.analysis.faultcov import check_sources

    findings, _ = check_sources(
        {"m.py": 'ACTIVE.hit("dispatch")\n'},
        {"t.py": '"transient@dispatch:n=1"\n'},
        sites=("dispatch", "ghost_site"),
    )
    fps = [f.fingerprint for f in findings]
    assert fps == ["faultcov:faults.SITES@never-consulted:ghost_site"]


def test_faultcov_flags_uncovered_site():
    """RED: a consulted site no test spec ever targets — a new fault
    site cannot land untested."""
    from tpu_bfs.analysis.faultcov import check_sources

    prod = {"m.py": 'ACTIVE.hit("dispatch")\nACTIVE.hit("fetch")\n'}
    tests = {"t.py": 'spec = "transient@dispatch:n=1"\n'}
    findings, info = check_sources(
        prod, tests, sites=("dispatch", "fetch")
    )
    assert [f.fingerprint for f in findings] == [
        "faultcov:tests@uncovered:fetch"
    ]
    assert info["coverage"]["dispatch"] == ["transient"]


def test_faultcov_parses_spec_strings_with_default_sites():
    """Coverage credits the DEFAULT_SITE of site-less clauses — the
    common `seed=7:transient:p=0.05` shape lands on `dispatch`."""
    from tpu_bfs.analysis.faultcov import coverage_from_source

    cov = coverage_from_source(
        'SPEC = "seed=7:transient:p=0.05,corrupt_ckpt:n=1"\n'
    )
    assert cov["dispatch"] == {"transient"}
    assert cov["ckpt_save"] == {"corrupt_ckpt"}


def test_faultcov_clean_on_tree():
    """Every declared site is consulted, every consulted site is
    drivable from tests/ or the chaos smokes."""
    from tpu_bfs.analysis.faultcov import check_tree
    from tpu_bfs.faults import SITES

    findings, info = check_tree(repo_root())
    assert findings == [], [f.render() for f in findings]
    assert set(info["sites"]) == set(SITES)
    for site in SITES:
        assert info["coverage"][site], f"site {site} has no coverage"


# --- the JSON report (ISSUE 13 satellite) -----------------------------------


def test_cli_json_report_shape(capsys):
    """`tpu-bfs-analyze --json` emits one machine-readable object the
    chip-session pre-flight can gate on — verdict, per-pass info, and
    the ladder certificates — without scraping exit text."""
    import json as _json

    from tpu_bfs.analysis.cli import main

    rc = main(["--fast", "--json", "--skip", "uniformity,dtype,transfer"])
    out = capsys.readouterr().out
    rep = _json.loads(out)
    assert rc == 0 and rep["ok"] is True
    assert rep["findings"] == [] and rep["stale_baseline"] == []
    assert {"locks", "memory", "lifecycle", "faultcov"} <= set(rep["passes"])
    ladders = rep["passes"]["memory"]["ladders"]
    assert "wide-d1" in ladders and ladders["wide-d1"][0]["model_bytes"] > 0
    assert rep["passes"]["faultcov"]["coverage"]["dispatch"]


def test_cli_rejects_unknown_skip(capsys):
    from tpu_bfs.analysis.cli import main

    assert main(["--fast", "--skip", "nosuchpass"]) == 2


def test_donation_lint_accepts_bare_int_donate_argnums():
    """jax accepts `donate_argnums=1`; the lint must read it as (1,),
    not flag a correctly-donating carry (review catch)."""
    from tpu_bfs.analysis.memory import lint_donation_sources

    src = (
        "import jax\n"
        "from jax import lax\n"
        "from functools import partial\n\n"
        "@partial(jax.jit, donate_argnums=1)\n"
        "def step_loop(tbl, fw):\n"
        "    return lax.while_loop(lambda f: f.any(),\n"
        "                          lambda f: f & tbl[0], fw)\n"
    )
    findings, info = lint_donation_sources({"fix.py": src})
    assert findings == [], [f.render() for f in findings]
    assert info["donating"] == 1


def test_lifecycle_break_path_skips_loop_else():
    """Python runs a loop's `else` only on non-break exhaustion: a span
    closed ONLY in the else clause leaks on the break path (review
    catch — the walker must not route break states through orelse)."""
    from tpu_bfs.analysis.lifecycle import check_sources

    leaky = (
        "class S:\n"
        "    def f(self, rec, items):\n"
        "        rec.begin(\"scan\", \"s1\")\n"
        "        for it in items:\n"
        "            if it:\n"
        "                break\n"
        "        else:\n"
        "            rec.end(\"scan\", \"s1\")\n"
    )
    findings, _ = check_sources({"fix.py": leaky})
    assert [f.fingerprint for f in findings] == [
        "lifecycle:fix.py:S.f@span:scan"
    ]
    closed = (
        "class S:\n"
        "    def f(self, rec, items):\n"
        "        rec.begin(\"scan\", \"s1\")\n"
        "        for it in items:\n"
        "            if it:\n"
        "                rec.end(\"scan\", \"s1\", early=True)\n"
        "                break\n"
        "        else:\n"
        "            rec.end(\"scan\", \"s1\")\n"
    )
    clean, _ = check_sources({"fix.py": closed})
    assert clean == [], [f.render() for f in clean]
