"""Bench robustness: a transient infra failure must not erase the number,
and nothing else may be retried into silence.

Pinned here: bounded retry on infrastructure-flavored errors only — never
on validation failures, never on a backend that fails to initialize
(on a TPU host that means libtpu or the chip is unusable, and waiting
does not fix it) — and a run that measured nothing exits nonzero.
"""

import json

import numpy as np
import pytest

import bench


class FakeJaxRuntimeError(RuntimeError):
    """Name-matched stand-in for jaxlib's JaxRuntimeError (matched by type
    name so bench works without importing jax at module import)."""


FakeJaxRuntimeError.__name__ = "JaxRuntimeError"


TRANSIENT_MSG = "UNAVAILABLE: Connection reset by peer"


def test_is_transient_recognizes_transport_failure():
    assert bench._is_transient(FakeJaxRuntimeError(TRANSIENT_MSG))


def test_is_transient_rejects_validation_failures():
    # AssertionError (numpy testing) and ValueError (check_distances) must
    # never be retried, even if their message contains a scary substring.
    assert not bench._is_transient(AssertionError("INTERNAL: mismatch"))
    assert not bench._is_transient(ValueError("Connection reset mentioned"))


def test_is_transient_rejects_non_infra_jax_errors():
    # Same type, non-infra message (lowering/shape errors): no retry.
    assert not bench._is_transient(
        FakeJaxRuntimeError("Invalid argument: shapes do not match")
    )
    # OOM is real, not transient.
    assert not bench._is_transient(
        FakeJaxRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")
    )
    # Deterministic Mosaic lowering bugs carry INTERNAL: but must surface
    # on the first attempt, not after 6 engine builds.
    assert not bench._is_transient(
        FakeJaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    )


def test_retry_transient_retries_then_succeeds(monkeypatch):
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise FakeJaxRuntimeError(TRANSIENT_MSG)
        return "ok"

    assert bench.retry_transient(flaky, attempts=3, label="t") == "ok"
    assert len(calls) == 3


def test_retry_transient_propagates_validation_immediately(monkeypatch):
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    calls = []

    def bad():
        calls.append(1)
        raise AssertionError("distance mismatch at vertex 7")

    with pytest.raises(AssertionError):
        bench.retry_transient(bad, attempts=3, label="t")
    assert len(calls) == 1


def test_retry_transient_exhausts_attempts(monkeypatch):
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    calls = []

    def always_down():
        calls.append(1)
        raise FakeJaxRuntimeError(TRANSIENT_MSG)

    with pytest.raises(FakeJaxRuntimeError):
        bench.retry_transient(always_down, attempts=3, label="t")
    assert len(calls) == 3


def test_bench_emits_json_despite_interrupted_first_attempt(
    monkeypatch, capsys, toy_graph
):
    """End-to-end: inject a transient failure into the first engine run;
    the bench must still complete and print the one-line JSON."""
    from tpu_bfs.algorithms.bfs import BfsEngine

    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setenv("TPU_BFS_BENCH_SOURCES", "2")
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: toy_graph)

    real_run = BfsEngine.run
    calls = {"n": 0}

    def flaky_run(self, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeJaxRuntimeError(TRANSIENT_MSG)
        return real_run(self, *args, **kwargs)

    monkeypatch.setattr(BfsEngine, "run", flaky_run)

    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
    assert result["unit"] == "GTEPS"
    assert calls["n"] >= 3  # failed warm-up + retried warm-up + timed runs


def test_bench_fails_loud_on_validation_error(monkeypatch, capsys, toy_graph):
    """A genuine wrong answer must NOT be retried into silence: corrupt the
    engine output and assert the bench fails on the first attempt — exit 1
    with the ValidationError carried in the one JSON line (round 4: main
    converts deterministic failures to a parseable value=null verdict
    instead of a bare traceback, but never retries or exits 0 on them)."""
    from tpu_bfs.algorithms.bfs import BfsEngine

    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setenv("TPU_BFS_BENCH_SOURCES", "2")
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: toy_graph)

    real_run = BfsEngine.run
    calls = {"n": 0}

    def corrupt_run(self, *args, **kwargs):
        calls["n"] += 1
        res = real_run(self, *args, **kwargs)
        bad = np.asarray(res.distance).copy()
        bad[0] += 1  # wrong distance for vertex 0
        object.__setattr__(res, "distance", bad)
        return res

    monkeypatch.setattr(BfsEngine, "run", corrupt_run)

    assert bench.main() == 1
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["value"] is None and "mismatch" in result["error"]
    # First validated run fails; the outer retry must not have re-run the
    # whole bench (which would double the run count).
    assert calls["n"] == 1


BACKEND_INIT_MSG = (
    "Unable to initialize backend 'tpu': UNAVAILABLE: TPU initialization "
    "failed (set JAX_PLATFORMS='' to automatically choose an available "
    "backend)"
)


def test_backend_init_failure_is_not_transient():
    # jax raises a plain RuntimeError when no backend comes up. It carries
    # UNAVAILABLE:, but it is not infrastructure to wait out: the run
    # must fail on the first attempt.
    assert not bench._is_transient(RuntimeError(BACKEND_INIT_MSG))


def test_is_transient_still_rejects_framework_runtime_errors():
    # RuntimeError eligibility must not make the framework's own
    # RuntimeErrors retryable: the plane-cap truncation raise signals a
    # wrong configuration and carries no transient pattern.
    assert not bench._is_transient(
        RuntimeError(
            "traversal truncated at 16 levels; num_planes=4 caps at 16 — "
            "construct the engine with more planes for this graph"
        )
    )


def test_outage_envelope_fails_fast_with_structured_json(
    monkeypatch, capsys, toy_graph
):
    """An always-UNAVAILABLE run must conclude within the wall-clock
    budget, print the one JSON line with value=null and a machine-readable
    error, and exit NONZERO — it measured nothing. Simulated time: the
    fake clock advances on every sleep, so the outage plays out
    instantly."""
    from tpu_bfs.algorithms.bfs import BfsEngine

    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setenv("TPU_BFS_BENCH_BUDGET_S", "20")
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: toy_graph)

    clock = {"t": 0.0}
    monkeypatch.setattr(bench.time, "monotonic", lambda: clock["t"])
    monkeypatch.setattr(
        bench.time, "sleep",
        lambda s: clock.__setitem__("t", clock["t"] + s),
    )

    def link_down(self, *args, **kwargs):
        raise FakeJaxRuntimeError(TRANSIENT_MSG)

    monkeypatch.setattr(BfsEngine, "__init__", link_down)

    assert bench.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["value"] is None and "stale" not in result
    assert result["vs_baseline"] is None
    assert "device unavailable for" in result["error"]
    # The envelope must conclude within the budget, not after it.
    assert clock["t"] <= 20.0


def test_outage_envelope_derates_waits_to_fit_budget(monkeypatch):
    """A retry whose standard wait would overshoot the deadline gets a
    shorter wait instead of being skipped, as long as a meaningful attempt
    still fits; below that floor, BudgetExhausted carries the cause."""
    clock = {"t": 0.0}
    monkeypatch.setattr(bench.time, "monotonic", lambda: clock["t"])
    waits = []

    def fake_sleep(s):
        waits.append(s)
        clock["t"] += s

    monkeypatch.setattr(bench.time, "sleep", fake_sleep)
    monkeypatch.setattr(bench, "_DEADLINE", 40.0)

    calls = []

    def always_down():
        calls.append(1)
        raise FakeJaxRuntimeError(TRANSIENT_MSG)

    with pytest.raises(bench.BudgetExhausted) as ei:
        bench.retry_transient(always_down, attempts=10, backoff_s=20.0, label="t")
    # Attempt 1 fails at t=0: wait 20 fits (20+10 <= 40). Attempt 2 fails
    # at t=20: wait 40 would overshoot, derated to 40-20-10=10. Attempt 3
    # fails at t=30: remaining 10, no room -> exhausted, cause preserved.
    assert waits == [20.0, 10.0]
    assert len(calls) == 3
    assert isinstance(ei.value.cause, FakeJaxRuntimeError)
    assert ei.value.unavailable_s == pytest.approx(30.0)


def test_budget_exhausted_is_not_retried_by_outer_ladders(monkeypatch):
    """Nested retry ladders must treat the budget verdict as final even
    though its message quotes a transient-looking cause string."""
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    calls = []

    def inner():
        calls.append(1)
        raise bench.BudgetExhausted(FakeJaxRuntimeError(TRANSIENT_MSG), 99.0)

    with pytest.raises(bench.BudgetExhausted):
        bench.retry_transient(inner, attempts=3, label="outer")
    assert len(calls) == 1


def test_backend_came_up_attribution(monkeypatch):
    # The watchdog's honest attribution: a live backend means the budget
    # lost the measurement, not an outage. In this pytest process the CPU
    # backend is initialized -> True; an empty registry -> False.
    from jax._src import xla_bridge

    assert bench._backend_came_up() is True
    monkeypatch.setattr(xla_bridge, "_backends", {})
    assert bench._backend_came_up() is False


def test_result_log_appends_and_disables(monkeypatch, tmp_path, capsys, toy_graph):
    # A healthy run appends one timestamped JSON line to the durable
    # result log; the empty-string override disables it entirely.
    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setenv("TPU_BFS_BENCH_SOURCES", "2")
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: toy_graph)
    log_path = tmp_path / "results.jsonl"
    monkeypatch.setenv("TPU_BFS_BENCH_RESULT_LOG", str(log_path))

    assert bench.main() == 0
    capsys.readouterr()
    lines = log_path.read_text().strip().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["mode"] == "single" and rec["value"] is not None and "utc" in rec

    monkeypatch.setenv("TPU_BFS_BENCH_RESULT_LOG", "")
    assert bench.main() == 0
    capsys.readouterr()
    assert len(log_path.read_text().strip().splitlines()) == 1


def test_backend_init_failure_is_raised_at_once(monkeypatch):
    # No backend reset, no 60 s floor, no second attempt: the failure
    # surfaces on the first try.
    waits = []
    monkeypatch.setattr(bench.time, "sleep", waits.append)
    calls = []

    def no_backend():
        calls.append(1)
        raise RuntimeError(BACKEND_INIT_MSG)

    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        bench.retry_transient(no_backend, attempts=3, label="t")
    assert calls == [1] and waits == []


def test_env_adaptive_default_on_and_overrides(monkeypatch):
    # Round 4: the flagship bench runs the level-adaptive push by default
    # at the measured caps; explicit off-tokens and "rows,deg" overrides
    # must keep working, and a malformed value degrades to off (never
    # crash a flagship build mid-bench).
    monkeypatch.delenv("TPU_BFS_BENCH_ADAPTIVE", raising=False)
    assert bench._env_adaptive() == (8192, 64)
    for tok in ("0", "off", "OFF", " no ", "false"):
        monkeypatch.setenv("TPU_BFS_BENCH_ADAPTIVE", tok)
        assert bench._env_adaptive() is None
    monkeypatch.setenv("TPU_BFS_BENCH_ADAPTIVE", "1024,32")
    assert bench._env_adaptive() == (1024, 32)
    for bad in ("8192", "a,b", "8192,64,1", "-1,64", "0,64"):
        monkeypatch.setenv("TPU_BFS_BENCH_ADAPTIVE", bad)
        assert bench._env_adaptive() is None


def test_main_emits_failure_json_on_deterministic_crash(
    monkeypatch, capsys, toy_graph
):
    # Round 4: the lj-hybrid run compile-OOM'd and died rc=1 with only a
    # traceback — no JSON. Deterministic failures must still leave one
    # parseable line (value=null + the error), with a NONZERO exit (a bug,
    # not an outage).
    monkeypatch.setenv("TPU_BFS_BENCH_MODE", "single")
    monkeypatch.setattr(bench, "load_graph", lambda scale, ef: toy_graph)

    def blows_up(*a, **k):
        raise RuntimeError("sizing bug: boom")

    monkeypatch.setattr(bench, "bench_single", blows_up)
    assert bench.main() == 1
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["value"] is None and "boom" in result["error"]


def test_hybrid_oom_sheds_adaptive_and_rebenches_plain(
    monkeypatch, toy_graph
):
    # Round 4: with the adaptive push table resident, the LJ stand-in
    # OOM'd (16.22G of 15.75G hbm). The bench must shed the push table and
    # re-bench plain — never surface worse behavior than the pre-default
    # bench did.
    calls = []

    class FakeHg:
        num_tiles = 1
        num_dense_edges = 1
        in_degree = np.ones(toy_graph.num_vertices)

        class a_tiles:
            nbytes = 0

    class FakeEngine:
        hg = FakeHg()
        lanes = 4096

        def __init__(self, g, **kw):
            self.kw = kw
            calls.append(kw)

    def fake_batch(g, desc, engine, in_degree, build_log, label):
        if "adaptive_push" in engine.kw:  # only the push-table build OOMs
            calls.append("oom")
            raise FakeJaxRuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"
            )
        return {"metric": label, "value": 1.0, "unit": "GTEPS",
                "vs_baseline": 0.1}

    monkeypatch.delenv("TPU_BFS_BENCH_ADAPTIVE", raising=False)
    import tpu_bfs.algorithms.msbfs_hybrid as mh

    monkeypatch.setattr(mh, "HybridMsBfsEngine", FakeEngine)
    monkeypatch.setattr(bench, "_bench_batch_packed", fake_batch)
    result = bench.bench_hybrid(toy_graph, 10, 16)
    # First build carried the push table, OOM'd, then a plain rebuild
    # landed the number with a plain label.
    assert "oom" in calls
    assert result["value"] == 1.0
    assert "adaptive-push" not in result["metric"]


def test_hybrid_lanes_dont_fit_sheds_adaptive_first(monkeypatch, toy_graph):
    # The LJ scenario: WITH the push table resident the hybrid can't reach
    # its 4096-lane minimum; the bench must retry the HYBRID without the
    # table (~10% cost) before falling back to the wide engine (~2x cost).
    from tpu_bfs.algorithms.msbfs_hybrid import LanesDontFitError

    builds = []

    class FakeHg:
        num_tiles = 1
        num_dense_edges = 1
        in_degree = np.ones(toy_graph.num_vertices)

        class a_tiles:
            nbytes = 0

    class FakeEngine:
        hg = FakeHg()
        lanes = 4096

        def __init__(self, g, **kw):
            builds.append(kw)
            if "adaptive_push" in kw:
                raise LanesDontFitError("push table pushes under 4096")

    def fake_batch(g, desc, engine, in_degree, build_log, label):
        return {"metric": label, "value": 2.0, "unit": "GTEPS",
                "vs_baseline": 0.2}

    monkeypatch.delenv("TPU_BFS_BENCH_ADAPTIVE", raising=False)
    import tpu_bfs.algorithms.msbfs_hybrid as mh

    monkeypatch.setattr(mh, "HybridMsBfsEngine", FakeEngine)
    monkeypatch.setattr(bench, "_bench_batch_packed", fake_batch)
    result = bench.bench_hybrid(toy_graph, 10, 16)
    assert len(builds) == 2  # adaptive build failed, plain build landed
    assert "adaptive_push" in builds[0] and "adaptive_push" not in builds[1]
    assert result["value"] == 2.0
    assert "adaptive-push" not in result["metric"]
