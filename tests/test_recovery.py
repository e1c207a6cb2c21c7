"""In-run failure detection + elastic recovery (utils/recovery.py).

The reference has no failure story: a failed rank hangs the MPI_Allreduce
(bfs_mpi.cu:621; SURVEY.md §5 'failure detection: none'). Here a transient
device/compile failure mid-traversal rebuilds the engine and resumes from
the last durable checkpoint, bit-identical to an unfailed run. These tests
inject a transient transport failure into real engines.
"""

import numpy as np
import pytest

from tpu_bfs.parallel.dist_bfs import DistBfsEngine, make_mesh
from tpu_bfs.utils.recovery import advance_with_recovery, is_transient_failure


class FakeJaxRuntimeError(RuntimeError):
    pass


FakeJaxRuntimeError.__name__ = "JaxRuntimeError"

TRANSIENT_MSG = "UNAVAILABLE: Connection reset by peer"


def _flaky_engine_factory(g, fail_times: list):
    """DistBfsEngine factory whose engines fail transiently on the first
    ``advance`` call for each entry left in ``fail_times``."""

    def make():
        eng = DistBfsEngine(g, make_mesh(4), backend="dopt")
        real_advance = eng.advance

        def advance(ckpt, levels=None):
            if fail_times:
                fail_times.pop()
                raise FakeJaxRuntimeError(TRANSIENT_MSG)
            return real_advance(ckpt, levels)

        eng.advance = advance
        return eng

    return make


def test_recovery_completes_bit_identical(random_small):
    g = random_small
    baseline = DistBfsEngine(g, make_mesh(4), backend="dopt").run(42)

    make = _flaky_engine_factory(g, fail_times=[1])
    engine = make()
    st = engine.start(42)
    msgs = []
    engine, st, restarts = advance_with_recovery(
        make, st, engine=engine, levels_per_chunk=1, log=msgs.append
    )
    assert restarts == 1 and st.done
    assert any("rebuilding engine" in m for m in msgs)
    res = engine.finish(st)
    np.testing.assert_array_equal(res.distance, baseline.distance)
    np.testing.assert_array_equal(res.parent, baseline.parent)


def test_recovery_tiled_engine(rmat_small):
    # Round 4 gave the tiled single-stream engine the checkpoint protocol;
    # the recovery driver must rebuild + resume it bit-identically too.
    from tpu_bfs.algorithms.bfs_tiled import TiledBfsEngine

    g = rmat_small
    baseline = TiledBfsEngine(g, tile_thr=4).run(1)
    fail_times = [1]

    def make():
        eng = TiledBfsEngine(g, tile_thr=4)
        real_advance = eng.advance

        def advance(ckpt, levels=None):
            if fail_times:
                fail_times.pop()
                raise FakeJaxRuntimeError(TRANSIENT_MSG)
            return real_advance(ckpt, levels)

        eng.advance = advance
        return eng

    engine = make()
    st = engine.start(1)
    engine, st, restarts = advance_with_recovery(
        make, st, engine=engine, levels_per_chunk=1, log=lambda m: None
    )
    assert restarts == 1 and st.done
    res = engine.finish(st)
    np.testing.assert_array_equal(res.distance, baseline.distance)
    np.testing.assert_array_equal(res.parent, baseline.parent)


def test_recovery_resumes_from_last_saved_chunk(random_small, tmp_path):
    # The failure hits mid-traversal; the save callback captured the chunks
    # before it, and the traversal still finishes from them.
    from tpu_bfs.utils import checkpoint as ck

    g = random_small
    p = str(tmp_path / "st.npz")
    saved_levels = []

    def save(c):
        ck.save_checkpoint(p, c)
        saved_levels.append(c.level)

    make = _flaky_engine_factory(g, fail_times=[1, 1])
    engine = make()
    # Burn the first engine's failure so the NEXT one fires mid-loop.
    with pytest.raises(FakeJaxRuntimeError):
        engine.advance(engine.start(42), levels=1)
    engine2, st, restarts = advance_with_recovery(
        make, engine.start(42), engine=engine, levels_per_chunk=2, save=save,
    )
    assert restarts == 1 and st.done
    assert saved_levels == sorted(saved_levels)
    # The on-disk checkpoint is the finished state (saved after each chunk).
    assert ck.load_checkpoint(p).level == st.level


def test_recovery_survives_transient_rebuild_failure(random_small):
    # The rebuild itself is compile-heavy; a blip there must consume
    # restart budget, not kill the run.
    g = random_small
    fail_advance = [1]
    fail_build = [1]

    def make():
        if fail_build:
            fail_build.pop()
            raise FakeJaxRuntimeError(TRANSIENT_MSG)
        return _flaky_engine_factory(g, fail_times=[])()

    first = _flaky_engine_factory(g, fail_times=fail_advance)()
    engine, st, restarts = advance_with_recovery(
        make, first.start(42), engine=first, levels_per_chunk=1,
        max_restarts=3,
    )
    assert st.done and restarts == 2  # one advance blip + one rebuild blip
    baseline = DistBfsEngine(g, make_mesh(4), backend="dopt").run(42)
    np.testing.assert_array_equal(
        engine.finish(st).distance, baseline.distance
    )


def test_recovery_gives_up_after_max_restarts(random_small):
    make = _flaky_engine_factory(random_small, fail_times=[1] * 10)
    engine = make()
    with pytest.raises(FakeJaxRuntimeError):
        advance_with_recovery(
            make, engine.start(42), engine=engine, max_restarts=2
        )


def test_recovery_propagates_non_transient(random_small):
    eng = DistBfsEngine(random_small, make_mesh(2))

    def bad_advance(ckpt, levels=None):
        raise ValueError("checkpoint has 7 vertices, graph has 500")

    eng.advance = bad_advance
    with pytest.raises(ValueError):
        advance_with_recovery(lambda: eng, eng.start(0), engine=eng)


def test_recovery_respects_max_level(random_small):
    eng = DistBfsEngine(random_small, make_mesh(2))
    _, st, restarts = advance_with_recovery(
        lambda: eng, eng.start(42), engine=eng, levels_per_chunk=1,
        max_level=2,
    )
    assert st.level == 2 and restarts == 0


def test_is_transient_failure_classifier():
    assert is_transient_failure(FakeJaxRuntimeError(TRANSIENT_MSG))
    assert not is_transient_failure(AssertionError(TRANSIENT_MSG))
    assert not is_transient_failure(
        FakeJaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    )


# The jaxlib mesh-death strings (ISSUE 12 satellite): each marker pinned
# INDIVIDUALLY so a dropped entry fails red — real device loss must route
# through the same retry/degrade path as the injected kinds.
MESH_DEATH_SHAPES = [
    "DATA_LOSS: Attempting to fetch value instead of handling error",
    "UNAVAILABLE: slice health check failed; restarting the slice",
    "INTERNAL: Program hung (awaiting completion of all-reduce)",
]


@pytest.mark.parametrize("msg", MESH_DEATH_SHAPES)
def test_mesh_death_markers_are_transient(msg):
    from tpu_bfs.utils.recovery import is_mesh_fault

    exc = FakeJaxRuntimeError(msg)
    assert is_transient_failure(exc), msg  # retryable infrastructure
    assert is_mesh_fault(exc), msg  # AND mesh-classified (degrade path)


@pytest.mark.parametrize("msg", MESH_DEATH_SHAPES)
def test_mesh_death_markers_cover_each_marker(msg):
    """Red-before-green per marker: remove any MESH_FAULT_MARKERS entry
    and exactly its shape stops classifying."""
    from tpu_bfs.utils.recovery import MESH_FAULT_MARKERS

    assert sum(m in msg for m in MESH_FAULT_MARKERS) == 1


def test_mesh_fault_is_subset_of_transient():
    """One definition: every mesh marker rides TRANSIENT_PATTERNS, and
    ordinary transients are NOT mesh faults (no spurious degrades)."""
    from tpu_bfs.utils.recovery import (
        TRANSIENT_PATTERNS,
        is_mesh_fault,
        MESH_FAULT_MARKERS,
    )

    for m in MESH_FAULT_MARKERS:
        assert m in TRANSIENT_PATTERNS
    assert not is_mesh_fault(FakeJaxRuntimeError(TRANSIENT_MSG))
    assert not is_mesh_fault(
        FakeJaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")
    )


def test_cli_single_source_recovers(capsys, monkeypatch):
    # End-to-end: the first distributed advance dies with the round-2
    # failure; the CLI rebuilds the engine, resumes, and still validates.
    from tpu_bfs import cli

    calls = {"n": 0}
    real_advance = DistBfsEngine.advance

    def flaky(self, ckpt, levels=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeJaxRuntimeError(TRANSIENT_MSG)
        return real_advance(self, ckpt, levels)

    monkeypatch.setattr(DistBfsEngine, "advance", flaky)
    rc = cli.main(["3", "random:n=300,m=1200,seed=5", "--devices", "2",
                   "--ckpt", "/tmp/recov_cli.npz", "--ckpt-every", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[recovery]" in out and "Output OK" in out


def test_cli_multi_source_recovers(capsys, monkeypatch):
    from tpu_bfs import cli
    from tpu_bfs.parallel.dist_msbfs_wide import DistWideMsBfsEngine

    calls = {"n": 0}
    real_advance = DistWideMsBfsEngine.advance

    def flaky(self, ckpt, levels=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FakeJaxRuntimeError(TRANSIENT_MSG)
        return real_advance(self, ckpt, levels)

    monkeypatch.setattr(DistWideMsBfsEngine, "advance", flaky)
    rc = cli.main(["3", "random:n=300,m=1200,seed=5", "--devices", "2",
                   "--multi-source", "9", "--engine", "wide",
                   "--ckpt", "/tmp/recov_cli2.npz"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[recovery]" in out and "Output OK" in out


def test_recovery_backend_init_failure_is_not_retried(random_small):
    # A backend that fails to initialize is not transient: the rebuild
    # surfaces it on the first occurrence instead of sleeping and
    # re-probing a chip that will not come up.
    g = random_small
    init_msg = (
        "Unable to initialize backend 'tpu': UNAVAILABLE: TPU "
        "initialization failed"
    )
    assert not is_transient_failure(RuntimeError(init_msg))
    builds = []

    def make():
        builds.append(1)
        raise RuntimeError(init_msg)

    first = _flaky_engine_factory(g, fail_times=[1])()
    # First advance blips (transient), triggering a rebuild; the rebuild
    # hits the init failure and it propagates at once.
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        advance_with_recovery(
            make, first.start(42), engine=first, levels_per_chunk=1,
            max_restarts=3,
        )
    assert builds == [1]
