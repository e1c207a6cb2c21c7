"""CLI end-to-end tests (the reference's main() flow, bfs.cu:783-823).

Run through cli.main() in-process on CPU with generated graphs; every run
includes the golden validation step, so a passing exit code means the full
load -> CPU golden -> device BFS -> checkOutput pipeline agreed.
"""

import numpy as np
import pytest

from tpu_bfs import cli


def test_cli_single_source_validates(capsys, tmp_path):
    dist_path = tmp_path / "d.npy"
    rc = cli.main(
        ["3", "random:n=300,m=1200,seed=5", "--stats",
         "--save-dist", str(dist_path)]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "Number of vertices 300" in out
    assert "Output OK" in out
    assert '"level"' in out  # --stats JSON lines
    d = np.load(dist_path)
    assert d.shape == (300,) and d[3] == 0


def test_cli_file_graph(capsys, tmp_path):
    p = tmp_path / "g.txt"
    p.write_text("4 3\n0 1\n1 2\n2 3\n")
    rc = cli.main(["0", str(p), "--no-parents"])
    assert rc == 0
    assert "Reached 4 vertices in 3 levels" in capsys.readouterr().out


def test_cli_multi_source_engines(capsys):
    for engine in ("packed", "wide", "hybrid"):
        rc = cli.main(
            ["0", "random:n=200,m=900,seed=3",
             "--multi-source", "5,9", "--engine", engine]
        )
        out = capsys.readouterr().out
        assert rc == 0, engine
        assert "Output OK" in out, engine
        assert "source 9:" in out, engine


def test_cli_distributed(capsys):
    rc = cli.main(["1", "random:n=250,m=1000,seed=8", "--devices", "4"])
    assert rc == 0
    assert "Output OK" in capsys.readouterr().out


def test_cli_truncation_exits_with_hint(tmp_path):
    # 64-vertex path exceeds the wide engine's default 32-level cap; the CLI
    # must exit with the --planes/--engine hint, not a raw traceback.
    p = tmp_path / "path.txt"
    lines = ["64 63"] + [f"{i} {i+1}" for i in range(63)]
    p.write_text("\n".join(lines) + "\n")
    with pytest.raises(SystemExit, match="--planes 8"):
        cli.main(["0", str(p), "--multi-source", "1", "--engine", "wide"])
    # And the suggested remedies work.
    assert cli.main(["0", str(p), "--multi-source", "1", "--engine", "wide",
                     "--planes", "8"]) == 0
    assert cli.main(["0", str(p), "--multi-source", "1",
                     "--engine", "packed"]) == 0


def test_cli_checkpoint_resume_roundtrip(capsys, tmp_path):
    ck = str(tmp_path / "st.npz")
    # Checkpointed run: chunked advancing, still golden-validated at the end.
    rc = cli.main(["2", "random:n=300,m=1200,seed=5", "--ckpt", ck,
                   "--ckpt-every", "1"])
    out = capsys.readouterr().out
    assert rc == 0 and "Output OK" in out and "checkpointed at level" in out
    # Resuming the FINISHED checkpoint immediately finishes and validates
    # (source comes from the checkpoint, not argv).
    rc = cli.main(["0", "random:n=300,m=1200,seed=5", "--resume", ck])
    out = capsys.readouterr().out
    assert rc == 0 and "resumed source 2" in out and "Output OK" in out
    # And on a 4-device mesh (elastic restart).
    rc = cli.main(["0", "random:n=300,m=1200,seed=5", "--resume", ck,
                   "--devices", "4"])
    assert rc == 0 and "Output OK" in capsys.readouterr().out


def test_cli_rejects_bad_source():
    with pytest.raises(SystemExit):
        cli.main(["999", "random:n=100,m=300,seed=1"])


def test_cli_multi_source_distributed(capsys, tmp_path):
    # One binary reaches the distributed MS engines (the reference reaches
    # every capability from its single binary, README.md:13,22).
    out = tmp_path / "p.npy"
    for engine, exchange in (("hybrid", "ring"), ("wide", "sparse")):
        rc = cli.main(
            ["0", "random:n=200,m=900,seed=3", "--devices", "4",
             "--multi-source", "7,19", "--engine", engine,
             "--exchange", exchange, "--save-parent", str(out)]
        )
        assert rc == 0
        assert "Output OK" in capsys.readouterr().out
        assert np.load(out).shape == (3, 200)


def test_cli_multi_source_distributed_ckpt(capsys, tmp_path):
    ck = tmp_path / "ck.npz"
    rc = cli.main(
        ["0", "random:n=200,m=900,seed=3", "--devices", "2",
         "--multi-source", "7", "--ckpt", str(ck), "--ckpt-every", "1"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "checkpoint @ level" in out and "Output OK" in out
    rc = cli.main(
        ["0", "random:n=200,m=900,seed=3", "--devices", "2",
         "--multi-source", "7", "--resume", str(ck)]
    )
    assert rc == 0
    assert "Output OK" in capsys.readouterr().out


def test_cli_rejects_multi_source_2d_mesh():
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--mesh", "2x2",
                  "--multi-source", "1"])


def test_cli_rejects_packed_engine_multichip():
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--devices", "2",
                  "--multi-source", "1", "--engine", "packed"])


def test_cli_rejects_allreduce_multi_source_multichip():
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--devices", "2",
                  "--multi-source", "1", "--exchange", "allreduce"])


def test_cli_multi_source_lanes_flag(capsys):
    # --lanes reaches every packed engine (single-chip and distributed)
    # from the one binary; 8192 selects the wider (w=256) rows.
    for extra in (
        ["--engine", "wide", "--lanes", "8192"],
        ["--engine", "hybrid", "--lanes", "8192"],
        ["--engine", "wide", "--lanes", "8192", "--devices", "2"],
    ):
        rc = cli.main(
            ["0", "random:n=200,m=900,seed=3", "--multi-source", "5,9"]
            + extra
        )
        out = capsys.readouterr().out
        assert rc == 0, extra
        assert "Output OK" in out, extra


def test_cli_resume_derives_width_from_checkpoint(capsys, tmp_path):
    # A checkpoint written at an explicit narrower width must resume
    # WITHOUT --lanes even though the engine default is wider now (the
    # default moved 4096 -> 8192 lanes in round 4): the CLI derives the
    # engine width from the checkpoint's packed tables. An explicit
    # mismatched --lanes still gets the descriptive rejection.
    ck = tmp_path / "ck.npz"
    rc = cli.main(
        ["0", "random:n=200,m=900,seed=3", "--multi-source", "7",
         "--engine", "wide", "--lanes", "64",
         "--ckpt", str(ck), "--ckpt-every", "1"]
    )
    assert rc == 0
    capsys.readouterr()
    rc = cli.main(
        ["0", "random:n=200,m=900,seed=3", "--multi-source", "7",
         "--engine", "wide", "--resume", str(ck)]
    )
    out = capsys.readouterr().out
    assert rc == 0 and "(64 lanes)" in out and "Output OK" in out
    with pytest.raises(Exception):
        cli.main(
            ["0", "random:n=200,m=900,seed=3", "--multi-source", "7",
             "--engine", "wide", "--resume", str(ck), "--lanes", "96"]
        )


def test_console_entry_points_resolve():
    # pyproject's [project.scripts] must keep pointing at callables that
    # accept argv=None (the console-script calling convention) — a rename
    # in cli/graph500 would otherwise ship a broken `tpu-bfs` binary.
    import importlib
    import inspect
    import os

    tomllib = pytest.importorskip(
        "tomllib", reason="stdlib tomllib needs Python 3.11+"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "pyproject.toml"), "rb") as f:
        scripts = tomllib.load(f)["project"]["scripts"]
    assert set(scripts) == {"tpu-bfs", "tpu-bfs-graph500", "tpu-bfs-serve",
                            "tpu-bfs-analyze"}
    for target in scripts.values():
        mod, fn = target.split(":")
        func = getattr(importlib.import_module(mod), fn)
        sig = inspect.signature(func)
        assert all(
            p.default is not inspect.Parameter.empty
            for p in sig.parameters.values()
        ), target  # callable with zero args


def test_cli_wire_pack_distributed(capsys):
    # --wire-pack reaches the 1D and 2D engines and results still
    # validate (packing is wire encoding only — ISSUE 5).
    rc = cli.main(["1", "random:n=250,m=1000,seed=8", "--devices", "4",
                   "--wire-pack"])
    assert rc == 0
    assert "Output OK" in capsys.readouterr().out


def test_cli_rejects_wire_pack_single_chip():
    # A single chip moves nothing over the wire; packing there is a
    # config error, not a silent no-op.
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--wire-pack"])


def test_cli_sparse_delta_planner(capsys):
    # The ISSUE 7 planner flags reach the 1D engine through the sparse
    # exchange and results still validate (delta/sieve/predict are wire
    # encoding + selection policy only).
    rc = cli.main(["1", "random:n=250,m=1000,seed=8", "--devices", "4",
                   "--exchange", "sparse", "--sparse-delta",
                   "--sparse-sieve", "--sparse-predict"])
    assert rc == 0
    assert "Output OK" in capsys.readouterr().out


def test_cli_rejects_planner_flag_misuse():
    # Planner flags without the sparse exchange (or off-mesh) are config
    # errors, not silent no-ops.
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--sparse-delta"])
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--devices", "2",
                  "--sparse-delta"])  # exchange defaults to ring
    with pytest.raises(SystemExit):
        cli.main(["0", "random:n=100,m=300,seed=1", "--devices", "2",
                  "--exchange", "sparse", "--multi-source", "5",
                  "--sparse-sieve"])  # sieve is single-source only
