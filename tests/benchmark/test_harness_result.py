"""The result line, and the runs that must print none."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_helpers import REPO, off_chip, tiny_tree  # noqa: F401

from benchmark import run


def test_no_tpu_exits_nonzero_before_any_phase(capsys):
    """On the CPU the look for a chip fails: no result line."""
    rc = run.main(["--workload", "g500-s21-batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err and "[setup]" not in err


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    class Fake:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(run.NotRunnable, match="needs 4 chips"):
        run.require_devices(4)


def test_a_tree_without_the_program_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    cannot run: the program under test is missing."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "g500-s21-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "program is not in" in proc.stderr


@pytest.mark.parametrize("cell", ["g500-s21-batch", "g500-s22-serve-nodist"])
def test_last_line_keys(cell, tiny_tree, off_chip, capsys, monkeypatch):
    whole_run = run.run
    monkeypatch.setattr(run, "run",
                        lambda args: whole_run(args, root=tiny_tree))
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 9),
                   "--seconds", "0.5", "--trace", "0"])
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(last)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert "setup_s" in res["metrics"]
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in res["compared"].values():
        assert set(c) == {"value", "limit", "rule"}
