"""Helpers of the benchmark's own tests: a copy of the benchmark tree at
tiny sizes, one run of a cell in this process, and the fixtures that
steer a run past the look for a chip. Test files import the fixtures
by name (a second ``conftest.py`` here would shadow the suite's own).

The tests run on the CPU; no number a test prints is a device number.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: Tiny sizes for each configuration and cell: graph scale and traffic.
TINY_CONFIGS = {
    "graph500-s21": {"scale": 10},
    "graph500-s22-serve": {
        "scale": 9,
        "server_flags": ["--engine", "wide", "--lanes", "64", "--ladder",
                         "32,64", "--statsz-interval-s", "0.2"],
    },
}
TINY_PARAMS = {
    "g500-s21-batch": {"batch": 64, "cli_flags": [
        "--engine", "hybrid", "--lanes", "64", "--planes", "5"]},
    "g500-s21-keys64": {"batch": 16},
    "g500-s22-serve-nodist": {"outstanding": 64},
}


def _update_json(path: str, changes: dict) -> None:
    with open(path) as f:
        doc = json.load(f)
    doc.update(changes)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def make_tiny_tree(dest: str) -> str:
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` under ``dest`` with
    every configuration and cell cut to a CPU-sized graph and traffic."""
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for name, changes in TINY_CONFIGS.items():
        _update_json(os.path.join(dest, "benchmark", "configs",
                                  f"{name}.json"), changes)
    for name, changes in TINY_PARAMS.items():
        path = os.path.join(dest, "benchmark", "workloads", f"{name}.json")
        with open(path) as f:
            params = json.load(f)["params"]
        _update_json(path, {"params": dict(params, **changes)})
    return dest


def run_cell(run, root: str, cell: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0, prepare=None) -> dict:
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, root=root, prepare=prepare)


@pytest.fixture
def tiny_tree(tmp_path):
    return make_tiny_tree(str(tmp_path / "tree"))


@pytest.fixture
def off_chip(monkeypatch):
    """Steer a run past the harness's look for a chip and its check that
    the program lives in the tree it runs from."""
    import jax

    from benchmark import run

    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "check_program", lambda root: None)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    return run
