"""Discovery by name: every declared cell, configuration, traffic kind and
metric resolves to a file of its own, and a new cell, configuration and
metric need only new files (and new entries in BENCHMARK.json)."""

from __future__ import annotations

import json
import os
import re

import pytest

from bench_helpers import REPO, off_chip, run_cell, tiny_tree  # noqa: F401

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_has_exactly_the_contract_keys():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert [m["name"] for m in bench["end_to_end"]] == [
        "batch_gteps", "keys64_gteps", "serve_qps", "setup_s"]
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
    assert os.path.isfile(os.path.join(REPO, bench["command"][-1]))


def test_names_units_and_keys_are_within_the_contract():
    bench = _bench()
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
            if "unit" in entry:
                assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", entry["unit"])
                assert entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell, REPO)
    assert c.config_name == c.workload["config"]
    assert hasattr(c.driver_module(), "Driver")
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in reported
        assert callable(c.reader_module(m["name"]).read)


def test_every_config_file_lies_under_the_paths_and_is_used():
    bench = _bench()
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for cfg in bench["configs"]:
        assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
        assert cfg["name"] in used
        with open(os.path.join(REPO, cfg["file"])) as f:
            doc = json.load(f)
        assert sorted(doc["reduced"]) == sorted(cfg["reduced"])
        files.add(cfg["file"])
    assert len(files) == len(bench["configs"])


def test_a_split_metric_falls_back_to_the_reader_of_its_quantity(tmp_path):
    """``<name>.<suffix>`` is read by ``metrics/<name>.<suffix>.py`` where
    that file exists, else by ``metrics/<name>.py``."""
    metrics = tmp_path / "benchmark" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "q.py").write_text("def read(ctx):\n    return 1\n")
    (metrics / "q.own.py").write_text("def read(ctx):\n    return 2\n")
    cell = harness.Cell(name="c", chips=1, config_name="x", config={},
                        workload={}, end_to_end=[], per_layer=[],
                        root=str(tmp_path))
    assert cell.reader_module("q.any").read(None) == 1
    assert cell.reader_module("q.own").read(None) == 2
    assert cell.reader_module("q").read(None) == 1
    with pytest.raises(FileNotFoundError):
        cell.reader_module("r.any")


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", REPO)


def test_new_cell_config_and_metric_need_only_new_files(tiny_tree, off_chip):
    """A configuration, a cell and a metric added as files and entries run
    with no edit to any file that was there before."""
    root = tiny_tree
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    with open(os.path.join(root, "benchmark", "configs",
                           "graph500-s21.json")) as f:
        cfg = json.load(f)
    cfg["scale"] = 9
    with open(os.path.join(root, "benchmark", "configs", "tiny-new.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "tiny-new-cell.json"), "w") as f:
        json.dump({"config": "tiny-new", "traffic": "batch-32",
                   "kind": "batch", "why": "a test cell",
                   "params": {"batch": 32, "cli_flags": []}}, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "batches_run.new.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.counters.get('batches')\n")
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-new", "source": "a test",
                             "file": "benchmark/configs/tiny-new.json",
                             "reduced": ["scale"], "why": "a test"})
    bench["workloads"].append({"name": "tiny-new-cell", "config": "tiny-new",
                               "traffic": "batch-32", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("tiny-new-cell")
    bench["per_layer"].append({
        "name": "batches_run.new", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "batch_gteps",
        "workloads": ["tiny-new-cell"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)

    out = run_cell(off_chip, root, "tiny-new-cell", seconds=0.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"batch_gteps", "setup_s"}
    traced = run_cell(off_chip, root, "tiny-new-cell", seconds=0.5, trace=1)
    assert traced["metrics"]["batches_run.new"]["value"] >= 1
    for p, content in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == content, f"{p} was edited"
