"""What every cell shares: discovery by name, set-up phases, compile
counting, and the numbers compared for ``correct``.

Discovery is by file name only. A cell ``<cell>`` of ``BENCHMARK.json``
reads ``workloads/<cell>.json``; that names its configuration
(``configs/<config>.json``) and its traffic kind, whose driver is
``traffic/<kind>.py``; each per-layer metric ``<metric>`` is read by
``metrics/<metric>.py``, or, for a quantity split by the end-to-end
metric it moves (``<name>.<suffix>``), by ``metrics/<name>.py``. Adding a cell, a configuration, a traffic kind or
a metric is adding files and entries; no file here names any of them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the file at ``path`` as module ``name`` (file names may hold
    dots, so they are not importable by the usual dotted path)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config_name: str
    config: dict
    workload: dict
    end_to_end: list  # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list  # BENCHMARK.json per_layer entries this cell reports
    root: str

    @property
    def kind(self) -> str:
        return self.workload["kind"]

    @property
    def params(self) -> dict:
        return self.workload["params"]

    def driver_module(self):
        return load_module(
            os.path.join(self.root, "benchmark", "traffic", f"{self.kind}.py"),
            f"benchmark_traffic_{self.kind}")

    def reader_module(self, metric: str):
        """``metrics/<metric>.py``, else the reader of the quantity it
        splits, ``metrics/<name>.py`` for ``<name>.<suffix>``."""
        metrics = os.path.join(self.root, "benchmark", "metrics")
        path = os.path.join(metrics, f"{metric}.py")
        if not os.path.isfile(path) and "." in metric:
            path = os.path.join(metrics, f"{metric.split('.', 1)[0]}.py")
        return load_module(path, f"benchmark_metric_{metric.replace('.', '_')}")


def _reports(entry: dict, cell: str, reported: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    # Without a list, a per-layer metric is due wherever the end-to-end
    # metric it moves is reported; an end-to-end metric, everywhere.
    moves = entry.get("moves")
    return moves is None or moves in reported


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(entries)})")
    entry = entries[name]
    workload = load_json(os.path.join(root, "benchmark", "workloads",
                                      f"{name}.json"))
    for key in ("config", "traffic"):
        if workload[key] != entry[key]:
            raise ValueError(f"workloads/{name}.json has {key} "
                             f"{workload[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    f"{entry['config']}.json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, reported)]
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                workload=workload, end_to_end=e2e, per_layer=per_layer,
                root=root)


class CompileListener:
    """Sums JAX's ``/jax/core/compile/*`` event durations, as the
    program's chip smoke does, and counts backend compiles."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1

    def install(self) -> "CompileListener":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


class Phases:
    """Set-up phases, each printed on its own line with its compile share."""

    def __init__(self, compiles: CompileListener):
        self.compiles = compiles
        self.walls: dict = {}
        self.compile_s: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.compiles.seconds, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compiles.seconds - c0
        self.walls[name] = self.walls.get(name, 0.0) + wall
        self.compile_s[name] = self.compile_s.get(name, 0.0) + comp
        log(f"[setup] {name}: {wall:.3f} s, of which compile {comp:.3f} s")


@dataclasses.dataclass
class Compared:
    """One number of the ``correct`` comparison and its limit: correct
    needs ``value <= limit`` (or ``value >= limit`` when ``at_least``)."""

    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if self.at_least:
            return self.value >= self.limit
        return self.value <= self.limit

    def as_dict(self) -> dict:
        return {"value": self.value, "limit": self.limit,
                "rule": ">=" if self.at_least else "<="}


def program_graph(dataset, ingest: dict):
    """The program's own ingestion of the dataset's edge tuples
    (``tpu_bfs.graph.io.from_edges``), as its RMAT loader calls it."""
    import numpy as np

    from tpu_bfs.graph.io import from_edges

    u, v = dataset.u, dataset.v
    if ingest.get("drop_self_loops", True):
        keep = u != v
        u, v = u[keep], v[keep]
    return from_edges(
        u.astype(np.int64), v.astype(np.int64),
        num_vertices=dataset.num_vertices, directed=False,
        num_input_edges=dataset.num_input_edges,
        dedup=bool(ingest.get("dedup", False)),
    )


def draw_keys(rng, eligible, n: int):
    """``n`` distinct search keys drawn from ``eligible``."""
    return eligible[rng.choice(len(eligible), size=n, replace=False)]
