"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m benchmark.run ...`` from the checkout root is the same.)

Set-up: load the configuration's dataset (generated once and cached under
``benchmark/.cache``), let the cell's traffic driver build the system and
warm up every shape the window uses, each phase on its own line. Then the
window: ``--seconds`` of the cell's traffic, with the profiler on when
``--trace 1``. After it: the device's peak memory, the program's state
freed, and the comparison with the plain reference that decides
``correct``, whose numbers and limits are the last lines on standard
error. The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``.

The run exits nonzero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where the program under test is
not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Run as a script, Python puts this directory first on the path, where its
# module names could shadow others; the checkout root goes there instead.
if sys.path and os.path.abspath(sys.path[0]) == os.path.dirname(
        os.path.abspath(__file__)):
    sys.path.pop(0)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# The TPU runtime logs to a fixed /tmp path unless told otherwise; keep
# them inside the checkout.
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, "benchmark", ".cache", "tpu_logs"))

from benchmark import data, harness, trace_reduce  # noqa: E402
from benchmark.harness import log  # noqa: E402


class NotRunnable(RuntimeError):
    """No chip, too few chips, or no program: nothing is measured."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the traffic (any whole number >= 0)")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window, report per-layer metrics")
    return ap.parse_args(argv)


def check_program(root: str) -> None:
    """The program under test has to be the checkout's own."""
    try:
        import tpu_bfs
    except ImportError as exc:
        raise NotRunnable(f"the program is not in {root}: {exc}") from None
    where = os.path.abspath(tpu_bfs.__file__)
    if os.path.commonpath([where, root]) != root:
        raise NotRunnable(f"tpu_bfs comes from {where}, not from {root}")


def require_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NotRunnable(f"JAX finds no TPU: {devices}")
    if len(devices) < chips:
        raise NotRunnable(f"the cell needs {chips} chips, JAX finds "
                          f"{len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout), keeping every
    program however short its compile, so only a cell's first run in a
    checkout compiles."""
    import jax

    from tpu_bfs.utils.compile_cache import enable_compile_cache as enable

    log(f"[setup] compile cache: {enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _trace_dir(root: str, cell: str) -> str:
    d = os.path.join(root, "benchmark", ".cache", "trace", cell)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, root: str = ROOT, t_start: float = T_START,
        prepare=None) -> dict:
    """One run of a cell; returns the result line's object. ``prepare``
    (the control's hook) is called on the traffic driver before set-up."""
    cell = harness.load_cell(args.workload, root)
    check_program(root)
    devices = require_devices(cell.chips)
    import jax

    enable_compile_cache()
    compiles = harness.CompileListener().install()
    phases = harness.Phases(compiles)
    log(f"[setup] jax {jax.__version__}; devices {devices}")
    with phases.phase("graph_load"):
        ds = data.load(cell.config_name, data.generator_of(cell.config),
                       os.path.join(root, "benchmark", ".cache"), log=log)
    driver = cell.driver_module().Driver(cell, ds, args.seed)
    if prepare is not None:
        prepare(driver)
    driver.setup(phases)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s {setup_s:.3f} s")

    compiled_before = compiles.backend_compiles
    trace_dir = _trace_dir(root, cell.name) if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            driver.window(args.seconds, jax.profiler.TraceAnnotation)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    log(f"[window] backend compiles inside the window: "
        f"{compiles.backend_compiles - compiled_before}")
    driver.finish()
    driver.report()
    peak = _peak_bytes(devices[: cell.chips])
    summary = None
    if trace_dir:
        events = trace_reduce.load_events(trace_reduce.find_trace(trace_dir))
        if any(e.plane.startswith(trace_reduce.DEVICE_PREFIX) for e in events):
            summary = trace_reduce.reduce(events)
            log(f"[trace] window {summary.window_s:.6f} s, device busy "
                f"{summary.busy_s:.6f} s over {summary.devices} device(s)")
        else:
            log("[trace] the trace holds no TPU plane: no device metric")
    e2e = dict(driver.end_to_end(), setup_s=setup_s)
    attempted, failed = driver.attempted_failed()
    counters = driver.counters
    driver.release()
    compared = driver.compare()

    metrics = {}
    if not args.trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(trace=summary, counters=counters,
                                    device_kind=devices[0].device_kind)
        for m in cell.per_layer:
            v = cell.reader_module(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in compared), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["compared"] = {c.name: c.as_dict() for c in compared}
    for c in compared:
        log(f"compared {c.name} = {c.value} (limit {'>=' if c.at_least else '<='}"
            f" {c.limit}) {'ok' if c.ok else 'FAILED'}")
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run(args)
    except NotRunnable as exc:
        log(f"benchmark: not run: {exc}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
