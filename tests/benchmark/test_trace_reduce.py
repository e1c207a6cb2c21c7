"""The reduction from trace events to busy time, per-name time and
labelled idle gaps."""

from __future__ import annotations

import os

import pytest

import bench_helpers  # noqa: F401  (puts the checkout root on the path)

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def _events():
    return [
        ev(HOST, "py", "bench.window", 1000, 10000),
        ev(HOST, "py", "bench.batch", 1000, 6000),
        ev(HOST, "py", "bench.read_rows", 7500, 1000),
        # Device 0: two ops back to back, a gap, one op crossing the end.
        ev(DEV0, tr.OPS_LINE, "fusion.1", 1500, 2000),
        ev(DEV0, tr.OPS_LINE, "tile_spmm", 3500, 500),
        ev(DEV0, tr.OPS_LINE, "fusion.1", 8000, 4000),
        ev(DEV0, tr.MODULES_LINE, "jit_core(123)", 1500, 2500),
        # Before the window: left out.
        ev(DEV0, tr.OPS_LINE, "fusion.9", 0, 500),
        # Device 1: busy for 2000 ns.
        ev(DEV1, tr.OPS_LINE, "fusion.2", 2000, 2000),
    ]


def test_busy_union_idle_share_and_per_name_time():
    s = tr.reduce(_events())
    assert s.window_s == pytest.approx(10000e-9)
    assert s.devices == 2
    # Device 0: [1500, 4000) and [8000, 11000) inside the window = 5500 ns;
    # device 1: 2000 ns; the mean is 3750 ns.
    assert s.busy_s == pytest.approx(3750e-9)
    assert s.idle_share == pytest.approx(1 - 3750 / 10000)
    assert s.op_s["fusion.1"] == pytest.approx((2000 + 3000) * 1e-9)
    assert s.op_count["fusion.1"] == 2
    assert s.op_s["tile_spmm"] == pytest.approx(500e-9)
    assert "fusion.9" not in s.op_s
    assert s.module_s == {"jit_core(123)": pytest.approx(2500e-9)}


def test_gaps_are_labelled_by_the_benchmark_spans():
    s = tr.reduce(_events())
    # Device 0 idles [1000, 1500), [4000, 8000): the long gap's midpoint
    # (6000) lies in bench.batch; the short one (1250) too.
    assert s.gaps[0] == ("bench.batch", pytest.approx(4000e-9))
    assert s.gaps[1] == ("bench.batch", pytest.approx(500e-9))
    br = tr.breakdown(s)
    assert br["device_ops"][0][0] == "fusion.1"
    assert br["idle_gaps"][0][0] == "bench.batch"


def test_a_gap_after_every_span_is_labelled_by_the_last():
    events = [ev(HOST, "py", "bench.window", 0, 1000),
              ev(HOST, "py", "bench.request", 100, 10),
              ev(DEV0, tr.OPS_LINE, "x", 0, 100)]
    s = tr.reduce(events)
    assert s.gaps[0][0] == "after bench.request"


def test_an_enclosing_op_keeps_only_its_own_time():
    """A ``while`` op spans its body's ops on the same line: per-name time
    counts each moment once, and busy time is the union."""
    events = [ev(HOST, "py", "bench.window", 0, 1000),
              ev(DEV0, tr.OPS_LINE, "%while.1", 100, 800),
              ev(DEV0, tr.OPS_LINE, "%fusion.2", 150, 300),
              ev(DEV0, tr.OPS_LINE, "%fusion.3", 500, 200)]
    s = tr.reduce(events)
    assert s.op_s["%while.1"] == pytest.approx(300e-9)
    assert s.op_s["%fusion.2"] == pytest.approx(300e-9)
    assert s.busy_s == pytest.approx(800e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_the_small_recorded_trace():
    """A trace recorded on a v5e (``record_small_trace.py``): three runs of
    one jitted program inside ``bench.batch`` spans, with host sleeps
    between them."""
    s = tr.reduce(tr.load_events(os.path.join(HERE, "data",
                                              "small.xplane.pb")))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.026752192, rel=1e-6)
    assert s.busy_s == pytest.approx(2.6792e-05, rel=1e-3)
    assert 0.99 < s.idle_share < 1.0
    # The device's clock runs about 0.9 ms ahead of the host's in this
    # trace, so the first run lands before the window's host span opens
    # and is left out; a 10 s window loses at most that much at each end.
    (module, seconds), = s.module_s.items()
    assert module.startswith("jit_core") and s.module_count[module] == 2
    assert seconds == pytest.approx(2 * 13.4e-6, rel=0.01)
    fusion = [k for k in s.op_s if k.startswith("%fusion ")]
    assert len(fusion) == 1 and s.op_count[fusion[0]] == 2
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    # The three long gaps are the host sleeping after each batch's span.
    assert [g[0] for g in s.gaps[:3]] == ["after bench.batch"] * 3
    assert all(g[1] > 0.008 for g in s.gaps[:3])


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce([ev(DEV0, tr.OPS_LINE, "x", 0, 1)])
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce([ev(HOST, "py", "bench.window", 0, 1)])
