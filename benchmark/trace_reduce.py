"""From a profiler trace to device busy time, per-name device time and
labelled idle gaps.

``load_events`` reads the ``.xplane.pb`` the JAX profiler writes with
nothing but JAX (``jax.profiler.ProfileData``) and flattens it into
``Event`` rows; ``reduce`` works on those rows alone, so it can be checked
on a small recorded trace.

- Device planes are those named ``/device:TPU:<n>``. A device is busy
  while an event of its ``XLA Ops`` line runs; busy time is the length of
  the union of those intervals inside the window, averaged over devices.
- The window is the host span the benchmark wraps around its measured
  window (``bench.window``, a ``jax.profiler.TraceAnnotation``).
- Per-name time sums the durations of ``XLA Ops`` events (kernels and
  fusions) and, apart, of ``XLA Modules`` events (whole jitted programs),
  each clipped to the window.
- An idle gap is a stretch of the window in which the first device runs
  nothing. It is labelled by the innermost benchmark span (``bench.*``)
  that covers its midpoint, or ``after <span>`` by the last one that
  ended before it.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NAME_CHARS = 160  # an op's name is its HLO text; the breakdown keeps its head


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over devices
    devices: int
    op_s: dict  # name -> device seconds (XLA Ops), summed over devices
    op_count: dict  # name -> number of events
    module_s: dict  # name -> device seconds (XLA Modules)
    module_count: dict
    gaps: list  # [(label, seconds)], longest first, first device

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_events(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        keep_plane = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if keep_plane and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not keep_plane and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ev: Event, lo: float, hi: float) -> float:
    return max(0.0, min(ev.end_ns, hi) - max(ev.start_ns, lo))


def _self_times(ops: list, lo: float, hi: float) -> list:
    """(event, own ns inside [lo, hi]) for each op: an op that encloses
    others on the line (a ``while`` around its body's ops) keeps only the
    time no op nested in it covers, so per-name sums count each moment
    once."""
    out = []
    stack = []  # [event, own ns]
    for e in sorted(ops, key=lambda x: (x.start_ns, -x.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= _clip(e, lo, hi)
        stack.append([e, _clip(e, lo, hi)])
    out.extend(tuple(x) for x in reversed(stack))
    return [(e, max(own, 0.0)) for e, own in out]


def _label(mid: float, spans: list) -> str:
    inner = None
    for sp in spans:
        if sp.name != WINDOW_SPAN and sp.start_ns <= mid <= sp.end_ns:
            if inner is None or sp.dur_ns < inner.dur_ns:
                inner = sp
    if inner is not None:
        return inner.name
    before = [sp for sp in spans
              if sp.name != WINDOW_SPAN and sp.end_ns <= mid]
    if before:
        return "after " + max(before, key=lambda sp: sp.end_ns).name
    return "window"


def reduce(events: list, *, top: int = 10) -> TraceSummary:
    spans = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)]
    windows = [e for e in spans if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    device_planes = sorted({e.plane for e in events
                            if e.plane.startswith(DEVICE_PREFIX)})
    if not device_planes:
        raise ValueError("the trace holds no device plane")
    op_s, op_n, mod_s, mod_n = {}, {}, {}, {}
    busy = []
    gaps = []
    for k, plane in enumerate(device_planes):
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE
               and e.end_ns > lo and e.start_ns < hi]
        mods = [e for e in events if e.plane == plane
                and e.line == MODULES_LINE and e.end_ns > lo
                and e.start_ns < hi]
        for e, own in _self_times(ops, lo, hi):
            op_s[e.name] = op_s.get(e.name, 0.0) + own / 1e9
            op_n[e.name] = op_n.get(e.name, 0) + 1
        for e in mods:
            mod_s[e.name] = mod_s.get(e.name, 0.0) + _clip(e, lo, hi) / 1e9
            mod_n[e.name] = mod_n.get(e.name, 0) + 1
        merged = _union((max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2])
                           if e > s), key=lambda g: g[0] - g[1])[:top]
    gaps = [(_label((s + e) / 2, spans), (e - s) / 1e9) for s, e in gaps]
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy),
        devices=len(device_planes),
        op_s=op_s, op_count=op_n, module_s=mod_s, module_count=mod_n,
        gaps=gaps[:top],
    )


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took the
    most time and the longest idle gaps, at most ``top`` of each."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n[:NAME_CHARS], s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:top]]}


def main(argv=None) -> int:
    """Print what a trace holds, for a look by hand: each plane's lines
    with their event counts and most frequent names."""
    import argparse
    import collections

    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("log_dir")
    ap.add_argument("--names", type=int, default=12)
    args = ap.parse_args(argv)
    path = find_trace(args.log_dir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            total = collections.Counter()
            for e in evs:
                total[e.name] += e.duration_ns
            t = [e.start_ns for e in evs]
            span = f"{min(t):.0f}..{max(t):.0f}" if t else "-"
            print(f"  line {line.name!r}: {len(evs)} events, start_ns {span}")
            for name, ns in total.most_common(args.names):
                print(f"    {names[name]:6d} {ns / 1e6:12.3f} ms  {name[:160]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
