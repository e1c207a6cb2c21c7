"""The program's own names in a profiler trace: device time per level-loop
phase, and the per-batch host spans with their stats.

The program names its level loop's phases on the device with
``tpu_bfs.obs.scope`` (``level.gather``, ``level.claim``, ...; a core of
width W inside ``lanes<W>``), which XLA keeps as each op's ``op_name``
metadata, and its per-batch host steps with ``tpu_bfs.obs.host_span``
(``tpu_bfs.serve.dispatch``, ...), ``jax.profiler`` annotations whose stats
the program computes while the profiler records. The trace carries the
first as each ``XLA Ops`` event's ``tf_op`` metadata stat, which
``jax.profiler.ProfileData`` does not expose, so this module decodes the
``.xplane.pb`` itself: a protobuf wire decoder over XSpace > XPlane >
XLine > XEvent and each plane's event and stat metadata, with nothing but
the standard library.

- ``find(trace_s)`` picks the run's trace under ``benchmark/.cache/trace``:
  the newest whose ``bench.window`` lasts ``trace_s`` (the window the
  benchmark's own reduction read), so a stale trace is never read.
- An op belongs to the innermost ``level.*`` / ``batch.*`` component of its
  ``tf_op`` and to the ``lanes<W>`` component around it. Per-scope time is
  self time inside the window (``trace_reduce._self_times``): an op that
  encloses others (a ``while`` around its body) keeps only its own time.
- Host spans are the ``tpu_bfs.*`` and ``bench.*`` events of the host
  planes, with their stats.

    python3 benchmark/program_trace.py <dir or .xplane.pb>

prints device time by scope, what of the core no scope names, and the idle
gaps labelled by the innermost program span.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct
import sys

if __package__ in (None, ""):  # run as a script: the checkout root first
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from benchmark import trace_reduce  # noqa: E402

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".cache", "trace")
SPAN_PREFIXES = ("tpu_bfs.", "bench.")
SCOPE_PREFIXES = ("level.", "batch.")
CORE_PREFIX = "jit(core)/"
LANES = re.compile(r"^lanes(\d+)$")
WINDOW_TOL_S = 1e-6


@dataclasses.dataclass(frozen=True)
class Op:
    """One ``XLA Ops`` event of a device plane."""

    plane: str
    name: str  # the op's HLO text
    tf_op: str  # its op_name metadata ("" where XLA made the op)
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    """One host event of the program or the benchmark, with its stats."""

    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class ProgramTrace:
    ops: list  # [Op] inside the window, every device plane
    spans: list  # [Span], every host plane
    lo: float  # the window, ns
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def self_times(self, core_only: bool = False) -> list:
        """[(op, own seconds inside the window)], per device plane."""
        out = []
        for plane in sorted({op.plane for op in self.ops}):
            ops = [op for op in self.ops if op.plane == plane
                   and (not core_only or op.tf_op.startswith(CORE_PREFIX))]
            out += [(op, ns / 1e9) for op, ns in
                    trace_reduce._self_times(ops, self.lo, self.hi)]
        return out

    def scope_s(self, core_only: bool = True) -> dict:
        """Device seconds by innermost scope (None: no scope)."""
        out: dict = {}
        for op, s in self.self_times(core_only):
            k = scope_of(op.tf_op)
            out[k] = out.get(k, 0.0) + s
        return out

    def lanes_s(self) -> dict:
        """Device seconds by the ``lanes<W>`` around each op (None: none)."""
        out: dict = {}
        for op, s in self.self_times():
            k = lanes_of(op.tf_op)
            out[k] = out.get(k, 0.0) + s
        return out

    def in_window(self, name: str) -> list:
        """The spans named ``name`` that start inside the window."""
        return [sp for sp in self.spans
                if sp.name == name and self.lo <= sp.start_ns < self.hi]


def scope_of(tf_op: str) -> str | None:
    """The innermost ``level.*`` / ``batch.*`` component of an op name."""
    for part in reversed(tf_op.split(":", 1)[0].split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return None


def lanes_of(tf_op: str) -> int | None:
    """W of the ``lanes<W>`` component of an op name."""
    for part in tf_op.split(":", 1)[0].split("/"):
        m = LANES.match(part)
        if m:
            return int(m.group(1))
    return None


# --- protobuf wire format ---------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple:
    r = shift = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << shift
        if b < 0x80:
            return r, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field, wire type, value) of each field of buf[i:end]: an int for a
    varint, (start, end) for a length-delimited field, raw bytes else."""
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _str(buf: bytes, v) -> str:
    return buf[v[0]:v[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, rng, stat_names: dict) -> tuple:
    """(name, value) of one XStat."""
    mid, val = 0, None
    for f, _wt, v in _fields(buf, *rng):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f in (5, 6):
            val = _str(buf, v)
        elif f == 7:
            val = stat_names.get(v, "")
    return stat_names.get(mid, ""), val


def _plane(buf: bytes, rng):
    """Decode one XPlane: (name, device ops or host spans)."""
    name, lines, ev_md_raw, stat_names = "", [], [], {}
    for f, _wt, v in _fields(buf, *rng):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_md_raw.append(v)
        elif f == 5:
            for ef, _ewt, ev in _fields(buf, *v):
                if ef == 2:
                    sid, sname = 0, ""
                    for sf, _swt, sv in _fields(buf, *ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = _str(buf, sv)
                    stat_names[sid] = sname
    device = name.startswith(trace_reduce.DEVICE_PREFIX)
    md = {}  # id -> (name, tf_op)
    for rng_md in ev_md_raw:
        for ef, _ewt, ev in _fields(buf, *rng_md):
            if ef != 2:
                continue
            mid, mname, tf_op = 0, "", ""
            for mf, _mwt, mv in _fields(buf, *ev):
                if mf == 1:
                    mid = mv
                elif mf == 2:
                    mname = _str(buf, mv)
                elif mf == 5 and device:
                    k, val = _stat(buf, mv, stat_names)
                    if k == "tf_op":
                        tf_op = str(val)
            if device or mname.startswith(SPAN_PREFIXES):
                md[mid] = (mname, tf_op)
    out = []
    for lrng in lines:
        lname, ts, events = "", 0, []
        for f, _wt, v in _fields(buf, *lrng):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                ts = v
            elif f == 4:
                events.append(v)
        if device and lname != trace_reduce.OPS_LINE:
            continue
        for erng in events:
            mid, off, dur, stats = 0, 0, 0, []
            for f, _wt, v in _fields(buf, *erng):
                if f == 1:
                    mid = v
                    if mid not in md:
                        break
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4:
                    stats.append(v)
            if mid not in md:
                continue
            mname, tf_op = md[mid]
            start = ts + off / 1e3
            if device:
                out.append(Op(name, mname, tf_op, start, dur / 1e3))
            else:
                out.append(Span(mname, start, dur / 1e3, dict(
                    _stat(buf, s, stat_names) for s in stats)))
    return name, device, out


def decode(path: str) -> ProgramTrace:
    """The device ops inside the longest ``bench.window`` and every
    program and benchmark span of the trace at ``path``."""
    with open(path, "rb") as f:
        buf = f.read()
    ops, spans = [], []
    for f, _wt, v in _fields(buf, 0, len(buf)):
        if f == 1:
            _name, device, rows = _plane(buf, v)
            (ops if device else spans).extend(rows)
    windows = [sp for sp in spans if sp.name == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path} holds no {trace_reduce.WINDOW_SPAN!r} span")
    win = max(windows, key=lambda sp: sp.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    ops = [op for op in ops if op.end_ns > lo and op.start_ns < hi]
    return ProgramTrace(ops=ops, spans=spans, lo=lo, hi=hi)


_CACHE: dict = {}  # (path, mtime) -> ProgramTrace


def find(window_s: float, root: str | None = None) -> ProgramTrace | None:
    """The newest trace under ``root`` (default ``TRACE_ROOT``) whose
    window lasts ``window_s`` (within a microsecond), decoded once per
    process; None if none does."""
    paths = glob.glob(os.path.join(root or TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        key = (path, os.path.getmtime(path))
        if key not in _CACHE:
            try:
                _CACHE[key] = decode(path)
            except (OSError, ValueError, IndexError, struct.error) as exc:
                print(f"[trace] {path}: not decoded ({exc!r})",
                      file=sys.stderr, flush=True)
                _CACHE[key] = None
        pt = _CACHE[key]
        if pt is not None and abs(pt.window_s - window_s) <= WINDOW_TOL_S:
            return pt
    return None


def of(ctx) -> ProgramTrace | None:
    """The run's program trace, for a per-layer metric's reader: None
    without a traced window or where no trace under ``benchmark/.cache``
    matches it."""
    if getattr(ctx, "trace", None) is None:
        return None
    return find(ctx.trace.window_s)


def scope_ms_per_pass(ctx, scope: str) -> float | None:
    """Device ms per level-loop pass of the core's ops in ``scope``: None
    where no core op carries a ``level.*`` scope (a program without
    them) or no pass ran."""
    passes = ctx.counters.get("levels_run") if ctx.counters else None
    pt = of(ctx)
    if pt is None or not passes:
        return None
    by_scope = pt.scope_s()
    if not any(k and k.startswith("level.") for k in by_scope):
        return None
    return 1e3 * by_scope.get(scope, 0.0) / passes


# --- a look by hand ---------------------------------------------------------

def _label(mid: float, spans: list) -> str:
    inner = None
    for sp in spans:
        if sp.name != trace_reduce.WINDOW_SPAN and sp.start_ns <= mid <= sp.end_ns:
            if inner is None or sp.dur_ns < inner.dur_ns:
                inner = sp
    if inner is not None:
        return inner.name
    before = [sp for sp in spans if sp.name != trace_reduce.WINDOW_SPAN
              and sp.end_ns <= mid]
    if before:
        return "after " + max(before, key=lambda sp: sp.end_ns).name
    return "window"


def gaps(pt: ProgramTrace, top: int = 12) -> list:
    """The longest stretches in which the first device runs nothing,
    each labelled by the innermost span over its midpoint."""
    planes = sorted({op.plane for op in pt.ops})
    if not planes:
        return []
    merged = trace_reduce._union(
        (max(op.start_ns, pt.lo), min(op.end_ns, pt.hi))
        for op in pt.ops if op.plane == planes[0])
    edges = [pt.lo] + [x for iv in merged for x in iv] + [pt.hi]
    spans = [sp for sp in pt.spans if sp.end_ns > pt.lo and sp.start_ns < pt.hi]
    found = sorted(((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                   key=lambda g: g[0] - g[1])[:top]
    return [(_label((s + e) / 2, spans), (e - s) / 1e9) for s, e in found]


def main(argv=None) -> int:
    import argparse
    import collections

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else trace_reduce.find_trace(args.trace))
    pt = decode(path)
    print(f"{path}: window {pt.window_s:.6f} s, {len(pt.ops)} device ops")
    core = pt.self_times(core_only=True)
    core_s = sum(s for _, s in core)
    every_s = sum(s for _, s in pt.self_times())
    print(f"device busy (self time) {every_s:.6f} s; jit(core) {core_s:.6f} s")
    for k, s in sorted(pt.scope_s().items(), key=lambda kv: -kv[1]):
        share = 100 * s / core_s if core_s else 0.0
        print(f"  {k or '(no scope)':14s} {s:12.6f} s  {share:6.2f} % of core")
    for k, s in sorted(pt.lanes_s().items(), key=lambda kv: -kv[1]):
        share = 100 * s / every_s if every_s else 0.0
        print(f"  lanes {k!s:8s} {s:12.6f} s  {share:6.2f} % of busy")
    missed = collections.Counter()
    for op, s in core:
        if scope_of(op.tf_op) is None:
            missed[(op.name[:100], op.tf_op)] += s
    for (name, tf_op), s in missed.most_common(args.top):
        print(f"  no scope {s:10.6f} s  {name}  [{tf_op}]")
    names = collections.Counter(sp.name for sp in pt.spans
                                if pt.lo <= sp.start_ns < pt.hi)
    print("spans in the window:", dict(names))
    for name in ("tpu_bfs.serve.coalesce", "tpu_bfs.serve.dispatch"):
        for sp in pt.in_window(name)[:args.top]:
            print(f"  {name} {sp.dur_ns / 1e6:9.3f} ms {sp.stats}")
    for label, s in gaps(pt, args.top):
        print(f"  idle {s * 1e3:9.3f} ms  {label}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
