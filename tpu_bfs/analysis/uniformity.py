"""Pass 1: collective-uniformity verification (ISSUE 8 tentpole).

The exchange planner (PR 7) and the cap ladder before it make branch
choice a per-level RUNTIME decision inside a `lax.cond` whose arms issue
*different* collective schedules (a delta all-to-all on one arm, the
dense ring on another). On a real mesh that is only safe when every rank
selects the same branch — a divergent selection leaves rank A parked in
an all-to-all that rank B never enters, hanging the whole mesh mid-BFS.
Nothing crashes on the single-host CPU test mesh (XLA emulates all ranks
in one process), so the invariant must be PROVEN, not tested:

- **jaxpr taint analysis** (:func:`analyze_program`): for every traced
  mesh program, every value is tagged with the set of mesh axes over
  which it is provably UNIFORM (identical on all ranks along that axis).
  Sources of uniformity: replicated shard_map inputs, literals/constants,
  full-axis psum/pmax/pmin/all_gather outputs; sinks: `axis_index`,
  sharded inputs. Uniformity propagates through pure ops by set
  intersection, through `while`/`scan` carries by fixed point, and
  through `cond` outputs gated by the predicate's own uniformity. THE
  CHECK: every `cond` whose branches' collective signatures differ, and
  every `while` whose body communicates, must have a predicate uniform
  over every axis those collectives use. Violations name the offending
  equation's source line (the planner scalar that skipped its pmax).
- **compiled-HLO audit** (:func:`check_hlo_conditionals`): the same
  invariant re-checked on the artifact XLA actually emits — every
  ``conditional``'s arms carry an identical ordered collective signature
  (op kind, operand shape, replica grouping, program order) or are
  collective-free; arms that differ are acceptable ONLY when the taint
  pass certified every differing-collective branch point of the same
  program as uniformly selected.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from tpu_bfs.analysis import Finding
from tpu_bfs.analysis.hlo import mismatched_conditionals

#: Communication primitives at the jaxpr level. psum2 is psum's
#: post-0.4.30 spelling on some paths; pbroadcast rides shard_map's
#: replication rewrite.
COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_to_all", "all_gather", "reduce_scatter",
}
#: Full-axis reductions whose OUTPUT is definitionally identical on every
#: rank of the reduced axes (when axis_index_groups is None).
_UNIFORMIZING = {"psum", "psum2", "pmax", "pmin", "all_gather"}
#: Collectives whose output is per-rank DIFFERENT even from mesh-uniform
#: inputs: all_to_all hands rank r the r-th chunk of every sender, and
#: reduce_scatter the r-th reduced chunk — their axes must LEAVE the
#: output's uniform set (a scalar derived from either must re-reduce
#: before it may select a branch). ppermute is NOT here: permuting
#: values that are identical along the axis yields identical values, so
#: the plain input-meet is exact for it.
_DIVERGING = {"all_to_all", "reduce_scatter"}
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr")


def _axes_of(eqn) -> tuple[str, ...]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name"))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _source_of(eqn) -> str:
    """'collectives.py:702 (planned_sparse_exchange_or)' — the innermost
    user frame of the equation's provenance, so a finding names the exact
    branch-selection site."""
    try:
        from jax._src import source_info_util

        frames = list(source_info_util.user_frames(eqn.source_info))
        if frames:
            fr = frames[0]
            fname = fr.file_name.rsplit("/", 1)[-1]
            return f"{fname}:{fr.start_line} ({fr.function_name})"
    except Exception:  # noqa: BLE001 — provenance is best-effort
        pass
    return "<unknown source>"


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")  # jax.core.Literal ducks; Vars don't


def _inner_jaxpr(obj):
    """Jaxpr of a param that may be a ClosedJaxpr or an open Jaxpr."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _sub_jaxprs(eqn):
    for key in _SUBJAXPR_PARAMS:
        v = eqn.params.get(key)
        if v is not None and hasattr(_inner_jaxpr(v), "eqns"):
            yield _inner_jaxpr(v)


# --- collective signatures at the jaxpr level --------------------------------


def jaxpr_collective_signature(jaxpr, _memo: dict | None = None) -> tuple:
    """Ordered communication schedule of a jaxpr, transitively: one entry
    per collective (primitive, axes, operand avals) in program order, with
    structural markers for branch-/iteration-shaped control flow. Two
    `cond` arms are deadlock-compatible under a divergent predicate iff
    their signatures are equal."""
    if _memo is None:
        _memo = {}
    key = id(jaxpr)
    if key in _memo:
        return _memo[key]
    sig: list = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            avals = tuple(
                str(v.aval) for v in eqn.invars if hasattr(v, "aval")
            )
            sig.append((name, _axes_of(eqn), avals))
        elif name == "cond":
            arms = tuple(
                jaxpr_collective_signature(b.jaxpr, _memo)
                for b in eqn.params["branches"]
            )
            if any(arms):
                sig.append(("cond", arms))
        elif name == "while":
            subs = tuple(
                jaxpr_collective_signature(
                    _inner_jaxpr(eqn.params[k]), _memo
                )
                for k in ("cond_jaxpr", "body_jaxpr")
            )
            if any(subs):
                sig.append(("while", subs))
        elif name == "scan":
            inner = jaxpr_collective_signature(
                _inner_jaxpr(eqn.params["jaxpr"]), _memo
            )
            if inner:
                sig.append(("scan", eqn.params.get("length"), inner))
        else:
            for sub in _sub_jaxprs(eqn):
                sig.extend(jaxpr_collective_signature(sub, _memo))
    _memo[key] = tuple(sig)
    return _memo[key]


def signature_axes(sig) -> frozenset:
    """Every mesh axis a signature communicates over."""
    axes: set = set()

    def walk(s):
        for entry in s:
            if not entry:
                continue
            if entry[0] in COLLECTIVE_PRIMS:
                axes.update(entry[1])
            elif entry[0] in ("cond", "while"):
                for sub in entry[1]:
                    walk(sub)
            elif entry[0] == "scan":
                walk(entry[2])

    walk(sig)
    return frozenset(axes)


# --- the taint analysis ------------------------------------------------------


@dataclasses.dataclass
class UniformityReport:
    program: str
    findings: list[Finding]
    conds_checked: int = 0
    certified_divergent_safe: int = 0  # differing-collective branch points
    #                                    whose predicate proved uniform
    shard_maps: int = 0


class _Taint:
    """Per-var uniform-axis sets over one shard_map body."""

    def __init__(self, full: frozenset):
        self.full = full
        self.env: dict[Any, frozenset] = {}

    def read(self, atom) -> frozenset:
        if _is_literal(atom):
            return self.full
        return self.env.get(atom, self.full)  # trace consts are replicated

    def write(self, var, taint: frozenset) -> None:
        self.env[var] = taint

    def meet_inputs(self, eqn) -> frozenset:
        out = self.full
        for v in eqn.invars:
            out = out & self.read(v)
        return out


def _analyze_body(jaxpr, taint: _Taint, report: UniformityReport,
                  seen: set) -> None:
    """One pass over a (sub)jaxpr propagating uniform-axis sets and
    checking every divergence-sensitive control-flow equation."""
    full = taint.full
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        meet = taint.meet_inputs(eqn)
        outs: list[frozenset] | None = None

        if name == "axis_index":
            outs = [full - set(_axes_of(eqn))]
        elif name in _DIVERGING:
            outs = [meet - set(_axes_of(eqn)) for _ in eqn.outvars]
        elif name in _UNIFORMIZING and eqn.params.get(
            "axis_index_groups"
        ) is None:
            outs = [meet | set(_axes_of(eqn)) for _ in eqn.outvars]
        elif name == "cond":
            outs = _analyze_cond(eqn, taint, report, seen)
        elif name == "while":
            outs = _analyze_while(eqn, taint, report, seen)
        elif name == "scan":
            outs = _analyze_scan(eqn, taint, report, seen)
        elif name == "shard_map":
            # Nested shard_map inside a body — not a shape this repo
            # compiles; treat conservatively as fully divergent.
            outs = [frozenset() for _ in eqn.outvars]
        else:
            subs = list(_sub_jaxprs(eqn))
            if subs and name in ("pjit", "closed_call", "core_call",
                                 "custom_jvp_call", "custom_vjp_call",
                                 "remat2", "checkpoint"):
                sub = subs[0]
                for var, inv in zip(sub.invars, eqn.invars):
                    taint.write(var, taint.read(inv))
                _analyze_body(sub, taint, report, seen)
                outs = [taint.read(v) for v in sub.outvars]
            else:
                outs = [meet for _ in eqn.outvars]

        for var, t in zip(eqn.outvars, outs):
            taint.write(var, t)


def _check_divergence(eqn, pred_taint: frozenset, arm_sigs, report,
                      seen: set, kind: str) -> bool:
    """The core invariant: where collective schedules differ across the
    runtime decision, the deciding scalar must be uniform over every axis
    those collectives use. Returns True when the branch point has
    differing collective arms (certified or not)."""
    distinct = len(set(arm_sigs)) > 1
    has_colls = any(arm_sigs)
    if kind == "while":
        # A while's arms are its iterations: any communication in the body
        # makes trip-count divergence a deadlock.
        differs = has_colls
    else:
        differs = distinct
    if not differs:
        return False
    used = frozenset()
    for s in arm_sigs:
        used = used | signature_axes(s)
    if used <= pred_taint:
        report.certified_divergent_safe += 1
        return True
    where = f"{report.program}:{_source_of(eqn)}"
    if where not in seen:
        seen.add(where)
        missing = sorted(used - pred_taint)
        report.findings.append(Finding(
            "uniformity",
            where,
            f"{kind} selects between collective schedules but its "
            f"selection scalar is NOT mesh-uniform over axis(es) "
            f"{missing}: ranks can take different arms and deadlock the "
            f"mesh mid-level. Route the scalar through a full-axis "
            f"psum/pmax (or loop-carry an already-uniform value) before "
            f"branching.",
        ))
    return True


def _analyze_cond(eqn, taint, report, seen):
    branches = eqn.params["branches"]
    pred_t = taint.read(eqn.invars[0])
    op_taints = [taint.read(v) for v in eqn.invars[1:]]
    sigs = [jaxpr_collective_signature(b.jaxpr) for b in branches]
    report.conds_checked += 1
    _check_divergence(eqn, pred_t, sigs, report, seen, "cond")
    outs = None
    for b in branches:
        sub = b.jaxpr
        for var, t in zip(sub.invars, op_taints):
            taint.write(var, t)
        _analyze_body(sub, taint, report, seen)
        branch_outs = [taint.read(v) for v in sub.outvars]
        outs = branch_outs if outs is None else [
            a & c for a, c in zip(outs, branch_outs)
        ]
    # A divergent predicate makes even identical-schedule arms produce
    # rank-divergent VALUES wherever the arms' outputs differ.
    return [t & pred_t for t in outs]


def _analyze_while(eqn, taint, report, seen):
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    cond_jx = _inner_jaxpr(eqn.params["cond_jaxpr"])
    body_jx = _inner_jaxpr(eqn.params["body_jaxpr"])
    cond_consts = [taint.read(v) for v in eqn.invars[:cn]]
    body_consts = [taint.read(v) for v in eqn.invars[cn:cn + bn]]
    carry = [taint.read(v) for v in eqn.invars[cn + bn:]]
    body_sig = jaxpr_collective_signature(body_jx)
    cond_sig = jaxpr_collective_signature(cond_jx)
    pred_t: frozenset = taint.full
    converged = False
    # Meets only shrink, so the fixed point lands within
    # carries x axes rounds; the hard bound guards pathological shapes —
    # a non-converged walk bottoms out below (sound, never optimistic).
    for _ in range(len(carry) * max(len(taint.full), 1) + 2):
        for var, t in zip(cond_jx.invars, cond_consts + carry):
            taint.write(var, t)
        _analyze_body(cond_jx, taint, report, seen)
        pred_t = taint.read(cond_jx.outvars[0])
        for var, t in zip(body_jx.invars, body_consts + carry):
            taint.write(var, t)
        _analyze_body(body_jx, taint, report, seen)
        new_carry = [
            c & taint.read(v) & pred_t
            for c, v in zip(carry, body_jx.outvars)
        ]
        if new_carry == carry:
            converged = True
            break
        carry = new_carry
    if not converged:
        carry = [frozenset() for _ in carry]
        pred_t = frozenset()
    _check_divergence(eqn, pred_t, (cond_sig, body_sig), report, seen,
                      "while")
    return carry


def _analyze_scan(eqn, taint, report, seen):
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    jx = _inner_jaxpr(eqn.params["jaxpr"])
    consts = [taint.read(v) for v in eqn.invars[:nc]]
    carry = [taint.read(v) for v in eqn.invars[nc:nc + ncar]]
    xs = [taint.read(v) for v in eqn.invars[nc + ncar:]]
    ys: list[frozenset] = []
    converged = False
    for _ in range(max(ncar, 1) * max(len(taint.full), 1) + 2):
        for var, t in zip(jx.invars, consts + carry + xs):
            taint.write(var, t)
        _analyze_body(jx, taint, report, seen)
        outs = [taint.read(v) for v in jx.outvars]
        new_carry = [c & o for c, o in zip(carry, outs[:ncar])]
        ys = outs[ncar:]
        if new_carry == carry:
            converged = True
            break
        carry = new_carry
    if not converged:
        carry = [frozenset() for _ in carry]
        ys = [frozenset() for _ in ys]
    # Trip count is static — no divergence check needed; a scan cannot
    # run different iteration counts on different ranks.
    return carry + ys


def find_shard_maps(jaxpr):
    """Every shard_map equation reachable from a jaxpr (through pjit /
    control-flow sub-jaxprs)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            yield eqn
        else:
            for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
                v = eqn.params.get(key)
                if v is not None and hasattr(_inner_jaxpr(v), "eqns"):
                    yield from find_shard_maps(_inner_jaxpr(v))
            for b in eqn.params.get("branches", ()):
                yield from find_shard_maps(b.jaxpr)


def analyze_jaxpr(name: str, closed) -> UniformityReport:
    """Taint-verify every shard_map region of an already-traced program
    (the runner traces once and shares the jaxpr with the dtype pass)."""
    report = UniformityReport(program=name, findings=[])
    seen: set = set()
    for sm in find_shard_maps(closed.jaxpr):
        report.shard_maps += 1
        full = frozenset(sm.params["mesh"].axis_names)
        body = _inner_jaxpr(sm.params["jaxpr"])
        taint = _Taint(full)
        for var, spec in zip(body.invars, sm.params["in_specs"]):
            # Mesh axes named anywhere in the input's PartitionSpec.
            sharded = {
                ax
                for entry in spec if entry is not None
                for ax in ((entry,) if isinstance(entry, str) else entry)
            }
            taint.write(var, full - sharded)
        _analyze_body(body, taint, report, seen)
    return report


def analyze_program(name: str, fn, args) -> UniformityReport:
    """Trace ``fn(*args)`` (no compile) and taint-verify every shard_map
    region found: the jaxpr half of the uniformity pass."""
    import jax

    return analyze_jaxpr(name, jax.make_jaxpr(fn)(*args))


# --- the compiled-HLO half ---------------------------------------------------


def check_hlo_conditionals(
    name: str, hlo_text: str, jaxpr_report: UniformityReport | None
) -> list[Finding]:
    """Audit the compiled artifact: every ``conditional``'s arms must share
    one ordered collective signature or be collective-free. Arms that
    differ are certified ONLY by a clean taint pass over the same program
    that proved at least one uniformly-selected differing-collective
    branch point (the cap ladder / planner case); without that
    certificate each mismatched conditional is a finding."""
    mism = mismatched_conditionals(hlo_text)
    if not mism:
        return []
    certified = (
        jaxpr_report is not None
        and not jaxpr_report.findings
        and jaxpr_report.certified_divergent_safe > 0
    )
    if certified:
        return []
    out = []
    for m in mism:
        where = f"{name}:{m['source'] or m['computation']}"
        arms = ", ".join(
            f"arm{i}={len(s)} collective(s)" for i, s in
            enumerate(m["signatures"])
        )
        out.append(Finding(
            "uniformity/collective-signature",
            where,
            f"conditional arms issue MISMATCHED collective schedules "
            f"({arms}) and no taint certificate proves the predicate "
            f"mesh-uniform — a divergent selection deadlocks the mesh. "
            f"Make the arms' collective schedules identical, keep the "
            f"arms collective-free, or derive the predicate from a "
            f"full-axis reduction.",
        ))
    return out
