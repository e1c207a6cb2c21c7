"""level_ms: device milliseconds of the traversal core per level-loop
pass, one metric per end-to-end rate it moves (``level_ms.<suffix>``). The
core is the jitted program named ``core`` (XLA module ``jit_core``); its
module events in the trace are summed and divided by the passes the
window's batches ran (each batch's levels plus the pass that finds the
frontier empty)."""

MODULE_PREFIX = "jit_core"


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("levels_run"):
        return None
    s = sum(v for k, v in ctx.trace.module_s.items()
            if k.startswith(MODULE_PREFIX))
    if s <= 0:
        return None
    return 1e3 * s / ctx.counters["levels_run"]
