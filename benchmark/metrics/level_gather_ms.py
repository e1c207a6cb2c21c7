"""level_gather_ms: device milliseconds per level-loop pass of the core's
ops in the ``level.gather`` scope (residual bucketed-ELL gathers and their
OR accumulation, in every expansion tier), one metric per end-to-end rate
it moves (``level_gather_ms.<suffix>``). Self time inside the traced
window (``benchmark/program_trace.py``) over the passes ``level_ms``
divides by; nothing where the program names no level-loop phase.

A fusion takes the ``op_name`` of its root op. In the packed engine
(g500-s21-keys64) XLA fuses the claim into the last gather fusion, whose
root is a gather OR, so there this metric holds the claim's time too and
no ``level_claim_ms`` is read."""

from benchmark.program_trace import scope_ms_per_pass


def read(ctx):
    return scope_ms_per_pass(ctx, "level.gather")
