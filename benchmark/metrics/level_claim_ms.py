"""level_claim_ms: device milliseconds per level-loop pass of the core's
ops in the ``level.claim`` scope (claim, visited OR and plane ripple, the
loop's termination test and carry), one metric per end-to-end rate it
moves (``level_claim_ms.<suffix>``). Self time inside the traced window
(``benchmark/program_trace.py``) over the passes ``level_ms`` divides by;
nothing where the program names no level-loop phase. Read only where
the claim has fusions of its own (the hybrid engine, g500-s21-batch)."""

from benchmark.program_trace import scope_ms_per_pass


def read(ctx):
    return scope_ms_per_pass(ctx, "level.claim")
