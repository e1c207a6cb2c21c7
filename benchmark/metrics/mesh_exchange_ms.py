"""mesh_exchange_ms: device milliseconds per level-loop pass of the
sharded core's ops in the ``level.exchange`` scope (the frontier's
all-gather between chips and its reorder into row order), the mean over
chips (``benchmark/mesh.py``); nothing where the program names no
exchange. A chip that reaches the gather before the others waits there,
so this holds the wait too."""

from benchmark.mesh import EXCHANGE, scope_ms


def read(ctx):
    return scope_ms(ctx, EXCHANGE)
