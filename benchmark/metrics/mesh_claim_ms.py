"""mesh_claim_ms: device milliseconds per level-loop pass of the sharded
core's ops in the ``level.claim`` scope (the claim of new vertices, the
distance planes' ripple and the visited update), the mean over chips
(``benchmark/mesh.py``); nothing where the program names no exchange."""

from benchmark.mesh import scope_ms


def read(ctx):
    return scope_ms(ctx, "level.claim")
