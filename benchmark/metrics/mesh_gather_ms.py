"""mesh_gather_ms: device milliseconds per level-loop pass of the
sharded core's ops in the ``level.gather`` scope (each chip's expand of
its own residual ELL rows against the gathered frontier, and their
permutation to row order), the mean over chips (``benchmark/mesh.py``);
nothing where the program names no exchange."""

from benchmark.mesh import scope_ms


def read(ctx):
    return scope_ms(ctx, "level.gather")
