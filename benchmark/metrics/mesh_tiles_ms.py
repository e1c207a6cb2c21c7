"""mesh_tiles_ms: device milliseconds per level-loop pass of the sharded
core's ops in the ``level.tiles`` scope (each chip's ``tile_spmm`` over
its own dense tiles), the mean over chips (``benchmark/mesh.py``);
nothing where the program names no exchange."""

from benchmark.mesh import scope_ms


def read(ctx):
    return scope_ms(ctx, "level.tiles")
