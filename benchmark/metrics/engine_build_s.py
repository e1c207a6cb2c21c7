"""engine_build_s: host seconds of the engine constructor (batch cells) or
of the server's start-up to its READY line (serve cells), less the compile
seconds inside it."""


def read(ctx):
    return ctx.counters.get("engine_build_s")
