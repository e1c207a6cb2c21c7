"""compile_s: seconds JAX spent compiling during set-up, summed from its
``/jax/core/compile/*`` duration events (the harness's listener)."""


def read(ctx):
    return ctx.counters.get("compile_s")
