"""Seconds of tracing, lowering and compiling inside the window, from
JAX's ``jax.monitoring`` duration events.  Zero in a sound window."""


def read(ctx):
    return ctx.compile_s
