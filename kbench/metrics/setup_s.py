"""Seconds from the harness's entry to the start of the window: JAX start,
the ensemble, ``Engine.open`` with its tile sweep, and the warm-up."""


def read(ctx):
    return ctx.setup_s
