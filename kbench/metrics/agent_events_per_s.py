"""Agent events whose outputs reached the host in the window, per second of
the window: M * A * valid steps / window seconds, on the host clock."""


def read(ctx):
    cfg, win = ctx.config, ctx.window
    return cfg["num_markets"] * cfg["num_agents"] * win.steps / win.seconds
