"""Device time of the collective operations (the ``ppermute`` ring of the
coupling halo) per chunk launch, averaged over the chips, in ms."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.events_by_kind.get("collective"):
        return None
    per_chip = tr.seconds_by_kind["collective"] / tr.devices
    return 1e3 * per_chip / max(1, ctx.window.requests)
