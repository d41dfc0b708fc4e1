"""Share of device operation time spent outside the clearing kernel and the
collectives (the runner's pads, slices and copies), in percent."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    total = sum(tr.seconds_by_kind.values())
    if total <= 0:
        return None
    return 100.0 * tr.seconds_by_kind.get("other", 0.0) / total
