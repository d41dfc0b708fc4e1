"""The clearing kernel's share of its roofline, in percent: the least time
the semantics' work of the traced window needs on the chips (the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth; see
``kbench/work.py``) over the summed device time of the kernel's events."""
from kbench import work


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.events_by_kind.get("kernel"):
        return None
    least, _ = work.least_seconds(ctx.work, ctx.peak["flops"],
                                  ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / tr.seconds_by_kind["kernel"]
