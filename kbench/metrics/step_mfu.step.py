"""The whole window's share of the chips' peak FLOP/s, in percent: the
operations the semantics needs for the work of the traced window (see
``kbench/work.py``) over window seconds times chips times peak."""


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.work.ops <= 0:
        return None
    return 100.0 * ctx.work.ops / (tr.window_s * ctx.chips
                                   * ctx.peak["flops"])
