"""Host time of the program's ``kinetic.dispatch`` span (operands, the
jitted launch, the path slices; the enqueue, not the device's work) per
chunk dispatched in the traced window, in ms. Reads ``ctx.spans``
(``kbench/spans.py``); ``None`` where the run has no such spans."""


def read(ctx):
    spans = getattr(ctx, "spans", None) or {}
    s = spans.get("kinetic.dispatch")
    if s is None or not s.count:
        return None
    return 1e3 * s.total_s / s.count
