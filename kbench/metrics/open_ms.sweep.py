"""Mean duration of the program's ``kinetic.open`` span (``Engine.open``
until the session is ready: runner lookup, tile sweep, placement) per
session opened in the traced window, in ms. Reads ``ctx.spans``
(``kbench/spans.py``); ``None`` where the run has no such spans."""


def read(ctx):
    spans = getattr(ctx, "spans", None) or {}
    s = spans.get("kinetic.open")
    if s is None or not s.count:
        return None
    return 1e3 * s.total_s / s.count
