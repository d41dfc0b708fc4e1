"""Host time of the program's ``kinetic.to_host.copy`` span per chunk
dispatched in the traced window, in ms: a finished batch's device-to-host
path copies, from the host's return from waiting for the device until the
last path is a numpy array. The copies are enqueued before that wait, and
the runtime starts them when it sees the device done, which is also when
the wait returns; so the span holds the whole transfer and its
de-linearisation on the host. Reads ``ctx.spans`` (``kbench/spans.py``);
``None`` where the run has no such spans."""


def read(ctx):
    spans = getattr(ctx, "spans", None) or {}
    copy, chunks = spans.get("kinetic.to_host.copy"), spans.get(
        "kinetic.dispatch")
    if copy is None or chunks is None or not chunks.count:
        return None
    return 1e3 * copy.total_s / chunks.count
