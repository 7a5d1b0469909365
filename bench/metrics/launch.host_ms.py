"""Host time a request spends calling its jitted programs: the window's
``launch`` spans (the enqueue, plus any trace or compile; the device runs
on after the call returns) over the requests served, in ms.  ``None``
where the program has no such span, or where the span ring dropped some of
the window's."""

from repro.obs import TRACER


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "launch"]
    if not spans or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return 1e3 * sum(spans) / ctx.requests
