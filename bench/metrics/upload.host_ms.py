"""Host time a request spends putting host arrays on the device: the
window's ``upload`` spans (the request's table, the plan's bucket or group
arrays, the source and send maps) over the requests served, in ms.
``None`` where the program has no such span, or where the span ring dropped
some of the window's."""

from repro.obs import TRACER


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "upload"]
    if not spans or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return 1e3 * sum(spans) / ctx.requests
