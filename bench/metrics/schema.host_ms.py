"""Host time a request spends getting its mapping schema: the window's
``schema`` spans (``plan_a2a``, ``plan_x2y``: the plan-cache lookup, the
literal-weights memo, and on a miss the plan and its remap to the
caller's order) over the requests served, in ms.  ``None`` where the
program has no such span, or where the span ring dropped some of the
window's."""

from repro.obs import TRACER


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "schema"]
    if not spans or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return 1e3 * sum(spans) / ctx.requests
