"""Host time a request spends lowering its schema to a reducer plan: the
window's ``lower`` spans (``_plan_for``, ``_x2y_plan_for``,
``block_subplan``: the lookup in the object-keyed cache, and the build on a
miss) over the requests served, in ms.  ``None`` where the program has no
such span, or where the span ring dropped some of the window's."""

from repro.obs import TRACER


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "lower"]
    if not spans or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return 1e3 * sum(spans) / ctx.requests
