"""Host time a request spends on the per-plan artefacts its executor
derives: the window's ``maps`` spans (source maps, shard partitions,
stacked groups, the coded send and source maps: the lookup on the plan,
and the build on a miss) over the requests served, in ms.  ``None`` where
the program has no such span, or where the span ring dropped some of the
window's."""

from repro.obs import TRACER


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "maps"]
    if not spans or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return 1e3 * sum(spans) / ctx.requests
