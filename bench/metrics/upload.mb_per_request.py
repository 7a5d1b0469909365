"""Host-to-device bytes a request puts: the ``bytes`` of the window's
``upload`` spans (the host arrays each put copies) over the requests
served, in MB (10^6 B).  An exact count: it repeats from run to run.
``None`` where the program has no such span, or where the span ring
dropped some of the window's."""

from repro.obs import TRACER


def read(ctx):
    nbytes = [s.attrs.get("bytes", 0) for s in TRACER.spans()
              if s.name == "upload"]
    if not nbytes or not ctx.requests or getattr(TRACER, "dropped", 0):
        return None
    return sum(nbytes) / 1e6 / ctx.requests
