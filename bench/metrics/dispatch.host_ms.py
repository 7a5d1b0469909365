"""Host time of the executor's path per request: the median over the
window's requests of the program's ``execute`` span (source-map lookup,
uploads, dispatch; the device work runs on after it closes)."""

import statistics


def read(ctx):
    spans = [d for name, d in ctx.spans if name == "execute"]
    return statistics.median(spans) * 1e3 if spans else None
