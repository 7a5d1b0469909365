"""``x2y``: cross similarity of a query table against a base table a
request (``PairwiseService.x2y``).

Traffic parameters: ``pool``, the distinct (X, Y) table pairs made in
set-up and cycled by the window.  They share the configuration's two size
profiles, so one plan and one program: one request warms them all.
"""

from traffic import Request, rows, sizes


def setup(mix, svc):
    c = mix.config
    mix.state["wx"] = sizes(c["sizes_x"], c["mx"], c["q"])
    mix.state["wy"] = sizes(c["sizes_y"], c["my"], c["q"])
    pool = []
    for k in range(mix.spec["pool"]):
        x = rows(mix.rng, c["mx"], c["d"], c["dtype"])
        y = rows(mix.rng, c["my"], c["d"], c["dtype"])
        pool.append(Request(k, x, y, (0, 0), False))
    return pool


def warm(mix):
    return mix.requests[:1]


def serve(mix, svc, req):
    return svc.x2y(req.a, req.b, wx=mix.state["wx"], wy=mix.state["wy"])[0]
