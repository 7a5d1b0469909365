"""``similarity``: all-pairs similarity of one table a request
(``PairwiseService.similarity``).

Traffic parameters: ``pool``, the distinct (m, d) tables made in set-up
and cycled by the window.  They share the configuration's one size
profile, so one plan and one program: one request warms them all.
"""

from traffic import Request, rows, sizes


def setup(mix, svc):
    c = mix.config
    mix.state["w"] = sizes(c["sizes"], c["m"], c["q"])
    pool = []
    for k in range(mix.spec["pool"]):
        x = rows(mix.rng, c["m"], c["d"], c["dtype"])
        pool.append(Request(k, x, x, (0, 0), True))
    return pool


def warm(mix):
    return mix.requests[:1]


def serve(mix, svc, req):
    return svc.similarity(req.a, weights=mix.state["w"])[0]
