"""``block``: tiles of the pair matrix of one resident table
(``PairwiseService.load_block_table`` in set-up, then ``block``).

Traffic parameters: ``tile``, a tile's side; ``corners``, the tiles the
window cycles over, drawn from ``layout_seed``, the first ``diagonal`` of
them on the global diagonal.  Tile shapes vary with position, so set-up
serves every tile once.
"""

import numpy as np

from traffic import Request, rows, sizes


def setup(mix, svc):
    c, s = mix.config, mix.spec
    m, t = c["m"], s["tile"]
    table = rows(mix.rng, m, c["d"], c["dtype"])
    svc.load_block_table(table, weights=sizes(c["sizes"], m, c["q"]))
    lay = np.random.default_rng(s["layout_seed"])
    tiles = []
    for k in range(s["corners"]):
        i0 = int(lay.integers(0, m - t + 1))
        j0 = i0 if k < s["diagonal"] else int(lay.integers(0, m - t + 1))
        tiles.append(Request(k, table[i0:i0 + t], table[j0:j0 + t],
                             (i0, j0), True))
    return tiles


def warm(mix):
    return mix.requests


def serve(mix, svc, req):
    i0, j0 = req.origin
    return svc.block(i0, i0 + req.a.shape[0], j0, j0 + req.b.shape[0])[0]
