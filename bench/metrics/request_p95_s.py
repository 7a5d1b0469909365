"""95th percentile, by nearest rank, of the client time of every request
of the window (rows sent to answer on the host); below 20 requests, the
largest."""

import math


def read(ctx):
    v = sorted(ctx.request_s)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]
