"""Device time per request of the collectives (the coded executor's
residual all-to-all), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["collective_s"] or not ctx.requests:
        return None
    return 1e3 * t["collective_s"] / t["chips"] / ctx.requests
