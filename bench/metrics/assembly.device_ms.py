"""Device time per request of the request's programs other than the
kernel and the collectives (plan rows to slot ids, block finish, the
concatenation and the source-map gather), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["other_s"] or not ctx.requests:
        return None
    return 1e3 * t["other_s"] / t["chips"] / ctx.requests
