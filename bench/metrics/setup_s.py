"""Seconds from process start to the window's start: data from the seed,
planning, every request shape served once, compilation."""


def read(ctx):
    return ctx.setup_s
