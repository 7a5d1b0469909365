"""Entries of the answers returned in the window over the window's wall
time: all the work over all the time, the window ending when the last
request started in it returns."""


def read(ctx):
    return ctx.entries / ctx.window_s
