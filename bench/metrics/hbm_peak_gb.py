"""The most bytes any of the cell's chips held (buffers plus program
scratch, ``peak_bytes_reserved``), read from the device runtime after the
window, in GB."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
