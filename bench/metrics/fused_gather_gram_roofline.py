"""The fused gather+Gram kernel's share of its roofline.

The least time of the window's requests (``work.py``: each distinct
pair's dot product once, each touched row read once, each answer written
once, at the cell's chips' published peaks) over the kernel's device time
in the trace, averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["kernel_s"]:
        return None
    return 100.0 * ctx.least_s / (t["kernel_s"] / t["chips"])
