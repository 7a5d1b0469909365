"""Bytes of reducer blocks the window's programs wrote per result entry
returned.

The executors record, with each request's comm-ledger record (counter
``ledger.block_bytes``), the float32 bytes of the blocks its programs
write: per stack of blocks, reducers times X width times Y width times 4,
padding rows and every shard included.  Each request's bytes also sit on
its ``execute`` span as ``block_bytes``, which is how the window's are
told from set-up's.  Their total over the entries of the answers
returned.  An exact count: it repeats from run to run.  ``None`` where
the program records no such bytes, or where the span ring dropped some of
the window's."""

from repro.obs import TRACER


def read(ctx):
    nbytes = sum(s.attrs.get("block_bytes", 0) for s in TRACER.spans())
    if not nbytes or not ctx.entries or getattr(TRACER, "dropped", 0):
        return None
    return nbytes / ctx.entries
