"""Bytes the shuffle gathered per result entry returned.

The comm ledger's ``ledger.gathered_bytes`` counter (valid gather slots
times the row size, per execution: the paper's communication cost as the
device gathers it) over the window, divided by the entries of the
answers returned in it.  An exact count: it repeats from run to run."""


def read(ctx):
    nbytes = ctx.counters.get("ledger.gathered_bytes", 0)
    if not nbytes or not ctx.entries:
        return None
    return nbytes / ctx.entries
