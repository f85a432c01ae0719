"""Share of the traced window in which the device ran no operation and no
clock synchronization or simulator engine span was the innermost program
span: orchestration, epoch set-up outside the synchronization, and time
under no program span. With ``idle_sync.sim`` and ``idle_engine.sim`` it
adds up to ``idle_share.sim``."""

from benchlib import progspans


def read(run):
    split = progspans.idle_split(run)
    return split["other"] if split else None
