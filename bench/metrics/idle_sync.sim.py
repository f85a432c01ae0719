"""Share of the traced window in which the device ran no operation while
the innermost program span was the launch epochs' clock synchronization
(``clock_sync``, in ``_SimEpoch``)."""

from benchlib import progspans


def read(run):
    split = progspans.idle_split(run)
    return split["sync"] if split else None
