"""Share of the traced window in which the device ran no operation while
the innermost program span was the simulator engine's host work
(``sim_engine``) or its host reads of device results (``sim_wait``)."""

from benchlib import progspans


def read(run):
    split = progspans.idle_split(run)
    return split["engine"] if split else None
