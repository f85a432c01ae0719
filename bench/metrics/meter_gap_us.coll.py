"""Mean host gap of the meter's timed collective calls in the completed
campaigns: each ``timed_call`` span (``runtime_meter.timed_calls``:
dispatch and ``block_until_ready``) less the device time of the
collectives' programs inside it (``jit_call_wrapped``: every pmapped op
and size lowers to that name). Standard error gets it per case."""

from benchlib import callgap
from benchlib.systems.collectives import PROGRAM_KEY, report_by_case


def read(run):
    calls = callgap.timed_calls(run, PROGRAM_KEY)
    if not calls:
        return None
    report_by_case(run.cell.calls.order, calls)
    return sum(span - dev for span, dev in calls) / len(calls) * 1e-3
