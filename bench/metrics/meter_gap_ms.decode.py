"""Mean host gap of the meter's timed decode calls in the completed
campaigns: each ``timed_call`` span (``runtime_meter.timed_calls``:
dispatch and ``block_until_ready``) less the device time of the
``serve_step`` programs inside it, the key ``decode_roofline`` uses."""

from benchlib import progspans


def read(run):
    return progspans.meter_gap_ms(run, "serve_step")
