"""Device time of the simulator's sample and window programs per record
(case x epoch), over the campaigns completed in the traced window. Keys on
the jitted programs' names (``jit_sample*``, ``jit_window*``)."""

from benchlib import trace as tr


def read(run):
    spans = [s for s in run.trace.spans if s[2] == "campaign"]
    done = [(s, c) for s, c in zip(spans, run.campaigns) if c["completed"]]
    records = sum(c["info"].get("records", 0) for _, c in done)
    if not done or not records:
        return None
    ns = sum(tr.program_ns(run.trace, "sample", s, e)
             + tr.program_ns(run.trace, "window", s, e) for s, e, _ in
             (sp for sp, _ in done))
    return ns * 1e-6 / records if ns > 0 else None
