"""Device dispatches the simulator issued per completed campaign: the
delta of ``repro.simjax.engine_stats()["n_dispatches"]``."""


def read(run):
    d = getattr(run.cell, "dispatches", [])
    return sum(d) / len(d) if d else None
