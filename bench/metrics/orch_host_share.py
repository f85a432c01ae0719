"""Share of campaign wall time spent outside backend calls (``measure``,
``measure_epochs``), from the benchmark's own host-clock spans, over the
campaigns completed in the window."""


def read(run):
    camp = [c for c in run.campaigns if c["completed"]]
    calls = run.spans.of("backend_call")
    total = inside = 0.0
    for c in camp:
        total += c["end"] - c["start"]
        inside += sum(min(e, c["end"]) - max(s, c["start"]) for s, e in calls
                      if min(e, c["end"]) > max(s, c["start"]))
    return 100.0 * (1.0 - inside / total) if total > 0 else None
