"""One module per system the benchmark drives, named by a configuration
file's ``system`` key. Each has ``make_cell(config, traffic, run)``."""
