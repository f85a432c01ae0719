"""The benchmark's own library: the harness, its probes, the trace
reduction, the operation and byte counts, and the plain references.
Nothing here is imported by the program under test."""
