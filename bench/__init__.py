"""On-chip serving benchmark of the packed W4A4 engine (see PERF.md)."""
