"""On-chip benchmark of the federated round engine (see PERF.md)."""
