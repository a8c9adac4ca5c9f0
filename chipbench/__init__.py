"""Chip benchmark of the federated minimax training round (see
`BENCHMARK.json` and `PERF.md`)."""
