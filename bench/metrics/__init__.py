"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``. Each defines ``read(ctx) -> float | None``: None when
the trace holds nothing for it to read, and the harness then leaves the
metric out of the result line."""
