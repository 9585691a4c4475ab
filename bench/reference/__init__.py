"""Plain float32 PFP references, independent of ``src/repro``: they import
nothing of the program and take nothing it made (weights come from
``bench.weights`` by role)."""
