"""Per-layer metrics: one module per metric, named as in BENCHMARK.json.

Each module has ``read(ctx)`` over a :class:`chipbench.harness.Context`
and returns the metric's value, ``{"value": v, "note": {...}}`` to name
what it found on an earlier output line, or None when the trace holds
nothing it reads (the harness then leaves the metric out).
"""
