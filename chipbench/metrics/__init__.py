"""Per-layer metric readers, one file per metric, named as the metric
is in ``BENCHMARK.json``.  Each has ``read(m)``, which returns the metric
or None where the run holds nothing to read it from."""
