"""The chip benchmark's yardstick: peaks, work model, trace reduction,
float32 references, data and arrival generators, and the harness that
runs one cell of ``BENCHMARK.json``.  Only ``program`` (which builds the
program's configuration) and the drivers in ``bench/drivers`` import the
program under test; the references import nothing of it."""
