"""The benchmark's yardstick: everything a cell's number is computed
with lives here, under ``paths`` in ``BENCHMARK.json``, where a PR that
claims a gain cannot change it. From the program the benchmark takes
only the system under test and what it journals."""
