"""The chip benchmark of the sparse CP-decomposition system: one harness
(``bench.run``) driven by the configuration, traffic, limit and metric
files that ``BENCHMARK.json`` names."""
