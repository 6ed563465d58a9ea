"""A benchmark of the served EC path and the EC codec tool on TPU:
one runner (`benchmark.run`) driven by the cells of BENCHMARK.json."""
