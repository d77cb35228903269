"""The benchmark: ``python3 -m benchmark.run --workload <cell> ...``.

Importing this package (or any module in it but ``device``)
touches neither JAX nor the program, so spawned input and reference
workers stay off the chip."""
