"""On-chip serving benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything that decides a number lives here, apart from the program it
measures: traffic generation, weights from the seed, the plain float32
reference, operation and byte counts, the peaks table, percentile
arithmetic and the reduction of profiler traces.  ``BENCHMARK.json`` at
the checkout root names the cells; each cell's configuration, traffic
mix and per-layer metric is a file of its own under this directory.
"""
