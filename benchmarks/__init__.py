"""The benchmark: BENCHMARK.json's command, its data files and its yardstick.

`python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell once.  Everything that belongs to one model
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own (configs/, traffic/, cells/, metrics/), found by the name in
BENCHMARK.json; a new cell of an existing kind is three JSON files and one
`workloads` entry.  The yardstick -- traffic generation, serve arithmetic,
operation counts, the table of peaks, the trace reduction and the plain
reference -- lives here and imports nothing from bench.py, chip_smoke.py,
scripts/ or examples/.
"""
