"""On-chip serving benchmark: a harness driven by the data files beside it.

`BENCHMARK.json` at the repository root names the cells; each cell's
configuration, traffic mix, offered rate and per-layer metrics live in
files of their own under this directory, found by name (`spec.py`).
`python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell on the chip and prints one JSON line.
"""
