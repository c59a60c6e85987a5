"""The chip benchmark of DeKRR-DDRF: harness, yardstick and cells.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once. Everything that belongs to one
configuration, traffic mix, generator or per-layer metric lives in a file of
its own, found by name (`bench.registry`).
"""
