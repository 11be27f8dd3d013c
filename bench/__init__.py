"""Chip benchmark of the serving path: one cell per run, driven by data.

`BENCHMARK.json` at the root names the cells; each names a configuration
(`bench/configs/<name>.json`) and a traffic mix (`bench/traffic/<name>.json`),
and every metric is a reader of its own (`bench/metrics/<name>.py`).  The
harness code holds none of those names.  Run a cell with

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
