"""On-chip serving benchmark: one harness driven by the data files beside it.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of BENCHMARK.json.  Configurations, traffic mixes and metric
readers are found by name under `configs/`, `traffic/` and `metrics/`; the
family a configuration names (its weights, reference and yardstick) under
`families/`.
"""
