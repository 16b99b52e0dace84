"""The benchmark: BENCHMARK.json's harness, yardstick and plain references.

`python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
"""
