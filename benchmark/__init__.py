"""The benchmark of trlx_tpu: cells, traffic, yardstick and reduction.

Everything a later PR may not change lives here (BENCHMARK.json `paths`).
From the program it takes only the system under test (`trlx_tpu`) and its
records: metrics.jsonl, the jitted programs' names, the Pallas kernels'
function names. `python benchmark/run.py --help` is the entry point.
"""
