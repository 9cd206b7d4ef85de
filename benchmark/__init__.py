"""The benchmark: one cell a run, driven by the data files beside this one.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` is the whole interface; ``BENCHMARK.json`` at the root of
the repo names the cells. Nothing outside this directory (and
``tests/benchmark``) belongs to the yardstick.
"""
