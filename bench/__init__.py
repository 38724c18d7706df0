"""The chip benchmark of the embedding server: one run of one cell is
``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Cells, metrics and bounds are listed in ``BENCHMARK.json`` at the root of
the checkout; each configuration, traffic mix and metric has a file of its
own here, found by its name, and so has each architecture that a
configuration names (``bench/arch/<arch>.py``).
"""
