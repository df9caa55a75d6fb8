"""The benchmark of the factor-modification system (see ``BENCHMARK.json``).

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell on the chip it is started on and prints one JSON line.
Everything a cell needs is found by name: its configuration in
``bench/configs/``, its traffic mix in ``bench/traffic/``, and each of its
per-layer metrics in ``bench/layer_metrics/``.
"""
