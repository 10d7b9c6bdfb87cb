"""The benchmark: one command runs one cell (a configuration under a
traffic mix) once on the chip and prints one JSON line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under ``bench/``, found by the name
``BENCHMARK.json`` gives it (see ``bench/spec.py``).
"""
