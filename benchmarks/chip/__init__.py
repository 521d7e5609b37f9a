"""Chip benchmark of the packed continuous serving path.

One command runs one cell of ``BENCHMARK.json`` once:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own under this directory and is found by the name that
``BENCHMARK.json`` gives it (see :mod:`benchmarks.chip.cells`).
"""
