"""Chip benchmark of the serving engine: one cell per run, on a TPU.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

See ``README.md`` for the layout and how to add a configuration, a
traffic mix, a metric or a cell.
"""
