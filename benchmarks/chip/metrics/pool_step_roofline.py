"""Pool traversal kernel: least time for the traced span's word moves
(``work/pool_step.py``) over its device time, in percent."""
from benchmarks.chip.work import pool_step


def read(run):
    return run.roofline(pool_step)
