"""Decode attention kernels: least time for the traced span's decode
work (``work/decode_attn.py``) over their device time, in percent."""
from benchmarks.chip.work import decode_attn


def read(run):
    return run.roofline(decode_attn)
