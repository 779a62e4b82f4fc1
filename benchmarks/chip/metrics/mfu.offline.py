"""Whole model step: model FLOPs of the window's tokens
(``work/model_step.py``) over the window's seconds times the chip's
peak, in percent."""


def read(run):
    return run.mfu()
