"""Trainer: median host time for `train_step` to return, before the
fence — what the host spends per step while the device could be idle."""

import statistics


def read(run):
    d = run.get("dispatch_s")
    return statistics.median(d) * 1e3 if d else None
