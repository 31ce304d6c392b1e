"""Trainer (`parallel/api.py` `Trainer`): median host time of a step
fenced by a fetch of its loss, feed included."""

import statistics


def read(run):
    return statistics.median(run["step_s"]) * 1e3 if run.get("step_s") else None
