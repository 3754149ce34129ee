"""Seconds a prepare task waited for a prepare worker, mean over the
traced window's tasks (one per lane): the engine's `engine.prepare_queue`
counter, from `submit_lane` to the task's start."""

from spans import delta


def read(run):
    d = delta(run, "engine.prepare_queue")
    if d is None or d[1] <= 0:
        return None
    return d[0] / d[1]
