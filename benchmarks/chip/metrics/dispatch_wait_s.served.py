"""Seconds a prepared lane waited for the solve worker to dispatch it,
mean over the traced window's lanes: the engine's `engine.dispatch_wait`
counter, from the end of the lane's prepare to the solve worker taking
its prepared data."""

from spans import delta


def read(run):
    d = delta(run, "engine.dispatch_wait")
    if d is None or d[1] <= 0:
        return None
    return d[0] / d[1]
