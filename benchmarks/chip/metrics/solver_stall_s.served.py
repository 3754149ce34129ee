"""Seconds per lane the engine's solve worker stood blocked on the lane's
prepare (span `repro.engine.await_prepare`) in the traced window."""

from spans import delta


def read(run):
    d = delta(run, "repro.engine.await_prepare")
    lanes = run.window["stats_after"]["lanes"] - \
        run.window["stats_before"]["lanes"]
    if d is None or lanes <= 0:
        return None
    return d[0] / lanes
