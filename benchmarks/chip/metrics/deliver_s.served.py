"""Seconds per lane the solve worker spent in the lane's done-callbacks
(span `repro.engine.callbacks`: the frontend's fan-out and the server's
delivery, the wait for the device included) in the traced window."""

from spans import delta


def read(run):
    d = delta(run, "repro.engine.callbacks")
    lanes = run.window["stats_after"]["lanes"] - \
        run.window["stats_before"]["lanes"]
    if d is None or lanes <= 0:
        return None
    return d[0] / lanes
