"""Requests per lane the frontend dispatched in the traced window."""


def read(run):
    b, a = run.window["stats_before"], run.window["stats_after"]
    lanes = a["lanes"] - b["lanes"]
    if lanes <= 0:
        return None
    return (a["lane_members"] - b["lane_members"]) / lanes
