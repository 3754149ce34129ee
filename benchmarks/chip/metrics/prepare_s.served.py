"""Host prepare seconds per answered request in the traced window (the
engine's summed `prepare_seconds`; its prepare workers run in parallel,
so this is work per request, not latency)."""


def read(run):
    b, a = run.window["stats_before"], run.window["stats_after"]
    done = a["completed"] - b["completed"]
    if done <= 0:
        return None
    return (a["engine"]["prepare_seconds"]
            - b["engine"]["prepare_seconds"]) / done
