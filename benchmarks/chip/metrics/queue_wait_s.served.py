"""Mean seconds a request waited in the frontend's coalescing hold over
the traced window (frontend `mean_queue_wait`, taken as a difference of
its running totals)."""


def read(run):
    b, a = run.window["stats_before"], run.window["stats_after"]
    done = a["completed"] - b["completed"]
    if done <= 0:
        return None
    total = (a["mean_queue_wait"] * a["completed"]
             - b["mean_queue_wait"] * b["completed"])
    return total / done
