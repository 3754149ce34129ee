"""Host seconds per answered request spent on the LSH tables: building
them, hashing every point and splitting the keys (span
`repro.prepare.lsh`) in the traced window."""

from spans import delta


def read(run):
    d = delta(run, "repro.prepare.lsh")
    b, a = run.window["stats_before"], run.window["stats_after"]
    done = a["completed"] - b["completed"]
    if d is None or done <= 0:
        return None
    return d[0] / done
