"""Host seconds per answered request spent building the multi-tree
embedding and uploading its codes (span `repro.prepare.embed`) in the
traced window."""

from spans import delta


def read(run):
    d = delta(run, "repro.prepare.embed")
    b, a = run.window["stats_before"], run.window["stats_after"]
    done = a["completed"] - b["completed"]
    if d is None or done <= 0:
        return None
    return d[0] / done
