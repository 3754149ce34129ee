"""Seconds per result sent that the server's delivery waited for the
device to finish the lane and copied the result to the host (span
`repro.net.fetch`) in the traced window."""

from spans import delta


def read(run):
    d = delta(run, "repro.net.fetch")
    b, a = (run.window[k]["net"] for k in ("stats_before", "stats_after"))
    sent = a["results_sent"] - b["results_sent"]
    if d is None or sent <= 0:
        return None
    return d[0] / sent
