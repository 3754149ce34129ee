"""Seconds per answered request spent on the wire: the server's network
breakdown (frame decode to admit, result encode and send) over the
results it sent in the traced window."""


def read(run):
    b, a = (run.window[k]["net"] for k in ("stats_before", "stats_after"))
    sent = a["results_sent"] - b["results_sent"]
    if sent <= 0:
        return None
    return (a["breakdown"]["network_s"] - b["breakdown"]["network_s"]) / sent
