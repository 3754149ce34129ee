"""Share of the traced window in which the chip ran nothing (served)."""


def read(run):
    return 100.0 * run.trace.idle_share
