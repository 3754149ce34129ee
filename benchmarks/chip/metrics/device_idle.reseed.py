"""Share of the traced window in which the chip ran nothing (re-seeding)."""


def read(run):
    return 100.0 * run.trace.idle_share
