"""Device: the share of the traced window, in %, in which no operation ran
on the chip."""


def read(run):
    return 100.0 * run.trace.idle_share
