"""Device: share of the traced slice in which no operation ran on the
chip, in a decode cell, in %."""


def read(run):
    return 100.0 * (1.0 - run.busy_s / run.window_s)
