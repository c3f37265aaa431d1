"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window), in percent."""


def read(run):
    return 100.0 * run.trace.idle_share
