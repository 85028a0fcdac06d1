"""Percent of the traced window in which the device runs no operation:
1 - (union of device-op intervals / window), a mean over the cell's
devices."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
