"""Percent of the traced window in which no program ran on the device
(1 - union of program executions / window)."""


def read(run):
    return run.device_idle()
