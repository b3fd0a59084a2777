"""Percent of the HBM roofline the lookup programs reach: the least bytes
the traced dispatches must move (bench/roofline.py, counted from the
data) at the chip's peak, over the device time of those programs."""


def read(run):
    return run.lookup_roofline()
