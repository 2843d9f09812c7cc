"""Idle share of the device over the traced window: 1 - (union of the
intervals in which an operation ran on the chip) / (window length),
averaged over the cell's chips."""
NAME = "device.idle"
UNIT = "%"
LAYER = "device"
MOVES = "img_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or run.trace.n_ops == 0:
        return None
    return 100.0 * run.trace.idle_share
