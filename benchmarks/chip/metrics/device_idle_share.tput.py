"""device_idle_share: 1 minus the union of device operation intervals
over the traced window."""
from chipbench import readers


def read(run):
    return readers.idle_share(run)
