"""Share of the traced window with no operation on the device,
reasoning cells, in %."""
from perfbench import readers


def read(run):
    return readers.device_idle_pct(run)
