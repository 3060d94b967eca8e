"""Share of its roofline the ``dms_decode`` kernel reaches over the
traced window, in % (bytes-bound: the live K/V bytes at bf16 over HBM
bandwidth)."""
from perfbench import readers


def read(run):
    return readers.kernel_roofline_pct(run, "dms_decode")
