"""95th percentile, over every request due in the window, of due
time to the tick that delivered its first token (open mixes)."""
from perfbench import readers


def read(run):
    if run["mode"] != "open":
        return None
    return readers.p95(readers.ttft_s(run))
