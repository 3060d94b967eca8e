"""95th percentile of due time to admission over the requests due
in the window (open mixes)."""
from perfbench import readers


def read(run):
    if run["mode"] != "open":
        return None
    return readers.p95(readers.queue_wait_s(run))
