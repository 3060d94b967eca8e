"""95th percentile, over the requests due in the window, of
(last token - first token) / (tokens - 1), in ms (open mixes)."""
from perfbench import readers


def read(run):
    if run["mode"] != "open":
        return None
    v = readers.p95(readers.tpot_s(run))
    return None if v is None else v * 1e3
