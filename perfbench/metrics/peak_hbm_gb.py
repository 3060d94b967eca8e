"""Peak device bytes in use after the window, in GB (1e9)."""


def read(run):
    b = run["peak_bytes"]
    return None if b is None else b / 1e9
