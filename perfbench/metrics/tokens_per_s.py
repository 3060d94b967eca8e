"""Output tokens emitted in the window, over the window (closed mixes)."""


def read(run):
    if run["tokens"] is None:
        return None
    return run["tokens"] / run["window_s"]
