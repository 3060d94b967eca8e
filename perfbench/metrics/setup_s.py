"""Process start to window open: weights, engine, compilation or cache
loads, warm-up, and for closed mixes the prefill of the first batch."""


def read(run):
    return run["setup_s"]
