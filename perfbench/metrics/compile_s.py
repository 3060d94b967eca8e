"""Seconds of JAX compile events (tracing, lowering, backend compile)
in the run."""


def read(run):
    return run["compile_s"]
