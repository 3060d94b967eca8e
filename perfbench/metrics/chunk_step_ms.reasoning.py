"""Device time of the chunk program per scan step, reasoning cells."""
from perfbench import readers


def read(run):
    return readers.chunk_step_ms(run)
