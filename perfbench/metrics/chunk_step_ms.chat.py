"""Device time of the chunk program per scan step, chat cells."""
from perfbench import readers


def read(run):
    return readers.chunk_step_ms(run)
