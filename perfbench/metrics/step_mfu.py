"""Model FLOPs of every active lane-step of the traced ticks (matmuls at
bf16, attention over the live tokens) over the traced window's seconds x
peak bf16 FLOP/s, in %."""
from perfbench import readers


def read(run):
    return readers.step_mfu_pct(run)
