"""On-chip benchmark: cells (configuration x traffic mix) driven through the
serving stack, metrics read from the host clock, program counters and the
device trace.  ``python3 perfbench/run.py --workload <cell> ...``."""
