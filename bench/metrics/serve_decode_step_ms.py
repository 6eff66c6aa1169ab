"""Median device time of one run of the engine's jitted decode program
(all slots, k replicas, the robust aggregation), in milliseconds."""
import numpy as np

from bench import trace as tr

# The decode program's module name in a v5e trace.
PROGRAM = r"^jit_decode\("


def read(ctx):
    runs = tr.program_ns(ctx["trace"], PROGRAM)
    if not runs:
        return None
    return float(np.median(runs)) * 1e-6
