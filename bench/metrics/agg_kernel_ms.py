"""Device time per training step of the robust aggregation's kernels, in
milliseconds: the Pallas phocas kernels as the trace names them."""
from bench import trace as tr

# The Pallas kernels of the phocas rule as a v5e trace names them:
# phocas_counts_pallas (aggregate and drop counts of the raw matrix, for the
# defense's scores) and phocas_pallas (aggregate of the gated matrix).
KERNELS = r"^phocas_(counts_)?pallas\b"


def read(ctx):
    ns = tr.kernel_ns(ctx["trace"], KERNELS)
    if ns <= 0:
        return None
    return ns * 1e-6 / ctx["steps"]
