"""Share of the HBM roofline that the aggregation kernels reach: the bytes
the rule must move (read the (m, D) float32 worker matrix once, write D
outputs; ``costs.aggregation_bytes``) over ``agg_kernel_ms``, against the
chip's HBM bandwidth, in percent."""
from bench.metrics import agg_kernel_ms


def read(ctx):
    ms = agg_kernel_ms.read(ctx)
    if ms is None:
        return None
    c = ctx["costs"]
    moved = c.aggregation_bytes(ctx["job"]["workers"],
                                c.param_count(ctx["config"]))
    return 100.0 * moved / (ms * 1e-3) / ctx["peaks"]["hbm_bw"]
