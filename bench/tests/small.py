"""A cell cut to a size the CPU runs in seconds, for the tests: the
harness's run with its look for a chip skipped."""
from __future__ import annotations

import argparse

SMALL = {"hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 1, "vocab_size": 256,
         "embedding_multiplier": 128 ** 0.5}
# At this size phocas drops a different worker on a larger share of its
# few coordinates than at the cells' size, so sound runs read about ten
# times higher (loss 1e-3, gradient 1e-2 at most on the seeds tried) and
# the limits are this size's own; the faults still read 0.3 and more.
SMALL_TRAIN = {"seq_len": 32, "pool": 4, "active_vocab": 128,
               "limits": {"loss_gap": 0.01, "grad_gap": 0.1,
                          "change_gap": 0.1}}
SMALL_SERVE = {"max_slots": 4, "max_seq_len": 96, "prompt_lens": [16, 32],
               "output_median": 8, "output_cap": 64, "rate_per_s": 20.0,
               "check_tokens": 30, "check_requests": 3}


def harness(workload: str, *, seed: int = 1234, seconds: float = 0.5,
            config=None, traffic=None):
    """The harness of one run of ``workload``, cut to the CPU's size."""
    import jax

    from bench.harness import Harness
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    h = Harness(args, t_start=Harness.clock())
    h.config = dict(h.config, **SMALL, **(config or {}))
    small = SMALL_TRAIN if h.traffic["driver"] == "train" else SMALL_SERVE
    h.traffic = dict(h.traffic, **small)
    h.traffic = dict(h.traffic, **(traffic or {}))
    h.devices = jax.devices()[:1]
    h.count_compiles()
    h.memory_peak = lambda: 0
    return h
