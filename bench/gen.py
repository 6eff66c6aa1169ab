"""The one traffic generator: every traffic mix is a data file that this
module reads.

* ``token_pool`` makes a training cell's batches: the bigram process of the
  program's ``TokenStream`` (``repro.data.pipeline``), a fixed random table
  of ``modes`` next-token distributions over the first ``active_vocab`` ids,
  copied here as ONE jitted program that makes the whole pool on the device
  before the measured window.  Step ``i`` of a run trains on
  ``pool[i % len(pool)]``.
* ``request_schedule`` makes a serving cell's open-loop requests: arrival
  gaps, prompt and output lengths from the mix's distributions.  The set of
  sizes and gaps is drawn once from the mix's own ``base_seed``; ``--seed``
  only permutes them and draws the prompt tokens, so every seed offers the
  same work in another order.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also one past 32 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _pool_program(batch: int, seq_len: int, pool: int, modes: int,
                  active: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        k_table, k_mode, k_seq = jax.random.split(key, 3)
        logp = jax.nn.log_softmax(
            2.5 * jax.random.normal(k_table, (modes, active)))
        mode_of = jax.random.randint(k_mode, (active,), 0, modes)
        n = pool * batch
        k_first, k_scan = jax.random.split(k_seq)
        first = jax.random.randint(k_first, (n,), 0, active)

        def step(tok, kk):
            nxt = jax.random.categorical(kk, logp[mode_of[tok]], axis=-1)
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, jax.random.split(k_scan, seq_len))
        seqs = jnp.concatenate([first[None], rest]).T.astype(jnp.int32)
        seqs = seqs.reshape(pool, batch, seq_len + 1)
        return tuple({"tokens": seqs[i, :, :-1], "labels": seqs[i, :, 1:]}
                     for i in range(pool))

    return jax.jit(make)


def token_pool(seed: int, *, batch: int, seq_len: int, pool: int,
               modes: int, active_vocab: int) -> tuple:
    """``pool`` global batches of ``batch`` rows, each ``{"tokens",
    "labels"}`` (batch, seq_len) int32 on the default device, labels the
    next tokens.  One compiled program makes all of them."""
    return _pool_program(batch, seq_len, pool, modes, active_vocab)(
        seed_key(seed))


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def request_schedule(mix: dict, seed: int, vocab: int,
                     seconds: float) -> list:
    """The open-loop requests due in a window of ``seconds``.

    ``mix``: ``rate_per_s`` (Poisson arrivals), ``prompt_lens`` and
    ``prompt_probs``, ``output_median``, ``output_sigma`` (lognormal),
    ``output_cap``, ``base_seed``.  The window holds ``rate_per_s *
    seconds`` requests.  Their sizes and exponential gaps come from
    ``base_seed`` alone, the gaps scaled to fill the window; ``seed``
    permutes sizes and gaps and draws the prompt tokens."""
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    base = np.random.default_rng(mix["base_seed"])
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    plens = base.choice(mix["prompt_lens"], size=n, p=mix["prompt_probs"])
    outs = np.exp(np.log(mix["output_median"])
                  + mix["output_sigma"] * base.standard_normal(n))
    outs = np.clip(np.rint(outs), 1, mix["output_cap"]).astype(int)
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    sizes = rng.permutation(n)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Request(float(t), rng.integers(0, vocab, int(plens[i]),
                                           dtype=np.int32), int(outs[i]))
            for t, i in zip(due, sizes)]
