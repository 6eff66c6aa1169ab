"""Serving cells: the program's paged ``ServeEngine`` with k replicated
robust replicas, driven open-loop by ``ServeEngine.step``.

Requests come due on the mix's schedule (``gen.request_schedule``) and are
submitted when due, whatever the engine is doing; each is timed from when it
was due.  Token times are read when ``step()`` returns, which waits for the
step's tokens.  After the window closes the engine runs on until every
request due in it has finished, a minute past the close at most; the
latencies count that wait, and a request that never finishes has failed.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, peaks
from bench.drivers import common
from bench.reference import granite

ANNOTATIONS = ("admit", "step", "idle")
DRAIN_S = 60.0


@functools.lru_cache(maxsize=None)
def _weights_program(cfg_items: tuple, scale: float):
    cfg = dict(cfg_items)

    def make(key):
        k_honest, k_bad = jax.random.split(key)
        honest = granite.init_weights(k_honest, cfg)
        spec = granite.shapes(cfg)
        bad = {n: (scale * jax.random.normal(k, s, jnp.float32)
                   ).astype(jnp.bfloat16)
               for k, (n, s) in zip(jax.random.split(k_bad, len(spec)),
                                    spec.items())}
        return honest, bad

    return jax.jit(make)


def serve_weights(key, cfg: dict, scale: float):
    """The honest weights and a corrupted replica's (``scale`` times
    N(0, 1) in every array), made by one compiled program."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return _weights_program(items, scale)(key)


def make_engine(model, honest: dict, bad: dict, mix: dict):
    from repro.serve import RobustDecoder, ServeEngine
    k = mix["replicas"]
    reps = tuple(common.to_program(bad if i in mix["corrupted"] else honest)
                 for i in range(k))
    return ServeEngine(model, reps, max_slots=mix["max_slots"],
                       max_seq_len=mix["max_seq_len"],
                       block_tokens=mix["block_tokens"],
                       decoder=RobustDecoder(rule=mix["rule"], k=k))


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Run every (group size, prompt length) prefill and the decode step
    that the mix can reach through the engine's own ``step()``: groups
    are padded to powers of two up to ``max_slots``."""
    g = 1
    while g <= mix["max_slots"]:
        for s0 in mix["prompt_lens"]:
            for i in range(g):
                engine.submit(np.full(s0, (7 * i) % vocab, np.int32), 2)
            engine.run()
        g *= 2
    engine.scheduler.completed.clear()


class Tracker:
    """Per request: when it was due and when each of its tokens came."""

    def __init__(self):
        self.reqs: list = []          # (program Request, due time, sent)
        self.times: dict = {}         # rid -> token times

    def add(self, req, due: float, sent) -> None:
        self.reqs.append((req, due, sent))
        self.times[req.rid] = []

    def observe(self, now: float) -> None:
        for req, _, _ in self.reqs:
            t = self.times[req.rid]
            if len(req.generated) > len(t):
                t.extend([now] * (len(req.generated) - len(t)))

    def pending(self) -> bool:
        return any(len(self.times[r.rid]) < r.max_new_tokens
                   for r, _, _ in self.reqs)


def p95(values) -> float:
    """The 95th percentile, interpolated linearly between order statistics
    (numpy's default); a request that never answered counts as infinite."""
    v = np.sort(np.asarray(values, float))
    pos = 0.95 * (len(v) - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if hi == lo or v[hi] == v[lo]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def drive(h, engine, schedule: list) -> tuple:
    """Offer ``schedule`` open-loop for the window and drain.  Returns
    (tracker, window length, lateness of the generator in seconds)."""
    tr = Tracker()
    pending = list(schedule)
    h.start_trace()
    h.open_window()
    t0 = h.t_open
    late = 0.0
    closed = False
    while True:
        now = h.clock()
        if not closed and now - t0 >= h.seconds:
            h.close_window()
            closed = True
        with h.annotate("admit"):
            while pending and t0 + pending[0].due_s <= now:
                r = pending.pop(0)
                late = max(late, now - (t0 + r.due_s))
                tr.add(engine.submit(r.prompt, r.max_new_tokens),
                       t0 + r.due_s, r)
        if engine.scheduler.busy:
            with h.annotate("step"):
                engine.step()
            tr.observe(h.clock())
        elif closed:
            break
        else:
            with h.annotate("idle"):
                wait = t0 + pending[0].due_s - h.clock() if pending else 0
                if wait > 0:
                    time.sleep(min(wait, 0.002))
        if closed and (not tr.pending() or now - h.t_close > DRAIN_S):
            break
    return tr, h.t_close - t0, late


def metrics(tr: Tracker, t_open: float, t_close: float) -> dict:
    ttft, gaps, in_window = [], [], 0
    for req, due, _ in tr.reqs:
        t = tr.times[req.rid]
        if t:
            ttft.append(t[0] - due)
        else:
            ttft.append(float("inf"))
        gaps.extend(np.diff(t).tolist())
        in_window += sum(1 for x in t if x <= t_close)
    return {"serve_ttft_p95_ms": 1e3 * p95(ttft),
            "serve_tpot_p95_ms": 1e3 * p95(gaps) if gaps else float("inf"),
            "serve_tokens_per_s": in_window / (t_close - t_open)}


@functools.lru_cache(maxsize=None)
def _ref_logits_program(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)

    def f(w, tokens):
        with jax.default_matmul_precision("highest"):
            return granite.forward(w, cfg, tokens, precision)[0]

    return jax.jit(f)


def logit_gaps(w: dict, cfg: dict, samples: list, length: int,
               precision: str = "f32") -> list:
    """For each (prompt, served tokens): the reference's logits over the
    prompt and the served tokens (padded to ``length``; causal, so the
    padding changes nothing before it), and the gap by which each served
    token's logit lies below the best at its position.  With ``precision``
    "fp8" the served tokens are replaced by the fp8 reference's own first
    choices, and their gaps are read under the float32 reference."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    ref = _ref_logits_program(items, "f32")
    low = _ref_logits_program(items, precision) if precision != "f32" \
        else None
    out = []
    for prompt, served in samples:
        seq = np.zeros((1, length), np.int32)
        full = np.concatenate([prompt, served[:-1]])
        seq[0, :len(full)] = full
        pos = np.arange(len(prompt) - 1, len(full))
        logits = np.asarray(ref(w, jnp.asarray(seq)))[pos]
        chosen = np.asarray(served)
        if low is not None:
            chosen = np.asarray(low(w, jnp.asarray(seq)))[pos].argmax(-1)
        gaps = logits.max(-1) - logits[np.arange(len(pos)), chosen]
        out.append(float(gaps.max()))
    return out


def sample(tr: Tracker, rng, min_tokens: int, min_requests: int) -> list:
    """Finished requests drawn from the seed, the one with the most served
    tokens first, until both minimums are met."""
    done = [(req, sent) for req, _, sent in tr.reqs
            if len(req.generated) >= req.max_new_tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][0].generated))
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, tokens = [], 0
    for i in order:
        if tokens >= min_tokens and len(out) >= min_requests:
            break
        req, sent = done[i]
        out.append((np.asarray(sent.prompt), np.asarray(req.generated)))
        tokens += len(req.generated)
    return out


def run(h) -> dict:
    cfg, mix = h.config, h.traffic
    key = gen.seed_key(h.seed)
    model = common.program_model(cfg)
    honest, bad = serve_weights(jax.random.fold_in(key, 1), cfg,
                                mix["corrupt_scale"])
    common.check_layout(model, common.to_program(honest))
    engine = make_engine(model, honest, bad, mix)
    del bad
    warm_up(engine, mix, cfg["vocab_size"])
    schedule = gen.request_schedule(mix, h.seed, cfg["vocab_size"],
                                    h.seconds)
    tr, window_s, late = drive(h, engine, schedule)
    trace = h.stop_trace(ANNOTATIONS)
    memory = h.memory_peak()
    failed = sum(len(r.generated) < r.max_new_tokens for r, _, _ in tr.reqs)
    e2e = metrics(tr, h.t_open, h.t_close)
    ejected = engine.decoder.ejected_replicas()
    del engine
    gc.collect()
    picked = sample(tr, np.random.default_rng(h.seed), mix["check_tokens"],
                    mix["check_requests"])
    gaps = logit_gaps(honest, cfg, picked, mix["max_seq_len"])
    import sys
    print(f"serve: {len(tr.reqs)} requests due, generator at most "
          f"{1e3 * late:.1f} ms late, corrupted replicas ejected {ejected}, "
          f"checked {len(picked)} requests / "
          f"{sum(len(s) for _, s in picked)} tokens", file=sys.stderr)
    res = {"attempted": len(tr.reqs), "failed": failed, "memory": memory,
           "checks": {"logit_gap": (max(gaps) if gaps else float("inf"),
                                    mix["limits"]["logit_gap"])}}
    if not h.trace:
        res["metrics"] = {
            "serve_tokens_per_s": {"value": e2e["serve_tokens_per_s"],
                                   "unit": "tokens/s"},
            "setup_s": {"value": h.setup_s(), "unit": "s"}}
        return res
    from bench import harness
    from bench import trace as tx
    ctx = {"trace": trace, "config": cfg, "job": mix, "served": e2e,
           "peaks": peaks.peaks_for(h.devices[0].device_kind),
           "chips": len(h.devices)}
    res["metrics"] = harness.per_layer(h, ctx)
    res["busy_s"] = tx.busy_ns(trace) * 1e-9
    res["window_s"] = tx.window_ns(trace) * 1e-9
    res["breakdown"] = tx.breakdown(trace)
    return res
