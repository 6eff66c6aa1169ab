"""Serving runtime (repro.serve v2, DESIGN.md §11).

Two tiers:

* The **dense tier** (``make_serve_step`` / ``generate``) is the original
  static-batch greedy loop, now with a true batched prefill: one forward
  pass writes the whole prompt into the KV cache instead of stepping it
  token-by-token (the old loop survives as :func:`generate_stepwise`, the
  regression oracle).  Multi-device serving reuses the ``repro.dist`` rules
  unchanged.

* The **paged tier** (:class:`ServeEngine`) is the production path: paged
  KV cache with per-request block tables (``serve/cache.py``), continuous
  batching with admission control (``serve/scheduler.py``), and optional
  k-replica Byzantine-robust decode (``serve/robust_decode.py``).  Every
  decode step is ONE fixed-shape jitted call over all ``max_slots`` slots —
  inactive slots write to the reserved trash block and their outputs are
  ignored — so continuous join/retire never recompiles.  Prefills are
  grouped by prompt length and the group batch padded to a power of two,
  bounding compilation to O(log max_slots) shapes per prompt length.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.serve.cache import DEFAULT_BLOCK_TOKENS, PagedKVCache
from repro.serve.robust_decode import RobustDecoder
from repro.serve.scheduler import DECODE, Request, Scheduler


def shard_cache(cache, mesh: Mesh):
    """Device-put a KV cache according to ``repro.dist.cache_pspec``."""
    from repro.dist.sharding import cache_pspec
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.device_put(
            leaf, NamedSharding(mesh, cache_pspec(path, leaf, mesh))),
        cache)


def make_serve_step(model, *, mesh: Optional[Mesh] = None, donate=True):
    """Returns ``serve_step(params, cache, tokens, pos) -> (next_tokens,
    logits, new_cache)``.  With tokens (B,1)/scalar pos it is one decode
    step; with tokens (B,S0)/pos=arange(S0) it is a batched prefill whose
    next_tokens continue the prompt."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = model.decode_step(params, cache, tokens, pos)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tokens[:, None], logits, cache

    jitted = jax.jit(serve_step, donate_argnums=(1,) if donate else ())
    if mesh is None:
        return jitted

    def stepped(params, cache, tokens, pos):
        with mesh:       # ambient mesh: activates shard_hint constraints
            return jitted(params, cache, tokens, pos)

    return stepped


def batched_prefill_supported(cfg, prompt_len: int) -> bool:
    """Whether one decode_step call can prefill a (B, prompt_len) prompt:
    recurrent state (SSM/hybrid) steps by construction, enc-dec prefills in
    its own forward, and windowed ring buffers only hold prompt_len <= W."""
    if cfg.is_ssm or cfg.hybrid or cfg.is_encdec:
        return False
    return all(w is None or prompt_len <= w for w in cfg.layer_windows())


def generate(model, params, prompts: jax.Array, max_new_tokens: int,
             *, max_len: Optional[int] = None,
             mesh: Optional[Mesh] = None):
    """Greedy batched generation.  prompts: (B, S0) int32.  Prefills the
    whole prompt in ONE forward pass when the architecture allows it
    (falling back to the stepwise loop otherwise), then decodes greedily.
    Returns (B, S0 + max_new_tokens)."""
    B, S0 = prompts.shape
    total = S0 + max_new_tokens if max_len is None else max_len
    if not (S0 > 1 and batched_prefill_supported(model.cfg, S0)):
        return generate_stepwise(model, params, prompts, max_new_tokens,
                                 max_len=max_len, mesh=mesh)
    cache = model.init_cache(B, total)
    if mesh is not None:
        from repro.train.step import shard_params
        params = shard_params(params, mesh)
        cache = shard_cache(cache, mesh)
    step = make_serve_step(model, mesh=mesh, donate=False)

    toks = prompts
    nxt, _, cache = step(params, cache, prompts, jnp.arange(S0))
    toks = jnp.concatenate([toks, nxt], axis=1)
    t = S0
    while toks.shape[1] < total:
        nxt, _, cache = step(params, cache, nxt, jnp.int32(t))
        toks = jnp.concatenate([toks, nxt], axis=1)
        t += 1
    return toks


def generate_stepwise(model, params, prompts: jax.Array,
                      max_new_tokens: int, *, max_len: Optional[int] = None,
                      mesh: Optional[Mesh] = None):
    """The original decode-path prefill: step the prompt token-by-token.
    Kept as the fallback for architectures batched prefill cannot cover and
    as the regression oracle ``generate`` must match bit-for-bit."""
    B, S0 = prompts.shape
    total = S0 + max_new_tokens if max_len is None else max_len
    cache = model.init_cache(B, total)
    if mesh is not None:
        from repro.train.step import shard_params
        params = shard_params(params, mesh)
        cache = shard_cache(cache, mesh)
    step = make_serve_step(model, mesh=mesh, donate=False)

    toks = prompts
    nxt = prompts[:, :1]
    for t in range(total - 1):
        cur = toks[:, t : t + 1] if t < S0 else nxt
        nxt, _, cache = step(params, cache, cur, jnp.int32(t))
        if t >= S0 - 1:
            toks = jnp.concatenate([toks, nxt], axis=1)
        if toks.shape[1] >= total:
            break
    return toks


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class ServeEngine:
    """Continuous-batching paged-cache serving engine.

    ``params`` is the model's params pytree — or, when ``decoder`` is given,
    the length-``decoder.k`` tuple of per-replica pytrees from
    ``robust_decode.make_replicas`` (corrupt replicas with
    ``corrupt_replica`` to test the defense; the tuple layout is a perf
    constraint, see make_replicas).  ``submit()`` enqueues requests; each
    ``step()`` retires
    finished requests, admits queued ones (slot + cache-footprint gates),
    prefills joiners, and runs one decode step over every active slot.
    ``run()`` loops until drained.
    """

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_seq_len: int = 256,
                 block_tokens: int = DEFAULT_BLOCK_TOKENS,
                 num_blocks: Optional[int] = None,
                 decoder: Optional[RobustDecoder] = None,
                 telemetry=None):
        if not model.supports_paged:
            raise NotImplementedError(
                f"arch {model.cfg.name!r} is not paged-serving capable "
                "(see models.stack.paged_supported); use serve.generate")
        if decoder is not None and (not isinstance(params, tuple)
                                    or len(params) != decoder.k):
            raise ValueError(
                f"replicated decode needs params as a length-{decoder.k} "
                "tuple of per-replica pytrees (see "
                "robust_decode.make_replicas)")
        self.model = model
        self.params = params
        self.decoder = decoder
        # Any telemetry shape adapts onto the bus: a Recorder passes
        # through, a raw TelemetryWriter becomes its JSONL sink, None
        # becomes the shared disabled Recorder (every obs call a no-op).
        from repro.obs.metrics import as_recorder
        self.obs = as_recorder(telemetry)
        self.telemetry = telemetry
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.cache = PagedKVCache(
            model, max_slots=max_slots, max_seq_len=max_seq_len,
            block_tokens=block_tokens, num_blocks=num_blocks,
            replicas=decoder.k if decoder is not None else 1)
        self.pool = self.cache.pool
        self.scheduler = Scheduler(
            max_slots=max_slots,
            can_cover=self.cache.can_cover,
            reserve=self.cache.ensure,
            release=self.cache.release)
        self.steps_run = 0
        self._build_steps()

    # -- jitted device steps -------------------------------------------------

    def _build_steps(self):
        # The pool argument is DONATED in both jitted steps: every caller
        # threads self.pool through (the old buffers are dead after the
        # call), and in-place pool updates keep the k-replica decode step
        # within the perf guard's 3.5x-of-single budget.
        model = self.model
        if self.decoder is None:
            def prefill(params, pool, tokens, tables):
                logits, pool = model.prefill_paged(params, pool, tokens,
                                                   tables)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt, pool

            def decode(params, pool, tokens, positions, tables, rep_state):
                logits, pool = model.decode_step_paged(
                    params, pool, tokens, positions, tables)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return nxt, pool, rep_state, jnp.zeros((1,), jnp.float32)
        else:
            dec = self.decoder

            # params/pool are TUPLES of per-replica pytrees; the loops
            # unroll into k independent forwards inside one jitted program
            # (a stacked replica axis costs ~1.5x more — see make_replicas).
            def prefill(params, pool, tokens, tables):
                last, pools = [], []
                for p, c in zip(params, pool):
                    logits, nc = model.prefill_paged(p, c, tokens, tables)
                    last.append(logits[:, -1].astype(jnp.float32))
                    pools.append(nc)
                stacked = jnp.stack(last)                   # (k, B, V)
                k, B, V = stacked.shape
                # Aggregate through the current gate; reputation updates
                # stay on the homogeneous decode step (prefill batches are
                # partial and variable-shaped).
                agg, _ = dec.rule.reduce_gated_with_scores(
                    stacked.reshape(k, B * V), dec.rep_state["active"])
                nxt = jnp.argmax(agg.reshape(B, V), axis=-1).astype(jnp.int32)
                return nxt, tuple(pools)

            def decode(params, pool, tokens, positions, tables, rep_state):
                last, pools = [], []
                for p, c in zip(params, pool):
                    logits, nc = model.decode_step_paged(
                        p, c, tokens, positions, tables)
                    last.append(logits[:, -1])
                    pools.append(nc)
                agg, scores, new_state = dec.aggregate(
                    jnp.stack(last), rep_state)
                nxt = jnp.argmax(agg, axis=-1).astype(jnp.int32)
                return nxt, tuple(pools), new_state, scores

        self._prefill_fn = jax.jit(prefill, donate_argnums=(1,))
        self._decode_fn = jax.jit(decode, donate_argnums=(1,))

    # -- request API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               deadline_s: float = 0.0) -> Request:
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"request needs {len(prompt) + max_new_tokens} positions, "
                f"engine max_seq_len={self.max_seq_len}")
        return self.scheduler.submit(prompt, max_new_tokens,
                                     deadline_s=deadline_s)

    def cancel(self, req: Request) -> bool:
        """Cancel a request (client disconnect): frees its slot and KV
        reservation immediately so the next admit() can reuse them."""
        ok = self.scheduler.cancel(req)
        if ok:
            self.obs.count("serve_cancelled")
        return ok

    # -- degradation -----------------------------------------------------------

    def crash_replica(self, index: int) -> None:
        """Simulate replica ``index``'s host dying mid-serve: its params and
        KV pool are dropped, the decoder shrinks to the surviving k-1
        replicas with b re-resolved (RobustDecoder.shrink), and the engine
        re-jits its steps for the new replica count.  In-flight requests
        continue — the paged tables and survivor pools are untouched."""
        if self.decoder is None:
            raise ValueError(
                "crash_replica needs replicated robust decode "
                "(ServeEngine(decoder=...))")
        self.decoder.shrink(index)        # validates index, k >= 3
        self.params = tuple(p for i, p in enumerate(self.params)
                            if i != index)
        self.pool = tuple(c for i, c in enumerate(self.pool) if i != index)
        self._build_steps()
        self.obs.count("replica_crashes")

    # -- the loop --------------------------------------------------------------

    def step(self) -> int:
        """One engine iteration: retire -> admit -> prefill joiners -> one
        batched decode over every active slot.  Returns the number of
        tokens generated this step.  While tracing, the iteration is an
        ``engine`` span whose children split its host time: ``schedule``,
        ``prefill`` and ``decode`` (build and dispatch), ``readback``
        (waiting for the tokens) and ``append``."""
        obs = self.obs
        with obs.span("engine", step_num=self.steps_run):
            with obs.span("schedule"):
                admitted = self._schedule()
            produced = 0

            # Batched prefill, grouped by prompt length (one compile per
            # (padded group size, prompt length) pair).
            by_len: dict = {}
            for req in admitted:
                by_len.setdefault(req.prompt_len, []).append(req)
            for S0, group in sorted(by_len.items()):
                with obs.span("prefill", prompt_len=S0,
                              batch=_pow2(len(group))):
                    tokens = np.zeros((_pow2(len(group)), S0), np.int32)
                    tables = np.zeros((tokens.shape[0],
                                       self.cache.max_blocks), np.int32)
                    for i, req in enumerate(group):
                        tokens[i] = req.prompt
                        tables[i] = self.cache.tables[req.slot]
                    nxt, self.pool = self._prefill_fn(
                        self.params, self.pool, jnp.asarray(tokens),
                        jnp.asarray(tables))
                with obs.span("readback"):
                    nxt = np.asarray(nxt)
                with obs.span("append"):
                    for i, req in enumerate(group):
                        self.scheduler.mark_decoding(req, nxt[i])
                        produced += 1

            # One fixed-shape decode step over all slots (inactive slots
            # carry zero tokens/positions and all-zero table rows -> trash
            # block).
            decoding = [r for r in self.scheduler.active
                        if r.state == DECODE and not r.finished]
            if decoding:
                k = self.decoder.k if self.decoder is not None else 1
                with obs.span("decode", slots=len(decoding), k=k):
                    tokens = np.zeros((self.max_slots, 1), np.int32)
                    positions = np.zeros((self.max_slots,), np.int32)
                    for req in decoding:
                        tokens[req.slot, 0] = req.generated[-1]
                        positions[req.slot] = req.decode_pos
                    rep = (self.decoder.rep_state
                           if self.decoder is not None else {})
                    nxt, self.pool, new_rep, scores = self._decode_fn(
                        self.params, self.pool, jnp.asarray(tokens),
                        jnp.asarray(positions), self.cache.device_tables(),
                        rep)
                with obs.span("readback"):
                    nxt = np.asarray(nxt)
                with obs.span("append"):
                    for req in decoding:
                        self.scheduler.append_token(req, nxt[req.slot])
                        produced += 1
                    if self.decoder is not None:
                        self.decoder.observe(new_rep, scores,
                                             telemetry=obs,
                                             step=self.steps_run)
            obs.log("serve", self.steps_run,
                    active=len(self.scheduler.active),
                    queued=self.scheduler.queued, produced=produced,
                    free_blocks=self.cache.allocator.free_blocks)
        self.steps_run += 1
        return produced

    def _schedule(self) -> list:
        """Expire, retire and admit; returns the admitted requests."""
        sched = self.scheduler
        obs = self.obs
        expired = sched.expire_deadlines()
        if expired:
            obs.count("serve_deadline_expired", len(expired))
        retired = self.retire_finished()
        admitted = sched.admit()
        if retired:
            obs.count("serve_retired", len(retired))
        if admitted:
            obs.count("serve_admitted", len(admitted))
        # Admission-control save: slots are free but the queue head's cache
        # footprint doesn't fit — without the can_cover gate this step
        # would have raised OutOfBlocks mid-flight.
        if sched.queued and len(sched.active) < self.max_slots:
            obs.count("serve_outofblocks_averted")
        return admitted

    def retire_finished(self) -> List[Request]:
        """Retire every finished request (``Scheduler.retire_finished``)
        and hand each one's phases to the Recorder as a request record."""
        retired = self.scheduler.retire_finished()
        for r in retired:
            self.obs.request(r.rid, t_enqueue=r.t_enqueue,
                             t_admitted=r.t_admitted,
                             t_first_token=r.t_first_token, t_done=r.t_done)
        return retired

    def run(self, max_steps: int = 100_000) -> List[Request]:
        """Drive ``step()`` until every submitted request completed."""
        for _ in range(max_steps):
            if not self.scheduler.busy:
                break
            self.step()
        self.retire_finished()
        return list(self.scheduler.completed)

    # -- measurement -----------------------------------------------------------

    def time_decode_step(self, iters: int = 20) -> float:
        """Median wall-time (ms) of the jitted all-slots decode call at the
        engine's current occupancy — the per-step cost BENCH_serve and the
        perf guard compare across single vs k-replica configurations.
        The pool is donated, so each iteration threads it like ``step()``
        does (idle slots write the trash block; contents are unchanged)."""
        import time
        tokens = jnp.zeros((self.max_slots, 1), jnp.int32)
        positions = jnp.zeros((self.max_slots,), jnp.int32)
        tables = self.cache.device_tables()
        rep = self.decoder.rep_state if self.decoder is not None else {}

        def once():
            nxt, self.pool, _, _ = self._decode_fn(
                self.params, self.pool, tokens, positions, tables, rep)
            jax.block_until_ready(nxt)

        once()                                                 # compile
        samples = []
        for _ in range(iters):
            t0 = time.perf_counter()
            once()
            samples.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(samples))
