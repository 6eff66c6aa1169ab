"""repro.serve v2 tests (DESIGN.md §11): paged KV cache vs dense ring
cache equivalence, block-table alloc/free lifecycle, continuous
batching join/retire, batched-prefill regression, and replicated
Byzantine-robust decode (recovery + replica ejection)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import build_model
from repro.serve import (BlockAllocator, OutOfBlocks, PagedKVCache, Request,
                         RobustDecoder, Scheduler, ServeEngine,
                         batched_prefill_supported, corrupt_replica,
                         generate, generate_stepwise, make_replicas)

ARCH = "granite-8b-reduced"


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(get_arch(ARCH))
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(n, lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (lens[i % len(lens)],)).tolist()
            for i in range(n)]


# ---------------------------------------------------------------------------
# Block allocator / block-table lifecycle
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_block_zero_reserved(self):
        alloc = BlockAllocator(8)
        got = alloc.alloc(alloc.free_blocks)      # drain the pool
        assert 0 not in got
        assert sorted(got) == list(range(1, 8))

    def test_out_of_blocks(self):
        alloc = BlockAllocator(4)
        alloc.alloc(3)
        with pytest.raises(OutOfBlocks):
            alloc.alloc(1)

    def test_free_rejects_reserved_and_double_free(self):
        alloc = BlockAllocator(8)
        blocks = alloc.alloc(2)
        alloc.free(blocks)
        with pytest.raises(ValueError):
            alloc.free([blocks[0]])               # double free
        with pytest.raises(ValueError):
            alloc.free([0])                       # the null block

    def test_free_returns_capacity(self):
        alloc = BlockAllocator(8)
        blocks = alloc.alloc(7)
        assert alloc.free_blocks == 0
        alloc.free(blocks)
        assert alloc.free_blocks == 7


class TestPagedKVCacheLifecycle:
    def test_ensure_release_roundtrip(self, model_and_params):
        model, _ = model_and_params
        cache = PagedKVCache(model, max_slots=2, max_seq_len=32,
                             block_tokens=4)
        total = cache.allocator.free_blocks
        cache.ensure(0, 10)                       # 3 blocks of 4
        assert len(cache.owned_blocks(0)) == 3
        assert (cache.tables[0, :3] > 0).all()    # never the null block
        assert cache.tables[0, 3:].sum() == 0
        cache.ensure(0, 12)                       # still 3 blocks: no-op
        assert len(cache.owned_blocks(0)) == 3
        cache.ensure(0, 13)                       # grows to 4
        assert len(cache.owned_blocks(0)) == 4
        cache.release(0)
        assert cache.owned_blocks(0) == []
        assert cache.tables[0].sum() == 0
        assert cache.allocator.free_blocks == total

    def test_admission_gate(self, model_and_params):
        model, _ = model_and_params
        cache = PagedKVCache(model, max_slots=2, max_seq_len=32,
                             block_tokens=4, num_blocks=5)   # 4 usable
        assert cache.can_cover(16)
        assert not cache.can_cover(17)
        cache.ensure(0, 16)
        assert not cache.can_cover(1)
        with pytest.raises(OutOfBlocks):
            cache.ensure(1, 4)

    def test_beyond_table_capacity(self, model_and_params):
        model, _ = model_and_params
        cache = PagedKVCache(model, max_slots=1, max_seq_len=16,
                             block_tokens=4)
        with pytest.raises(OutOfBlocks):
            cache.ensure(0, 17)                   # > max_seq_len


# ---------------------------------------------------------------------------
# Batched prefill regression (dense path)
# ---------------------------------------------------------------------------

def test_batched_prefill_matches_stepwise(model_and_params):
    """generate()'s one-pass prefill must be bit-identical to the legacy
    token-by-token decode-path prefill."""
    model, params = model_and_params
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0,
                                 model.cfg.vocab_size)
    assert batched_prefill_supported(model.cfg, 5)
    new = generate(model, params, prompts, 6)
    old = generate_stepwise(model, params, prompts, 6)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_windowed_arch_uses_fallback():
    cfg = get_arch("gemma3-27b-reduced")          # windowed layers
    assert not batched_prefill_supported(cfg, prompt_len=10**9)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                 cfg.vocab_size)
    new = generate(model, params, prompts, 4)     # routes through stepwise
    old = generate_stepwise(model, params, prompts, 4)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# ---------------------------------------------------------------------------
# Paged vs dense bit-equivalence
# ---------------------------------------------------------------------------

def test_paged_prefill_and_decode_match_dense(model_and_params):
    """Logits through the paged path (block tables, scatter/gather) match
    the dense ring-cache path at every step, and greedy tokens agree."""
    model, params = model_and_params
    B, S0, NEW = 3, 5, 4
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, S0), 0,
                                 model.cfg.vocab_size)

    # The two paths reduce in a different order under the installed XLA,
    # so float32 logits differ in the last bits (1.9e-6 max observed).
    def assert_same(d_logits, p_logits):
        np.testing.assert_allclose(np.asarray(p_logits),
                                   np.asarray(d_logits),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(p_logits[:, -1], axis=-1)),
            np.asarray(jnp.argmax(d_logits[:, -1], axis=-1)))

    dense = model.init_cache(B, S0 + NEW)
    d_logits, dense = model.decode_step(params, dense, prompts,
                                        jnp.arange(S0))

    cache = PagedKVCache(model, max_slots=B, max_seq_len=S0 + NEW,
                         block_tokens=4)
    for s in range(B):
        cache.ensure(s, S0 + NEW)
    tables = cache.device_tables()
    p_logits, pool = model.prefill_paged(params, cache.pool, prompts,
                                         tables)
    assert_same(d_logits, p_logits)

    tok = jnp.argmax(d_logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for t in range(S0, S0 + NEW - 1):
        d_logits, dense = model.decode_step(params, dense, tok,
                                            jnp.int32(t))
        p_logits, pool = model.decode_step_paged(
            params, pool, tok, jnp.full((B,), t, jnp.int32), tables)
        assert_same(d_logits, p_logits)
        tok = jnp.argmax(d_logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


def test_unsupported_arch_raises(model_and_params):
    cfg = get_arch("mamba2-2.7b-reduced")
    model = build_model(cfg)
    assert not model.supports_paged
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        ServeEngine(model, params, max_slots=2, max_seq_len=16)


# ---------------------------------------------------------------------------
# Continuous batching: join/retire mid-loop
# ---------------------------------------------------------------------------

def test_engine_continuous_batching_matches_dense(model_and_params):
    """Requests joining and retiring mid-loop each reproduce their own
    dense-path greedy continuation exactly."""
    model, params = model_and_params
    engine = ServeEngine(model, params, max_slots=3, max_seq_len=32,
                         block_tokens=4)
    prompts = _prompts(5, lens=(5, 3, 7), vocab=model.cfg.vocab_size)
    news = [6, 4, 5, 6, 3]
    reqs = [engine.submit(p, n) for p, n in zip(prompts[:3], news[:3])]
    engine.step()                                  # 3 in flight
    engine.step()
    reqs += [engine.submit(p, n) for p, n in zip(prompts[3:], news[3:])]
    done = engine.run()
    assert len(done) == 5
    for p, n, r in zip(prompts, news, reqs):
        ref = generate(model, params, jnp.asarray([p], jnp.int32), n)
        assert r.generated == [int(t) for t in np.asarray(ref[0, len(p):])]
    # every block returned to the pool after retirement
    assert engine.cache.allocator.free_blocks == engine.cache.num_blocks - 1


def test_scheduler_join_retire_slot_reuse():
    """Pure-policy scheduler: a retired request's slot is reusable in the
    same step, and admission respects the cache gate."""
    reserved, released = [], []
    sched = Scheduler(max_slots=2, can_cover=lambda t: t <= 8,
                      reserve=lambda s, t: reserved.append((s, t)),
                      release=lambda s: released.append(s),
                      clock=lambda: 0.0)
    a = sched.submit([1, 2], max_new_tokens=2)
    b = sched.submit([3], max_new_tokens=3)
    big = sched.submit([1] * 7, max_new_tokens=9)  # budget 16 > gate
    assert sched.admit() == [a, b]
    assert reserved == [(0, 4), (1, 4)]
    sched.mark_decoding(a, 7)
    sched.append_token(a, 8)                       # a finished (2 tokens)
    assert a.finished
    assert sched.retire_finished() == [a]
    assert released == [0]
    assert sched.admit() == []                     # big can't cover
    assert sched.queued == 1 and big.state == "queued"
    assert sched.slot_of(0) is None                # slot 0 free again
    c = sched.submit([5], max_new_tokens=1)        # FIFO: big still blocks...
    assert sched.admit() == []                     # ...the queue head
    assert c.state == "queued"


def test_request_positions():
    r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
    r.generated.append(9)                          # from prefill
    assert r.decode_pos == 3                       # writes position 3 next
    r.generated.append(9)
    assert r.decode_pos == 4
    assert r.total_budget == 7


# ---------------------------------------------------------------------------
# Replicated Byzantine-robust decode
# ---------------------------------------------------------------------------

def test_robust_decode_recovers_clean_output(model_and_params):
    """One garbage-parameter replica out of k=3: phocas and trmean decode
    the clean model's greedy output exactly; plain mean diverges."""
    model, params = model_and_params
    prompt = _prompts(1, lens=(5,), vocab=model.cfg.vocab_size)[0]
    NEW = 8
    clean = generate(model, params, jnp.asarray([prompt], jnp.int32), NEW)
    clean = [int(t) for t in np.asarray(clean[0, len(prompt):])]

    replicas = corrupt_replica(make_replicas(params, 3), 2,
                               jax.random.PRNGKey(9))
    outputs = {}
    for rule in ("phocas", "trmean", "mean"):
        dec = RobustDecoder(rule=rule, k=3, b=1 if rule != "mean" else 0)
        engine = ServeEngine(model, replicas, max_slots=2, max_seq_len=16,
                             block_tokens=4, decoder=dec)
        req = engine.submit(prompt, NEW)
        engine.run()
        outputs[rule] = req.generated
    assert outputs["phocas"] == clean
    assert outputs["trmean"] == clean
    assert outputs["mean"] != clean


def test_reputation_ejects_corrupted_replica(model_and_params):
    """A persistently-corrupted replica's EMA reputation decays below the
    ejection threshold; honest replicas stay active.  Mean emits only
    uniform zero scores, so it never ejects."""
    model, params = model_and_params
    prompt = _prompts(1, lens=(4,), vocab=model.cfg.vocab_size)[0]
    replicas = corrupt_replica(make_replicas(params, 3), 1,
                               jax.random.PRNGKey(3))

    dec = RobustDecoder(rule="phocas", k=3)
    engine = ServeEngine(model, replicas, max_slots=1, max_seq_len=32,
                         block_tokens=4, decoder=dec)
    engine.submit(prompt, 20)                      # enough steps to decay
    engine.run()
    assert dec.ejected_replicas() == [1]
    rep = np.asarray(dec.rep_state["reputation"])
    assert rep[1] < 0.5 < min(rep[0], rep[2])

    dec_mean = RobustDecoder(rule="mean", k=3, b=0)
    engine = ServeEngine(model, replicas, max_slots=1, max_seq_len=32,
                         block_tokens=4, decoder=dec_mean)
    engine.submit(prompt, 20)
    engine.run()
    assert dec_mean.ejected_replicas() == []


def test_robust_decoder_validation():
    with pytest.raises(ValueError):
        RobustDecoder(k=1)
    with pytest.raises(ValueError):
        RobustDecoder(k=3, b=2)                    # b > (k+1)//2-1


def test_engine_rejects_mismatched_replicas(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError):
        ServeEngine(model, params,                 # not a replica tuple
                    max_slots=2, max_seq_len=16,
                    decoder=RobustDecoder(k=3))


def test_replica_telemetry_stream(model_and_params, tmp_path):
    from repro.defense.telemetry import TelemetryWriter, read_jsonl
    model, params = model_and_params
    path = str(tmp_path / "tel.jsonl")
    replicas = corrupt_replica(make_replicas(params, 3), 0,
                               jax.random.PRNGKey(5))
    with TelemetryWriter(path) as tel:
        engine = ServeEngine(model, replicas, max_slots=1, max_seq_len=16,
                             block_tokens=4,
                             decoder=RobustDecoder(rule="trmean", k=3),
                             telemetry=tel)
        engine.submit([1, 2, 3], 6)
        engine.run()
    records = read_jsonl(path)
    kinds = {r["kind"] for r in records}
    assert {"robust_decode", "serve"} <= kinds
    scored = [r for r in records if r["kind"] == "robust_decode"]
    assert scored and len(scored[0]["scores"]) == 3
    assert scored[-1]["scores"][0] > max(scored[-1]["scores"][1:])


def test_request_records_hold_queue_wait(model_and_params):
    """With tracing on, each retired request leaves one record of its
    phases, read from the scheduler's stamps: a request that waited for
    the one slot shows that wait as its queued phase."""
    from repro.obs import ObsConfig, make_recorder
    model, params = model_and_params
    with make_recorder(None, ObsConfig(enabled=False, trace=True)) as rec:
        engine = ServeEngine(model, params, max_slots=1, max_seq_len=16,
                             block_tokens=4, telemetry=rec)
        first = engine.submit([1, 2, 3], 4)
        queued = engine.submit([4, 5, 6], 4)
        engine.run()
    by_rid = {r["rid"]: r for r in rec.requests}
    assert sorted(by_rid) == [first.rid, queued.rid]
    q = by_rid[queued.rid]
    assert q["queued_ms"] == pytest.approx(
        (queued.t_admitted - queued.t_enqueue) * 1e3)
    assert queued.t_admitted >= first.t_done    # it waited for the slot
    assert q["queued_ms"] > by_rid[first.rid]["queued_ms"]
    assert q["prefill_ms"] == pytest.approx(
        (queued.t_first_token - queued.t_admitted) * 1e3)
    assert q["decode_ms"] == pytest.approx(
        (queued.t_done - queued.t_first_token) * 1e3)
    steps = [s for s in rec.spans if s.path == "engine"]
    assert len(steps) == engine.steps_run
    assert {s.path for s in rec.spans} >= {
        "engine/schedule", "engine/prefill", "engine/decode",
        "engine/readback", "engine/append"}
