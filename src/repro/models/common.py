"""Shared model components: norms, embeddings, RoPE, chunked GQA/MLA
attention with sliding windows + softcaps, GLU MLPs, KV caches.

Everything is pure jnp over plain-dict pytrees (no flax): ``init_*`` builds
parameters, ``*_fwd`` applies them.  All code is vmap-safe (the trainer vmaps
whole-model grads over worker groups) and eval_shape-safe (the dry-run lowers
against ShapeDtypeStructs).
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

# Query-chunk length for attention: bounds the live (B,H,qc,T) score tensor so
# 32k-token prefills fit without a flash kernel (DESIGN.md §2 adaptation note).
ATTN_QUERY_CHUNK = 1024

# Opt-in fused flash-attention Pallas kernel (§Perf P5).  Off by default: the
# dry-run roofline reads dot FLOPs from the HLO, and a custom-call kernel is
# opaque to that accounting; on real TPUs set REPRO_FLASH_ATTN=1.
USE_FLASH_ATTN = os.environ.get("REPRO_FLASH_ATTN", "0") == "1"


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.compute_dtype)


def model_axis_size() -> int:
    """Size of the ambient mesh's 'model' axis (0 when no mesh is active —
    single-device tests / examples)."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty or "model" not in am.axis_names:
        return 0
    return am.shape["model"]


def data_axis_size() -> int:
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty or "data" not in am.axis_names:
        return 0
    return am.shape["data"]


def shard_hint(x: jax.Array, spec: tuple) -> jax.Array:
    """with_sharding_constraint when a mesh is active; no-op otherwise."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return x
    from jax.sharding import PartitionSpec as P
    names = set(am.axis_names)
    spec = tuple(s if (s is None or s in names) else None for s in spec)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Param initializers
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, dtype) -> dict:
    scale = 1.0 / math.sqrt(d_in)
    return {"w": (scale * jax.random.normal(key, (d_in, d_out))).astype(dtype)}


def init_norm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def init_embedding(key, vocab: int, d: int, dtype) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


# ---------------------------------------------------------------------------
# Primitive ops
# ---------------------------------------------------------------------------

def linear(p: dict, x: jax.Array) -> jax.Array:
    return x @ p["w"]


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # broadcast heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (GQA + sliding window + softcap), query-chunked
# ---------------------------------------------------------------------------

def _attend(q, k, v, q_pos, k_pos, *, causal, window, cap, scale):
    """q: (B,Sq,H,hd) k/v: (B,T,Kv,hd); q_pos (Sq,), k_pos (T,) (-1=invalid)."""
    B, Sq, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    qg = q.reshape(B, Sq, Kv, rep, hd)
    # bf16 matmul inputs with f32 accumulation: MXU-native, and bf16 inputs
    # carry no extra information to justify f32 operand traffic (§Perf H2-b).
    s = jnp.einsum("bqkrh,btkh->bkrqt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    mask = (k_pos >= 0)[None, :]                       # (1, T) validity
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)                     # f32 softmax
    p = jnp.where(jnp.isnan(p), 0.0, p)                # fully-masked rows
    out = jnp.einsum("bkrqt,btkh->bqkrh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(v.dtype)


def attention_core(q, k, v, q_pos, k_pos, *, causal=True, window=None,
                   cap=None, scale=None, chunk=ATTN_QUERY_CHUNK):
    """Query-chunked masked attention; see _attend for shapes."""
    B, Sq, H, hd = q.shape
    if scale is None:
        scale = hd ** -0.5
    if (USE_FLASH_ATTN and causal and Sq > 1 and Sq == k.shape[1]
            and jnp.issubdtype(q.dtype, jnp.floating)):
        # fused Pallas flash attention (§Perf P5); self-attention train/
        # prefill path (q_pos == k_pos == arange).
        from repro.kernels.flashattn.ops import flash_attention
        return flash_attention(q, k, v, causal=True, window=window,
                               cap=cap, scale=scale)
    # §Perf H1: head counts not divisible by the model axis (starcoder 36,
    # hymba 25, whisper 20 on a 16-way axis) leave the score/AV matmuls
    # replicated across the whole model axis (~16x overcompute).  Expanding
    # GQA and zero-padding heads to the next multiple makes the head dim
    # shardable: <=33% padding waste instead of 16x replication.
    # (decode steps — Sq == 1 — skip it: the score matmul is tiny and
    # re-materializing a padded KV cache every token would cost far more
    # than the replicated compute it saves.)
    ms = model_axis_size()
    if ms > 1 and H % ms and Sq > 1:
        Kv = k.shape[2]
        rep = H // Kv
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        Hp = -(-H // ms) * ms
        padn = Hp - H
        q = jnp.pad(q, ((0, 0), (0, 0), (0, padn), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padn), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, padn), (0, 0)))
        hint = (None, None, "model", None)
        q, k, v = (shard_hint(t, hint) for t in (q, k, v))
        out = attention_core(q, k, v, q_pos, k_pos, causal=causal,
                             window=window, cap=cap, scale=scale, chunk=chunk)
        return out[:, :, :H]
    if Sq <= chunk or Sq % chunk != 0:
        return _attend(q, k, v, q_pos, k_pos, causal=causal, window=window,
                       cap=cap, scale=scale)
    nc = Sq // chunk
    qc = q.reshape(B, nc, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(nc, chunk)

    def one(args):
        qi, pi = args
        return _attend(qi, k, v, pi, k_pos, causal=causal, window=window,
                       cap=cap, scale=scale)

    out = jax.lax.map(one, (qc, pc))                   # (nc, B, chunk, H, hd)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# GQA attention block with ring-buffer KV cache
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    d, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": init_linear(ks[0], d, H * hd, dt),
        "wk": init_linear(ks[1], d, Kv * hd, dt),
        "wv": init_linear(ks[2], d, Kv * hd, dt),
        "wo": init_linear(ks[3], H * hd, d, dt),
    }


def init_attn_cache(cfg, batch: int, max_len: int, window: Optional[int]) -> dict:
    dt = dtype_of(cfg)
    size = min(window, max_len) if window else max_len
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, size, Kv, hd), dt),
        "v": jnp.zeros((batch, size, Kv, hd), dt),
    }


def _cache_positions(size: int, pos: jax.Array,
                     window: Optional[int]) -> jax.Array:
    """Global position stored in each ring slot at decode position ``pos``.

    Un-windowed caches are absolute: slot s holds position s (valid iff
    s <= pos).  Windowed ring buffers of size W: slot s holds the largest
    p <= pos with p ≡ s (mod W); never-written slots map to negative
    (invalid) positions — this also covers the not-yet-wrapped phase
    (pos < W), where it reduces to the absolute rule.
    """
    s = jnp.arange(size)
    if window is None:
        return jnp.where(s <= pos, s, -1)
    p = pos - ((pos - s) % size)
    return jnp.where(p >= 0, p, -1)


def attention_block(p, cfg, x, *, positions, window, cache=None):
    """x: (B,S,d).  Training (no cache) when cache is None; cached otherwise:
    decode (S==1, positions (1,)) or batched prefill (S==S0 contiguous
    positions, S0 <= the layer's ring size — engine-gated)."""
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, H, hd)
    k = linear(p["wk"], x).reshape(B, S, Kv, hd)
    v = linear(p["wv"], x).reshape(B, S, Kv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = attention_core(q, k, v, positions, positions, causal=True,
                             window=window, cap=cfg.attn_logit_softcap)
        new_cache = None
    else:
        size = cache["k"].shape[1]
        start = positions[0]                # write offset (decode: the step)
        last = positions[-1]                # newest position now in the cache
        slot = start % size
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        k_pos = _cache_positions(size, last, window)
        out = attention_core(q, ck, cv, positions, k_pos, causal=True,
                             window=window, cap=cfg.attn_logit_softcap)
        new_cache = {"k": ck, "v": cv}
    return linear(p["wo"], out.reshape(B, S, H * hd)), new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (repro.serve v2, DESIGN.md §11)
# ---------------------------------------------------------------------------
# One global pool of fixed-size blocks per layer; requests own disjoint block
# lists via per-request block tables (B, max_blocks) int32.  Block 0 is the
# reserved null/trash block: inactive batch slots carry an all-zero table row
# and scatter their k/v there — its contents are finite garbage that active
# requests never attend to (unused table-tail gathers of block 0 fall beyond
# the per-request validity mask, so softmax weighs them exactly 0).

def init_paged_kv(cfg, num_blocks: int, block_tokens: int) -> dict:
    dt = dtype_of(cfg)
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_blocks, block_tokens, Kv, hd), dt),
        "v": jnp.zeros((num_blocks, block_tokens, Kv, hd), dt),
    }


def _attend_paged(q, k, v, pos, *, cap, scale=None):
    """Decode attention with per-request lengths.  q: (B,1,H,hd); k/v:
    (B,T,Kv,hd) gathered per-request views; pos: (B,) newest position of
    each request.  Same einsum contractions / f32 softmax / NaN guard as
    :func:`_attend`, so paged and dense decode agree up to float32 rounding
    — the only change is the validity mask going per-request (B,T)."""
    B, Sq, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, Sq, Kv, rep, hd)
    s = jnp.einsum("bqkrh,btkh->bkrqt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    mask = jnp.arange(T)[None, :] <= pos[:, None]      # (B, T) causal+validity
    s = jnp.where(mask[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)                     # f32 softmax
    p = jnp.where(jnp.isnan(p), 0.0, p)                # fully-masked rows
    out = jnp.einsum("bkrqt,btkh->bqkrh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(v.dtype)


def attention_block_paged(p, cfg, x, *, positions, block_tables, cache):
    """One paged decode step.  x: (B,1,d); positions: (B,) per-request write
    position; block_tables: (B, max_blocks) int32; cache: the layer's block
    pool {"k","v"}: (N, bt, Kv, hd).  Scatter-writes the new k/v at
    (table[pos//bt], pos%bt) then attends over the gathered per-request
    view.  Global (un-windowed) layers only — see stack.paged_supported."""
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, H, hd)
    k = linear(p["wk"], x).reshape(B, S, Kv, hd)
    v = linear(p["wv"], x).reshape(B, S, Kv, hd)
    q = rope(q, positions[:, None], cfg.rope_theta)
    k = rope(k, positions[:, None], cfg.rope_theta)

    bt = cache["k"].shape[1]
    blk = jnp.take_along_axis(block_tables, (positions // bt)[:, None],
                              axis=1)[:, 0]            # (B,)
    off = positions % bt
    ck = cache["k"].at[blk, off].set(k[:, 0])
    cv = cache["v"].at[blk, off].set(v[:, 0])
    T = block_tables.shape[1] * bt
    keys = ck[block_tables].reshape(B, T, Kv, hd)
    vals = cv[block_tables].reshape(B, T, Kv, hd)
    out = _attend_paged(q, keys, vals, positions, cap=cfg.attn_logit_softcap)
    return linear(p["wo"], out.reshape(B, S, H * hd)), {"k": ck, "v": cv}


def attention_block_prefill_paged(p, cfg, x, *, positions, block_tables,
                                  cache):
    """Batched paged prefill.  x: (B,S0,d) whole prompts aligned at position
    0; positions: (S0,) = arange(S0).  Ordinary causal self-attention over
    the prompt (no cache read), with the computed k/v scattered into the
    block pool so subsequent paged decode steps see them."""
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, H, hd)
    k = linear(p["wk"], x).reshape(B, S, Kv, hd)
    v = linear(p["wv"], x).reshape(B, S, Kv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = attention_core(q, k, v, positions, positions, causal=True,
                         window=None, cap=cfg.attn_logit_softcap)

    bt = cache["k"].shape[1]
    blk = block_tables[:, positions // bt]             # (B, S0)
    off = jnp.broadcast_to(positions % bt, (B, S))
    ck = cache["k"].at[blk, off].set(k)
    cv = cache["v"].at[blk, off].set(v)
    return linear(p["wo"], out.reshape(B, S, H * hd)), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2) with latent KV cache
# ---------------------------------------------------------------------------

def init_mla(key, cfg) -> dict:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    d, H = cfg.d_model, cfg.num_heads
    nope, rdim, vdim, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim, cfg.kv_lora_rank)
    return {
        "wq": init_linear(ks[0], d, H * (nope + rdim), dt),
        "wkv_a": init_linear(ks[1], d, rank, dt),          # latent down-proj
        "wk_rope": init_linear(ks[2], d, rdim, dt),        # shared rope key
        "wk_b": init_linear(ks[3], rank, H * nope, dt),    # latent -> keys
        "wv_b": init_linear(ks[4], rank, H * vdim, dt),    # latent -> values
        "wo": init_linear(ks[5], H * vdim, d, dt),
    }


def init_mla_cache(cfg, batch: int, max_len: int) -> dict:
    dt = dtype_of(cfg)
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dt),
    }


def _mla_attend(cfg, q_nope, q_rope, k_nope, v, krope, q_pos, k_pos):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = (jnp.einsum("bqhn,bthn->bhqt", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhr,btr->bhqt", q_rope, krope,
                      preferred_element_type=jnp.float32)) * scale
    mask = (k_pos[None, :] >= 0) & (k_pos[None, :] <= q_pos[:, None])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    pattn = jax.nn.softmax(s, axis=-1)
    pattn = jnp.where(jnp.isnan(pattn), 0.0, pattn)
    out = jnp.einsum("bhqt,bthv->bqhv", pattn.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _mla_attend_chunked(p, cfg, q_nope, q_rope, ckv, krope, q_pos, k_pos,
                        chunk=ATTN_QUERY_CHUNK):
    B, Sq, H = q_nope.shape[:3]
    T = ckv.shape[1]
    # Expand latent -> per-head keys/values ONCE (chunk-invariant); only the
    # (B,H,chunk,T) score tensor is re-materialized per query chunk.
    k_nope = linear(p["wk_b"], ckv).reshape(B, T, H, cfg.qk_nope_head_dim)
    v = linear(p["wv_b"], ckv).reshape(B, T, H, cfg.v_head_dim)
    if Sq <= chunk or Sq % chunk != 0:
        return _mla_attend(cfg, q_nope, q_rope, k_nope, v, krope, q_pos, k_pos)
    nc = Sq // chunk

    def one(args):
        qn, qr, pi = args
        return _mla_attend(cfg, qn, qr, k_nope, v, krope, pi, k_pos)

    split = lambda a: a.reshape(B, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(one, (split(q_nope), split(q_rope),
                            q_pos.reshape(nc, chunk)))
    return out.swapaxes(0, 1).reshape(B, Sq, *out.shape[3:])


def mla_block(p, cfg, x, *, positions, cache=None, window=None):
    del window                                          # MLA archs are global
    B, S, d = x.shape
    H = cfg.num_heads
    nope, rdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = linear(p["wq"], x).reshape(B, S, H, nope + rdim)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    ckv_new = linear(p["wkv_a"], x)                     # (B,S,rank)
    krope_new = rope(linear(p["wk_rope"], x)[:, :, None], positions,
                     cfg.rope_theta)[:, :, 0]           # (B,S,rdim)

    if cache is None:
        out = _mla_attend_chunked(p, cfg, q_nope, q_rope, ckv_new, krope_new,
                                  positions, positions)
        new_cache = None
    else:
        start = positions[0]                # decode: the step; prefill: 0
        last = positions[-1]                # newest cached position
        ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv_new,
                                                  start, 1)
        krope = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope_new,
                                                    start, 1)
        T = ckv.shape[1]
        k_pos = jnp.where(jnp.arange(T) <= last, jnp.arange(T), -1)
        out = _mla_attend_chunked(p, cfg, q_nope, q_rope, ckv, krope,
                                  positions, k_pos)
        new_cache = {"ckv": ckv, "krope": krope}
    out = linear(p["wo"], out.reshape(B, S, H * cfg.v_head_dim))
    return out, new_cache


# ---------------------------------------------------------------------------
# Dense GLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: Optional[int] = None) -> dict:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": init_linear(ks[0], d, f, dt),
        "wg": init_linear(ks[1], d, f, dt),
        "wo": init_linear(ks[2], f, d, dt),
    }


def mlp_block(p, x: jax.Array) -> jax.Array:
    return linear(p["wo"], jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x))
