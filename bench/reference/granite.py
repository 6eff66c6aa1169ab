"""Plain reference of the granite-8b (llama architecture) decoder, the
configuration files' ``"reference": "granite"``.

Straightforward ``jax.numpy`` in float32, every matrix product at
``highest`` precision, no kernels, cache or batching tricks; it imports
nothing of the program.  It follows the configuration as stated in its
file: RMSNorm (eps ``rms_norm_eps``), embeddings scaled by
``embedding_multiplier``, grouped-query attention with rotary embeddings
that rotate interleaved (even, odd) pairs of each head, a SiLU-gated MLP and
an untied output head.

``precision="fp8"`` is the control: every matrix product takes float8
operands, each tensor scaled to the format's range (e4m3 forward, e5m2 for
the gradients flowing back), with float32 accumulation: the step below the
bfloat16 that the configuration states.
The weights are made here too, from the seed, in the type they are stored
in; the harness hands the same arrays to the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

def shapes(cfg: dict) -> dict:
    d, f, v, L = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["vocab_size"], cfg["num_hidden_layers"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v),
            "ln1": (L, d), "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d), "ln2": (L, d),
            "wg": (L, d, f), "wi": (L, d, f), "w2": (L, f, d)}


def init_weights(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Norm scales 1, embedding N(0, 0.02^2), every projection
    N(0, 1/fan_in); one key per array, in the order of ``shapes``."""
    out = {}
    spec = shapes(cfg)
    for k, (name, shp) in zip(jax.random.split(key, len(spec)),
                              spec.items()):
        if name in ("ln1", "ln2", "final_norm"):
            out[name] = jnp.ones(shp, dtype)
        else:
            std = 0.02 if name == "embed" else 1.0 / math.sqrt(shp[-2])
            out[name] = (std * jax.random.normal(k, shp, jnp.float32)
                         ).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def weights_program(cfg_items: tuple, dtype: str):
    cfg = dict(cfg_items)
    return jax.jit(lambda key: init_weights(key, cfg, jnp.dtype(dtype)))


def make_weights(key, cfg: dict, dtype="bfloat16") -> dict:
    """The weights, made on the default device by one compiled program."""
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim")
    return weights_program(tuple((k, cfg[k]) for k in keys), dtype)(key)


# -- matrix products ----------------------------------------------------------

def _f8(x, dtype):
    """``x`` rounded to float8 under a per-tensor scale that maps its
    largest magnitude to the format's largest, as float8 training and
    inference scale their operands; back in float32."""
    big = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / big
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _dot_fp8(a, b):
    return jnp.matmul(_f8(a, jnp.float8_e4m3fn), _f8(b, jnp.float8_e4m3fn),
                      precision="highest")


def _dot_fp8_fwd(a, b):
    a8, b8 = _f8(a, jnp.float8_e4m3fn), _f8(b, jnp.float8_e4m3fn)
    return jnp.matmul(a8, b8, precision="highest"), (a8, b8)


def _dot_fp8_bwd(res, g):
    a8, b8 = res
    g8 = _f8(g, jnp.float8_e5m2)
    da = jnp.matmul(g8, jnp.swapaxes(b8, -1, -2), precision="highest")
    db = jnp.matmul(jnp.swapaxes(a8, -1, -2), g8, precision="highest")
    # b may have been broadcast over leading axes of a
    while db.ndim > b8.ndim:
        db = db.sum(0)
    return da, db


_dot_fp8.defvjp(_dot_fp8_fwd, _dot_fp8_bwd)


def _dot(a, b, precision):
    if precision == "fp8":
        return _dot_fp8(a, b)
    return jnp.matmul(a, b, precision="highest")


# -- forward ------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, hd): rotate interleaved pairs by position * freq."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def forward(w: dict, cfg: dict, tokens, precision: str = "f32"):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    B, S = tokens.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    dot = functools.partial(_dot, precision=precision)
    x = w["embed"][tokens] * cfg["embedding_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(cfg["num_hidden_layers"]):
        a = _rms(x, w["ln1"][i], eps)
        q = _rope(dot(a, w["wq"][i]).reshape(B, S, h, hd), cfg["rope_theta"])
        k = _rope(dot(a, w["wk"][i]).reshape(B, S, kv, hd),
                  cfg["rope_theta"])
        v = dot(a, w["wv"][i]).reshape(B, S, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        s = dot(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = dot(p, v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
        x = x + dot(o.reshape(B, S, h * hd), w["wo"][i])
        a = _rms(x, w["ln2"][i], eps)
        x = x + dot(jax.nn.silu(dot(a, w["wg"][i])) * dot(a, w["wi"][i]),
                    w["w2"][i])
    return dot(_rms(x, w["final_norm"], eps), w["lm_head"])


def loss(w: dict, cfg: dict, batch: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of ``batch`` {"tokens", "labels"}."""
    logits = forward(w, cfg, batch["tokens"], precision)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None],
                                         -1))
