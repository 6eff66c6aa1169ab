"""Plain reference of the robust synchronous SGD step that the training
cells run: m workers' gradients of the reference model, the configured
attack on the first q of them, the coordinate-wise rule, and SGD on weights
stored in the configuration's type.  Imports nothing of the program.

Rules: ``mean``; ``phocas`` with trim b (Xie et al. 2018, arXiv:1805.09682,
Def. 3): per coordinate the trimmed mean of the m-2b middle values, then the
mean of the m-b values nearest to it.  Attack ``gaussian``: the first q
workers send N(0, std^2) noise in place of their gradient (paper 5.1.1),
drawn here from the reference's own key.  The reputation gate of the
defended step holds every worker in the first steps (reputation starts at 1
and cannot fall below the ejection level in fewer than about seven steps),
so over the steps compared it is the identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import granite


def phocas(g, b: int):
    """g (m, ...) -> (...)."""
    m = g.shape[0]
    s = jnp.sort(g, axis=0)
    center = jnp.mean(s[b:m - b], axis=0)
    idx = jnp.argsort(jnp.abs(g - center), axis=0)[:m - b]
    return jnp.mean(jnp.take_along_axis(g, idx, axis=0), axis=0)


def aggregate(g, job: dict):
    if job["rule"] == "mean":
        return jnp.mean(g, axis=0)
    if job["rule"] == "phocas":
        return phocas(g, job["b"])
    raise ValueError(f"no reference for rule {job['rule']!r}")


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)

    def f(w, batch):
        w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        return jax.value_and_grad(granite.loss)(w32, cfg, batch, precision)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _update_program(job_items: tuple):
    job = dict(job_items)

    def f(w, grads, key):
        g = jnp.stack(grads)                         # (m, ...) per leaf
        q = job.get("byzantine", 0)
        if job.get("attack", "none") == "gaussian" and q:
            noise = job.get("gaussian_std", 200.0) * jax.random.normal(
                key, (q,) + g.shape[1:], jnp.float32)
            g = g.at[:q].set(noise)
        agg = aggregate(g, job)
        return (w.astype(jnp.float32) - job["lr"] * agg).astype(w.dtype)

    return jax.jit(f)


def _frozen(d: dict) -> tuple:
    """The scalar items of a configuration, hashable."""
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


def step(w: dict, cfg: dict, job: dict, batch: dict, key,
         precision: str = "f32"):
    """One robust SGD step.  ``batch`` leaves are (m * rows, S), worker j
    owning rows [j * rows, (j + 1) * rows).  Returns (mean worker loss,
    new weights)."""
    m = job["workers"]
    grad = _grad_program(_frozen(cfg), precision)
    rows = batch["tokens"].shape[0] // m
    losses, grads = [], []
    with jax.default_matmul_precision("highest"):
        for j in range(m):
            part = jax.tree.map(lambda x: x[j * rows:(j + 1) * rows], batch)
            lj, gj = grad(w, part)
            losses.append(lj)
            grads.append(gj)
    update = _update_program(_frozen(job))
    new = {}
    for i, name in enumerate(sorted(w)):
        new[name] = update(w[name], [g[name] for g in grads],
                           jax.random.fold_in(key, i))
        for g in grads:
            del g[name]
    return float(jnp.mean(jnp.stack(losses))), new
