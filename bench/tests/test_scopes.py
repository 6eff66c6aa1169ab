"""Device time per named scope of the train step: the scope map parsed
from a small compiled step's text, joined to op intervals by instruction
name, and the attribution of overlapping intervals."""
import re

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, trace as tr
from repro.obs.profile import hlo_scopes

DEV = "/device:TPU:0"


def small_step_text() -> str:
    """A small step with the train step's shape of scopes."""
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w))

    def step(w, x, key):
        with jax.named_scope("grads"):
            g = jax.vmap(jax.grad(loss), in_axes=(None, 0))(w, x)
        with jax.named_scope("aggregate"):
            with jax.named_scope("attack"):
                g = g.at[0].set(jax.random.normal(key, g.shape[1:]))
            with jax.named_scope("rule"):
                a = jnp.median(g, axis=0)
        with jax.named_scope("optimizer"):
            w = w - 0.1 * a
        return w, jnp.sum(w * w)

    return jax.jit(step).lower(jnp.ones((8, 4)), jnp.ones((3, 5, 8)),
                               jax.random.PRNGKey(0)).compile().as_text()


def entry_instructions(text: str) -> list:
    """The names of the entry computation's instructions, in order."""
    body = text[text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    return re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", body, re.M)


def test_hlo_scopes_of_a_small_step():
    text = small_step_text()
    scope_of = hlo_scopes(text)
    assert set(scope_of.values()) == {"grads", "aggregate/attack",
                                      "aggregate/rule", "optimizer"}
    names = entry_instructions(text)
    # the dot products of the gradients, the sort of the median
    dots = [n for n in names if n.startswith("dot")]
    assert dots and all(scope_of[n] == "grads" for n in dots)
    assert scope_of[next(n for n in names if n.startswith("sort"))] == \
        "aggregate/rule"


def test_scopes_and_unscoped_sum_to_busy_time():
    text = small_step_text()
    scope_of = hlo_scopes(text)
    names = entry_instructions(text)
    # one op event per entry instruction, 10 ns apart, 8 ns long, in two
    # runs of the step program; a while op covers the ops after it for
    # 30 ns; one op of another program between the runs
    ops, t = [], 0
    for run in range(2):
        for n in names:
            ops.append(tr.Event(f"{n} f32[8]", t, 8))
            if n.startswith("while"):
                ops.append(tr.Event(f"{n} f32[8]", t, 30))
            t += 10
        ops.append(tr.Event("fusion.1 f32[2]", t, 5))
        t += 10
    runs = [tr.Event("jit_step(1)", 0, len(names) * 10),
            tr.Event("jit_step(1)", (len(names) + 1) * 10,
                     len(names) * 10),
            tr.Event("jit_other(2)", len(names) * 10, 10)]
    trace = tr.Trace({DEV: ops}, [], (0, t), {DEV: runs})
    got = scopes.scope_ns(trace, "jit_step", scope_of)
    assert sum(got.values()) == pytest.approx(tr.busy_ns(trace))
    assert got[scopes.OTHER] == 10
    assert scopes.UNSCOPED in got
    assert scopes.under(got, "aggregate") == pytest.approx(
        got.get("aggregate/attack", 0) + got.get("aggregate/rule", 0))
    assert scopes.under(got, "grads") > 0


def test_innermost_interval_takes_the_time():
    # a loop op [0, 100) around body ops [10, 20) and [50, 70); a second
    # op [90, 120) that overlaps the loop's end
    got = scopes.attribute([(0, 100, "loop"), (10, 20, "a"), (50, 70, "a"),
                            (90, 120, "b"), (200, 210, "c")])
    assert got == {"loop": 60, "a": 30, "b": 30, "c": 10}
    assert sum(got.values()) == tr.length(tr.union(
        [(0, 100), (90, 120), (200, 210)]))


def test_instruction_name_of_an_op_event():
    assert scopes.instruction(tr.op_name(
        "%fusion.17 = f32[1,8]{1,0} fusion(%p), kind=kLoop")) == "fusion.17"
    assert scopes.instruction("copy-start.3") == "copy-start.3"
