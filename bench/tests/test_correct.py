"""``correct`` at a size the CPU holds: a sound run of each cell passes its
checks, and a run with the measured path broken underneath fails them,
once for each fault the cell can have.  The harness's look for a chip is
skipped; everything after it runs."""
import dataclasses

import jax
import pytest

from bench.drivers import common, serve, train
from bench.tests.small import harness

TRAIN = ["granite-8b-train.phocas-gauss", "granite-8b-train.mean"]
SERVE = "granite-8b-serve.robust-k3-poisson"


def correct(res) -> bool:
    return all(v <= lim for v, lim in res["checks"].values())


@pytest.mark.parametrize("cell", TRAIN)
def test_sound_training_run_is_correct(cell):
    h = harness(cell)
    res = train.run(h)
    assert correct(res), res["checks"]
    assert h.compiles_in_window() == 0
    assert res["attempted"] > 0 and res["failed"] == 0


def test_window_counts_every_step_it_dispatched(monkeypatch):
    monkeypatch.setattr(train, "AHEAD_S", 0.05)
    h = harness(TRAIN[1], seconds=1.0)
    feed, _, failed, _ = train.run_program(h, h.config, h.traffic)
    warmup = h.traffic["warmup_steps"]
    assert feed.ahead >= 1 and failed == 0
    assert feed.window_steps == len(feed.losses) - warmup > feed.ahead
    assert h.t_open < feed.t_end == h.t_close


@pytest.mark.parametrize("cell", TRAIN)
def test_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    import repro.train.step as step
    monkeypatch.setattr(step, "apply_updates",
                        lambda cfg, params, grads, state: (
                            params, {**state, "step": state["step"] + 1}))
    res = train.run(harness(cell))
    assert not correct(res)
    assert res["checks"]["grad_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN)
def test_half_the_batch_left_out_fails(cell, monkeypatch):
    build = common.program_model

    def half(cfg):
        model = build(cfg)
        loss = model.loss
        return dataclasses.replace(model, loss=lambda p, b: loss(
            p, jax.tree.map(lambda x: x[: x.shape[0] // 2], b)))

    monkeypatch.setattr(common, "program_model", half)
    res = train.run(harness(cell))
    assert not correct(res), res["checks"]


def test_sound_serving_run_is_correct():
    h = harness(SERVE, seconds=1.0)
    res = serve.run(h)
    assert correct(res), res["checks"]
    assert h.compiles_in_window() == 0
    assert res["attempted"] > 0 and res["failed"] == 0


def test_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serve import scheduler
    append = scheduler.Scheduler.append_token
    altered = []

    def alter(self, req, token):
        if len(req.generated) == 3 and not altered:
            altered.append(req.rid)
            token = (int(token) + 1) % 256
        append(self, req, token)

    monkeypatch.setattr(scheduler.Scheduler, "append_token", alter)
    res = serve.run(harness(SERVE, seconds=1.0,
                            traffic={"check_requests": 100}))
    assert altered and not correct(res), res["checks"]
