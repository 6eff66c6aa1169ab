"""The harness finds every file by its name in BENCHMARK.json, the traffic
is a function of the seed, the batch pool is one compiled program, and a
run without a TPU fails."""
import importlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import gen, harness

ROOT = harness.ROOT
BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    w, config, traffic = harness.cell_files(BENCH, cell)
    importlib.import_module(f"bench.drivers.{traffic['driver']}")
    importlib.import_module(f"bench.reference.{config['reference']}")
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert conf["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in config and key in config["published"]
    e2e = harness.metrics_of(BENCH, cell, "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    layer = harness.metrics_of(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)
        assert m["moves"] in [x["name"] for x in e2e]


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def small_pool(seed):
    return gen.token_pool(seed, batch=4, seq_len=16, pool=3, modes=8,
                          active_vocab=32)


def test_token_pool_is_a_function_of_the_seed():
    a, b, c = small_pool(2**31 + 17), small_pool(2**31 + 17), small_pool(5)
    assert len(a) == 3
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    np.testing.assert_array_equal(a[0]["tokens"][:, 1:],
                                  a[0]["labels"][:, :-1])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])


def test_batch_pool_is_one_compiled_program():
    from jax._src import dispatch
    seen = []

    def listen(event, _secs, **_):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        pool = gen.token_pool(99, batch=2, seq_len=24, pool=5, modes=4,
                              active_vocab=16)
        jax.block_until_ready(pool)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(seen) == 1


def test_request_schedule_is_a_function_of_the_seed():
    _, _, mix = harness.cell_files(BENCH,
                                   "granite-8b-serve.robust-k3-poisson")
    a = gen.request_schedule(mix, 2**31 + 3, 1000, 20.0)
    b = gen.request_schedule(mix, 2**31 + 3, 1000, 20.0)
    c = gen.request_schedule(mix, 4, 1000, 20.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.due_s for r in a] != [r.due_s for r in c]
    # another seed offers the same work in another order
    key = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)
    assert key(a) == key(c)
    assert len(a) == round(mix["rate_per_s"] * 20.0)
    assert all(0 <= r.due_s < 20.0 for r in a)


def test_run_without_a_tpu_fails_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_steps_ahead_by_the_quickest_warm_up_step_after_the_first():
    from bench.drivers import train
    # the first interval compiles; the quickest later one is 0.1 s
    ticks = [0.0, 9.0, 9.2, 9.3, 9.45]
    assert train.steps_ahead(ticks) == round(train.AHEAD_S / 0.1)
    assert train.steps_ahead([0.0, 1.0, 1.0 + 2 * train.AHEAD_S]) == 1
