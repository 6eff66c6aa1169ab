"""The reductions from a trace to metrics, and the metric arithmetic, on
made-up intervals and on a small trace recorded on a v5e chip."""
import json
import os

import numpy as np
import pytest

from bench import costs, trace as tr
from bench.drivers import serve

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def make(ops, host=(), window=(0, 100), devices=None):
    devs = devices or {DEV: ops}
    return tr.Trace({d: [tr.Event(*o) for o in evs] for d, evs in
                     devs.items()},
                    [tr.Event(*a) for a in host], window)


def test_busy_is_the_union_of_op_intervals():
    t = make([("a", 10, 20), ("b", 20, 10), ("c", 50, 10), ("d", 95, 20)])
    # [10,30) overlapped, [50,60), [95,100) clipped to the window
    assert tr.busy_ns(t) == 20 + 10 + 5
    assert tr.window_ns(t) == 100


def test_busy_averages_over_devices():
    t = make(None, devices={DEV: [("a", 0, 40)],
                            "/device:TPU:1": [("a", 0, 20)]})
    assert tr.busy_ns(t) == 30


def test_kernel_time_by_name():
    t = make([("phocas_counts_kernel.1", 0, 7), ("fusion.3", 7, 5),
              ("_phocas_kernel", 20, 3), ("convolution", 30, 9)])
    assert tr.kernel_ns(t, r"phocas") == 10
    assert tr.kernel_ns(t, r"nothing") == 0


def test_idle_gaps_named_after_the_innermost_annotation():
    t = make([("a", 0, 10), ("b", 30, 10), ("c", 45, 55)],
             host=[("dispatch", 0, 100), ("batch", 15, 10),
                   ("loop", 40, 3)])
    # gap [10,30) mid 20 inside batch; gap [40,45) mid 42.5 inside loop
    assert tr.idle_gaps(t) == [("batch", 20), ("loop", 5)]
    t = make([("a", 0, 10), ("c", 40, 60)])
    assert tr.idle_gaps(t) == [("unannotated", 30)]


def test_exposed_collective_time():
    t = make([("all-gather-start", 0, 30), ("fusion", 10, 10),
              ("all-to-all.2", 50, 10), ("convolution", 55, 20)])
    # all-gather [0,30) minus compute [10,20) = 20; all-to-all [50,60)
    # minus [55,75) = 5
    assert tr.exposed_collective_ns(t) == 25
    assert tr.exposed_collective_ns(make([("fusion", 0, 10)])) is None


def test_op_names_are_the_ops_own():
    text = ("%add.3 = f32[1,64]{1,0:T(1,128)} fusion(f32[4,64]{1,0} "
            "%phocas_pallas.1), kind=kLoop")
    assert tr.op_name(text) == "add.3 f32[1,64]"
    assert tr.op_name("jit_decode(123)") == "jit_decode(123)"


def test_interval_arithmetic():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_breakdown_lists_ops_and_gaps_in_seconds():
    t = make([("a", 0, 10), ("b", 30, 20), ("a", 60, 30)])
    b = tr.breakdown(t)
    assert b["device_ops"] == [["a", 40e-9], ["b", 20e-9]]
    assert b["idle_gaps"][0] == ["unannotated", 20e-9]


def test_recorded_v5e_trace():
    path = os.path.join(DATA, "small.xplane.pb")
    t = tr.load(path, ("batch", "dispatch", "loop"))
    assert list(t.devices) == [DEV]
    busy, window = tr.busy_ns(t), tr.window_ns(t)
    assert 0 < busy < window
    with open(os.path.join(DATA, "small.expected.json")) as f:
        want = json.load(f)
    assert tr.kernel_ns(t, want["kernel"]) == pytest.approx(
        want["kernel_ns"])
    assert busy == pytest.approx(want["busy_ns"])
    assert window == pytest.approx(want["window_ns"])
    assert tr.op_totals(t)["phocas_counts_pallas.1 (f32[1,65536]"] > 0
    names = {n for n, _ in tr.idle_gaps(t)}
    assert names <= {"batch", "dispatch", "loop", "unannotated"}
    assert "batch" in names


def test_p95_over_all_requests_and_rate_over_the_window():
    trk = serve.Tracker()

    class R:
        def __init__(self, rid, n):
            self.rid, self.max_new_tokens, self.generated = rid, n, []

    # twenty requests due at 0..19 s, each gets its first token 1 s late,
    # one more token 0.5 s after that; one never gets any token
    for i in range(20):
        trk.add(R(i, 2), float(i), None)
        trk.times[i] = [i + 1.0, i + 1.5]
    trk.add(R(20, 2), 20.0, None)
    # 21 TTFTs, twenty of 1 s: p95 is the 20th in order, still finite
    assert serve.metrics(trk, 0.0, 10.0)["serve_ttft_p95_ms"] == 1000.0
    trk.add(R(21, 2), 21.0, None)
    # two of 22 never answer: p95 lies between them
    assert serve.metrics(trk, 0.0, 10.0)["serve_ttft_p95_ms"] == float("inf")
    del trk.reqs[20:]
    m = serve.metrics(trk, 0.0, 10.0)
    assert m["serve_ttft_p95_ms"] == pytest.approx(1000.0)
    assert m["serve_tpot_p95_ms"] == pytest.approx(500.0)
    # tokens at or before t=10: requests 0..9 first tokens (1..10 s) and
    # second tokens of 0..8 (1.5..9.5 s)
    assert m["serve_tokens_per_s"] == pytest.approx(19 / 10.0)
    assert serve.p95(np.arange(101)) == pytest.approx(95.0)


def test_aggregation_bytes_from_shapes():
    assert costs.aggregation_bytes(4, 10) == 4 * (40 + 10)
    granite = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "head_dim": 128, "num_hidden_layers": 1, "vocab_size": 6144}
    assert costs.param_count(granite) == 268_447_744
    assert costs.aggregation_bytes(4, 268_447_744) == 5_368_954_880


def test_mfu_flops_per_token_of_the_granite_cut():
    granite = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "head_dim": 128, "num_hidden_layers": 1, "vocab_size": 6144}
    # 6 x (41,943,040 attention + 176,160,768 MLP + 25,165,824 head)
    # + 6 x 1 x 32 x 128 x 1024 causal attention
    assert costs.train_flops_per_token(granite, 1024) == 1_484_783_616
