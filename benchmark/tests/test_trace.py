"""The trace reduction on a small synthetic trace, and the FLOP arithmetic
of both published configurations against hand arithmetic."""
from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from harness import roofline, trace  # noqa: E402
from harness.families import dense_gqa  # noqa: E402

US = 1000  # ns


def synthetic():
    """One device, window [0, 100us): a while loop of 60us enclosing a
    fusion (20), a flash_fwd (10) and an all-gather (10, of which 4 under
    no compute), then a gap of 30us, then a fusion of 10us."""
    return [[("%while.1", 0, 60 * US), ("%fusion.7", 5 * US, 20 * US),
             ("flash_fwd", 25 * US, 10 * US),
             ("%all-gather.2", 40 * US, 10 * US),
             ("%fusion.9", 90 * US, 10 * US)]]


def test_busy_idle_self_time_and_patterns():
    tr = trace.reduce_trace(synthetic(), 0, 100 * US)
    assert tr["busy_s"] == pytest.approx(70e-6)
    assert tr["window_s"] == pytest.approx(100e-6)
    ctx = {"trace": tr}
    assert trace.idle_share(ctx) == pytest.approx(30.0)
    # the loop keeps only what its body does not cover: 60 - 20 - 10 - 10
    assert tr["ops"]["while"][1] == pytest.approx(20e-6)
    assert tr["ops"]["fusion"] == [2, pytest.approx(30e-6)]
    assert trace.op_share(ctx, ["^fusion$"]) == pytest.approx(100 * 30 / 70)
    assert trace.op_share(ctx, ["^flash_"]) == pytest.approx(100 * 10 / 70)
    assert trace.op_share(ctx, ["^paged_attention"]) is None
    assert trace.idle_share({"trace": None}) is None


def test_window_clips_events():
    tr = trace.reduce_trace(synthetic(), 10 * US, 50 * US)
    assert tr["busy_s"] == pytest.approx(40e-6)
    assert tr["ops"]["fusion"][1] == pytest.approx(15e-6)
    assert "fusion" in tr["ops"] and tr["gaps"] == []


def test_collective_exposed_only_where_no_compute_overlaps():
    dev = [[("%all-gather.1", 0, 10 * US), ("%fusion.1", 6 * US, 10 * US)],
           [("%reduce-scatter.1", 0, 10 * US)]]
    tr = trace.reduce_trace(dev, 0, 20 * US)
    # chip 0 exposes 6us, chip 1 all 10us: the average over the chips
    assert tr["exposed_collective_s"] == pytest.approx(8e-6)
    assert trace.exposed_share({"trace": tr}) == pytest.approx(40.0)
    none = trace.reduce_trace(synthetic()[:1], 60 * US, 100 * US)
    assert trace.exposed_share({"trace": none}) is None


def test_gaps_go_to_the_innermost_covering_span():
    tr = trace.reduce_trace(synthetic(), 0, 100 * US)
    assert tr["gaps"] == [[60 * US, 90 * US]]
    spans = [("bench.tick", 0, 100 * US), ("decode_tick", 50 * US, 95 * US)]
    assert trace.attribute_gaps(tr["gaps"], spans) == {
        "decode_tick": pytest.approx(30e-6)}
    assert trace.attribute_gaps(tr["gaps"], []) == {
        "_no_span_": pytest.approx(30e-6)}
    bd = trace.breakdown(tr, {"decode_tick": 30e-6})
    assert bd["device_ops"][0] == ["fusion", pytest.approx(30e-6)]
    assert bd["idle_gaps"] == [["decode_tick", 30e-6]]


def test_span_readers():
    sp = trace.Spans()
    sp.items += [("bench.tick", 0.0, 0.2), ("bench.tick", 0.2, 0.6),
                 ("prefill_tick", 0.25, 0.35), ("bench.tick", 5.0, 9.0)]
    ctx = {"spans": sp, "t0": 0.0, "t1": 1.0}
    assert trace.span_ms(ctx, "bench.tick") == pytest.approx(300.0)
    assert trace.span_share(ctx, "prefill_tick", "bench.tick") == \
        pytest.approx(100 * 0.1 / 0.6)
    assert trace.span_ms(ctx, "nothing") is None


def _cfg(name):
    with open(os.path.join(REPO, "benchmark/configs", name)) as f:
        return json.load(f)


def test_flops_per_token_by_hand():
    i = _cfg("internlm2-1.8b-train.json")
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    n = 24 * per_layer + 92544 * 2048
    assert dense_gqa.matmul_params(i) == n == 1_699_479_552
    want = 6 * n + 6 * 24 * 2048 * 4096
    assert dense_gqa.train_flops_per_token(i, 4096) == want
    assert want / 1e9 == pytest.approx(11.4, abs=0.01)
    # Mistral-7B-v0.1's published widths at 10 of its 32 layers
    m = dict(hidden_size=4096, intermediate_size=14336,
             num_hidden_layers=10, num_attention_heads=32,
             num_key_value_heads=8, vocab_size=32000)
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    n = 10 * per_layer + 32000 * 4096
    assert dense_gqa.train_flops_per_token(m, 2048) == \
        6 * n + 6 * 10 * 4096 * 2048
    assert dense_gqa.train_flops_per_token(m, 2048) / 1e9 == \
        pytest.approx(14.4, abs=0.05)
    # serving: 2 N a token and 4 L h per position of context
    assert dense_gqa.serve_flops(i, [0, 10]) == \
        2 * 2 * dense_gqa.matmul_params(i) + 4 * 24 * 2048 * 10
    # the cache: K and V of 8 heads x 128, two bytes each, in 24 layers
    assert dense_gqa.cache_bytes_per_token(i) == 24 * 8 * 128 * 2 * 2 == 98_304


def test_rooflines_and_mfu():
    i = _cfg("internlm2-1.8b-train.json")
    pk = roofline.peaks("TPU v5 lite")
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    fl, nb = dense_gqa.flash_fwd_work(i, 1, 4096)
    assert fl == 2 * 4096 * 4096 * 2048
    assert nb == 2 * 4096 * (2 * 2048 + 2 * 1024) + 4 * 4096 * 16
    tr = {"ops": {"flash_fwd": [24, 24 * 2 * fl / pk["bf16_flops"]]},
          "busy_s": 1.0, "window_s": 1.0}
    ctx = {"trace": tr, "config": i, "peaks": pk, "chips": 1,
           "window_s": 1.0,
           "counters": {"batch_per_chip": 1, "seq": 4096,
                        "model_flops": 0.5 * pk["bf16_flops"]}}
    assert roofline.kernel_roofline(
        ctx, {"^flash_fwd": "flash_fwd"}) == pytest.approx(50.0)
    assert roofline.kernel_roofline(ctx, {"^nothing": "flash_fwd"}) is None
    assert roofline.mfu(ctx) == pytest.approx(50.0)
    assert roofline.mfu(dict(ctx, counters={})) is None
    tr["ops"]["paged_attention"] = [10, 1e-3]
    ctx["counters"]["paged_kv_bytes"] = 819e9 * 1e-4
    assert roofline.bytes_roofline(ctx, ["^paged_attention"],
                                   "paged_kv_bytes") == pytest.approx(10.0)
