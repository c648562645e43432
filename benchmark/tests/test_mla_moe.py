"""The mla_moe family through a whole tiny serving cell (ISSUE 30): the
cell comes out correct, and not correct under the int8 control or with a
fault planted in what the family adds: an expert left out, the router's
bias ignored, the cache row's rope half zeroed (test_control.py's way).
Sizes are a test's; the readings at the cell's own size are in PERF.md."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))
sys.path.insert(0, tiny.REPO)

CELL = "serve-latent-batch-closed"
#: the family's keys at a test's size (tiny.TINY gives the rest): a router
#: of 16 in 4 groups, 8 experts held, 1 dense + 2 expert layers
LATENT = dict(num_hidden_layers=3, first_k_dense_replace=1, q_lora_rank=32,
              kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=24,
              v_head_dim=16, moe_intermediate_size=32, n_routed_experts=8,
              router_experts=16, experts_held=[0, 8], num_experts_per_tok=4,
              n_group=4, topk_group=2)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_latent")
    bench = tiny.make_root(path, **LATENT)
    # more served tokens under the comparison than tiny's 24: a fault
    # shows as a served token that is not the reference's first choice
    conf = next(c for c in bench["configs"]
                if c["name"] == "dots-vlm1-ep16-serve")
    for file, change in (
            (conf["file"], lambda d: d["deployment"]["engine"].update(
                max_new_tokens=32)),
            ("benchmark/traffic/batch-2k-closed.json",
             lambda d: d.update(check_requests=4))):
        with open(os.path.join(path, file)) as f:
            data = json.load(f)
        change(data)
        with open(os.path.join(path, file), "w") as f:
            json.dump(data, f)
    return path


@pytest.fixture(autouse=True)
def wide_bias(monkeypatch):
    """At a test's 24 served tokens the published bias scale (0.02) may
    move no choice; a wider one makes ignoring it show."""
    from harness.families import mla_moe

    monkeypatch.setattr(mla_moe, "BIAS_STD", 0.5)
    mla_moe._make_leaf.clear_cache()
    yield
    mla_moe._make_leaf.clear_cache()


def test_the_family_has_every_name_and_is_served_only():
    from harness.families import mla_moe

    assert not [n for n in tiny.FAMILY_NAMES if not hasattr(mla_moe, n)]
    for name in ("RefTrainer", "training_model", "load_training_weights",
                 "seed_param", "train_flops_per_token"):
        with pytest.raises(NotImplementedError, match="served only"):
            getattr(mla_moe, name)({}, 0)


def test_the_cell_runs_and_is_correct(root):
    res, last = tiny.run_cell(root, CELL)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["served_compared"]["value"] >= 64
    assert set(res["metrics"]) == {"serve_output_tokens_per_s", "setup_s"}


def test_serve_flops_counts_the_pairs_the_program_recorded(root):
    """Without recorded ticks the held experts count by an even router's
    expectation; with them, by the ``local_pairs`` the ticks carry."""
    import run
    from harness.families import mla_moe
    from paddle_tpu.telemetry import trace as ptrace

    cfg = run.load_cell(str(root), CELL)[1]["config"]
    positions = list(range(12)) + [12, 13]
    ptrace.enable()
    ptrace.reset()
    try:
        even = mla_moe.serve_flops(cfg, positions)
        for name, pairs in (("prefill_tick", 30), ("decode_tick", 5)):
            with ptrace.span(name, {"local_pairs": pairs}, cat="serve"):
                pass
        got = mla_moe.serve_flops(cfg, positions)
    finally:
        ptrace.reset()
        ptrace.disable()
    per_pair = 2 * mla_moe.expert_params(cfg)
    expected_pairs = mla_moe.pairs_expected(cfg) * 2 * len(positions)
    assert got - even == pytest.approx((35 - expected_pairs) * per_pair)
    assert mla_moe.cache_bytes_per_token(cfg) == 3 * (32 + 24) * 2


def test_the_control_in_int8_is_not_correct(root):
    import numpy as np

    import run
    from harness import serve

    cell = run.load_cell(str(root), CELL)[1]
    cfg = cell["config"]
    rng = np.random.default_rng(0)
    finished = [(rng.integers(1, cfg["vocab_size"], 12).tolist(), [0] * 40)
                for _ in range(30)]
    low = serve.check_served(finished, cfg, 4, 30, control="int8")
    ok, rows = run.judge(low, cell["limits"])
    assert not ok and low["gap_max"] > 0, rows


def _first_held_expert_left_out(monkeypatch):
    from paddle_tpu.incubate.distributed.models.moe import grouped

    whole = grouped.held_expert_ffn

    def without_first(x, idx, w, eg, eu, ed, held, **kw):
        # the stacks are [layers, held experts, ...]
        return whole(x, idx, w, eg[:, 1:], eu[:, 1:], ed[:, 1:],
                     (held[0] + 1, held[1]), **kw)

    monkeypatch.setattr(grouped, "held_expert_ffn", without_first)


def _bias_ignored(monkeypatch):
    from paddle_tpu.incubate.distributed.models.moe import grouped

    route = grouped.grouped_sigmoid_route
    monkeypatch.setattr(
        grouped, "grouped_sigmoid_route",
        lambda logits, bias, **kw: route(logits, 0 * bias, **kw))


def _rope_half_of_the_cache_row_zeroed(monkeypatch):
    from paddle_tpu.inference import serving

    write = serving._kv_write_run
    r, dr = LATENT["kv_lora_rank"], LATENT["qk_rope_head_dim"]

    def zeroed(cache, li, tables, pos0, nvalid, vals):
        if vals.shape[-1] % 128 == 0 and vals.shape[2] == 1:  # a latent row
            vals = vals.at[..., r:r + dr].set(0)
        return write(cache, li, tables, pos0, nvalid, vals)

    monkeypatch.setattr(serving, "_kv_write_run", zeroed)


@pytest.mark.parametrize("fault", [_first_held_expert_left_out,
                                   _bias_ignored,
                                   _rope_half_of_the_cache_row_zeroed])
def test_a_planted_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res, _ = tiny.run_cell(root, CELL)
    assert res["correct"] is False, res["checks"]


def test_grouped_gemm_roofline_reads_the_ticks_counts(root):
    """The reader on synthetic ticks and a synthetic trace: bandwidth
    bounds a decode tick's few pairs, the peak a prefill pass's many;
    without the attrs (a dense model, a parent commit) it reads nothing."""
    import run
    from harness import experts, roofline
    from harness.families import mla_moe

    cfg = run.load_cell(str(root), CELL)[1]["config"]
    per = mla_moe.expert_params(cfg)
    peaks = roofline.peaks("TPU v5 lite")
    ticks = [("decode_tick", {"local_pairs": 4, "experts_hit": 3}),
             ("prefill_tick", {"local_pairs": 10 ** 9, "experts_hit": 8})]
    events = [{"ph": "X", "name": n, "ts": 1.0 + i, "dur": 0.5, "attrs": a}
              for i, (n, a) in enumerate(ticks)]
    ctx = {"config": cfg, "peaks": peaks, "t0": 0.0, "t1": 10.0,
           "trace": {"ops": {"gmm": [6, 50.0]}},
           "program": {"events": events, "epoch": 0.0, "beats": []}}
    args = dict(patterns=["^gmm"], spans=["decode_tick", "prefill_tick"])
    least = (2 * 3 * per / peaks["hbm_bytes_per_s"]
             + 2 * 10 ** 9 * per / peaks["bf16_flops"])
    assert experts.grouped_gemm_roofline(ctx, **args) == pytest.approx(
        100 * least / 50.0)
    for e in events:
        e["attrs"] = {"live": 4}
    assert experts.grouped_gemm_roofline(ctx, **args) is None
    assert experts.grouped_gemm_roofline(dict(ctx, trace=None),
                                         **args) is None
