"""The gated_delta_hybrid family through a whole tiny serving cell (ISSUE
37): the tiny root cuts every configuration to two layers and 4 / 2
heads, so the family must honour ``num_key_value_heads`` and
``layer_types[:num_hidden_layers]``; the cell comes out correct, and not
correct under the lower-precision control or with a fault planted in what
the family adds: a slot that keeps the state of the request before, a
chunk's padding advancing the state, the convolutions' tail lost between
chunks. Sizes are a test's;
the readings at the cell's own size are in PERF.md."""
from __future__ import annotations

import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))
sys.path.insert(0, tiny.REPO)

CELL = "serve-hybrid-rollout-closed"
#: the family's keys at a test's size (tiny.TINY gives the rest, 4 heads
#: over 2 K/V heads among them): one period of the published pattern,
#: whose first layer sees the embedding alone and whose second and third
#: see a stream of size one
HYBRID = dict(num_hidden_layers=4,
              layer_types=["linear_attention"] * 3 + ["full_attention"],
              linear_num_key_heads=4, linear_num_value_heads=4,
              linear_key_head_dim=8, linear_value_head_dim=16)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_hybrid")
    bench = tiny.make_root(path, **HYBRID)
    conf = next(c for c in bench["configs"]
                if c["name"] == "olmo-hybrid-7b-pp2-serve")
    # more served tokens under the comparison than tiny's 24, and prompts
    # that are no multiple of the chunk (8)
    for file, change in (
            (conf["file"], lambda d: d["deployment"]["engine"].update(
                max_new_tokens=24)),
            ("benchmark/traffic/rollout-256-512-closed.json",
             lambda d: d.update(check_requests=4,
                                prompt={"dist": "fixed", "length": 13}))):
        with open(os.path.join(path, file)) as f:
            data = json.load(f)
        change(data)
        with open(os.path.join(path, file), "w") as f:
            json.dump(data, f)
    return path


def test_the_family_has_every_name_and_is_served_only():
    from harness.families import gated_delta_hybrid as fam

    assert not [n for n in tiny.FAMILY_NAMES if not hasattr(fam, n)]
    for name in ("RefTrainer", "training_model", "load_training_weights",
                 "seed_param", "train_flops_per_token"):
        with pytest.raises(NotImplementedError, match="served only"):
            getattr(fam, name)({}, 0)


def test_the_configuration_states_every_published_width():
    """Every key of the catalog's config under the same name and value,
    the depth (and the list it cuts) excepted and listed; 4.10 B
    parameters on this stage."""
    from harness.families import gated_delta_hybrid as fam

    with open(os.path.join(
            tiny.REPO, "benchmark/configs/olmo-hybrid-7b-pp2-serve.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:16]
    widths = dict(hidden_size=3840, intermediate_size=11008,
                  num_attention_heads=30, num_key_value_heads=30,
                  vocab_size=100352, linear_num_key_heads=30,
                  linear_num_value_heads=30, linear_key_head_dim=96,
                  linear_value_head_dim=192, linear_conv_kernel_dim=4)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_parameters"] == {"rope_theta": None}
    shapes = fam.leaf_shapes(cfg)
    count = lambda g: sum(math.prod(s) for s in shapes[g].values())
    total = count("lin") + count("full") + count("top")
    assert round(total / 1e9, 2) == 4.10, total
    assert fam.cache_bytes_per_token(cfg) == 4 * 15360
    assert fam.state_bytes_per_row(cfg) == 12 * 2211840
    assert fam.KERNEL_WORK["gdn_decode_step"](cfg, 64, 0) == (
        64 * 6 * 30 * 96 * 192, 64 * 2 * 2211840)


def test_the_cell_runs_and_is_correct(root):
    res, last = tiny.run_cell(root, CELL)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["served_compared"]["value"] >= 64
    assert set(res["metrics"]) == {"serve_output_tokens_per_s", "setup_s"}


def test_the_family_honours_kv_heads_and_the_cut_of_layer_types(root):
    import run
    from harness.families import gated_delta_hybrid as fam

    cfg = run.load_cell(str(root), CELL)[1]["config"]
    assert fam.layer_types(cfg) == HYBRID["layer_types"]
    shapes = fam.leaf_shapes(cfg)
    assert shapes["full"]["wk"] == (1, 1, 64, 2 * 16)     # 2 K/V heads
    assert shapes["lin"]["wv"] == (1, 3, 64, 4 * 16)
    assert fam.period(dict(cfg, num_hidden_layers=2))[0] == [
        "linear_attention"]                  # the list is cut, not the key
    long = dict(cfg, num_hidden_layers=5)
    with pytest.raises(ValueError, match="shorter"):
        fam.layer_types(long)


def test_serve_flops_counts_the_recurrence_and_the_context(root):
    import run
    from harness.families import gated_delta_hybrid as fam

    cfg = run.load_cell(str(root), CELL)[1]["config"]
    lin, full = fam.layer_matmul_params(cfg)
    eng = cfg["deployment"]["engine"]
    sampled = eng["max_seq_len"] - eng["max_new_tokens"] - 1
    positions = [0, 5, sampled]
    want = (2 * 3 * (3 * lin + full) + 3 * 3 * 6 * 4 * 8 * 16
            + 4 * 4 * 16 * (5 + sampled) + 2 * 64 * 256)
    assert fam.serve_flops(cfg, positions) == want


def test_the_control_in_bf16_is_not_correct(root):
    """The reference in the nearest precision below the one the
    configuration states (bfloat16 matmuls AND a bfloat16 state) put in
    the program's place fails the limits."""
    import numpy as np

    import run
    from harness import serve

    cell = run.load_cell(str(root), CELL)[1]
    cfg = cell["config"]
    rng = np.random.default_rng(0)
    finished = [(rng.integers(1, cfg["vocab_size"], 13).tolist(), [0] * 40)
                for _ in range(30)]
    low = serve.check_served(finished, cfg, 4, 30, control="bf16")
    ok, rows = run.judge(low, cell["limits"])
    assert not ok and low["gap_max"] > 0, rows


def _a_slot_keeps_the_state_of_the_request_before(monkeypatch):
    from paddle_tpu.models import gated_delta_hybrid as m

    monkeypatch.setattr(m.GatedDeltaHybridServing, "_fresh",
                        staticmethod(lambda first, held: held))


def _a_chunks_padding_advances_the_state(monkeypatch):
    from paddle_tpu.models import gated_delta_hybrid as m

    monkeypatch.setattr(m.GatedDeltaHybridServing, "_real_only",
                        staticmethod(lambda real, g, beta: (g, beta)))


def _the_tail_is_lost_between_chunks(monkeypatch):
    from paddle_tpu.models import gated_delta_hybrid as m

    conv = m.GatedDeltaHybridServing._conv_qkv

    def headless(self, window, taps):
        if window.shape[1] > taps.shape[0]:          # a chunk, not a tick
            window = window.at[:, :taps.shape[0] - 1].set(0)
        return conv(self, window, taps)

    monkeypatch.setattr(m.GatedDeltaHybridServing, "_conv_qkv", headless)


@pytest.mark.parametrize("fault", [
    _a_slot_keeps_the_state_of_the_request_before,
    _a_chunks_padding_advances_the_state,
    _the_tail_is_lost_between_chunks])
def test_a_planted_fault_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res, _ = tiny.run_cell(root, CELL)
    assert res["correct"] is False, res["checks"]
