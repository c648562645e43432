"""`correct` has to be able to come out false. The control is the plain
reference, put in the program's place and computed in the nearest lower
precision (int8 under the configurations' bfloat16); the faults are
planted under a whole run that skips only the look for a chip. Sizes are
a test's; the readings at the cells' own sizes are in PERF.md."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))
sys.path.insert(0, tiny.REPO)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny")
    tiny.make_root(path)
    return path


def _cell(root, workload):
    import run

    return run.load_cell(str(root), workload)[1]


def test_training_control_in_int8_is_not_correct(root):
    import run
    from harness import train

    cell = _cell(root, "train-seq4096")
    cfg, mix = cell["config"], cell["mix"]
    ref = train.reference_readings(cfg, mix, 21, 2, 3)
    same = train.compare(train.reference_readings(cfg, mix, 21, 2, 3), ref)
    assert run.judge(dict(same, last_loss_finite=0.0), cell["limits"])[0]
    low = train.compare(
        train.reference_readings(cfg, mix, 21, 2, 3, mode="int8"), ref)
    ok, rows = run.judge(dict(low, last_loss_finite=0.0), cell["limits"])
    assert not ok, rows


def test_serving_control_in_int8_is_not_correct(root):
    import run
    from harness import serve

    cell = _cell(root, "serve-decode-closed")
    cfg = cell["config"]
    res, _ = tiny.run_cell(root, "serve-decode-closed")
    assert res["correct"]
    # the same positions, judged by the token the lower precision puts first
    import numpy as np

    rng = np.random.default_rng(0)
    finished = [(rng.integers(1, cfg["vocab_size"], 12).tolist(),
                 [0] * 40) for _ in range(30)]
    low = serve.check_served(finished, cfg, 4, 30, control="int8")
    ok, rows = run.judge(low, cell["limits"])
    assert not ok and low["gap_max"] > 0, rows


def test_state_left_unchanged_is_not_correct(root, monkeypatch):
    import bench

    build = bench.build_optimizer

    def frozen(model, sharded_update=False):
        opt = build(model, sharded_update)
        opt.get_lr = lambda: 0.0  # every step returns the parameters as is
        return opt

    monkeypatch.setattr(bench, "build_optimizer", frozen)
    res, _ = tiny.run_cell(root, "train-seq4096")
    assert res["correct"] is False
    assert res["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    from harness import train

    def half(self, ids, labels):
        n = ids.shape[0] // 2
        return self.model.loss(ids[:n], labels[:n])

    monkeypatch.setattr(train.Trainer, "_train_fn", half)
    res, _ = tiny.run_cell(root, "train-seq4096")
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["serve-decode-closed",
                                      "serve-chat-open"])
def test_an_altered_token_is_not_correct(root, monkeypatch, workload):
    from harness import serve

    emit = serve.Load._on_token

    def altered(self, rid, tok):
        n = len(self.req[rid]["tokens"])
        emit(self, rid, (tok + 1) % 256 if n == 3 else tok)

    monkeypatch.setattr(serve.Load, "_on_token", altered)
    res, _ = tiny.run_cell(root, workload)
    assert res["correct"] is False
