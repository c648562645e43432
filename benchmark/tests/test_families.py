"""The seam between the harness and a model family. The move of the dense
GQA decoder behind it changed no bit (checksums recorded on the parent
tree, PR 28, before anything moved); every configuration names a family
that provides the names the harness asks for; and a second family that
lives outside benchmark/harness/ runs a serving and a training cell with
no file of the harness edited, as a model_config PR will add one."""
from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402

sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))
sys.path.insert(0, tiny.REPO)

PIN_SEED = 2**31 + 29


def _sha(a):
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode()
                          + a.tobytes()).hexdigest()[:24]


def moved_checksums(mod, root):
    """Every leaf of make_weights and forward_logits on one sequence (the
    serving configuration, untied), RefTrainer's first loss, gradient
    norms and change_sumsq (the training one, tied): float32 at tiny.py's
    sizes, bit for bit. paddle_tpu is imported first, as in every run of
    the benchmark: it turns on jax_enable_x64, under which the norm gains'
    randint draws other bits (the program and the reference share one
    process, so they always draw alike)."""
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401

    import run
    from harness import traffic

    out = {}
    cfg = run.load_cell(str(root), "serve-decode-closed")[1]["config"]
    w = mod.make_weights(cfg, PIN_SEED, jnp.float32)
    out.update({f"weights.{k}": _sha(v) for k, v in sorted(w.items())})
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], 24)
    out["logits"] = _sha(mod.forward_logits(
        w, jnp.asarray(ids, jnp.int32), cfg))
    cell = run.load_cell(str(root), "train-seq4096")[1]
    cfg, mix = cell["config"], cell["mix"]
    ref = mod.RefTrainer(cfg, PIN_SEED, cfg["optimizer"], "float32")
    ids, labels = traffic.train_batches(mix, PIN_SEED, 2, cfg["vocab_size"])
    loss, grad = ref.step(ids[0], labels[0].astype(jnp.int32))
    out["loss1"] = float(loss).hex()
    out.update({f"grad.{k}": float(v).hex() for k, v in sorted(grad.items())})
    out.update({f"change.{k}": float(v).hex()
                for k, v in sorted(ref.change_sumsq().items())})
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny")
    tiny.make_root(path)
    return path


def test_the_moved_functions_give_the_parents_bits(root):
    from harness.families import dense_gqa

    with open(os.path.join(HERE, "dense_gqa_pins.json")) as f:
        pins = json.load(f)
    assert moved_checksums(dense_gqa, root) == pins


def test_a_configuration_without_a_family_is_an_error():
    from harness import families

    with pytest.raises(KeyError, match="family"):
        families.of({"hidden_size": 64})
    with pytest.raises(ModuleNotFoundError):
        families.of({"family": "no_such_family"})


def _harness_files():
    """Every file of benchmark/ but the tests' own, with its bytes."""
    out = {}
    for d, _, names in os.walk(os.path.join(tiny.REPO, "benchmark")):
        if "__pycache__" in d or d.startswith(HERE):
            continue
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                out[os.path.join(d, n)] = hashlib.sha256(f.read()).digest()
    return out


@pytest.fixture(scope="module")
def second(tmp_path_factory):
    """The second family's module on the families' search path, as if its
    file had been added beside dense_gqa.py, and a tiny tree of its own
    whose configurations name it."""
    from harness import families

    where = os.path.join(HERE, "second_family")
    families.__path__.append(where)
    path = tmp_path_factory.mktemp("second")
    tiny.make_root(path, family="unstacked")
    yield path, families.of({"family": "unstacked"})
    families.__path__.remove(where)


@pytest.mark.parametrize("workload", ["serve-decode-closed", "train-seq4096"])
def test_a_second_family_is_new_files_only(second, monkeypatch, workload):
    """One serving and one training cell of a family the harness has never
    heard of: a whole run but the look for a chip, correct, by that
    family's own reference, and no file of benchmark/ differs after."""
    root, family = second
    assert all(hasattr(family, n) for n in tiny.FAMILY_NAMES)
    assert not family.__file__.startswith(
        os.path.join(tiny.REPO, "benchmark", "harness"))
    asked = []
    for name in ("make_weights", "forward_logits", "RefTrainer",
                 "serving_model", "training_model", "serve_flops",
                 "train_flops_per_token"):
        def spy(*a, _f=getattr(family, name), _n=name, **kw):
            asked.append(_n)
            return _f(*a, **kw)
        monkeypatch.setattr(family, name, spy)
    before = _harness_files()
    res, _ = tiny.run_cell(root, workload)
    assert res["correct"] is True and res["failed"] == 0
    assert all(v["value"] > 0 for v in res["metrics"].values())
    want = ({"training_model", "make_weights", "RefTrainer",
             "train_flops_per_token"} if workload == "train-seq4096" else
            {"serving_model", "make_weights", "forward_logits",
             "serve_flops"})
    assert want <= set(asked), asked
    assert _harness_files() == before


def test_the_second_family_can_come_out_not_correct(second, monkeypatch):
    """Its cells are judged too: the reference a little off, not correct."""
    root, family = second
    real = family.forward_logits
    monkeypatch.setattr(
        family, "forward_logits",
        lambda w, ids, cfg, mode="f32": real(w, (ids + 1) % 256, cfg, mode))
    res, _ = tiny.run_cell(root, "serve-decode-closed")
    assert res["correct"] is False
