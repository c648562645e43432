"""A throw-away copy of the benchmark's data at sizes a CPU test can hold:
the same BENCHMARK.json, configurations cut to two tiny layers in float32,
mixes cut to a second of work, and limits fit for float32. The harness's
code is the repository's; only ``--root`` points here."""
from __future__ import annotations

import glob
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
            torch_dtype="float32")
TRAIN_LIMITS = {"loss1_gap": {"max": 1e-5}, "loss2_gap": {"max": 1e-5},
                "loss3_gap": {"max": 1e-5}, "grad_norm_gap": {"max": 1e-4},
                "change_norm_gap": {"max": 1e-3},
                "last_loss_finite": {"max": 0}}
SERVE_LIMITS = {"gap_max": {"max": 1e-4}, "served_compared": {"min": 8}}
#: what the harness asks of a family (harness/families/__init__.py)
FAMILY_NAMES = ("make_weights", "forward_logits", "RefTrainer",
                "serving_model", "training_model", "TRAIN_PARAMS",
                "load_training_weights", "seed_param", "matmul_params",
                "train_flops_per_token", "serve_flops",
                "cache_bytes_per_token", "KERNEL_WORK")


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(root, **over):
    """Write the tiny tree under ``root``; returns BENCHMARK.json's dict.
    ``over`` overrides keys of every configuration (MHA, untied, another
    ``family``: the key is carried through as every other)."""
    root = str(root)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(TINY)
        cfg.update(over)
        if "program" in cfg:
            cfg["program"].update(batch_candidates=[2], head_chunk=128,
                                  env={})
        if isinstance(cfg.get("deployment"), dict):
            cfg["deployment"]["engine"] = dict(
                max_slots=4, page_size=8, max_seq_len=64, prefill_chunk=8,
                max_new_tokens=8)
        _dump(cfg, os.path.join(root, c["file"]))
    bdir = os.path.join(root, "benchmark")
    for path in glob.glob(os.path.join(REPO, "benchmark/traffic/*.json")):
        with open(path) as f:
            mix = json.load(f)
        if mix["kind"] == "train":
            mix.update(seq=32, n_batches=4)
        elif mix["kind"] == "serve-open":
            mix.update(arrivals={"process": "exponential-quantiles", "rate": 20.0},
                       prompt={"dist": "lognormal", "median": 16,
                               "sigma": 0.6, "min": 4, "max": 40},
                       ramp_s=0.5, check_requests=3)
        else:
            mix.update(clients=4, prompt={"dist": "fixed", "length": 12},
                       warm_ticks=2, check_requests=3)
        _dump(mix, os.path.join(bdir, "traffic", os.path.basename(path)))
    for w in bench["workloads"]:
        with open(os.path.join(bdir, "traffic",
                               f"{w['traffic']}.json")) as f:
            kind = json.load(f)["kind"]
        _dump(TRAIN_LIMITS if kind == "train" else SERVE_LIMITS,
              os.path.join(bdir, "limits", f"{w['name']}.json"))
    shutil.copytree(os.path.join(REPO, "benchmark/layer_metrics"),
                    os.path.join(bdir, "layer_metrics"))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return bench


def run_cell(root, workload, seed=3000000019, seconds=1.0, trace=0):
    """One run of a cell of the tiny tree, without the look for a chip;
    returns (the parsed result, the printed last line)."""
    import contextlib
    import io
    import sys

    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace), "--root", str(root)],
                 require_chip=False)
    last = out.getvalue().strip().splitlines()[-1]
    return json.loads(last), last
