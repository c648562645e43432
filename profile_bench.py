"""Profile the headline bench step and print the per-op device-time table.

Dev tool (not part of the driver contract): runs a few train steps under
jax.profiler.trace and aggregates the device plane via
paddle_tpu.profiler.xplane — the guessing-free way to see where the step
time goes on the real chip.
"""
import json
import os
import shutil
import sys
import time

import numpy as np


def main():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.device import compile_cache_dir, require_accelerator
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    on_tpu = require_accelerator("profile_bench.py")
    compile_cache_dir()
    policy = os.environ.get("PTPU_BENCH_REMAT", "attn")
    if on_tpu:
        cfg = GPTConfig(vocab_size=32000, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=2048, dropout=0.0,
                        dtype="bfloat16", recompute=policy != "none",
                        recompute_policy=policy)
        batch, seq = int(os.environ.get("PTPU_BENCH_BATCH", "6")), 2048
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=256, dropout=0.0,
                        recompute=True, recompute_policy=policy)
        batch, seq = 2, 128

    with paddle.amp.auto_cast(enable=on_tpu, dtype="bfloat16", level="O2"):
        model = GPTForCausalLMPipe(cfg)
    if on_tpu:
        for _, p in model.named_parameters():
            p._data = p._data.astype(jax.numpy.bfloat16)
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
        factored=os.environ.get("PTPU_ADAM_FACTORED", "1") not in ("", "0"))
    step = TrainStep(model, lambda i, l: model.loss(i, l), opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))

    for _ in range(2):  # compile + warm
        _ = float(step(ids, labels).numpy())

    logdir = os.environ.get("PTPU_PROFILE_DIR", "/tmp/ptpu_profile")
    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        for _ in range(3):
            loss = step(ids, labels)
        _ = float(loss.numpy())

    from paddle_tpu.profiler.xplane import (device_op_stats, format_table,
                                            summarize_families)

    rows = device_op_stats(logdir)
    if not rows:
        print("no device events found under", logdir)
        sys.exit(1)
    print(format_table(rows, limit=40))
    print()
    fams = summarize_families(rows)
    print(json.dumps(fams, indent=1))
    total_us = sum(r["total_us"] for r in rows)
    print(f"total device time: {total_us/1e6:.3f} s over 3 steps "
          f"=> {total_us/3e6:.3f} s/step")


if __name__ == "__main__":
    main()
