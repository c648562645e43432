"""Quickstart 2: decoder-only pretraining on a hybrid-parallel mesh
(fleet dp x mp, BASELINE.md config 4 shape), then the FULL 3-axis
pp x mp x dp composition as one compiled step. On one host:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/02_pretrain_gpt_hybrid.py
On a pod, launch one process per host with
`python -m paddle_tpu.distributed.launch` and the same body.

Crash safety: pass ``--ckpt-dir DIR`` to save every step as a committed
CheckpointManager checkpoint and auto-resume from the newest committed
step after a kill/preemption (``--resume auto``, the default) — SIGTERM
mid-run triggers one final synchronous save and a clean exit
(docs/CHECKPOINT.md).
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.checkpoint.manager import (CheckpointManager,
                                                       PreemptionGuard)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default=None,
                    help="crash-safe checkpoint root (off when unset)")
    ap.add_argument("--resume", choices=("auto", "none"), default="auto")
    ap.add_argument("--guard", action="store_true",
                    help="resilience StepGuard around the 3-axis compiled "
                    "step: nonfinite/spike updates are discarded in-graph "
                    "(skip-only here — attach a per-model CheckpointManager "
                    "to get the rewind rung; the eager "
                    "train_batch loop is not guarded, docs/RESILIENCE.md)")
    args = ap.parse_args()

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                    num_heads=4, max_seq_len=256, dropout=0.0)
    model = GPTForCausalLMPipe(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())

    dmodel = fleet.distributed_model(model)
    dopt = fleet.distributed_optimizer(opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (8, 128)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (8, 128)).astype(np.int64))

    def lm_loss(logits, y):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), y.reshape([-1]))

    # crash-safe training state: committed per-step saves + auto-resume
    manager = None
    start = 0
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, keep=3)
        # newest GOOD step: restore only walks good steps, so a
        # BAD-inclusive latest_step() gate could crash post-abort
        if args.resume == "auto" and manager.last_good_step() is not None:
            start = manager.restore_training_state(model, opt)
            print(f"resumed from committed step {start}")

    with PreemptionGuard(manager) as guard:
        for step in range(start, 5):
            loss = dmodel.train_batch([ids, labels], dopt, loss_fn=lm_loss)
            print(f"step {step}: loss {float(loss):.4f}")
            if manager is not None:
                # train_step= syncs the compiled step's optimizer slots
                # back into `opt` before the state is snapshotted
                manager.save_training_state(
                    step + 1, model, opt, train_step=dmodel._train_step,
                    async_save=True)
            if guard.preempted:
                if manager is not None:
                    manager.wait()
                    manager.save_training_state(
                        step + 1, model, opt,
                        train_step=dmodel._train_step)
                    print(f"preempted: committed final step {step + 1}")
                return
    if manager is not None:
        manager.wait()

    # -- full 3-axis hybrid: pipeline stages x Megatron TP x data -------
    # parallel, ONE compiled program. Stage sharding comes from the
    # 'pp' placements; tp_axis="mp" adds column/row TP placements on
    # the stacked weights; the batch shards over dp. (Swap the dp axis
    # for sharding_degree=2 + shard_opt_states=True to get ZeRO-1 on
    # top — the 4-axis composition.)
    from paddle_tpu.distributed.parallel_step import ShardedTrainStep

    strategy3 = fleet.DistributedStrategy()
    strategy3.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                "pp_degree": 2, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy3)
    paddle.seed(0)
    model3 = GPTForCausalLMPipe(cfg)
    model3.decoder.apply_pipeline_placements(tp_axis="mp")
    opt3 = paddle.optimizer.AdamW(learning_rate=3e-4,
                                  parameters=model3.parameters())
    step3 = ShardedTrainStep(model3, lambda a, b: model3.loss(a, b),
                             opt3, fleet.get_fleet_mesh())
    if args.guard:
        # StepGuard over the hybrid compiled step: a nonfinite or
        # loss-spike update is discarded IN-GRAPH (pre-step state kept,
        # the loop retries), escalating to a committed-checkpoint rewind
        # when a manager is attached (docs/RESILIENCE.md)
        from paddle_tpu.resilience import StepGuard

        # skip-only policy here: `manager` holds the FIRST model's steps,
        # which must not be restored into model3 — attach a per-model
        # CheckpointManager (a per-model subroot) to get
        # the rollback rung of the escalation ladder
        guard3 = StepGuard(step3, manager=None)
        gstep = 1
        while gstep <= 3:
            out = guard3(gstep, ids, labels)
            if out.accepted:
                print(f"3-axis step {gstep - 1}: "
                      f"loss {float(out.loss.numpy()):.4f}")
            else:
                print(f"3-axis step {gstep - 1}: {out.action} "
                      f"({out.health.kind})")
            gstep = out.next_step
    else:
        for step in range(3):
            loss = step3(ids, labels)
            print(f"3-axis step {step}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
