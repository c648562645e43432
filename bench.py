"""The builders of the dense training lines: the tuned TPU settings, the
two tracked configurations, the model, the optimizer and the ZeRO-3 mesh,
as benchmark/harness and chip_smoke.py import them. The benchmark itself
is benchmark/run.py (BENCHMARK.json, PERF.md)."""
from __future__ import annotations

import os

#: the bf16 save list every remat policy starts from
BASE_SAVES = "attn_res,attn_lse,attn_q,attn_k,attn_v,rms_rstd"
#: the default remat policy (both ffn saves in bf16)
DEFAULT_POLICY = f"names:{BASE_SAVES},resid_mid,ffn_gate,ffn_up"


def apply_tpu_defaults():
    """The tuned TPU settings of the dense training lines, as
    ``os.environ.setdefault`` (an explicit env still wins). The ONE
    place they live: benchmark/harness/train.py and chip_smoke.py both
    call this.

    - Pallas rms kernel with saved rstd residual
    - flash fwd block 2048 (bwd stays 1024 — uniform 2048 bwd overflows
      scoped VMEM)
    - factored second-moment AdamW frees the m2 state; the headroom
      buys BOTH ffn saves — the backward re-runs no FFN matmuls at all
    - norm->ffn seam megakernel — (silu(gate)*up) @ wd streamed
      through VMEM, the [tokens, intermediate] product never touches
      HBM (ops/pallas/swiglu_down, docs/SCAN.md). PTPU_FUSED_FFN=0
      restores the unfused seam.

    The int8 weight-only LM head is not set here: the chunked-CE head
    turns it on by default WHEN the numeric parity gate passes
    (fused_cross_entropy.int8_head_enabled; PTPU_INT8_HEAD forces)."""
    os.environ.setdefault("PTPU_PALLAS_RMS", "1")
    os.environ.setdefault("PTPU_FA_BLOCK", "2048")
    os.environ.setdefault("PTPU_ADAM_FACTORED", "1")
    os.environ.setdefault("PTPU_FUSED_FFN", "1")


def quant_policy(policy, q):
    """``policy`` carrying the scaled-GEMM request ``q`` (docs/QUANT.md):
    the request rides the names: policy (models/gpt.py _resolve_remat
    strips + resolves it); other policies can't carry quant entries."""
    return (f"{policy},quant:{q}"
            if q and str(policy).startswith("names:") else policy)


def tpu_model_config(model_kind):
    """The two tracked TPU training configurations (bf16, seq 2048)."""
    from paddle_tpu.models.gpt import GPTConfig

    if model_kind == "llama":
        # BASELINE.md config-5 variant: LLaMA-7B architecture (h=4096,
        # GQA, swiglu, rope) depth-scaled to 8 layers so params+Adam
        # state fit one v5e chip. This line runs REAL sharding_stage=3
        # (group_sharded_parallel + the ZeRO execution mode,
        # docs/ZERO.md) over every addressable chip — degree = device
        # count.
        return GPTConfig(vocab_size=32000, hidden_size=4096,
                         num_layers=8, num_heads=32, num_kv_heads=8,
                         intermediate_size=11008, max_seq_len=2048,
                         dropout=0.0, dtype="bfloat16", recompute=True)
    # GPT-3 1.3B (BASELINE.md config 4) — the headline metric
    return GPTConfig(vocab_size=32000, hidden_size=2048,
                     num_layers=24, num_heads=16, max_seq_len=2048,
                     dropout=0.0, dtype="bfloat16", recompute=True)


def build_model(cfg, bf16):
    """Stacked-decoder flagship: lax.scan over layers keeps compile time
    constant in depth; recompute = jax.checkpoint per block. ``bf16``:
    AMP O2 build + bf16 parameters (the TPU lines)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLMPipe

    with paddle.amp.auto_cast(enable=bf16, dtype="bfloat16", level="O2"):
        model = GPTForCausalLMPipe(cfg)
    if bf16:
        for _, p in model.named_parameters():
            p._data = p._data.astype(jax.numpy.bfloat16)
    return model


def build_optimizer(model, sharded_update=False):
    """AdamW of the training lines.

    PTPU_ADAM_FACTORED=1: Adafactor-style factored second moment —
    frees the m2 state with fp32 math, no quant round-trips.
    The multi-chip stage-3 line (``sharded_update``) uses PLAIN fp32
    moments instead: factored moments compute cross-element
    statistics that can't run on a 1/degree shard (the zero plan would
    decline), and full moments divided by the shard degree beat
    factored's ~half saving from degree 2 up (docs/ZERO.md)."""
    import paddle_tpu as paddle

    return paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
        factored=(not sharded_update
                  and os.environ.get("PTPU_ADAM_FACTORED", "")
                  not in ("", "0")))


def zero3_mesh():
    """fleet mesh for the LLaMA-arch line: sharding_stage=3 END TO END
    (docs/ZERO.md) — params resident as dp shards, grads
    reduce-scattered, the update on 1/degree slots, scan-body
    just-in-time weight gathers — over every addressable chip. One
    chip is the degree-1 degenerate of the SAME code path (the zero
    plan disengages, GSPMD placements are no-ops), not a separate
    single-chip approximation. Returns (mesh, degree)."""
    import jax

    from paddle_tpu.distributed import fleet as _fleet

    degree = len(jax.devices())
    strategy = _fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1,
                               "sharding_degree": degree}
    _fleet.init(is_collective=True, strategy=strategy)
    return _fleet.get_fleet_mesh(), degree
