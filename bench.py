"""Headline benchmark: decoder-only (GPT/LLaMA-style) pretrain throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"memory", "telemetry"}. The reference publishes no absolute numbers
(BASELINE.md), so vs_baseline reports achieved model FLOPs utilisation
(MFU) against the chip peak — a hardware-normalised stand-in the driver
can track across rounds. "memory" is the batch/remat planner decision +
XLA peak bytes (docs/MEMORY.md); "telemetry" the runtime metric snapshot
(docs/TELEMETRY.md).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

#: the r5 bf16 save list every remat policy in the grid starts from
BASE_SAVES = "attn_res,attn_lse,attn_q,attn_k,attn_v,rms_rstd"
#: the r5 default remat policy (both ffn saves in bf16)
DEFAULT_POLICY = f"names:{BASE_SAVES},resid_mid,ffn_gate,ffn_up"


def apply_tpu_defaults():
    """The tuned TPU settings of the dense training lines, as
    ``os.environ.setdefault`` (an explicit env still wins). The ONE
    place they live: bench.py and chip_smoke.py both call this.

    - Pallas rms kernel with saved rstd residual (+3.1% MFU, r3)
    - flash fwd block 2048 (+0.6%, r4; bwd stays 1024 — uniform 2048
      bwd overflows scoped VMEM, decoupled q/k blocks measured worse)
    - r5: factored second-moment AdamW frees the m2 state (~2.6GB at
      1.3B); the headroom buys BOTH ffn saves at batch 3 — the backward
      re-runs no FFN matmuls at all (GPT 0.5468 -> 0.5629, LLaMA
      0.5806 -> 0.638, docs/ROUND5_RESPONSE.md)
    - r6+: norm->ffn seam megakernel — (silu(gate)*up) @ wd streamed
      through VMEM, the [tokens, intermediate] product never touches
      HBM (ops/pallas/swiglu_down, docs/SCAN.md). PTPU_FUSED_FFN=0
      restores the unfused seam; PTPU_FUSED_SEAMS=1 additionally
      engages the addrms attn->norm seam.

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

    PTPU_ADAM8=1: blockwise-int8 moments (8-bit Adam) — frees ~4GB of
    optimizer HBM at 1.3B, buying remat headroom (r4; measured LOSING
    on this chip, defaults off — docs/ROUND4_RESPONSE.md)
    PTPU_ADAM_FACTORED=1: Adafactor-style factored second moment —
    frees ~2.6GB (m2) with fp32 math, no quant round-trips (r5)
    The multi-chip stage-3 line (``sharded_update``) uses PLAIN fp32
    moments instead: factored/int8 moments compute cross-element
    statistics that can't run on a 1/degree shard (the zero plan would
    decline), and full moments divided by the shard degree beat
    factored's ~half saving from degree 2 up (docs/ZERO.md)."""
    import paddle_tpu as paddle

    return paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
        moment_dtype=(None if sharded_update else
                      ("int8" if os.environ.get("PTPU_ADAM8", "")
                       not in ("", "0") else None)),
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


def _serving_smoke_block():
    """Compact fleet-serving soak for the bench JSON (--serve): replica
    cold start (warmup compile, gated vs the previous round by
    bench_gate's COLD gate at the same scan mode) plus a 1-vs-2 replica
    goodput ratio and p99 TTFT vs a 10x-p50 budget (SERVE gate). The
    heavy 1..N sweep lives in tools/serve_bench.py (docs/SERVING.md);
    this block keeps the serving numbers tracked round over round next
    to the training metrics."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.fleet import build_workload, soak_block
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=128,
                      dropout=0.0)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    workload = build_workload(48, 200.0, (6, 10, 14), cfg.vocab_size,
                              seed=1)
    engine_kw = dict(max_slots=4, page_size=8, max_seq_len=64,
                     max_new_tokens=8, prefill_chunk=8)
    base = soak_block(model, replicas=1, workload=workload,
                      engine_kw=engine_kw)
    p50 = (base.get("ttft") or {}).get("p50")
    block = soak_block(model, replicas=2, workload=workload,
                       engine_kw=engine_kw, baseline=base,
                       ttft_budget=(10.0 * p50 if p50 else None))
    block["single"] = {"goodput_tokens_per_sec":
                       base.get("goodput_tokens_per_sec"),
                       "cold_start_seconds":
                       base.get("cold_start_seconds")}
    return block


def run_long_context(ckpt=None):
    """Long-context bench line (``*_seq32k``, docs/ATTENTION.md): the
    train step over a ``sep`` mesh with the ring-attention plan engaged
    — 32k tokens per sequence on TPU, a reduced-length CPU smoke
    otherwise (the honest-smoke discipline of BENCH_r06). Emits ONE
    JSON metric line whose ``"ring"`` block carries the plan summary
    and the ring-vs-dense parity probe ``tools/bench_gate.py`` gates
    reference-free; tokens/sec gates against earlier rounds like every
    metric line."""
    import time as _time

    import jax

    import paddle_tpu as paddle
    import paddle_tpu.telemetry as telemetry
    from paddle_tpu.device import (chip_peaks, device_record,
                                   require_accelerator)
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    on_tpu = require_accelerator("bench.py --long-context")
    telemetry.enable()
    telemetry.reset()
    n_dev = len(jax.devices())
    seq_env = os.environ.get("PTPU_BENCH_LONG_SEQ")
    if on_tpu:
        # GPT-1.3B arch at 32k context, batch 1: flash keeps attention
        # O(S) so the activation budget is the residual stream, not a
        # [32k, 32k] score matrix (asserted to not exist by the tests)
        cfg = GPTConfig(vocab_size=32000, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=32768, dropout=0.0,
                        dtype="bfloat16", recompute=True,
                        recompute_policy="names:attn_res,attn_lse,attn_q,"
                        "attn_k,attn_v,resid_mid")
        seq, steps, batch = int(seq_env or 32768), 5, 1
        os.environ.setdefault("PTPU_PALLAS_RMS", "1")
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=512, dropout=0.0)
        seq, steps, batch = int(seq_env or 512), 3, 2
    # sep = the largest device count that zigzag-divides the sequence
    sep = n_dev
    while sep > 1 and seq % (2 * sep):
        sep -= 1
    mesh = None
    if sep >= 2:
        from paddle_tpu.distributed import fleet as _fleet

        strategy = _fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": n_dev // sep,
                                   "mp_degree": 1, "pp_degree": 1,
                                   "sharding_degree": 1, "sep_degree": sep}
        _fleet.init(is_collective=True, strategy=strategy)
        mesh = _fleet.get_fleet_mesh()

    with paddle.amp.auto_cast(enable=on_tpu, dtype="bfloat16", level="O2"):
        model = GPTForCausalLMPipe(cfg)
    if on_tpu:
        for _, p in model.named_parameters():
            p._data = p._data.astype(jax.numpy.bfloat16)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())

    def train_fn(ids, labels):
        return model.loss(ids, labels)

    if mesh is not None:
        from paddle_tpu.distributed.parallel_step import ShardedTrainStep

        step = ShardedTrainStep(model, train_fn, opt, mesh)
    else:
        step = TrainStep(model, train_fn, opt)

    rng = np.random.default_rng(0)
    dp = (n_dev // sep) if mesh is not None else 1
    rows = max(batch, dp)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (rows, seq)).astype(np.int64))
    loss = step(ids, labels)                   # compile + warmup
    _ = float(loss.numpy())
    t0 = _time.perf_counter()
    for _i in range(steps):
        loss = step(ids, labels)
    _ = float(loss.numpy())
    dt = _time.perf_counter() - t0
    tokens_per_sec = rows * seq * steps / dt

    from paddle_tpu.distributed import collectives as _coll

    plan = step.ring_plan() if hasattr(step, "ring_plan") else None
    engaged = bool(getattr(step, "_ring_last_active", False))
    ring_block = {
        "enabled": plan is not None,
        "engaged": engaged,
        "seq": seq,
        "parity": _coll.ring_parity_probe(mesh),
    }
    if plan is not None:
        ring_block.update(plan.summary())

    n_params = sum(int(np.prod(p.shape))
                   for _, p in model.named_parameters())
    peak = chip_peaks()[0]["bf16_flops"]  # CPU: flagged placeholder
    mfu = 6.0 * n_params * tokens_per_sec / peak
    print(json.dumps({
        "metric": "gpt_long_context_tokens_per_sec_seq32k",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "device": device_record(),
        "seq": seq,
        "note": (None if on_tpu and seq >= 32768 else
                 f"reduced-length smoke (seq {seq}, {jax.default_backend()}"
                 ") — the 32k TPU number needs a TPU round"),
        "mfu": round(mfu, 4),
        "vs_baseline": round(mfu, 4),
        # ring plan + reference-free parity probe (docs/ATTENTION.md;
        # gated by bench_gate's RING gate)
        "ring": ring_block,
        "telemetry": telemetry.snapshot(),
    }), flush=True)


def run_model(model_kind, ckpt=None):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.telemetry as telemetry
    from paddle_tpu.device import (chip_peaks, device_record,
                                   require_accelerator)
    from paddle_tpu.telemetry import trace as ptrace
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTConfig
    import paddle_tpu.nn.functional as F
    from paddle_tpu import quant as _pquant

    on_tpu = require_accelerator("bench.py")

    # full-run telemetry: op dispatch, collectives, compile events, and
    # step timing all land in the snapshot attached to the bench JSON, so
    # a BENCH_r*.json regression explains itself (docs/TELEMETRY.md)
    telemetry.enable()
    telemetry.reset()

    # --trace / PTPU_TRACE=1: span tracer ON for the whole run — jit
    # build phases, per-step dispatch with cost_analysis attrs, plan
    # collectives, checkpoint phases — exported as Perfetto JSON + JSONL
    # next to the run, summarized in the JSON line's "anatomy" block
    # (docs/TELEMETRY.md Tracing section)
    trace_on = (bool(ckpt is not None and getattr(ckpt, "trace", False))
                or os.environ.get("PTPU_TRACE", "") not in ("", "0"))
    trace_dir = (getattr(ckpt, "trace_dir", None) or ".") if ckpt else "."
    if trace_on:
        ptrace.enable()
        ptrace.reset()

    # --record / PTPU_RECORD=1: background time-series recorder for the
    # whole run — registry samples every --record-interval seconds into
    # a JSONL timeline next to the bench output, summarized in the JSON
    # line's "timeline" block and readable by tools/telemetry_report.py
    # --timeline (docs/TELEMETRY.md "Time series, SLOs...")
    record_on = (bool(ckpt is not None and getattr(ckpt, "record", False))
                 or os.environ.get("PTPU_RECORD", "") not in ("", "0"))
    record_interval = float(
        (getattr(ckpt, "record_interval", None) if ckpt else None)
        or os.environ.get("PTPU_RECORD_INTERVAL", "") or 0.5)
    ts_recorder = None
    if record_on:
        os.makedirs(trace_dir, exist_ok=True)
        ts_recorder = telemetry.recorder(jsonl_path=os.path.join(
            trace_dir, f"timeline_{model_kind}.jsonl"))
        ts_recorder.start(record_interval)

    if on_tpu:
        apply_tpu_defaults()
        cfg = tpu_model_config(model_kind)
        seq, steps = 2048, 10
        batch_grid = (3, 4, 5)
    else:  # smoke path for CPU runs the caller asked for (JAX_PLATFORMS)
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=256, dropout=0.0)
        seq, steps = 128, 3
        batch_grid = (2,)

    # batch/remat chosen by the memory planner (paddle_tpu.memory): each
    # candidate is lowered+compiled unexecuted and priced by XLA's
    # memory_analysis against the chip HBM budget — no more hand-set
    # "b5 OOMs" caps. The grid pairs the r5 bf16 save list with int8
    # activation-checkpointing variants (int8:<name> saves the residual
    # blockwise-int8 at ~half the bytes, docs/MEMORY.md). Decisions are
    # cached per (config, chip); PTPU_BENCH_BATCH / PTPU_BENCH_REMAT
    # remain as overrides for perf sweeps (both set = planning skipped,
    # the override is still priced + recorded in the JSON).
    if on_tpu:
        policy_grid = (
            DEFAULT_POLICY,
            f"names:{BASE_SAVES},resid_mid,int8:ffn_gate,int8:ffn_up",
            f"names:{BASE_SAVES},int8:resid_mid,int8:ffn_gate,int8:ffn_up",
        )
    else:
        # CPU smoke pins the all-int8 policy so one tier-1 bench run
        # exercises planner + quantized save/restore end to end
        policy_grid = (
            f"names:{BASE_SAVES},int8:resid_mid,int8:ffn_gate,int8:ffn_up",
        )
    env_batch = os.environ.get("PTPU_BENCH_BATCH")
    env_remat = os.environ.get("PTPU_BENCH_REMAT")
    env_hchunk = os.environ.get("PTPU_BENCH_HEAD_CHUNK")
    # --autotune / PTPU_AUTOTUNE=1 (docs/AUTOTUNE.md): route this line
    # through the layout autotuner — the mesh/schedule lattice is
    # searched lowering-only and the headline runs the winning layout's
    # built ShardedTrainStep instead of the hand-picked config
    autotune_on = (bool(ckpt is not None and getattr(ckpt, "autotune",
                                                     False))
                   or os.environ.get("PTPU_AUTOTUNE", "")
                   not in ("", "0"))
    # fused-CE head chunk: a third plan dimension. Bigger chunks = fewer
    # serialized LSE scan steps; the resident [tokens, chunk] fp32 block
    # is what memory_analysis prices against batch/remat headroom.
    if env_hchunk:
        hchunk_grid = (int(env_hchunk),)
    elif on_tpu:
        hchunk_grid = (16384, 8192)
    else:
        hchunk_grid = (256,)  # CPU smoke: multiple chunks over vocab 512

    model = build_model(cfg, bf16=on_tpu)

    # config-5 (BASELINE.md): the LLaMA-arch line runs sharding_stage=3
    # over every addressable chip (zero3_mesh)
    zero_stage, zero_degree, zero_mesh = 0, 1, None
    if model_kind == "llama":
        zero_stage = 3
        zero_mesh, zero_degree = zero3_mesh()

    opt = build_optimizer(
        model, sharded_update=zero_stage >= 2 and zero_degree > 1)
    if zero_stage:
        from paddle_tpu.distributed import group_sharded_parallel

        model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")

    def train_fn(ids, labels):
        # fused chunked head+CE: full logits never materialize (models/gpt.py)
        return model.loss(ids, labels)

    def make_step():
        if zero_mesh is not None:
            from paddle_tpu.distributed.parallel_step import ShardedTrainStep

            return ShardedTrainStep(model, train_fn, opt, zero_mesh)
        return TrainStep(model, train_fn, opt)

    from paddle_tpu import memory as pmem

    # quant-compute axis (docs/QUANT.md): every grid candidate also
    # REQUESTS the scaled fp8/int8 GEMM mode (`quant:all` entries appended
    # to its names: policy). The request creates the amax buffer and rides
    # the plan-cache key; trace-time ENGAGEMENT still resolves behind the
    # parity gate / CPU default-off / PTPU_QUANT_COMPUTE, so a red gate
    # prices and runs the same wide programs with a passthrough buffer.
    # PTPU_BENCH_QUANT=0 drops the request (no buffer — the structural
    # escape hatch, hex-identical to the pre-quant programs).
    env_bquant = os.environ.get("PTPU_BENCH_QUANT", "").strip().lower()
    quant_grid = (None,) if env_bquant in ("0", "off") else ("all",)

    if env_batch and env_remat:
        # reproduce path: only pin the head chunk when the sweep pinned it
        # too — otherwise keep the kernel default the recorded round used.
        # The explicit policy is taken verbatim (carry your own quant:
        # entries to reproduce a quantized round).
        candidates = [pmem.Candidate(
            int(env_batch), env_remat,
            head_chunk=int(env_hchunk) if env_hchunk else None)]
        require_fit = False  # trust the sweep; still price + record it
    else:
        candidates = [
            pmem.Candidate(b, p, head_chunk=hc, quant=q)
            for b in ((int(env_batch),) if env_batch else batch_grid)
            for p in ((env_remat,) if env_remat else policy_grid)
            for hc in hchunk_grid
            for q in quant_grid
        ]
        require_fit = True

    def step_factory(cand):
        pol = quant_policy(cand.policy, getattr(cand, "quant", None))
        cfg.recompute = pol != "none"
        cfg.recompute_policy = pol
        cfg.head_chunk = cand.head_chunk
        s = make_step()
        return s, (jax.ShapeDtypeStruct((cand.batch, seq), jax.numpy.int32),
                   jax.ShapeDtypeStruct((cand.batch, seq), jax.numpy.int64))

    def act_bytes(cand):
        return pmem.estimate_stacked_activation_bytes(
            cand.policy, num_layers=cfg.num_layers, batch=cand.batch,
            seq=seq, hidden=cfg.hidden_size, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            intermediate=cfg.intermediate_size,
            act_bytes=2 if on_tpu else 4)

    # cache key must carry every knob that changes the lowered program's
    # memory profile — a decision priced under factored Adam reused for a
    # full-moment sweep would hand back a config that OOMs (the exact
    # failure class the planner exists to prevent)
    mem_envs = tuple(
        (k, os.environ.get(k, ""))
        for k in ("PTPU_ADAM_FACTORED", "PTPU_ADAM8", "PTPU_INT8_HEAD",
                  "PTPU_PALLAS_RMS", "PTPU_FUSED_ADDRMS", "PTPU_INT8_FFN",
                  "PTPU_FA_BLOCK", "PTPU_FA_BWD_BLOCK",
                  "PTPU_UNROLL_LAYERS", "PTPU_CE_CHUNK", "PTPU_CE_VCHUNK",
                  "PTPU_LOSS_HEAD", "PTPU_ROPE_HOIST",
                  # scan/seam knobs change the lowered program wholesale
                  # (scan body vs unrolled layers, fused vs plain seams);
                  # the planner key also carries the scan mode itself
                  # (memory/planner.py), this is belt + suspenders
                  "PTPU_SCAN_LAYERS", "PTPU_FUSED_FFN", "PTPU_FUSED_SEAMS",
                  # comms knobs change the lowered program (manual-region
                  # grad reduce, bucket layout, fused tp seams) — a plan
                  # priced under one comm regime must not be reused under
                  # another (docs/COMMS.md)
                  "PTPU_QUANT_COLLECTIVES", "PTPU_QUANT_GRADS",
                  "PTPU_COMM_BUCKET_MB", "PTPU_QUANT_MIN_NUMEL",
                  "PTPU_QUANT_EXCLUDE", "PTPU_TP_SEAM", "PTPU_COMM_SLAB",
                  # zero knobs change the whole step program (manual
                  # region layout, slot shapes, gather seams) —
                  # docs/ZERO.md
                  "PTPU_ZERO_MODE", "PTPU_ZERO_JIT_GATHER",
                  "PTPU_QUANT_PARAM_GATHER",
                  # quant-compute knobs: a plan priced with wide GEMMs
                  # must not replay across a PTPU_QUANT_COMPUTE flip
                  # (planner.py also keys on quant.cache_key_knobs() —
                  # belt + suspenders, docs/QUANT.md)
                  "PTPU_QUANT_COMPUTE", "PTPU_QUANT_DTYPE",
                  "PTPU_QUANT_AMAX_HIST", "PTPU_QUANT_GATE_TOL",
                  "PTPU_INT8_WEIGHTS", "PTPU_BENCH_QUANT",
                  # layout knobs (docs/AUTOTUNE.md): an autotuned
                  # decision priced under one engagement regime must
                  # not replay across a knob flip — nor may a
                  # hand-picked plan replay into an --autotune run
                  "PTPU_AUTOTUNE", "PTPU_PIPELINE_SCHEDULE",
                  "PTPU_RING_ATTN", "PTPU_SHARDED_HEAD", "PTPU_COMPOSED",
                  "PTPU_LINK_GBPS", "PTPU_LAYOUT_CACHE")
    ) + (("int8_head", F.int8_head_enabled()),  # gate outcome, not just env
         ("quant_gate", _pquant.quant_gate()))
    # ZeRO pricing record (docs/ZERO.md): the candidate programs compile
    # ON the sharded mesh, so their memory_analysis peak is already
    # per-device — analytic pools stay 0 and only stage/degree ride the
    # record + plan-cache key (a stage-3 decision never replays for a
    # stage-0 build). The analytic pools are for planning a SHARDED
    # config from an UNSHARDED compile (memory.zero_hbm_savings).
    zero_info = ({"stage": zero_stage, "degree": zero_degree,
                  "param_bytes": 0, "slot_bytes": 0, "grad_bytes": 0}
                 if zero_stage else None)
    cache_extra = (model_kind, cfg.vocab_size, cfg.hidden_size,
                   cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                   cfg.intermediate_size, seq,
                   "bf16" if on_tpu else "f32", mem_envs)
    layout_block = {"enabled": False}
    if autotune_on:
        # the layout autotuner (docs/AUTOTUNE.md) owns mesh + model +
        # step: it searches every (dp, sharding, mp, pp, sep) x zero x
        # schedule point the compose lattice accepts (pruning the rest
        # with structured Reasons, lowering-only pricing for survivors)
        # and hands back the BUILT ShardedTrainStep for the winner. The
        # hand-picked config rides along as the baseline — it is scored
        # through the same cost model, may legitimately win, and is
        # what the bench_gate LAYOUT gate compares against. batch in a
        # LayoutCandidate is rows PER DATA SHARD (global = batch x
        # dp*sharding*sep).
        import copy as _copy

        ndev = len(jax.devices())
        factory = pmem.flagship_gpt_factory(
            lambda: _copy.deepcopy(cfg), amp_bf16=on_tpu,
            optimizer_factory=lambda m: paddle.optimizer.AdamW(
                learning_rate=3e-4, parameters=m.parameters()))
        layouts = pmem.enumerate_layouts(
            ndev,
            batches=((int(env_batch),) if env_batch else batch_grid),
            policies=((env_remat,) if env_remat else policy_grid),
            head_chunks=hchunk_grid, quants=quant_grid)
        if model_kind == "llama":
            # the hand-picked config-5 layout: stage-3 over every chip
            base_layout = pmem.LayoutCandidate(
                sharding=ndev, zero_stage=3, batch=batch_grid[0],
                policy=policy_grid[0], head_chunk=hchunk_grid[0],
                quant=quant_grid[0])
        else:
            base_layout = pmem.LayoutCandidate(
                dp=ndev, batch=batch_grid[0], policy=policy_grid[0],
                head_chunk=hchunk_grid[0], quant=quant_grid[0])
        step, layout_decision = pmem.autotune_train_step(
            factory, seq_len=seq, layouts=layouts, baseline=base_layout,
            require_fit=require_fit, cache_extra=cache_extra)
        layout_block = layout_decision.as_json()
        # the winner's PlanDecision-shaped record keeps the "memory"
        # block (and everything downstream of `decision`) unchanged
        decision = pmem.PlanDecision(**layout_decision.memory)
        model, opt = step.model, step.optimizer
        batch = decision.batch
        cfg.recompute = decision.policy != "none"
        cfg.recompute_policy = quant_policy(
            decision.policy, getattr(decision, "quant", None))
        cfg.head_chunk = decision.head_chunk
    else:
        from paddle_tpu.nn.functional.fused_cross_entropy import (
            resolve_vocab_chunk)

        def _program_key(c):
            # head_chunk reaches the traced program only through the
            # RESOLVED CE vocab chunk — candidates whose chunks clamp
            # to the same effective value share one lowering (the
            # planner memoizes on this key, docs/MEMORY.md)
            return (c.batch,
                    quant_policy(c.policy, getattr(c, "quant", None)),
                    resolve_vocab_chunk(cfg.vocab_size, c.head_chunk),
                    getattr(c, "depth", None))

        decision = pmem.plan_train_step(
            step_factory, candidates, require_fit=require_fit,
            act_bytes_fn=act_bytes, zero=zero_info,
            opt_state_bytes=opt.slot_nbytes(
                {n: p._data for n, p in model.named_parameters()},
                shard_degree=zero_degree if zero_stage else 1),
            program_key_fn=_program_key,
            cache_extra=cache_extra)
        batch = decision.batch
        cfg.recompute = decision.policy != "none"
        cfg.recompute_policy = quant_policy(decision.policy,
                                             getattr(decision, "quant",
                                                     None))
        cfg.head_chunk = decision.head_chunk

        # NOTE: on a plan-cache miss the winning program compiles twice
        # (once AOT in the planner, once here at warmup — jit's dispatch
        # cache is not fed by the AOT path); JAX's persistent compile
        # cache (device.compile_cache_dir) turns the second into a
        # disk hit. The plan cache makes every later run of the same
        # config skip planning entirely.
        step = make_step()

    # Crash-safe checkpointing (--ckpt-dir): per-step committed saves via
    # CheckpointManager, --resume auto restore of the newest committed
    # step BEFORE warmup (the compiled step seeds its optimizer state
    # from the restored slots), and a PreemptionGuard that turns
    # SIGTERM/SIGINT into one final synchronous save + clean exit
    # (docs/CHECKPOINT.md). Default driver runs pass no flags: inactive.
    manager = guard = None
    start_step = 0
    if ckpt is not None and ckpt.ckpt_dir:
        from paddle_tpu.distributed.checkpoint.manager import (
            CheckpointManager, PreemptionGuard)

        # per-model subroot: the default TPU driver run trains BOTH
        # tracked configs, whose state dicts must not share a step dir
        manager = CheckpointManager(
            os.path.join(ckpt.ckpt_dir, model_kind), keep=ckpt.ckpt_keep)
        # gate on the newest GOOD step, not latest_step(): after a
        # guard-aborted run every committed step can carry a BAD marker,
        # and restore only walks good steps — gating on a BAD latest
        # would crash with NoCheckpointError instead of measuring fresh
        latest = manager.last_good_step()
        if ckpt.resume == "auto" and latest is not None:
            if latest < steps:
                start_step = manager.restore_training_state(model, opt)
            else:
                # a finished run's checkpoint would leave ZERO timed
                # steps and fabricate an absurd tokens/sec headline —
                # measure fresh instead (the committed steps remain)
                import sys

                print(f"# ckpt: latest committed step {latest} >= bench "
                      f"steps {steps}; measuring fresh (not resuming)",
                      file=sys.stderr)
        guard = PreemptionGuard(manager).install()

    # Resilience (--guard, docs/RESILIENCE.md): StepGuard wraps the
    # compiled step with the skip/rewind anomaly policy (the rewind is
    # CheckpointManager-backed when --ckpt-dir is set) and a HangWatchdog
    # heartbeats the timed loop, dumping debris under the checkpoint
    # root on a wedged step. The guard decision totals land in the
    # "resilience" block of the JSON line; tools/bench_gate.py fails a
    # clean run that reports any anomaly or rollback.
    step_guard = watchdog = None
    if ckpt is not None and getattr(ckpt, "guard", False):
        from paddle_tpu.resilience import HangWatchdog, StepGuard

        step_guard = StepGuard(step, manager=manager)
        # the watchdog always runs with --guard (the flag promises hang
        # protection): debris lands under the checkpoint root when one
        # exists, else in a temp dir named on stderr
        if manager is not None:
            debris_dir = os.path.join(manager.root, "debris")
        else:
            import sys
            import tempfile

            debris_dir = tempfile.mkdtemp(prefix="ptpu_bench_debris_")
            print(f"# --guard without --ckpt-dir: hang debris -> "
                  f"{debris_dir}", file=sys.stderr)
        watchdog = HangWatchdog(
            debris_dir,
            min_hang_seconds=float(
                os.environ.get("PTPU_HANG_SECONDS", "120"))).start()

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int64))

    loss = step(ids, labels)  # compile + warmup
    _ = float(loss.numpy())
    loss = step(ids, labels)
    _ = float(loss.numpy())

    bench_step = telemetry.histogram(
        "bench_step_seconds", "bench timed-loop per-step dispatch wall "
        "time (async: the device sync runs after the loop, so trailing "
        "device work shows up only in the tokens/sec line)")
    n_ran = 0
    t0 = time.perf_counter()
    t_prev = t0
    gstep = start_step + 1
    while gstep <= steps:
        # the "step" span is the anatomy root: everything recorded
        # inside (train_step/dispatch, ckpt phases, guard fetches)
        # decomposes it in trace.step_anatomy(). A no-op when tracing
        # is off (shared noop singleton).
        with ptrace.span("step", attrs={"step": gstep}, cat="step"):
            if watchdog is not None:
                watchdog.step_started(gstep)
            if step_guard is not None:
                out = step_guard(gstep, ids, labels)
                accepted, next_step = out.accepted, out.next_step
                if accepted:
                    loss = out.loss
            else:
                loss = step(ids, labels)
                accepted, next_step = True, gstep + 1
            if watchdog is not None:
                watchdog.step_finished()
            if accepted and manager is not None \
                    and gstep % ckpt.ckpt_every == 0:
                manager.save_training_state(gstep, model, opt,
                                            train_step=step,
                                            async_save=True)
        t_now = time.perf_counter()
        bench_step.observe(t_now - t_prev)
        t_prev = t_now
        if accepted:
            n_ran += 1
        # poll preemption on EVERY iteration, not only accepted ones: a
        # SIGTERM landing mid anomaly-retry storm must still commit the
        # (pre-anomaly, still-good) live state before the ladder can
        # abort. next_step-1 names the step the live trees correspond
        # to on every path (accept: gstep; skip: the last accepted
        # step; rollback: the restored step).
        if guard is not None and guard.should_stop():
            save_at = next_step - 1
            manager.wait()
            if save_at > start_step:
                manager.save_training_state(save_at, model, opt,
                                            train_step=step)
            break
        gstep = next_step
    _ = float(loss.numpy())  # sync
    dt = time.perf_counter() - t0
    if watchdog is not None:
        watchdog.stop()
    if manager is not None:
        manager.wait()  # surface any async writer failure before reporting
    if guard is not None:
        guard.uninstall()

    # dp-style loss sync over the default group: single-chip it degrades
    # to a no-op copy, but the collective call/byte counters it ticks are
    # exactly what a multi-chip run reports — the telemetry block always
    # carries the comms dimension
    import paddle_tpu.distributed as dist

    dist.all_reduce(loss, op=dist.ReduceOp.AVG)

    # "comms" block (docs/COMMS.md): bytes/calls/seconds per op+axis from
    # the telemetry counters, the exact-vs-int8 traffic split, and the
    # quantized-reduce parity probe tools/bench_gate.py gates on. On a
    # single chip the probe is skipped ({"enabled": false}) but the
    # per-op accounting still lands — the knob state is always visible.
    from paddle_tpu.distributed import collectives as _coll
    from paddle_tpu.distributed.fleet import active_mesh as _active_mesh

    comms = _coll.comms_summary(
        telemetry.snapshot(),
        parity=_coll.parity_probe(_active_mesh()))

    # "quant" block (docs/QUANT.md): the scaled fp8/int8 GEMM state of
    # THIS run — the request (candidate quant axis -> policy quant:
    # entries), the trace-time engagement verdict (compose's quant_gemm
    # plan row: engaged, or the structured decline reason), the numeric
    # parity-gate report, and an embedded reference-free loss-drift A/B
    # (exact vs scaled training on a fixed tiny problem, quant.gemm
    # loss_drift_probe) that tools/bench_gate.py's QUANT gate checks
    # against the 0.5% budget — no baseline file needed, like the comms
    # parity probe above.
    from paddle_tpu.distributed.collectives import compose as _compose_q

    _qv = _compose_q.last_verdicts().get("quant_gemm")
    _q_requested = bool(getattr(decision, "quant", None))
    quant_block = {
        "requested": _q_requested,
        "dtype": _pquant.quant_dtype(),
        "engaged": bool(_qv and _qv[0] == "engaged"),
        "verdict": _qv[0] if _qv else None,
        "reason": _qv[1] if _qv else None,
        "gate": _pquant.quant_gate_report(),
        "loss_drift_rel": round(float(_pquant.loss_drift_probe()), 6),
        "loss_drift_budget": 0.005,
        "amax_hist_len": _pquant.amax_hist_len(),
    }

    # "zero" block (docs/ZERO.md): the ZeRO execution state of THIS run —
    # stage/degree always recorded; when the plan engaged, the per-step
    # gathered-bytes / reduce-scattered-bytes accounting and param-kind
    # counts land next to "comms"/"memory". A degree-1 run records
    # engaged=false (the honest single-chip degenerate).
    zplan = step.zero_plan() if hasattr(step, "zero_plan") else None
    zero_block = (zplan.zero_summary() if zplan is not None
                  else {"engaged": False, "stage": zero_stage,
                        "shard_degree": zero_degree})

    # "pipe" block (docs/PIPELINE.md): pipeline-schedule state + bubble
    # accounting. Engagement comes from the composed plan
    # (collectives/compose); the bubble fractions are priced from
    # MEASURED per-phase stage costs on this host (pipeline.bubble_report
    # — wall-clocking the ring on a core-shared CPU mesh measures
    # contention, not idleness, docs/ZB_WALLCLOCK.md). Without a live pp
    # axis the reference pp=2 x n_micro=4 shape keeps the schedule
    # arithmetic tracked round over round; bench_gate's PIPE gate fails
    # a bubble fraction over the 1F1B budget or a pp-live mesh whose
    # composition never engaged.
    from paddle_tpu.distributed import pipeline as _pl

    cplan = (step.composed_plan()
             if hasattr(step, "composed_plan") else None)
    pp_engaged = bool(cplan is not None and cplan.pp_axis)
    _mesh_b = _active_mesh()
    pp_live = bool(_mesh_b is not None and "pp" in _mesh_b.dim_names
                   and _mesh_b.get_dim_size("pp") > 1)
    from paddle_tpu.distributed.collectives import compose as _compose_b

    # an escape-hatch knob explicitly disabling composition is an
    # intended A/B baseline, not a silent decline — recorded so the
    # PIPE gate only fails the "enabled-but-never-engaged" case.
    # composed_enabled() folds the PTPU_QUANT_COLLECTIVES master knob
    disabled_by_knob = bool(
        not _compose_b.composed_enabled()
        or _compose_b.pipeline_schedule_disabled())
    # the structured why-not for a pp-live mesh without a schedule: a
    # pp-replicated decoder (no stage placements) engages composition
    # without a pipeline row; otherwise the composed plan's own decline
    # reason carries the story. The PIPE gate passes the documented
    # config-shape declines and fails everything silent.
    decline_reason = None
    if pp_live and not pp_engaged:
        if cplan is not None:
            decline_reason = "no_stage_placements"
        else:
            _v = _compose_b.last_verdicts().get("composed")
            decline_reason = _v[1] if _v else None
    pipe_block = dict(
        _pl.bubble_report(
            cplan.pp if pp_engaged else 2,
            cplan.n_micro if pp_engaged else 4,
            schedule=(cplan.pp_schedule if pp_engaged
                      else getattr(cfg, "pp_schedule", "1f1b") or "1f1b")),
        engaged=pp_engaged, pp_axis_live=pp_live,
        disabled_by_knob=disabled_by_knob,
        decline_reason=decline_reason)

    # "compile" block (docs/SCAN.md): trace/lower/compile wall seconds +
    # serialized HLO bytes of THIS run's warmup TrainStep build, with the
    # depth and scan mode that produced them — the measurement behind the
    # scan-over-layers flat-compile claim. tools/bench_gate.py fails a
    # round whose compile time regresses >25% at the same depth/mode.
    from paddle_tpu import jit as pjit
    from paddle_tpu.models.gpt import scan_layers_enabled

    step_label = f"TrainStep[{type(model).__name__}]"
    compile_block = dict(pjit.compile_summary(step_label) or {},
                         function=step_label,
                         num_layers=cfg.num_layers,
                         scan_layers=bool(scan_layers_enabled()))

    tokens_per_sec = batch * seq * max(n_ran, 1) / dt

    # "anatomy" block (docs/TELEMETRY.md Tracing): the traced run's
    # per-phase decomposition of the timed loop, the cost-analysis
    # device estimate vs measured wall (host gap), and where the full
    # trace files landed. {"enabled": false} without --trace.
    anatomy = {"enabled": False}
    if trace_on:
        measured = dt / max(n_ran, 1)
        anat = ptrace.step_anatomy() or {}
        cost = (step.last_dispatch_cost()
                if hasattr(step, "last_dispatch_cost") else None)
        device = None
        if cost:
            dev = cost["device_seconds_est"]
            host_gap = max(0.0, measured - dev)
            placeholder = bool(cost["peak_model_placeholder"])
            device = {
                "flops_per_step": cost["flops"],
                "bytes_accessed_per_step": cost["bytes_accessed"],
                "device_seconds_est_per_step": round(dev, 6),
                "host_gap_seconds_per_step": round(host_gap, 6),
                # the host-overhead bench_gate input: None (not gated)
                # when the roofline peaks are placeholders (CPU dev)
                "host_gap_fraction": (round(host_gap / measured, 4)
                                      if measured > 0 and not placeholder
                                      else None),
                # cost-analysis MFU, alongside the measured "mfu" field:
                # program FLOPs over measured step wall over chip peak
                # (null on placeholder peaks — a CPU number would read
                # as a real attribution)
                "cost_mfu": (round(cost["flops"]
                                   / (measured * cost["peak_flops"]), 4)
                             if measured > 0 and not placeholder
                             else None),
                "peak_model_placeholder": placeholder,
            }
        os.makedirs(trace_dir, exist_ok=True)
        perfetto_path = os.path.join(
            trace_dir, f"trace_{model_kind}.perfetto.json")
        jsonl_path = os.path.join(trace_dir, f"trace_{model_kind}.jsonl")
        ptrace.to_perfetto(perfetto_path)
        ptrace.dump_jsonl(jsonl_path)
        anatomy = {
            "enabled": True,
            "steps_timed": max(n_ran, 1),
            "measured_step_seconds": round(measured, 6),
            "span_step_seconds_mean": anat.get("step_seconds_mean"),
            "phases": anat.get("phases") or {},
            "coverage": anat.get("coverage"),
            "device": device,
            "trace_files": {"perfetto": perfetto_path,
                            "jsonl": jsonl_path},
        }

    # fleet-serving smoke soak (--serve / PTPU_BENCH_SERVE=1): only on
    # the headline (non-llama) line so the driver pays one soak per run
    serving = {"enabled": False}
    serve_on = (bool(ckpt is not None and getattr(ckpt, "serve", False))
                or os.environ.get("PTPU_BENCH_SERVE", "") not in ("", "0"))
    if serve_on and model_kind != "llama":
        serving = _serving_smoke_block()

    # MFU: 6 * params * tokens/sec / peak_flops
    n_params = sum(int(np.prod(p.shape)) for _, p in model.named_parameters())
    model_flops = 6.0 * n_params * tokens_per_sec
    # bf16 peak per chip from the one chip table (an unknown TPU kind
    # raises; the CPU row is a flagged placeholder and its "mfu" rides
    # under the CPU smoke's own metric name)
    peak = chip_peaks()[0]["bf16_flops"]
    mfu = model_flops / peak

    if on_tpu:
        metric = ("llama7b_arch_8L_pretrain_tokens_per_sec"
                  if model_kind == "llama"
                  else "gpt3_1.3b_pretrain_tokens_per_sec")
    else:
        metric = "gpt_pretrain_tokens_per_sec"

    timeline_block = {"enabled": False}
    if ts_recorder is not None:
        ts_recorder.sample()        # the final totals land in the file
        ts_recorder.close()
        timeline_block = {
            "enabled": True,
            "path": ts_recorder.jsonl_path,
            "samples": ts_recorder.seq,
            "dropped": ts_recorder.dropped,
            "interval_seconds": record_interval,
        }
    print(json.dumps({
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "device": device_record(),
        "vs_baseline": round(mfu, 4),
        # explicit MFU field (same value as vs_baseline, which predates
        # it): model FLOPs 6*params*tokens/sec over the chip's bf16 peak
        # from paddle_tpu.device.CHIP_PEAKS — the driver-tracked headline
        "mfu": round(mfu, 4),
        # planner decision + XLA memory_analysis peak: a BENCH_r*.json
        # regression explains its memory state the same way the
        # "telemetry" key explains its time (tools/hbm_report.py diffs
        # two rounds' blocks; contract in docs/MEMORY.md)
        "memory": decision.as_json(),
        # layout autotuner outcome (--autotune / PTPU_AUTOTUNE=1,
        # docs/AUTOTUNE.md): winner + top-3 scored candidates, pruned
        # counts by compose Reason, search seconds — bench_gate's
        # LAYOUT gate fails a winner whose predicted score loses to
        # the hand-picked baseline or a silent fallback.
        # {"enabled": false} without the flag.
        "layout": layout_block,
        # guard decision totals (docs/RESILIENCE.md): a CLEAN bench run
        # must report zero anomalies and zero rollbacks — bench_gate
        # exits 1 otherwise. {"enabled": false} when --guard is off.
        # comms traffic split + parity probe (mirrors "telemetry"/
        # "memory"; contract in docs/COMMS.md, gated by bench_gate)
        "comms": comms,
        # low-precision compute state: request/engagement/decline, the
        # parity-gate report, and the embedded loss-drift A/B vs the
        # 0.5% budget (docs/QUANT.md; bench_gate QUANT gate)
        "quant": quant_block,
        # ZeRO execution state: stage, shard degree, gathered/rs bytes
        # per step (docs/ZERO.md contract)
        "zero": zero_block,
        # pipeline schedule + measured-cost bubble accounting
        # (docs/PIPELINE.md; bench_gate PIPE gate)
        "pipe": pipe_block,
        # warmup-build compile phases + HLO program size (docs/SCAN.md)
        "compile": compile_block,
        # fleet-serving smoke soak (--serve; docs/SERVING.md): replica
        # cold start + goodput scaling + p99 TTFT vs budget, gated by
        # bench_gate's SERVE/COLD gates
        "serving": serving,
        # background time-series recording (--record; docs/TELEMETRY.md
        # "Time series, SLOs..."): cadence samples of the registry in a
        # JSONL timeline next to the bench output, inspected by
        # tools/telemetry_report.py --timeline
        "timeline": timeline_block,
        # step anatomy from the span tracer (--trace / PTPU_TRACE=1):
        # per-phase seconds, device-vs-host split from cost_analysis,
        # cost-analysis MFU next to the measured "mfu" field, and the
        # exported trace file paths (docs/TELEMETRY.md Tracing;
        # tools/bench_gate.py gates host_gap_fraction)
        "anatomy": anatomy,
        "resilience": (dict(step_guard.summary(),
                            watchdog_fires=(len(watchdog.debris_files)
                                            if watchdog is not None else 0))
                       if step_guard is not None else {"enabled": False}),
        "telemetry": telemetry.snapshot(),
    }), flush=True)


def main():
    import argparse
    import gc
    import logging

    ap = argparse.ArgumentParser(
        description="paddle_tpu headline pretrain benchmark")
    ap.add_argument("--ckpt-dir", default=os.environ.get("PTPU_BENCH_CKPT")
                    or None, help="enable crash-safe checkpointing under "
                    "this root (docs/CHECKPOINT.md)")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="async committed save every N steps")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retention: newest N committed steps")
    ap.add_argument("--resume", choices=("auto", "none"), default="auto",
                    help="auto = restore the newest committed step")
    ap.add_argument("--trace", action="store_true",
                    default=os.environ.get("PTPU_TRACE", "")
                    not in ("", "0"),
                    help="span tracer ON for the run: Perfetto + JSONL "
                    "trace files and an 'anatomy' block in the JSON "
                    "line (docs/TELEMETRY.md Tracing)")
    ap.add_argument("--trace-dir", default=".",
                    help="where trace_<model>.perfetto.json / .jsonl "
                    "land (default: cwd)")
    ap.add_argument("--serve", action="store_true",
                    default=os.environ.get("PTPU_BENCH_SERVE", "")
                    not in ("", "0"),
                    help="attach a fleet-serving smoke soak block "
                    "(replica cold start, goodput scaling, p99 TTFT) "
                    "to the headline JSON line (docs/SERVING.md)")
    ap.add_argument("--guard", action="store_true",
                    default=os.environ.get("PTPU_BENCH_GUARD", "")
                    not in ("", "0"),
                    help="StepGuard anomaly policy + hang watchdog around "
                    "the timed loop (docs/RESILIENCE.md); decision totals "
                    "land in the JSON 'resilience' block")
    ap.add_argument("--record", action="store_true",
                    default=os.environ.get("PTPU_RECORD", "")
                    not in ("", "0"),
                    help="record a background time-series timeline "
                    "(registry samples every --record-interval seconds) "
                    "into timeline_<model>.jsonl next to the bench "
                    "output; adds the 'timeline' block to the JSON line "
                    "(docs/TELEMETRY.md)")
    ap.add_argument("--record-interval", type=float, default=None,
                    help="seconds between --record samples "
                    "(default 0.5, or PTPU_RECORD_INTERVAL)")
    ap.add_argument("--autotune", action="store_true",
                    default=os.environ.get("PTPU_AUTOTUNE", "")
                    not in ("", "0"),
                    help="route the headline lines through the layout "
                    "autotuner (mesh/schedule search over the compose "
                    "lattice, docs/AUTOTUNE.md); adds the 'layout' "
                    "block to the JSON line")
    ap.add_argument("--long-context", action="store_true",
                    default=os.environ.get("PTPU_BENCH_LONG", "")
                    not in ("", "0"),
                    help="additionally emit the *_seq32k long-context "
                    "metric line: ring attention over a sep mesh "
                    "(32k tokens on TPU; reduced-length CPU smoke) — "
                    "docs/ATTENTION.md")
    args = ap.parse_args()

    # surface which attention path ran (proof the Pallas kernel engaged)
    logging.basicConfig()
    logging.getLogger("paddle_tpu.pallas").setLevel(logging.INFO)

    from paddle_tpu.device import compile_cache_dir, require_accelerator

    on_tpu = require_accelerator("bench.py")
    compile_cache_dir()
    kind = os.environ.get("PTPU_BENCH_MODEL")
    if kind is not None or not on_tpu:
        if args.long_context:
            run_long_context(ckpt=args)
            gc.collect()
        run_model(kind or "gpt", ckpt=args)
        return
    # default driver run: BOTH tracked lines — config-5 (LLaMA-arch)
    # FIRST, the headline GPT line LAST so the parsed metric stays stable
    run_model("llama", ckpt=args)
    gc.collect()
    if args.long_context:
        run_long_context(ckpt=args)
        gc.collect()
    run_model("gpt", ckpt=args)


if __name__ == "__main__":
    main()
