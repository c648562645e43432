"""The yardstick's arithmetic: the chip's published peaks, the model FLOPs
a token needs, what a kernel call must compute and move, and the readers
of the per-layer metrics that rest on them. Nothing here is measured."""
from __future__ import annotations

#: Published per-chip peaks by jax ``device_kind`` (Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
#: 819 GB/s; copied from paddle_tpu.device.CHIP_PEAKS). An unknown kind is
#: an error, not a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(kind):
    if kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "it to benchmark/harness/roofline.py with its source")
    return CHIP_PEAKS[kind]


def matmul_params(cfg):
    """Parameters that multiply every token: the projections of every
    layer and the head (the embedding lookup multiplies nothing)."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * m
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * h


def train_flops_per_token(cfg, seq):
    """6 N for the matmuls forward and backward, plus causal attention
    (two matmuls forward, four backward, over half the square): 6 L h S.
    Recomputed operations do not count."""
    return (6 * matmul_params(cfg)
            + 6 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def serve_flops(cfg, positions):
    """Forward FLOPs of tokens processed at the given absolute positions
    (prefill or decode alike): 2 N each, plus attention over the context
    before it, 4 L h per position of context."""
    n, ctx = len(positions), sum(positions)
    return (2 * matmul_params(cfg) * n
            + 4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * ctx)


# ------------------------------------------------- kernel work, per call
def flash_fwd_work(cfg, batch, seq):
    """Causal flash forward of one layer: (flops, bytes). QK^T and PV over
    half the square; reads q, k, v and writes o (bf16) and the lse (f32)."""
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    flops = 2 * batch * seq * seq * h
    nbytes = 2 * batch * seq * (2 * h + 2 * kv) \
        + 4 * batch * seq * cfg["num_attention_heads"]
    return flops, nbytes


def flash_bwd_work(cfg, batch, seq):
    """Causal flash backward: five matmuls (scores again, dv, dp, dq, dk)
    over half the square; reads q, k, v, o, do, lse and writes dq, dk, dv."""
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    flops = 5 * batch * seq * seq * h
    nbytes = 2 * batch * seq * (4 * h + 4 * kv) \
        + 4 * batch * seq * cfg["num_attention_heads"]
    return flops, nbytes


KERNEL_WORK = {"flash_fwd": flash_fwd_work, "flash_bwd": flash_bwd_work}


def least_seconds(flops, nbytes, pk):
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])


# ----------------------------------------------------------------- readers
# A reader takes the run's context (counters, spans and the reduced trace)
# and its arguments from the metric's file; it returns None when there is
# nothing to read, never 0 for a share of a peak.
def mfu(ctx, flops="model_flops"):
    """The whole window's model FLOPs over window, chips and peak."""
    f = ctx["counters"].get(flops)
    if not f:
        return None
    return 100.0 * f / (ctx["window_s"] * ctx["chips"]
                        * ctx["peaks"]["bf16_flops"])


def kernel_roofline(ctx, kernels):
    """Least time of the calls seen over the time they took. ``kernels``
    maps an op-name pattern to a KERNEL_WORK function name; the shapes
    come from the run (counters batch, seq)."""
    from . import trace as _trace

    tr = ctx.get("trace")
    if tr is None:
        return None
    least = took = 0.0
    c = ctx["counters"]
    for pattern, work in kernels.items():
        calls, seconds = _trace.op_calls_seconds(tr, [pattern])
        if not calls:
            continue
        fl, nb = KERNEL_WORK[work](ctx["config"], c["batch_per_chip"],
                                   c["seq"])
        least += calls * least_seconds(fl, nb, ctx["peaks"])
        took += seconds
    return 100.0 * least / took if took else None


def bytes_roofline(ctx, patterns, nbytes):
    """Bytes the algorithm must move (a counter) over the bytes the chip
    could have moved in the time the matching ops took."""
    from . import trace as _trace

    tr, need = ctx.get("trace"), ctx["counters"].get(nbytes)
    if tr is None or not need:
        return None
    _, seconds = _trace.op_calls_seconds(tr, patterns)
    if not seconds:
        return None
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
