"""The yardstick's arithmetic: the chip's published peaks and the readers
of the per-layer metrics that rest on them. The model FLOPs a token needs
and what a kernel call must compute and move are the configuration's
family's (harness/families/). Nothing here is measured."""
from __future__ import annotations

from . import families

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
    maps an op-name pattern to a name in the family's KERNEL_WORK; the
    shapes come from the run (counters batch, seq)."""
    from . import trace as _trace

    tr = ctx.get("trace")
    if tr is None:
        return None
    least = took = 0.0
    c = ctx["counters"]
    kernel_work = families.of(ctx["config"]).KERNEL_WORK
    for pattern, work in kernels.items():
        calls, seconds = _trace.op_calls_seconds(tr, [pattern])
        if not calls:
            continue
        fl, nb = kernel_work[work](ctx["config"], c["batch_per_chip"],
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
