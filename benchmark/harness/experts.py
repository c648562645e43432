"""Readers of what an expert layer recorded of itself: the routed counts
on the program's ``decode_tick`` and ``prefill_tick`` spans
(``local_pairs``, ``experts_hit``: docs/TELEMETRY.md) against the device
time of the grouped GEMM. A program without these attrs (a dense model, a
parent commit) gives None and the metric is left out."""
from __future__ import annotations

from . import families, program, roofline, trace


def grouped_gemm_roofline(ctx, patterns, spans):
    """Least time of the window's grouped expert GEMMs over the time the
    matching ops took. A tick's least time is the larger of its routed
    pairs' FLOPs (2 x three matrices a pair) over the peak and of the
    hit experts' weights, read once each, over the bandwidth; activations
    are not counted, so the share is a floor's and cannot pass 100%."""
    tr = ctx.get("trace")
    family = families.of(ctx["config"])
    if tr is None or not hasattr(family, "expert_params"):
        return None
    ticks = [a for span in spans for a in program.in_window(ctx, "X", span)
             if a.get("local_pairs") is not None]
    _, seconds = trace.op_calls_seconds(tr, patterns)
    if not ticks or not seconds:
        return None
    per = family.expert_params(ctx["config"])
    least = sum(roofline.least_seconds(
        2 * a["local_pairs"] * per, 2 * a["experts_hit"] * per,
        ctx["peaks"]) for a in ticks)
    return 100.0 * least / seconds
