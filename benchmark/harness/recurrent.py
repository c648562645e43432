"""Readers of what a recurrent layer recorded of itself: the rows whose
state each decode tick stepped (``state_rows`` on the program's
``decode_tick`` spans: docs/TELEMETRY.md) against the device time of the
kernel that steps it. A program without these attrs (a model that keeps no
state by slot, a parent commit) gives None and the metric is left out."""
from __future__ import annotations

from . import families, program, trace


def state_step_roofline(ctx, patterns, spans):
    """Bytes of recurrent state the window's ticks had to move (each live
    row's state over all linear layers, read once and written once) over
    the bytes the chip could have moved in the time the matching ops
    took. q, k, v and o (1/96 of the state) are not counted, so the
    share is a floor's and cannot pass 100%."""
    tr = ctx.get("trace")
    family = families.of(ctx["config"])
    if tr is None or not hasattr(family, "state_bytes_per_row"):
        return None
    rows = [a["state_rows"] for span in spans
            for a in program.in_window(ctx, "X", span)
            if a.get("state_rows") is not None]
    _, seconds = trace.op_calls_seconds(tr, patterns)
    if not rows or not seconds:
        return None
    need = 2 * sum(rows) * family.state_bytes_per_row(ctx["config"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
