"""The reader of where a warm set-up went, as the program measured it: the
``setup`` instants that a ``ContinuousBatchingEngine`` and a ``TrainStep``
publish once, at their first traced step inside the window, with the sums of
their programs' trace, lowering, load and first-run seconds and the seconds
since they finished warming (``paddle_tpu.jit.SetupRecord``). A program that
publishes no such instant gives None and the metric is left out."""
from __future__ import annotations

from .program import in_window


def setup_sum(ctx, attr):
    """Sum of one attr over the window's ``setup`` instants that carry it."""
    vals = [a[attr] for a in in_window(ctx, "i", "setup")
            if a.get(attr) is not None]
    return sum(vals) if vals else None
