"""StepGuard: skip-and-rewind policy over a compiled train step.

Detection is in-graph (jit.TrainStep computes the StepHealth bundle and
applies the skip select); this module is the HOST-side policy: the
rolling spike threshold fed into the step, the consecutive-anomaly
escalation ladder, the CheckpointManager-backed rewind, and the loud
abort. Contract and knobs: docs/RESILIENCE.md.
"""
from __future__ import annotations

import collections
import math
import statistics
from typing import NamedTuple, Optional

from .. import telemetry as _telemetry
from ..telemetry import flight as _flight

_ANOMALIES = _telemetry.counter(
    "guard_anomalies_total",
    "anomalous train steps by detection kind",
    labelnames=("kind",))
_SKIPS = _telemetry.counter(
    "guard_skips_total",
    "train-step updates discarded in-graph (pre-step state kept)")
_ROLLBACKS = _telemetry.counter(
    "guard_rollbacks_total",
    "checkpoint rewinds after persistent anomalies")
_LAST_GOOD = _telemetry.gauge(
    "guard_last_good_step",
    "newest global step the guard accepted")


class StepHealth(NamedTuple):
    """Host view of the fused in-graph health bundle (one device fetch)."""

    finite: bool      # loss AND every grad leaf all-finite
    grad_norm: float  # global L2 grad norm (the clip reduction, reused)
    loss: float       # this step's loss, as float32
    ok: bool          # finite AND loss <= spike threshold (update adopted)

    @property
    def kind(self) -> Optional[str]:
        """Detection kind of the anomaly, or None when healthy.

        Independent of ``ok``: an UNGUARDED nonfinite step adopts its
        update (ok=True, legacy semantics) but still reports
        ``kind == "nonfinite"`` — monitoring that polls ``last_health``
        must see the anomaly, per the module contract."""
        if not self.finite:
            return "nonfinite"
        return None if self.ok else "spike"


class StepOutcome(NamedTuple):
    """What the guard decided for one attempted global step."""

    step: int             # the global step that was attempted
    action: str           # "accept" | "skip" | "rollback"
    loss: object          # Tensor on accept, None otherwise
    health: StepHealth
    next_step: int        # where the loop continues: step+1 on accept,
                          # step on skip (retry), last_good+1 on rollback
    restored_step: Optional[int] = None  # rollback only

    @property
    def accepted(self) -> bool:
        return self.action == "accept"


class GuardAbortError(RuntimeError):
    """The escalation ladder is exhausted — stop the run loudly.

    Raised when K consecutive anomalies persist with no manager to rewind
    through, or when R rollbacks did not cure the anomaly. A supervisor
    must treat this as a poisoned run, not a preemption."""


class StepGuard:
    """Anomaly policy around a ``jit.TrainStep`` / ``ShardedTrainStep``.

    Usage (the loop owns the step counter; the guard owns the verdict)::

        guard = StepGuard(step, manager=ckpt_manager)
        gstep = start + 1
        while gstep <= total:
            out = guard(gstep, *batch_for(gstep))
            if out.accepted:
                consume(out.loss)          # checkpoint, log, ...
            gstep = out.next_step          # retry / rewind / advance

    Args:
        train_step: the compiled step (must expose ``_guard_threshold``,
            ``last_health``, ``model``, ``optimizer``, ``_opt_state``).
        manager: CheckpointManager for the escalation rewind (None =
            skip-only policy; K consecutive anomalies then abort).
        window / min_history: rolling loss window for the spike
            threshold; below ``min_history`` accepted losses no spike
            detection happens (threshold +inf).
        zmax: spike threshold = median + zmax * MAD-scale of the window.
        max_consecutive (K): consecutive anomalies before escalating
            from skip to rollback.
        max_rollbacks (R): rollbacks before ``GuardAbortError``.
    """

    def __init__(self, train_step, manager=None, window=32, zmax=8.0,
                 min_history=8, max_consecutive=3, max_rollbacks=2):
        self.train_step = train_step
        self.manager = manager
        self.zmax = float(zmax)
        self.min_history = int(min_history)
        self.max_consecutive = max(1, int(max_consecutive))
        self.max_rollbacks = int(max_rollbacks)
        # (step, loss) of accepted steps, step-ordered: a rollback trims
        # entries above the restored step instead of clearing, so spike
        # detection stays live through the replay (a cleared window
        # would ACCEPT the very spike the rewind was meant to cure)
        self._losses = collections.deque(maxlen=int(window))
        self._consecutive = 0
        self._last_restore = None
        # post-accept (RNG state, optimizer._step_count) per recent
        # step: a rollback to step S restores S's stream so replayed
        # steps draw the SAME keys the clean run drew, and S's step
        # count so replays don't double-increment it (window-bounded;
        # rewinds reaching further back than this keep
        # deterministic-model bitwise parity only)
        self._rng_history = {}
        self._rng_window = 1024
        # run totals (the bench "resilience" block reads these)
        self.anomalies = {}          # kind -> count
        self.skips = 0
        self.rollbacks = 0
        self.last_good_step = None
        self.aborted = False

    # -- detection inputs ----------------------------------------------------
    def spike_threshold(self) -> float:
        """Rolling median + zmax·MAD upper bound on an acceptable loss.

        The MAD scale is floored (1e-3 of the median's magnitude) so a
        perfectly flat window does not flag the first sub-ulp wiggle."""
        losses = [loss for _, loss in self._losses]
        if len(losses) < self.min_history:
            return math.inf
        med = statistics.median(losses)
        mad = statistics.median(abs(x - med) for x in losses)
        scale = max(1.4826 * mad, 1e-3 * max(1.0, abs(med)))
        return med + self.zmax * scale

    # -- the verdict ---------------------------------------------------------
    def __call__(self, step, *batch) -> StepOutcome:
        from .. import framework

        step = int(step)
        # RNG discipline: a discarded attempt must not shift the random
        # stream (dropout masks etc.) relative to the clean run the
        # guard reproduces — restore the pre-attempt state on skip, and
        # the restored step's post-accept state on rollback, so accepted
        # steps consume exactly one key each, in clean-run order.
        rng_before = framework._rng_key_state()
        # arm the in-graph skip ONLY for this driven call: a later direct
        # call on the raw step must get legacy adopt-everything semantics,
        # not a frozen stale threshold silently discarding its updates
        self.train_step._guard_threshold = self.spike_threshold()
        try:
            loss = self.train_step(*batch)
        finally:
            self.train_step._guard_threshold = None
        # the one extra device fetch — under tracing it gets its own
        # span, because under async dispatch this is where a guarded
        # loop actually blocks on the device
        with _telemetry.trace.span("guard:health_fetch",
                                   attrs={"step": step}, cat="step"):
            health = self.train_step.last_health
        if health.ok:
            self._consecutive = 0
            # accepted progress proves the last rewind target CURED its
            # episode: a later, independent episode rewinding to the
            # same (still-newest) commit must not mark_bad a good state
            self._last_restore = None
            self._losses.append((step, health.loss))
            self.last_good_step = step
            _LAST_GOOD.set(step)
            # post-accept (rng, optimizer step count): a rollback to this
            # step restores BOTH, so replayed steps draw clean-run keys
            # AND re-increment _step_count from the restored value
            # instead of double-counting (the checkpoint itself persists
            # only tensors, never "@step")
            self._rng_history[step] = (framework._rng_key_state(),
                                       self.train_step.optimizer._step_count)
            while len(self._rng_history) > self._rng_window:
                self._rng_history.pop(next(iter(self._rng_history)))
            return StepOutcome(step, "accept", loss, health, step + 1)
        framework._set_rng_key_state(rng_before)
        # the in-graph select discarded the update, so the attempt must
        # not count as an optimizer step: a step-6 checkpoint's "@step"
        # must equal the clean run's 6, not the attempt count (health is
        # already fetched — this costs no extra sync; unguarded anomalies
        # ADOPT the update, so their increment stands)
        self.train_step.optimizer._step_count -= 1

        kind = health.kind
        _ANOMALIES.inc(labels=(kind,))
        _telemetry.trace.instant("guard:anomaly",
                                 {"step": step, "kind": kind}, cat="step")
        self.anomalies[kind] = self.anomalies.get(kind, 0) + 1
        self._consecutive += 1
        if self._consecutive < self.max_consecutive:
            # the update was already discarded in-graph; retry the step
            _SKIPS.inc()
            self.skips += 1
            return StepOutcome(step, "skip", None, health, step)

        # escalate: K consecutive anomalies on the same pre-step state
        if self.manager is None:
            self.aborted = True
            # forensics before the raise: the flight bundle carries the
            # recent sample/alert window the exception message cannot
            _flight.maybe_dump("guard_abort", {
                "step": int(step), "kind": kind,
                "consecutive": self._consecutive,
                "loss": repr(health.loss),
                "grad_norm": repr(health.grad_norm),
                "why": "no CheckpointManager to rewind through"})
            raise GuardAbortError(
                f"step {step}: {self._consecutive} consecutive "
                f"{kind} anomalies and no CheckpointManager to rewind "
                f"through (loss={health.loss!r}, "
                f"grad_norm={health.grad_norm!r})")
        if self.rollbacks >= self.max_rollbacks:
            self.aborted = True
            _flight.maybe_dump("guard_abort", {
                "step": int(step), "kind": kind,
                "rollbacks": self.rollbacks,
                "max_rollbacks": self.max_rollbacks,
                "why": "max_rollbacks exhausted"})
            raise GuardAbortError(
                f"step {step}: {kind} anomaly persisted through "
                f"{self.rollbacks} checkpoint rollbacks "
                f"(max_rollbacks={self.max_rollbacks}); the run is "
                f"poisoned — refusing to continue")
        restored = self._rollback(step)
        return StepOutcome(step, "rollback", None, health, restored + 1,
                           restored_step=restored)

    def _rollback(self, step) -> int:
        mgr = self.manager
        mgr.wait()  # pending async saves must land before we pick a target
        if self._last_restore is not None:
            # _last_restore survives only while NO step has been
            # accepted since the previous rewind (accepts clear it): the
            # state we ACTUALLY restored — which can sit below the
            # newest good step when restore fell back past a corrupt
            # one — did not cure the anomaly, so mark IT bad and reach
            # further back. Comparing against last_good_step() instead
            # would never match the fallback-restored step and the
            # ladder would re-land on the same poisoned state forever.
            mgr.mark_bad(self._last_restore,
                         reason=f"anomaly recurred by step {step}")
        from ..distributed.checkpoint.manager import NoCheckpointError

        try:
            restored = mgr.restore_last_good(
                self.train_step.model, self.train_step.optimizer,
                before_step=step)
        except NoCheckpointError as e:
            self.aborted = True
            _flight.maybe_dump("guard_abort", {
                "step": int(step), "error": repr(e),
                "why": "no good committed checkpoint remains"})
            raise GuardAbortError(
                f"step {step}: rewind needed but no good committed "
                f"checkpoint remains ({e})") from e
        # the compiled step must reseed its functional slots from the
        # restored eager slots (jit._init_opt_state), not keep the
        # poisoned in-flight tree
        self.train_step._opt_state = None
        # rewind the RNG stream with the state: replayed steps must draw
        # the keys the clean run drew at those steps
        from .. import framework

        hist = self._rng_history.get(restored)
        if hist is not None:
            rng, step_count = hist
            framework._set_rng_key_state(rng)
            self.train_step.optimizer._step_count = step_count
            for s in [s for s in self._rng_history if s > restored]:
                self._rng_history.pop(s)
        self._last_restore = restored
        self._consecutive = 0
        # trim (never clear) the window to the restored step: replayed
        # steps reproduce exactly the trimmed-away losses, and keeping
        # the older history means the recurring spike is re-flagged on
        # its first replayed attempt — clearing would return +inf
        # thresholds for min_history steps, adopt the spike, and poison
        # the rolling median with it (the ladder then never aborts)
        while self._losses and self._losses[-1][0] > restored:
            self._losses.pop()
        self.rollbacks += 1
        _ROLLBACKS.inc()
        return restored

    # -- reporting -----------------------------------------------------------
    def summary(self) -> dict:
        """JSON-able run totals (the "resilience" block that
        tools/bench_gate.py gates on)."""
        return {
            "enabled": True,
            "anomalies": dict(self.anomalies),
            "anomalies_total": sum(self.anomalies.values()),
            "skips": self.skips,
            "rollbacks": self.rollbacks,
            "last_good_step": self.last_good_step,
            "aborted": self.aborted,
        }
