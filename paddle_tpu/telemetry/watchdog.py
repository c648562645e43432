"""Recompile watchdog — the single most common silent TPU perf killer.

Every jit-cache miss in the framework (StaticFunction program-cache
misses, TrainStep builds, serving decode-step retraces) reports here as a
(function, abstract-shape-signature) pair. The watchdog keeps the set of
distinct signatures per function; when one function crosses the
threshold it emits a ``RecompileWarning`` naming the function and its
recent signatures — a varying python scalar or an unpadded dynamic shape
is almost always the cause.

Counts land in the shared registry as ``jit_recompiles_total{function}``
so bench snapshots and Prometheus scrapes expose compile churn even when
the warning threshold is never crossed.
"""
from __future__ import annotations

import os
import threading
import time
import warnings


class RecompileWarning(UserWarning):
    """N distinct compilations observed for one traced function."""


DEFAULT_THRESHOLD = int(os.environ.get("PTPU_RECOMPILE_WARN", "5"))

_MAX_SIG_HISTORY = 8


class RecompileWatchdog:
    def __init__(self, registry, threshold=None):
        self._registry = registry
        self.threshold = (DEFAULT_THRESHOLD if threshold is None
                          else int(threshold))
        self._lock = threading.Lock()
        self._sigs = {}    # fn name -> set of distinct signatures
        self._recent = {}  # fn name -> last few signature reprs
        self._warned = set()
        self._counter = registry.counter(
            "jit_recompiles_total",
            "distinct jit compilations per traced function",
            labelnames=("function",))

    def configure(self, threshold):
        self.threshold = int(threshold)
        return self

    def record(self, fn_name, signature):
        """Report one jit-cache miss. `signature` must be hashable (the
        abstract shape/dtype/guard key the cache missed on)."""
        if not self._registry.enabled:
            return
        self._counter.inc(labels=(fn_name,))
        with self._lock:
            sigs = self._sigs.setdefault(fn_name, set())
            if signature in sigs:
                return  # same program recompiled (e.g. cache eviction):
                        # counted above, but not a NEW shape signature
            sigs.add(signature)
            recent = self._recent.setdefault(fn_name, [])
            recent.append(repr(signature))
            del recent[:-_MAX_SIG_HISTORY]
            n = len(sigs)
            should_warn = n >= self.threshold and fn_name not in self._warned
            if should_warn:
                self._warned.add(fn_name)
        if should_warn:
            warnings.warn(
                f"recompile watchdog: '{fn_name}' has compiled {n} distinct "
                f"programs (threshold {self.threshold}). Recompilation "
                "discards the cached XLA program and stalls the device — "
                "common causes are shape-varying inputs (pad or bucket "
                "them) and python scalars mutated between calls. Recent "
                f"signatures: {recent[-3:]}",
                RecompileWarning, stacklevel=3)

    def stats(self):
        with self._lock:
            return {name: len(sigs) for name, sigs in self._sigs.items()}

    def reset(self):
        with self._lock:
            self._sigs.clear()
            self._recent.clear()
            self._warned.clear()


_JAX_LISTENER = {"installed": False, "registry": None, "hist": None}

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def install_jax_compile_listener(registry=None):
    """Mirror jax's own compile events (best-effort: the monitoring API
    and its event names vary across jax releases). Given a registry,
    every compile-duration event lands in ``jax_compilation_seconds``
    while that registry is enabled; and while the span tracer is on,
    every backend compile jax reports, whichever program or eager op
    caused it, is written as an ``xla_compile`` span ending at the
    report. Registered once per process (``telemetry.enable()`` and
    ``trace.enable()`` both arm it); the listener itself checks the
    enabled flags, so disable() silences it without deregistration."""
    st = _JAX_LISTENER
    if registry is not None and st["hist"] is None:
        st["registry"] = registry
        st["hist"] = registry.histogram(
            "jax_compilation_seconds",
            "XLA compile wall time as reported by jax.monitoring",
            labelnames=("event",))
    if st["installed"]:
        return
    st["installed"] = True
    try:
        from jax import monitoring

        from . import trace

        tracer = trace.get_tracer()

        def _on_duration(event, duration, **kw):
            reg = st["registry"]
            if reg is not None and reg.enabled and "compil" in event:
                st["hist"].observe(duration, labels=(event.strip("/"),))
            if tracer.enabled and event == BACKEND_COMPILE_EVENT:
                tracer.complete(
                    "xla_compile", time.perf_counter() - duration, duration,
                    {"event": event, "seconds": round(duration, 6)},
                    cat="jit")

        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # noqa: BLE001 — telemetry must never break startup
        pass
