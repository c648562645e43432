"""paddle.jit — trace-to-XLA compilation (parity: python/paddle/jit).

The reference captures python bytecode (SOT eval-frame hook, §3.6 of the
survey) and compiles the captured graph through CINN.  The TPU-native design
replaces that whole pipeline with jax tracing: because every eager op is a
pure jax function over the Tensor's payload, running a Layer's forward with
tracer payloads *is* the capture.  ``to_static`` wraps a Layer as a pure
function of (parameters, buffers, inputs) and hands it to ``jax.jit``;
``TrainStep`` compiles forward+backward+optimizer into one donated-buffer XLA
program — the analogue of the reference's whole-graph `pir_partial_program`
plus CINN, with XLA doing fusion/scheduling.
"""
from __future__ import annotations

import functools
import time as _time

import jax
import jax.numpy as jnp
from jax import tree_util

from .. import framework
from .. import telemetry as _telemetry
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer

_TRAIN_STEP_SECONDS = _telemetry.histogram(
    "train_step_seconds",
    "TrainStep dispatch wall time (async under jit: device sync excluded)",
    labelnames=("model",))
_TRAIN_STEPS = _telemetry.counter(
    "train_steps_total", "TrainStep invocations", labelnames=("model",))

# -- compile-phase telemetry (docs/TELEMETRY.md, docs/SCAN.md) --------------
# Wall seconds of the newest program build, split by phase, plus the
# serialized HLO module size — the measurement behind the scan-over-layers
# "compile time and program size flat in depth" claim (the "compile"
# block that tools/bench_gate.py gates).
_TRACE_SECONDS = _telemetry.gauge(
    "trace_seconds", "jax tracing wall seconds of the newest program "
    "build for this function", labelnames=("function",))
_LOWER_SECONDS = _telemetry.gauge(
    "lower_seconds", "StableHLO lowering wall seconds of the newest "
    "program build for this function", labelnames=("function",))
_COMPILE_SECONDS = _telemetry.gauge(
    "compile_seconds", "XLA backend-compile wall seconds of the newest "
    "program build for this function", labelnames=("function",))
_HLO_PROGRAM_BYTES = _telemetry.gauge(
    "hlo_program_bytes", "serialized HLO module size (bytes) of the "
    "newest compiled program for this function", labelnames=("function",))

#: newest per-function phase record: {label: {"trace_seconds": ..,
#: "lower_seconds": .., "compile_seconds": .., "hlo_program_bytes": ..}}
_LAST_COMPILE = {}


def _device_peaks():
    """(peak_flops, peak_bytes_per_sec, placeholder?) for device 0 —
    the roofline denominators behind the dispatch-span cost attrs and
    the bench anatomy's cost-analysis MFU, from the one chip table
    (``paddle_tpu.device.CHIP_PEAKS``). CPU runs get the flagged
    placeholder row; an unknown TPU kind raises."""
    from ..device import chip_peaks

    peaks, placeholder = chip_peaks()
    return peaks["bf16_flops"], peaks["hbm_bytes_per_sec"], placeholder


def compiled_cost_summary(compiled):
    """``compiled.cost_analysis()`` distilled to the anatomy contract:
    {"flops", "bytes_accessed", "device_seconds_est" (roofline:
    max(flops/peak_flops, bytes/peak_bw)), "peak_flops",
    "peak_bytes_per_sec", "peak_model_placeholder"} — or None when the
    executable exposes no cost analysis."""
    ca = compiled.cost_analysis()
    if not ca:
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    pf, pb, placeholder = _device_peaks()
    return {
        "flops": flops,
        "bytes_accessed": nbytes,
        "device_seconds_est": max(flops / pf, nbytes / pb),
        "peak_flops": pf,
        "peak_bytes_per_sec": pb,
        "peak_model_placeholder": bool(placeholder),
    }


def _memory_record(compiled):
    """``compiled.memory_analysis()`` as the planners' pricing dict.
    ``peak_bytes`` is what gets compared with the HBM budget: on a TPU,
    XLA's own high-water mark (``peak_memory_in_bytes``). Measured on
    the v5e (PR 23) for GPT-1.3B: at batch 3 it reports 15.36 GB and the
    program loads and runs in the chip's 15.75 GiB, while argument +
    temp gives 18.71 GB for that same program (the TPU's temp size does
    not net out the donated buffers); batch 4 the compiler itself
    refuses at 16.69 G of 15.75 G. The CPU backend's
    ``peak_memory_in_bytes`` leaves the temporaries out, so there the
    sum stays."""
    ma = compiled.memory_analysis()
    if jax.devices()[0].platform == "tpu":
        peak = ma.peak_memory_in_bytes
    else:
        peak = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(peak),
    }


def _traced_dispatch(ex, label, cost, op_args):
    """Run one compiled dispatch, recording a ``dispatch`` span with the
    program's cost-analysis attrs when tracing is on: flops, bytes and
    the roofline device-seconds estimate. Under async dispatch the
    span's wall time is enqueue time, so nothing per call is derived
    from it (``last_dispatch_cost()`` over a step's wall is the MFU
    figure). Plain call when the tracer is disabled — the hot path pays
    one attribute check."""
    tr = _telemetry.trace
    if not tr.enabled():
        return ex(*op_args)
    t0 = _time.perf_counter()
    out = ex(*op_args)
    dt = _time.perf_counter() - t0
    attrs = {"function": label}
    if cost:
        attrs.update(
            flops=cost["flops"], bytes_accessed=cost["bytes_accessed"],
            device_seconds_est=round(cost["device_seconds_est"], 6))
    tr.complete("dispatch", t0, dt, attrs, cat="jit")
    return out


def _serialized_hlo_bytes(lowered):
    """Size of the lowered program: its serialized HLO module proto."""
    return len(lowered.compiler_ir(
        dialect="hlo").as_serialized_hlo_module_proto())


def _record_compile_phases(label, trace_s, lower_s, compile_s, hlo_bytes):
    labels = (label,)
    _TRACE_SECONDS.set(trace_s, labels=labels)
    _LOWER_SECONDS.set(lower_s, labels=labels)
    _COMPILE_SECONDS.set(compile_s, labels=labels)
    _HLO_PROGRAM_BYTES.set(hlo_bytes, labels=labels)
    _LAST_COMPILE[label] = {
        "trace_seconds": trace_s, "lower_seconds": lower_s,
        "compile_seconds": compile_s, "hlo_program_bytes": hlo_bytes}


def compile_summary(label=None):
    """Newest compile-phase record for ``label`` (None = all labels):
    the bench "compile" block's data source. Returns None for an
    unknown label."""
    if label is None:
        return {k: dict(v) for k, v in _LAST_COMPILE.items()}
    rec = _LAST_COMPILE.get(label)
    return dict(rec) if rec is not None else None


def timed_lower_compile(jitfn, label, *args, **kwargs):
    """AOT trace -> lower -> compile of a ``jax.jit`` function, feeding
    the per-phase gauges. Returns the Compiled executable (same program
    jit dispatch would build — donation and shardings preserved)."""
    t0 = _time.perf_counter()
    traced = jitfn.trace(*args, **kwargs)
    t1 = _time.perf_counter()
    lowered = traced.lower()
    t2 = _time.perf_counter()
    compiled = lowered.compile()
    t3 = _time.perf_counter()
    hlo_bytes = _serialized_hlo_bytes(lowered)
    _record_compile_phases(label, t1 - t0, t2 - t1, t3 - t2, hlo_bytes)
    tr = _telemetry.trace
    if tr.enabled():
        # the three build phases as spans so a trace shows WHERE a cold
        # start went (compile churn shows as repeated jit:* triplets)
        attrs = {"function": label}
        tr.complete("jit:trace", t0, t1 - t0, dict(attrs), cat="jit")
        tr.complete("jit:lower", t1, t2 - t1, dict(attrs), cat="jit")
        tr.complete("jit:compile", t2, t3 - t2,
                    dict(attrs, hlo_program_bytes=hlo_bytes), cat="jit")
    return compiled


def _wrap_arrays(tree):
    return tree_util.tree_map(lambda a: Tensor(a), tree)


def _unwrap_tensors(tree):
    return tree_util.tree_map(
        lambda t: t._data if isinstance(t, Tensor) else t,
        tree,
        is_leaf=lambda x: isinstance(x, Tensor),
    )


def functional_call(layer: Layer, state: dict, *args, **kwargs):
    """Run `layer` as a pure function of `state` (name -> array).

    Returns (outputs_pytree_of_arrays, mutated_state_dict)."""
    with layer._swap_state(state) as mutated:
        with framework.no_grad():
            wrapped_args = _wrap_arrays(args)
            wrapped_kwargs = _wrap_arrays(kwargs)
            out = layer(*wrapped_args, **wrapped_kwargs)
    return _unwrap_tensors(out), mutated


class StaticFunction:
    """Compiled wrapper around a Layer or a pure tensor function.

    Guards (reference: jit/sot guard.py semantics): the compiled-program
    cache is keyed on (training, input shapes, input dtypes) — a shape or
    dtype change triggers a retrace instead of running a stale program.
    Graph breaks (reference: SOT graph-break fallback): data-dependent
    Python control flow raises a jax concretization error during tracing;
    the call falls back to eager for that invocation with a one-time
    warning instead of a hard failure.
    """

    def __init__(self, function, input_spec=None,
                 bucket_dynamic_shapes=False, **kwargs):
        if isinstance(function, Layer):
            self._layer = function
            self._fn = None
        else:
            self._layer = getattr(function, "__self__", None)
            self._fn = function
        self._input_spec = input_spec
        target = self._fn if self._fn is not None else self._layer
        # invariant per StaticFunction: computed once, not per dispatch
        self._dispatch_label = (getattr(target, "__qualname__", None)
                                or type(target).__name__)
        # LRU-bounded program cache: value guards key on python scalars
        # (below), so a Layer that mutates a fresh scalar every call
        # (self.calls += 1 in forward) would otherwise grow this dict
        # without bound while retracing per call — correct (the old
        # behavior silently reused a stale program) but it must not
        # leak. 32 programs covers shape buckets x a few guard states.
        import collections

        self._compiled = collections.OrderedDict()
        self._compiled_cap = 32
        self._fallback_warned = False
        # dynamic-dim bucketing (SURVEY hard-part 6): dims declared
        # None/-1 in input_spec are padded up to the next power of two, so
        # a stream of varying lengths costs O(log) compilations instead of
        # one per shape. Opt-in: padding changes values for ops that
        # reduce over the padded region — the caller owns masking, exactly
        # like the reference's dynamic-shape dy2st deployments pad inputs.
        self._bucket_axes = None
        self._bucket_kw = None
        if bucket_dynamic_shapes and input_spec is not None:
            from ..static import InputSpec

            axes, kw = [], {}
            for spec in (input_spec if isinstance(input_spec, (list, tuple))
                         else [input_spec]):
                if isinstance(spec, InputSpec):
                    dyn = tuple(i for i, d in enumerate(spec.shape)
                                if d is None or d == -1)
                    axes.append(dyn)
                    # NAMED specs additionally bucket same-named kwargs
                    if getattr(spec, "name", None):
                        kw[spec.name] = dyn
                else:
                    axes.append(())
            self._bucket_axes = axes
            self._bucket_kw = kw

    @staticmethod
    def _next_bucket(n):
        b = 8
        while b < n:
            b *= 2
        return b

    def _bucketize(self, raw_args):
        if self._bucket_axes is None:
            return raw_args
        import numpy as _np

        out = []
        for i, a in enumerate(raw_args):
            axes = (self._bucket_axes[i]
                    if i < len(self._bucket_axes) else ())
            if axes and hasattr(a, "shape"):
                a = self._pad_to_buckets(a, axes)
            out.append(a)
        return tuple(out)

    def _pad_to_buckets(self, a, axes):
        import numpy as _np

        pad = [(0, 0)] * a.ndim
        needs = False
        for ax in axes:
            tgt = self._next_bucket(a.shape[ax])
            if tgt != a.shape[ax]:
                pad[ax] = (0, tgt - a.shape[ax])
                needs = True
        if not needs:
            return a
        return (_np.pad(a, pad) if isinstance(a, _np.ndarray)
                else jnp.pad(a, pad))

    def _bucketize_kwargs(self, raw_kwargs):
        """Bucket keyword tensors through their NAMED InputSpecs."""
        if self._bucket_axes is None or not raw_kwargs:
            return raw_kwargs
        out = {}
        for k, v in raw_kwargs.items():
            axes = (self._bucket_kw or {}).get(k, ())
            if hasattr(v, "shape") and v.ndim >= 1:
                if axes:
                    v = self._pad_to_buckets(v, axes)
                elif k not in (self._bucket_kw or {}):
                    raise ValueError(
                        "bucket_dynamic_shapes: tensor keyword argument "
                        f"{k!r} has no matching NAMED InputSpec — name the "
                        "spec (InputSpec(shape, name=...)) or pass the "
                        "tensor positionally")
            elif k not in (self._bucket_kw or {}) and any(
                    hasattr(leaf, "shape")
                    for leaf in tree_util.tree_leaves(v)):
                # tensors hidden in containers can't be bucketed — raise
                # loudly rather than silently recompiling per shape
                raise ValueError(
                    "bucket_dynamic_shapes: keyword argument "
                    f"{k!r} contains tensors inside a container — pass "
                    "them as named top-level arguments so they can be "
                    "padded to their bucket")
            out[k] = v
        return out

    _GUARD_SCALARS = (bool, int, float, str, bytes, type(None))

    def _value_guard_sig(self):
        """Python-state value guards (reference: jit/sot guard.py —
        guards on object attributes and closure cells read by the traced
        frame). A trace bakes python scalars into the program
        (`if self.use_cache:`, a closed-over scale float), so the cache
        key must carry them: the cheap 90% is every scalar attribute on
        the Layer tree plus the function's scalar closure cells —
        mutating one maps to a NEW key (retrace); restoring it reuses
        the old compiled program."""
        parts = []
        if self._layer is not None:
            # per-call tree walk, deliberately uncached: a sublayer
            # attached AFTER the first call must still be guarded on its
            # scalar mutations (a snapshot would silently reuse stale
            # programs). The generator walk is cheap next to jit dispatch.
            for path, layer in self._layer.named_sublayers(
                    include_self=True):
                for k, v in layer.__dict__.items():
                    if k.startswith("_") or k == "training":
                        continue
                    if isinstance(v, self._GUARD_SCALARS):
                        parts.append((path, k, v))
        fn = self._fn
        if fn is not None:
            try:
                closure = fn.__closure__ or ()
            except AttributeError:
                closure = ()
            for i, cell in enumerate(closure):
                try:
                    v = cell.cell_contents
                except ValueError:
                    continue
                if isinstance(v, self._GUARD_SCALARS):
                    parts.append(("<closure>", i, v))
        return tuple(parts)

    def _trace_key(self, raw_args, raw_kwargs):
        training = self._layer.training if self._layer is not None else False

        def leaf_sig(a):
            if hasattr(a, "shape"):
                return (tuple(a.shape), str(a.dtype))
            if isinstance(a, float):
                # floats trace as values inside the program — keying by
                # value would recompile per lr/scale; key by type only
                return ("<float>",)
            return a  # bools/ints/strings: small value sets, key by value

        sig = tuple(leaf_sig(a)
                    for a in tree_util.tree_leaves((raw_args, raw_kwargs)))
        return (training, sig, self._value_guard_sig())

    def _get_compiled(self, key):
        if key in self._compiled:
            self._compiled.move_to_end(key)
        else:
            while len(self._compiled) >= self._compiled_cap:
                self._compiled.popitem(last=False)
        if key not in self._compiled:
            layer = self._layer
            fn = self._fn
            # jit-cache miss: every new (training, shapes, guards) key is
            # a fresh trace+compile — feed the recompile watchdog with the
            # function identity and the signature it missed on
            target = fn if fn is not None else layer
            _telemetry.record_compile(
                getattr(target, "__qualname__", None)
                or type(target).__name__, key)

            if layer is not None:
                def pure(state, key_arr, args, kwargs):
                    with layer._swap_state(state) as mutated:
                        with framework.no_grad(), framework.rng_key_scope(key_arr):
                            wa = _wrap_arrays(args)
                            wk = _wrap_arrays(kwargs)
                            if fn is not None:
                                out = fn(*wa, **wk)
                            else:
                                out = layer(*wa, **wk)
                    return _unwrap_tensors(out), dict(mutated)

                self._compiled[key] = [jax.jit(pure), None, None]
            else:
                def pure_fn(key_arr, args, kwargs):
                    with framework.no_grad(), framework.rng_key_scope(key_arr):
                        out = fn(*_wrap_arrays(args), **_wrap_arrays(kwargs))
                    return _unwrap_tensors(out)

                self._compiled[key] = [jax.jit(pure_fn), None, None]
        return self._compiled[key]

    def _run_slot(self, slot, *args):
        """Run a compiled-program slot ([jit fn, executable|None, cost]):
        the first call builds the executable through timed_lower_compile
        so the compile-phase gauges (trace/lower/compile seconds +
        hlo_program_bytes, labeled by function) cover to_static programs
        too, and caches the program's cost_analysis summary for the
        dispatch trace span. A failed build propagates: graph-break
        tracer errors reach __call__'s eager fallback, anything else is
        the caller's error — never a second, untimed compile."""
        jitfn, ex = slot[0], slot[1]
        label = self._dispatch_label
        if ex is None:
            ex = timed_lower_compile(jitfn, label, *args)
            slot[2] = compiled_cost_summary(ex)
            slot[1] = ex
        try:
            return _traced_dispatch(ex, label, slot[2], args)
        except (TypeError, ValueError):
            if ex is jitfn:
                raise
            slot[1] = jitfn
            slot[2] = None
            return jitfn(*args)

    _GRAPH_BREAK_ERRORS = (
        jax.errors.TracerBoolConversionError,
        jax.errors.TracerIntegerConversionError,
        jax.errors.TracerArrayConversionError,
        jax.errors.ConcretizationTypeError,
    )

    def _eager_call(self, args, kwargs):
        fn = self._fn if self._fn is not None else self._layer
        # Partial-graph capture around graph breaks — ops compile as
        # segments (prefix up to the .item()/bool(), host branch, suffix),
        # the SOT-granularity answer (function_graph.py) without bytecode
        # rewriting. Memoized per op-sequence, so steady-state calls reuse
        # the compiled programs. Under grad (training fallback), each
        # flushed segment lands on the tape as ONE GradNode whose vjp runs
        # through the cached jitted program — staged autograd, so a
        # one-.item() training model keeps its FLOPs compiled.
        from .lazy import materialize_tree, segment_capture

        with segment_capture(
                grad_mode=framework.is_grad_enabled()) as trace:
            out = fn(*args, **kwargs)
        self._segment_stats = {"segments": trace.segments,
                               "ops": trace.recorded_ops}
        return materialize_tree(out)

    def __call__(self, *args, **kwargs):
        raw_args = self._bucketize(_unwrap_tensors(args))
        raw_kwargs = self._bucketize_kwargs(_unwrap_tensors(kwargs))
        key = self._trace_key(raw_args, raw_kwargs)
        if self._compiled.get(key, False) is None:  # known graph break
            return self._eager_call(args, kwargs)
        slot = self._get_compiled(key)
        key_arr = framework.next_rng_key()
        try:
            if self._layer is not None:
                state = {k: v._data
                         for k, v in self._layer.state_dict().items()}
                out_arrays, mutated = self._run_slot(slot, state, key_arr,
                                                     raw_args, raw_kwargs)
                # write back mutated buffers (e.g. batchnorm stats)
                entries = self._layer.state_dict()
                for name, arr in mutated.items():
                    if name in entries:
                        entries[name]._data = arr
                return _wrap_arrays(out_arrays)
            return _wrap_arrays(self._run_slot(slot, key_arr, raw_args,
                                               raw_kwargs))
        except self._GRAPH_BREAK_ERRORS as e:
            # graph break: data-dependent Python control flow cannot trace;
            # run this call eagerly (SOT fallback semantics) and remember so
            # later same-signature calls skip the doomed trace
            self._compiled[key] = None
            if not self._fallback_warned:
                self._fallback_warned = True
                import warnings

                target = self._fn or self._layer
                warnings.warn(
                    f"to_static: graph break in "
                    f"{getattr(target, '__name__', type(target).__name__)} "
                    f"({type(e).__name__}); falling back to eager for such "
                    "calls — hoist data-dependent Python branching out of "
                    "forward (or use paddle.where / lax.cond) to stay "
                    "compiled")
            return self._eager_call(args, kwargs)

    @property
    def dygraph_function(self):
        return self._fn or self._layer

    def concrete_program(self):  # compat stub
        return None


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """paddle.jit.to_static — decorator or direct call."""

    def decorate(fn):
        if isinstance(fn, Layer):
            static = StaticFunction(fn, input_spec, **kwargs)
            # wrap the layer: calling the proxy runs the compiled path while
            # attribute access (parameters, state_dict...) hits the layer
            return _StaticLayerProxy(fn, static)
        return functools.wraps(fn)(StaticFunction(fn, input_spec, **kwargs))

    if function is not None:
        return decorate(function)
    return decorate


class _StaticLayerProxy:
    """Layer wrapper whose __call__ runs the compiled program."""

    def __init__(self, layer, static):
        object.__setattr__(self, "_layer", layer)
        object.__setattr__(self, "_static", static)

    def __call__(self, *args, **kwargs):
        return self._static(*args, **kwargs)

    def __getattr__(self, name):
        if name == "_segment_stats":  # capture observability lives on the
            return self._static._segment_stats  # StaticFunction, not the layer
        return getattr(self._layer, name)

    def __setattr__(self, name, value):
        setattr(self._layer, name, value)


def not_to_static(fn):
    return fn


def enable_to_static(flag=True):
    pass


def ignore_module(modules):
    pass


# ---------------------------------------------------------------------------
# TrainStep: compiled forward+backward+update (the perf path)
# ---------------------------------------------------------------------------
def _global_grad_sumsq(grads):
    """One fused reduction: sum of squares over the flattened grad tree
    (float32). Shared by the in-graph StepHealth bundle and global-norm
    clipping — the norm is computed once per step, never twice."""
    leaves = [g for g in tree_util.tree_leaves(grads) if g is not None]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)


def _functional_clip_global_norm(grads, clip_norm, gnorm=None):
    leaves = [g for g in tree_util.tree_leaves(grads) if g is not None]
    if not leaves:
        return grads
    if gnorm is None:
        gnorm = jnp.sqrt(_global_grad_sumsq(grads))
    clip = jnp.asarray(clip_norm, jnp.float32)
    scale = clip / jnp.maximum(gnorm, clip)
    return tree_util.tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)


def _step_update_tail(opt, clip, reg, params, grads, loss, new_buffers,
                      buffers, opt_state, lr, guard, *,
                      gsumsq_fn=_global_grad_sumsq):
    """The post-gradient step tail — chaos injection, regularizer,
    StepHealth bundle, grad clip, optimizer update, guard keep-select —
    shared by ``TrainStep._build`` and the ZeRO
    ``ShardedTrainStep._build_zero`` so the PR 5 guard semantics live in
    ONE place (the zero step passes param/grad SHARD views and a
    ``gsumsq_fn`` that psums the sharded leaves; everything here is
    elementwise or scale-broadcast, so it is layout-agnostic).

    Returns ``(loss, new_params, new_buffers, new_opt_state, health)``
    with ``new_params`` in the same layout as ``params``."""
    # chaos anomaly seam: a zero injection selects the original bytes —
    # the select with a false predicate is the identity, so clean runs
    # are bit-identical with or without a hook installed
    ginj, linj = guard[1], guard[2]
    do_g = ginj != 0.0  # nan != 0 and inf != 0 are both True
    grads = tree_util.tree_map(
        lambda g: jnp.where(do_g, jnp.full_like(g, ginj.astype(g.dtype)),
                            g),
        grads)
    loss = jnp.where(linj != 0.0, linj.astype(loss.dtype), loss)
    if reg is not None:
        grads = {
            n: reg._apply_arr(params[n], g) for n, g in grads.items()
        }
    # StepHealth: ONE reduction over the flattened grad tree, shared
    # with global-norm clipping below — no second pass, no extra HBM
    # arrays (4 scalars ride out with the step)
    gsumsq = gsumsq_fn(grads)
    gnorm = jnp.sqrt(gsumsq)
    loss32 = loss.astype(jnp.float32)
    finite = jnp.isfinite(loss32) & jnp.isfinite(gsumsq)
    from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue

    if isinstance(clip, ClipGradByGlobalNorm):
        grads = _functional_clip_global_norm(grads, clip.clip_norm,
                                             gnorm=gnorm)
    elif isinstance(clip, ClipGradByValue):
        grads = tree_util.tree_map(
            lambda g: jnp.clip(g, clip.min, clip.max), grads
        )
    elif isinstance(clip, ClipGradByNorm):
        # (the zero plan declines ClipGradByNorm at build — per-tensor
        # norms need the full grad tensor — so this branch only runs on
        # full layouts)
        def _clip_one(g):
            n = jnp.linalg.norm(g.astype(jnp.float32).reshape(-1))
            c = jnp.asarray(clip.clip_norm, jnp.float32)
            return (g * jnp.minimum(c / jnp.maximum(n, c), 1.0)).astype(g.dtype)

        grads = tree_util.tree_map(_clip_one, grads)
    new_params, new_opt_state = opt.functional_update(params, grads,
                                                      opt_state, lr)
    # in-graph skip (StepGuard): a nonfinite or above-threshold step
    # keeps the pre-step param/slot/buffer trees. select on a true
    # predicate returns the update bytes unchanged, and the pre-step
    # operands are already live inside the step, so this costs no extra
    # HBM and composes with buffer donation.
    ok = (guard[3] == 0.0) | (finite & (loss32 <= guard[0]))

    def _keep(new, old):
        return jnp.where(ok, new, old)

    new_params = tree_util.tree_map(_keep, new_params, params)
    new_opt_state = tree_util.tree_map(_keep, new_opt_state, opt_state)
    new_buffers = {n: _keep(new_buffers[n], buffers[n])
                   for n in new_buffers}
    health = jnp.stack([finite.astype(jnp.float32), gnorm, loss32,
                        ok.astype(jnp.float32)])
    return loss, new_params, new_buffers, new_opt_state, health


class TrainStep:
    """Compile (forward, loss, backward, optimizer update) into one XLA program.

    train_fn(*batch_tensors) -> scalar loss Tensor, closing over `model`.
    Parameters and optimizer slots are donated — updates happen in-place in
    HBM with zero copies, like the reference's fused optimizer kernels.
    """

    def __init__(self, model: Layer, train_fn, optimizer, scaler=None):
        self.model = model
        self.train_fn = train_fn
        self.optimizer = optimizer
        self._compiled = None
        self._execs = {}  # input-signature -> AOT executable (or jit fn)
        self._exec_costs = {}  # input-signature -> cost_analysis summary
        self._last_cost = None  # newest executable's cost summary
        self._param_names = None
        self._buffer_names = None
        self._opt_state = None
        # resilience guard inputs (docs/RESILIENCE.md): the spike
        # threshold rides into the compiled step as an OPERAND, so the
        # guard never causes a recompile. None = +inf = never skip.
        self._guard_threshold = None
        self._call_index = 0      # 1-based invocation count (chaos seam)
        self._last_health = None  # device f32[4], fetched lazily

    def _build(self):
        model, train_fn, opt = self.model, self.train_fn, self.optimizer
        from ..utils.flags import get_flags as _gf

        # planner-driven AOT builds are labeled apart from real training
        # compiles: a cold-cache plan lowers up to a full candidate grid,
        # which would false-positive the watchdog's ">1 recompile per
        # function means shape churn" triage rule (docs/TELEMETRY.md)
        _telemetry.record_compile(
            f"TrainStep[{type(self.model).__name__}]"
            + ("[plan]" if getattr(self, "_planning", False) else ""),
            ("build", bool(_gf("check_nan_inf")["check_nan_inf"])))
        entries = model.state_dict()
        from ..core.tensor import Parameter

        self._param_names = [
            n for n, t in entries.items()
            if isinstance(t, Parameter) and t.trainable
        ]
        self._buffer_names = [n for n in entries if n not in self._param_names]
        clip = opt._grad_clip
        reg = opt.regularization

        def make_loss_of(buffers, key_arr, batch):
            # the (buffers, rng key, batch) closure is built through this
            # factory so subclasses can re-close it over PER-SHARD values
            # (ShardedTrainStep's quantized dp-grad reduce rebuilds it
            # inside a manual shard_map region with the batch split over
            # the data axes — distributed/collectives)
            def loss_of(params):
                state = dict(params)
                state.update(buffers)
                with model._swap_state(state) as mutated:
                    with framework.no_grad(), framework.rng_key_scope(key_arr):
                        loss_t = train_fn(*_wrap_arrays(batch))
                new_buffers = {n: mutated[n] for n in self._buffer_names}
                return loss_t._data, new_buffers

            return loss_of

        def step(params, buffers, opt_state, lr, guard, key_arr, batch):
            # guard: f32[4] operand = [spike_threshold, grad_inject,
            # loss_inject, armed]. Thresholds/injections are VALUES, not
            # shapes — guarded and unguarded runs execute this same
            # program. `armed` gates the skip select: only an attached
            # StepGuard discards anomalous updates; an unguarded step
            # adopts them exactly as it always did (a silent drop would
            # hide real divergence from users who never opted in).
            (loss, new_buffers), grads = self._value_and_grads(
                make_loss_of, params, buffers, key_arr, batch)
            return _step_update_tail(opt, clip, reg, params, grads, loss,
                                     new_buffers, buffers, opt_state, lr,
                                     guard)

        from ..utils.flags import get_flags

        self._execs = {}
        if get_flags("check_nan_inf")["check_nan_inf"]:
            # FLAGS_check_nan_inf inside the COMPILED step: checkify
            # instruments every float op so the raised error names the
            # first NaN-producing primitive and its traceback — the
            # compiled-mode analogue of the reference's per-kernel
            # CheckNumerics pass (paddle/fluid/framework/details/
            # nan_inf_utils_detail). Costs extra compute; debug-only.
            from jax.experimental import checkify

            self._checkified = True
            # NO buffer donation in debug mode: on a nan error the step's
            # outputs are discarded and the caller must still be able to
            # inspect the pre-step params/opt-state
            self._compiled = jax.jit(
                checkify.checkify(step, errors=checkify.float_checks))
        else:
            self._checkified = False
            self._compiled = jax.jit(step, donate_argnums=(0, 2))

    def _compile_label(self):
        return (f"TrainStep[{type(self.model).__name__}]"
                + ("[plan]" if getattr(self, "_planning", False) else ""))

    @staticmethod
    def _exec_sig(tree):
        def leaf_sig(a):
            if hasattr(a, "shape"):
                return (tuple(a.shape), str(a.dtype))
            # python scalars are traced as weak-typed OPERANDS (jit
            # reuses one program across values) — key them by class,
            # never by value, or a per-step int in the batch would force
            # a full recompile per distinct value
            if isinstance(a, bool):
                return "<b>"
            if isinstance(a, int):
                return "<i>"
            if isinstance(a, float):
                return "<f>"
            return repr(a)

        return tuple(leaf_sig(l) for l in tree_util.tree_leaves(tree))

    def _dispatch_compiled(self, *op_args):
        """Run the step program through an explicitly built executable so
        the build splits into measured trace/lower/compile phases
        (compile-phase gauges + the bench "compile" block). Signature
        miss -> timed AOT build; a failed build raises — it is never
        swallowed into a second compile through ``jax.jit`` dispatch."""
        key = self._exec_sig(op_args)
        ex = self._execs.get(key)
        if ex is None:
            ex = timed_lower_compile(self._compiled,
                                     self._compile_label(), *op_args)
            cost = compiled_cost_summary(ex)
            self._exec_costs[key] = cost
            if cost is not None:
                self._last_cost = cost
            self._execs[key] = ex
        try:
            return _traced_dispatch(ex, self._compile_label(),
                                    self._exec_costs.get(key), op_args)
        except (TypeError, ValueError):
            # AOT argument check rejected the operands BEFORE execution
            # (an aval/layout property the signature key didn't capture):
            # jit dispatch is authoritative for this signature from now
            # on. Execution-time errors re-raise unchanged.
            if ex is self._compiled:
                raise
            self._execs[key] = self._compiled
            self._exec_costs.pop(key, None)
            return self._compiled(*op_args)

    def _value_and_grads(self, make_loss_of, params, buffers, key_arr,
                         batch):
        """Differentiation seam inside the compiled step: returns
        ``((loss, new_buffers), grads)``. The base implementation is the
        pre-PR program verbatim; ShardedTrainStep overrides it to run
        the backward inside a manual data-axis region with a bucketed /
        quantized gradient reduce (distributed/collectives) when its
        plan engages — and delegates HERE when it doesn't, which is what
        makes ``PTPU_QUANT_COLLECTIVES=0`` byte-identical."""
        loss_of = make_loss_of(buffers, key_arr, batch)
        return jax.value_and_grad(loss_of, has_aux=True)(params)

    def last_dispatch_cost(self):
        """cost_analysis summary of the newest compiled step executable
        (compiled_cost_summary shape), or None before the first build /
        when the program exposes no cost analysis — the bench anatomy
        block's device-side estimate."""
        return self._last_cost

    def __call__(self, *batch):
        model_label = (type(self.model).__name__,)
        _TRAIN_STEPS.inc(labels=model_label)
        with _telemetry.timer(_TRAIN_STEP_SECONDS, labels=model_label):
            tr = _telemetry.trace
            if tr.enabled():
                with tr.span("train_step",
                             attrs={"model": model_label[0]}, cat="step"):
                    return self._call_impl(*batch)
            return self._call_impl(*batch)

    def _call_impl(self, *batch):
        from ..utils.flags import get_flags

        want_check = bool(get_flags("check_nan_inf")["check_nan_inf"])
        if self._compiled is None or want_check != getattr(
                self, "_checkified", False):
            self._build()  # flag flipped since last compile: rebuild
        entries = self.model.state_dict()
        params = {n: entries[n]._data for n in self._param_names}
        buffers = {n: entries[n]._data for n in self._buffer_names}
        if self._opt_state is None:
            self._opt_state = self._init_opt_state(params)
        lr = self.optimizer.get_lr()
        guard_arr = self._guard_operand()
        key_arr = framework.next_rng_key()
        raw_batch = _unwrap_tensors(batch)
        if self._checkified:
            err, out = self._dispatch_compiled(params, buffers,
                                               self._opt_state, lr,
                                               guard_arr, key_arr, raw_batch)
            # raise BEFORE adopting any of the step's outputs: params,
            # buffers, and opt state all stay at their pre-step values so
            # the user can inspect or skip the batch
            err.throw()
            loss, new_params, new_buffers, self._opt_state, health = out
        else:
            loss, new_params, new_buffers, self._opt_state, health = \
                self._dispatch_compiled(
                    params, buffers, self._opt_state, lr, guard_arr,
                    key_arr, raw_batch
                )
        self._last_health = health
        for n, arr in new_params.items():
            entries[n]._data = arr
        for n, arr in new_buffers.items():
            entries[n]._data = arr
        if self.optimizer._lr_scheduler is not None:
            pass  # stepped by the caller per paddle convention
        self.optimizer._step_count += 1
        # quant-compute flops accounting (docs/QUANT.md): one counter tick
        # per executed step, rate recorded by the last engaged trace
        from ..quant import note_step_tokens

        shape = getattr(raw_batch[0], "shape", ()) if raw_batch else ()
        note_step_tokens(int(shape[0]) * int(shape[1])
                         if len(shape) >= 2 else 0)
        return Tensor(loss)

    def _guard_operand(self):
        """f32[4] guard operand: [spike_threshold, grad_inject,
        loss_inject, armed]. `armed` is 1 only while a StepGuard drives
        the step (``_guard_threshold`` set) — unguarded steps keep their
        legacy adopt-everything semantics. Also advances the chaos
        anomaly seam (resilience._ANOMALY_FAULT_HOOK) by one invocation.
        The device array is cached per value tuple: unguarded runs and
        a guard still inside its warmup (+inf threshold) re-upload
        nothing; once the rolling spike threshold is live it changes
        per accepted step, costing one f32[4] (16-byte) upload."""
        self._call_index += 1
        thr = self._guard_threshold
        armed = 0.0 if thr is None else 1.0
        thr = float("inf") if thr is None else float(thr)
        ginj = linj = 0.0
        from .. import resilience as _resilience

        hook = _resilience._ANOMALY_FAULT_HOOK
        if hook is not None:
            res = hook(self._call_index)
            if res is not None:
                site, val = res
                if site == "grads":
                    ginj = float(val)
                elif site == "loss":
                    linj = float(val)
                else:
                    raise ValueError(
                        f"anomaly hook site {site!r} not in "
                        "('grads', 'loss')")
        key = (thr, ginj, linj, armed)
        cached = getattr(self, "_guard_arr_cache", None)
        if cached is None or cached[0] != key:
            cached = (key, jnp.asarray(key, jnp.float32))
            self._guard_arr_cache = cached
        return cached[1]

    @property
    def last_health(self):
        """`resilience.StepHealth` of the most recent step (None before
        the first). This is the guard's ONE extra device fetch per step:
        the fused 4-scalar bundle computed inside the compiled program."""
        if self._last_health is None:
            return None
        import numpy as _np

        from ..resilience.guard import StepHealth

        v = _np.asarray(self._last_health)
        return StepHealth(finite=bool(v[0]), grad_norm=float(v[1]),
                          loss=float(v[2]), ok=bool(v[3]))

    def aot_compile(self, *batch):
        """Lower + compile this step WITHOUT executing it (the memory
        planner's entry point, paddle_tpu.memory.plan_train_step):
        returns the jax Compiled object, whose ``memory_analysis()``
        prices the program's HBM before anything runs.

        Every operand is passed as an aval (ShapeDtypeStruct) — params
        and buffers from the live model's shapes, optimizer state via
        ``eval_shape`` over ``functional_state`` — so candidate configs
        can be compiled back to back without allocating a single device
        buffer. ``batch`` may be Tensors, arrays, or ShapeDtypeStructs.
        (ShardedTrainStep's ``_prepare_batch`` hook still places model +
        opt state on the mesh so the lowered program matches a real
        step's shardings — the zero-allocation guarantee is for the
        single-program TrainStep the planner drives.)"""
        return timed_lower_compile(
            self._compiled_fn(), self._compile_label(),
            *self._aot_operands(*batch))

    def lowered_text(self, *batch):
        """StableHLO text of this step's program for ``batch`` (Tensors,
        arrays or ShapeDtypeStructs): trace + lower, no compile and no
        execution. Every Mosaic kernel in the step appears as a
        ``tpu_custom_call`` carrying its ``kernel_name`` — how
        chip_smoke.py proves the Pallas kernels are IN the program
        rather than replaced by a reference."""
        return self._compiled_fn().trace(
            *self._aot_operands(*batch)).lower().as_text()

    def _compiled_fn(self):
        if self._compiled is None:
            self._build()
        return self._compiled

    def _aot_operands(self, *batch):
        """The step's operands as avals, for lowering without buffers
        (call after :meth:`_compiled_fn`: the build names the params)."""
        raw_batch = self._prepare_batch(_unwrap_tensors(batch))

        def aval(a):
            # keep a MESH placement (ShardedTrainStep places batch/state
            # with NamedShardings via _prepare_batch — the lowered
            # program must see the same placements a real step would).
            # A plain single-device array carries none into a real
            # dispatch either: pinning its SingleDeviceSharding here
            # would annotate every operand, so the priced program and
            # the dispatched one would differ in text and never share a
            # compile-cache entry.
            sh = getattr(a, "sharding", None)
            if isinstance(sh, jax.sharding.NamedSharding):
                return jax.ShapeDtypeStruct(tuple(a.shape),
                                            jnp.dtype(a.dtype), sharding=sh)
            return jax.ShapeDtypeStruct(tuple(a.shape), jnp.dtype(a.dtype))

        entries = self.model.state_dict()
        params = {n: aval(entries[n]._data) for n in self._param_names}
        buffers = {n: aval(entries[n]._data) for n in self._buffer_names}
        if self._opt_state is not None:
            opt_state = tree_util.tree_map(aval, self._opt_state)
        else:
            opt_state = jax.eval_shape(self._functional_state, params)
        lr = self.optimizer.get_lr()
        guard_aval = jax.ShapeDtypeStruct((4,), jnp.float32)
        key_arr = aval(framework.next_rng_key())
        batch_avals = tree_util.tree_map(aval, raw_batch)
        return (params, buffers, opt_state, lr, guard_aval, key_arr,
                batch_avals)

    def memory_stats(self, *batch):
        """XLA buffer-assignment stats for this step's program: dict of
        argument/output/temp bytes (CompiledMemoryStats). Lowers and
        compiles ahead-of-time without executing (aot_compile) — meant
        for small trial programs (the auto_tuner's measure mode) and the
        memory planner, not the training hot path."""
        return _memory_record(self.aot_compile(*batch))

    def aot_report(self, *batch):
        """One AOT compile, both pricing surfaces: ``(memory, cost)``
        where ``memory`` is the :meth:`memory_stats` dict and ``cost``
        the :func:`compiled_cost_summary` roofline record (or None when
        the executable exposes no cost analysis). The layout autotuner
        (memory/autotune.py) scores every candidate from this — calling
        memory_stats and a separate cost pass would pay the
        lower+compile twice per candidate."""
        compiled = self.aot_compile(*batch)
        return _memory_record(compiled), compiled_cost_summary(compiled)

    def _prepare_batch(self, raw_batch):
        """Hook: sharded subclasses place batch arrays on the mesh so the
        lowered program sees the same input shardings as a real step."""
        return raw_batch

    def _functional_state(self, params):
        """Layout hook: fresh functional slots for this step. The ZeRO
        ShardedTrainStep overrides it to create flat dp-sharded slots
        for chunk-updated params (distributed/collectives/zero)."""
        return self.optimizer.functional_state(params)

    def _init_opt_state(self, params):
        """Fresh functional slots, seeded from any eager slots already on
        the optimizer — the checkpoint-restore path: set_state_dict fills
        optimizer._slots, and a resumed compiled step must continue from
        those moments, not from zeros (reference resume semantics:
        opt.set_state_dict before the next train_batch)."""
        state = self._functional_state(params)
        entries = self.model.state_dict()
        for n in self._param_names:
            slots = self.optimizer._slots.get(id(entries[n]))
            if slots:
                pshape = tuple(entries[n]._data.shape)
                st = dict(state[n])
                for k, v in slots.items():
                    if k not in st:
                        continue
                    arr = jnp.asarray(v._data if isinstance(v, Tensor)
                                      else v)
                    adapted = self._adapt_restored_slot(arr, st[k], n,
                                                        pshape)
                    if adapted is None:
                        continue  # incompatible layout: keep fresh slots
                    # COPY: the compiled step donates opt state
                    # (donate_argnums) — seeding by reference would let
                    # the first step delete the eager slot buffers and
                    # the checkpoint arrays they share
                    st[k] = jnp.array(adapted, copy=True)
                state[n] = st
        return state

    def _adapt_restored_slot(self, arr, tgt, pname, pshape):
        """Shape-adapt one restored eager slot ``arr`` to the functional
        target ``tgt``, or None to keep the fresh slot. The ONE place
        the slot-layout conversion rules live (the ZeRO
        ShardedTrainStep overrides it for the flat dp-sharded layout,
        docs/ZERO.md checkpoint contract). Base rules: identical shapes
        pass through; a ZeRO flat ``[padded]`` slot un-pads losslessly
        into a param-shaped target (the flat layout is exactly
        flatten + zero-pad)."""
        import numpy as _np

        if tuple(arr.shape) == tuple(tgt.shape):
            return arr
        pnumel = int(_np.prod(pshape)) if pshape else 1
        if (arr.ndim == 1 and arr.size >= pnumel
                and tuple(tgt.shape) == pshape):
            return arr[:pnumel].reshape(pshape)
        return None

    def sync_optimizer_state(self):
        """Push functional opt state back into the eager optimizer slots."""
        if self._opt_state is None:
            return
        entries = self.model.state_dict()
        for n in self._param_names:
            p = entries[n]
            self.optimizer._slots[id(p)] = self._opt_state[n]


# ---------------------------------------------------------------------------
# jit.save / jit.load: serialized-program inference artifact
# (capability slot: fluid/jit + inference AnalysisPredictor program files —
#  analysis_predictor.h:101. NO pickled Python objects: the artifact is a
#  serialized StableHLO program + raw weight bytes, loadable in a process
#  that has never seen the model's class.)
# ---------------------------------------------------------------------------
_ARTIFACT_VERSION = 1


def _pack_weights(weights, names):
    """Shared artifact weight packing (used by jit.save and
    inference.convert_to_mixed_precision — one format, one writer)."""
    import numpy as np

    packed, params_meta = {}, []
    for i, (n, w) in enumerate(zip(names, weights)):
        a = np.asarray(w)
        packed[f"w{i}"] = np.frombuffer(a.tobytes(), np.uint8)
        # self-describing sidecar keys: the npz alone decodes without the
        # meta json (static.deserialize_persistables relies on this)
        packed[f"w{i}_name"] = np.asarray(n)
        packed[f"w{i}_dtype"] = np.asarray(str(a.dtype))
        packed[f"w{i}_shape"] = np.asarray(list(a.shape), np.int64)
        params_meta.append({"name": n, "dtype": str(a.dtype),
                            "shape": list(a.shape)})
    return packed, params_meta


def _encode_struct(tree, counter):
    """JSON-able description of an output pytree; leaves become indices."""
    if isinstance(tree, (list, tuple)):
        return {"kind": "tuple" if isinstance(tree, tuple) else "list",
                "items": [_encode_struct(t, counter) for t in tree]}
    if isinstance(tree, dict):
        return {"kind": "dict",
                "keys": sorted(tree),
                "items": [_encode_struct(tree[k], counter) for k in sorted(tree)]}
    if tree is None:
        return {"kind": "none"}
    i = counter[0]
    counter[0] += 1
    return {"kind": "leaf", "index": i}


def _decode_struct(desc, leaves):
    k = desc["kind"]
    if k == "leaf":
        return leaves[desc["index"]]
    if k == "none":
        return None
    if k == "dict":
        return {key: _decode_struct(d, leaves)
                for key, d in zip(desc["keys"], desc["items"])}
    items = [_decode_struct(d, leaves) for d in desc["items"]]
    return tuple(items) if k == "tuple" else items


def _input_avals(input_spec, layer):
    import numpy as np

    from ..static import InputSpec

    specs = input_spec
    if specs is None:
        specs = getattr(layer, "_last_call_spec", None)
        if specs is None:
            raise ValueError(
                "jit.save needs input_spec (or call the layer once first so "
                "its input signature is recorded)")
    if isinstance(specs, (InputSpec, Tensor)):
        specs = [specs]
    avals = []
    scope = None
    sym_count = [0]

    def _sym_shape(dims):
        """InputSpec None/-1 dims become jax.export symbolic dims, so the
        artifact serves any batch size (reference: dynamic-axis InputSpec)."""
        nonlocal scope
        from jax import export as jax_export

        names = []
        for d in dims:
            if d is None or (isinstance(d, int) and d < 0):
                names.append(f"_dyn{sym_count[0]}")
                sym_count[0] += 1
            else:
                names.append(str(int(d)))
        spec_str = ", ".join(names) if names else ""
        if scope is None:
            scope = jax_export.SymbolicScope()
        return jax_export.symbolic_shape(spec_str, scope=scope)

    for s in specs:
        if isinstance(s, InputSpec):
            dims = list(s.shape)
            if any(d is None or (isinstance(d, int) and d < 0) for d in dims):
                shape = _sym_shape(dims)
            else:
                shape = tuple(int(d) for d in dims)
            avals.append(jax.ShapeDtypeStruct(tuple(shape),
                                              jnp.dtype(_np_dtype(s.dtype))))
        elif isinstance(s, Tensor):
            avals.append(jax.ShapeDtypeStruct(tuple(s.shape), s._data.dtype))
        elif isinstance(s, tuple) and len(s) == 2:  # recorded (shape, dtype)
            avals.append(jax.ShapeDtypeStruct(tuple(s[0]), jnp.dtype(s[1])))
        else:
            a = np.asarray(s)
            avals.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
    return avals


def _np_dtype(d):
    from .. import dtypes as _dt

    return _dt.to_np(d)


def save(layer, path, input_spec=None, **configs):
    """Serialize `layer` into a class-free inference artifact.

    Writes {path}.pdmodel (StableHLO program over (weights, *inputs)),
    {path}.pdiparams (raw weight bytes), {path}.pdmeta.json (names, input
    avals, output structure).
    """
    import json
    import os

    import numpy as np
    from jax import export as jax_export

    if isinstance(layer, _StaticLayerProxy):
        layer = layer._layer
    if isinstance(layer, StaticFunction):
        layer = layer._layer
    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects a Layer (or to_static Layer)")

    avals = _input_avals(input_spec, layer)
    entries = layer.state_dict()
    names = sorted(entries)
    weights = [entries[n]._data for n in names]

    was_training = layer.training
    layer.eval()
    try:
        # discover the output structure, then export a flat-output program
        def run(state_list, *inputs):
            state = dict(zip(names, state_list))
            out, _ = functional_call(layer, state, *inputs)
            return out

        out_shape = jax.eval_shape(run, weights, *avals)
        counter = [0]
        struct = _encode_struct(out_shape, counter)

        def pure(state_list, *inputs):
            out = run(state_list, *inputs)
            return tuple(tree_util.tree_leaves(out))

        # platform-polymorphic artifact (cpu dev / tpu)
        exported = jax_export.export(
            jax.jit(pure), platforms=("cpu", "tpu"))(weights, *avals)
        blob = exported.serialize()
    finally:
        if was_training:
            layer.train()

    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    packed, params_meta = _pack_weights(weights, names)
    with open(path + ".pdiparams", "wb") as f:
        np.savez(f, **packed)
    meta = {
        "version": _ARTIFACT_VERSION,
        "params": params_meta,
        "inputs": [{"shape": [d if isinstance(d, int) else -1
                              for d in a.shape],
                    "dtype": str(a.dtype)}
                   for a in avals],
        "input_names": [getattr(s, "name", None) or f"input_{i}"
                        for i, s in enumerate(input_spec or avals)],
        "outputs": struct,
    }
    with open(path + ".pdmeta.json", "w") as f:
        json.dump(meta, f)


def load_artifact(path, params_file=None):
    """(exported_program, weights[list of jax arrays], meta) from jit.save files.

    `path` is the save prefix; `params_file` overrides the default
    `{path}.pdiparams` (the reference Config takes them separately)."""
    import json

    import numpy as np
    from jax import export as jax_export

    import os

    if not os.path.exists(path + ".pdmeta.json"):
        raise FileNotFoundError(
            f"{path}.pdmeta.json not found — not a paddle_tpu jit.save "
            "artifact (models saved before the serialized-program format "
            "must be re-saved with jit.save)")
    with open(path + ".pdmeta.json") as f:
        meta = json.load(f)
    if meta.get("version") != _ARTIFACT_VERSION:
        raise ValueError(
            f"artifact version {meta.get('version')} != supported "
            f"{_ARTIFACT_VERSION}; re-save the model with this release")
    with open(path + ".pdmodel", "rb") as f:
        blob = f.read()
    if blob[:1] == b"\x80":  # pickle protocol header = legacy jit.save file
        raise ValueError(
            f"{path}.pdmodel is a legacy pickled model; re-save with the "
            "current jit.save (serialized-program artifact)")
    exported = jax_export.deserialize(bytearray(blob))
    import ml_dtypes  # noqa: F401  (registers bfloat16 et al with numpy)

    weights = []
    with np.load(params_file or path + ".pdiparams",
                 allow_pickle=False) as z:
        for i, pm in enumerate(meta["params"]):
            raw = z[f"w{i}"].tobytes()
            a = np.frombuffer(raw, dtype=np.dtype(pm["dtype"])).reshape(
                pm["shape"])
            weights.append(jnp.asarray(a))
    return exported, weights, meta


def load(path, **configs):
    return TranslatedLayer._construct(path)


class TranslatedLayer(Layer):
    """A loaded jit.save artifact (parity: jit/translated_layer.py) — runs the
    serialized program; the original Python class is not needed."""

    def __init__(self, exported, weights, meta):
        super().__init__()
        self._exported = exported
        self._weights = list(weights)
        self._meta = meta
        self._run = jax.jit(exported.call)

    @staticmethod
    def _construct(model_path, configs=None):
        return TranslatedLayer(*load_artifact(model_path))

    def forward(self, *args):
        raw = [a._data if isinstance(a, Tensor) else jnp.asarray(a)
               for a in args]
        flat = self._run(self._weights, *raw)
        out = _decode_struct(self._meta["outputs"],
                             [Tensor(l) for l in flat])
        return out

    # weights live outside Layer's parameter machinery; expose the standard
    # state-dict surface directly
    def state_dict(self, *a, **kw):
        return {pm["name"]: Tensor(w)
                for pm, w in zip(self._meta["params"], self._weights)}

    def set_state_dict(self, state_dict, *a, **kw):
        for i, pm in enumerate(self._meta["params"]):
            v = state_dict.get(pm["name"])
            if v is not None:
                arr = v._data if isinstance(v, Tensor) else jnp.asarray(v)
                self._weights[i] = arr.astype(self._weights[i].dtype)

    def program(self):  # compat: the loaded "program" is the exported module
        return self._exported


def set_code_level(level=100, also_to_stdout=False):
    pass  # SOT bytecode logging has no analogue: tracing is the capture


def set_verbosity(level=0, also_to_stdout=False):
    pass
