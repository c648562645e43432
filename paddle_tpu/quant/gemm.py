"""Scaled low-precision GEMMs with delayed scaling: the quantized-compute core.

Every prior quantization in this repo wraps the matmuls — int8 activation
saves (memory/int8_ckpt), the int8 LM head, quantized collectives, int8
param gathers, int8 paged KV. This module quantizes the matmuls themselves:
per-tensor scaled fp8 (e4m3) forward GEMMs — int8 fallback where the
platform can't dot fp8 — with the backward kept wide and exact via
``custom_vjp``, so master weights and grad accumulation never see narrow
dtypes. The contract:

* **forward narrow**: ``out = dequant(q(x/sx) @ q(w/sw)) * sx * sw`` with
  the accumulator wide (f32 for fp8, int32 for int8);
* **backward wide**: ``dx = g @ w.T``, ``dw = x.T @ g`` in f32 against the
  *original* operands — AD never differentiates through round/clip, and the
  scales get zero cotangents;
* **delayed scaling**: scales come from a short per-(site, operand) amax
  history (`PTPU_QUANT_AMAX_HIST`, default 4) threaded through the model as
  a persistable buffer, so they ride ``TrainStep``/``ShardedTrainStep``,
  ``StepGuard`` skip/rollback, and ``CheckpointManager`` exactly like the
  RNG-key chain. The first step bootstraps from the current amax (history
  all-zero) so step 0 is not catastrophically mis-scaled.

Engagement mirrors the int8-head discipline: ``quant:<site>`` entries in
the existing ``names:`` recompute-policy syntax request sites per layer;
``PTPU_QUANT_COMPUTE`` forces (``0`` is the structural escape hatch — no
amax buffer is created, programs are bit-identical to pre-quant builds);
unset, a cached numeric parity probe must pass (drift → loud default-off,
and CPU backends default off). See docs/QUANT.md for the full matrix.
"""
from __future__ import annotations

import functools
import os
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from ..memory.int8_ckpt import SCALE_EPS, quantize_rows_int8

#: saturation bound of float8_e4m3fn (no inf encoding — values past this
#: become NaN on cast, so operands are clamped first)
E4M3_MAX = 448.0
INT8_MAX = 127.0

#: the seven narrow-quantizable GEMM sites of one decoder block, in
#: ``models/gpt.py::_block_pure`` order. Index into the amax state's site
#: axis is ``GEMM_SITES.index(site)``.
GEMM_SITES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")

#: ``quant:`` policy-entry aliases expanding to site groups
SITE_ALIASES = {
    "attn": ("wq", "wk", "wv", "wo"),
    "ffn": ("wg", "wu", "wd"),
    "all": GEMM_SITES,
}

#: env knobs that change quant-compute decisions — every plan/bench cache
#: key must carry these (the PR 2 staleness class)
QUANT_KNOBS = (
    "PTPU_QUANT_COMPUTE",
    "PTPU_QUANT_DTYPE",
    "PTPU_QUANT_AMAX_HIST",
    "PTPU_QUANT_GATE_TOL",
    "PTPU_QUANT_PARAM_GATHER",
    "PTPU_INT8_WEIGHTS",
)

_OFF_VALUES = ("", "0", "off", "false")


def cache_key_knobs():
    """Tuple of (knob, value) for every quant env knob, for cache keys."""
    return tuple((k, os.environ.get(k, "")) for k in QUANT_KNOBS)


# ---------------------------------------------------------------------------
# dtype resolution


_FP8_DOT_OK = [None]


def fp8_dot_supported():
    """Whether this backend can dot float8_e4m3fn operands (cached probe)."""
    if _FP8_DOT_OK[0] is None:
        try:
            a = jnp.asarray(np.ones((8, 8), np.float32)).astype(
                jnp.float8_e4m3fn)
            out = jnp.matmul(a, a, preferred_element_type=jnp.float32)
            _FP8_DOT_OK[0] = bool(np.isfinite(np.asarray(out)).all())
        except Exception:  # noqa: BLE001 - any failure means "no fp8 here"
            _FP8_DOT_OK[0] = False
    return _FP8_DOT_OK[0]


def quant_dtype():
    """Resolve the narrow GEMM dtype: ``PTPU_QUANT_DTYPE`` = fp8 | int8 |
    auto (default). ``auto`` picks e4m3 where the platform can dot it and
    falls back to int8 elsewhere."""
    env = os.environ.get("PTPU_QUANT_DTYPE", "auto").strip().lower()
    if env in ("fp8", "int8"):
        return env
    if env not in ("auto", ""):
        raise ValueError(
            f"PTPU_QUANT_DTYPE={env!r}: expected fp8, int8 or auto")
    return "fp8" if fp8_dot_supported() else "int8"


def dtype_max(dtype):
    return E4M3_MAX if dtype == "fp8" else INT8_MAX


# ---------------------------------------------------------------------------
# the scaled GEMM: narrow forward, wide exact backward


def _narrow_matmul(dtype, x, w, sx, sw):
    xf = x.astype(jnp.float32) / sx
    wf = w.astype(jnp.float32) / sw
    if dtype == "fp8":
        xq = jnp.clip(xf, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
        wq = jnp.clip(wf, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
        acc = jnp.matmul(xq, wq, preferred_element_type=jnp.float32)
    else:
        xq = jnp.clip(jnp.round(xf), -INT8_MAX, INT8_MAX).astype(jnp.int8)
        wq = jnp.clip(jnp.round(wf), -INT8_MAX, INT8_MAX).astype(jnp.int8)
        acc = jnp.matmul(xq, wq,
                         preferred_element_type=jnp.int32).astype(jnp.float32)
    return (acc * (sx * sw)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scaled_matmul(dtype, x, w, sx, sw):
    """``x @ w`` computed narrow (fp8/int8) with per-tensor scales sx/sw.

    The vjp is the *wide* exact rule against the original operands — the
    quantization noise is forward-only, grads and master weights stay
    exact (the "forward narrow, backward wide" contract)."""
    return _narrow_matmul(dtype, x, w, sx, sw)


def _scaled_matmul_fwd(dtype, x, w, sx, sw):
    return _narrow_matmul(dtype, x, w, sx, sw), (x, w)


def _scaled_matmul_bwd(dtype, res, g):
    del dtype
    x, w = res
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    dx = jnp.matmul(gf, jnp.swapaxes(wf, -1, -2)).astype(x.dtype)
    xt = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    dw = jnp.matmul(xt.T, gf.reshape(-1, g.shape[-1])).astype(w.dtype)
    return (dx, dw, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))


_scaled_matmul.defvjp(_scaled_matmul_fwd, _scaled_matmul_bwd)


def amax_hist_len():
    """Delayed-scaling history length (``PTPU_QUANT_AMAX_HIST``, min 1)."""
    return max(int(os.environ.get("PTPU_QUANT_AMAX_HIST", "4")), 1)


def scaled_gemm(x, w, hist_x, hist_w, *, dtype=None):
    """Delayed-scaling scaled GEMM.

    ``hist_x`` / ``hist_w`` are ``[H]`` f32 amax-history rows (most recent
    first). Scales come from the history max; an all-zero history (fresh
    state) bootstraps from the current step's amax so the first step is
    sanely scaled. Returns ``(out, new_hist_x, new_hist_w)`` — the caller
    threads the shifted histories back into its amax state.
    """
    dtype = dtype or quant_dtype()
    dmax = dtype_max(dtype)
    ax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)).astype(jnp.float32))
    aw = jax.lax.stop_gradient(jnp.max(jnp.abs(w)).astype(jnp.float32))
    hx_max = jnp.max(hist_x)
    hw_max = jnp.max(hist_w)
    eff_x = jnp.where(hx_max > 0, hx_max, ax)
    eff_w = jnp.where(hw_max > 0, hw_max, aw)
    sx = jnp.maximum(eff_x / dmax, SCALE_EPS)
    sw = jnp.maximum(eff_w / dmax, SCALE_EPS)
    out = _scaled_matmul(dtype, x, w, sx, sw)
    new_hx = jnp.concatenate([ax[None], hist_x[:-1]])
    new_hw = jnp.concatenate([aw[None], hist_w[:-1]])
    return out, new_hx, new_hw


def inline_scaled_gemm(x, w, *, dtype=None):
    """One-shot scaled GEMM with inline (current-step) absmax scales — the
    delayed-scaling entry with an empty history, for callers that carry no
    state (incubate fp8_gemm)."""
    h = jnp.zeros((1,), jnp.float32)
    out, _, _ = scaled_gemm(x, w, h, h, dtype=dtype)
    return out


# ---------------------------------------------------------------------------
# per-layer amax state + the trace-time context the decoder block uses


def init_amax_state(num_layers, hist=None):
    """Fresh delayed-scaling state: f32 zeros ``[L, n_sites, 2, H]``
    (2 = x/w operand rows). All-zero rows mean "bootstrap from current"."""
    h = amax_hist_len() if hist is None else int(hist)
    return np.zeros((int(num_layers), len(GEMM_SITES), 2, h), np.float32)


class GemmQuantCtx:
    """Per-trace context for one decoder layer's scaled GEMMs.

    Holds the layer's amax slice ``[n_sites, 2, H]``, routes engaged sites
    through :func:`scaled_gemm`, and collects the updated histories so the
    block can return them as explicit outputs (``jax.checkpoint`` purity —
    the scan threads them back into the stacked buffer).
    """

    def __init__(self, sites, amax_layer, dtype):
        self.sites = frozenset(sites)
        self.dtype = dtype
        self._amax = amax_layer
        self._new = {}

    def gemm(self, x, w, site):
        if site not in self.sites:
            return x @ w
        i = GEMM_SITES.index(site)
        out, nhx, nhw = scaled_gemm(
            x, w, self._amax[i, 0], self._amax[i, 1], dtype=self.dtype)
        self._new[site] = jnp.stack([nhx, nhw])
        return out

    def collect(self):
        """Updated ``[n_sites, 2, H]`` state: new histories for sites that
        ran, passthrough rows for the rest."""
        rows = []
        for i, s in enumerate(GEMM_SITES):
            rows.append(self._new.get(s, self._amax[i]))
        return jnp.stack(rows)


# ---------------------------------------------------------------------------
# policy parsing: quant:<site> entries in the names: syntax


def split_quant_entries(spec):
    """Split ``quant:<site>`` entries out of a ``names:`` policy payload.

    ``"attn_q,int8:resid_mid,quant:attn"`` ->
    ``("attn_q,int8:resid_mid", frozenset({"wq","wk","wv","wo"}))``.
    The remainder feeds ``parse_save_names`` unchanged; sites accept the
    block's GEMM names (wq wk wv wo wg wu wd) or the aliases attn/ffn/all.
    """
    rest, sites = [], set()
    for raw in str(spec).split(","):
        nm = raw.strip()
        if not nm:
            continue
        if nm.startswith("quant:"):
            site = nm[len("quant:"):].strip()
            if not site:
                raise ValueError(f"empty quant: entry in remat names {spec!r}")
            if site in SITE_ALIASES:
                sites.update(SITE_ALIASES[site])
            elif site in GEMM_SITES:
                sites.add(site)
            else:
                raise ValueError(
                    f"quant:{site}: unknown GEMM site — expected one of "
                    f"{GEMM_SITES} or aliases {tuple(SITE_ALIASES)} "
                    "(docs/QUANT.md)")
        else:
            rest.append(nm)
    return ",".join(rest), frozenset(sites)


def quant_sites_from_policy(policy):
    """The quant sites a recompute policy requests (``names:`` only — the
    coarse dots/attn policies carry no quant syntax)."""
    if isinstance(policy, str) and policy.startswith("names:"):
        _, sites = split_quant_entries(policy[len("names:"):])
        return sites
    return frozenset()


# ---------------------------------------------------------------------------
# parity gate (int8-head discipline) + engagement resolution


_GATE_CACHE = {}


def _gate_probe(tol, dtype):
    """Deterministic parity probe: a scaled GEMM chain's loss and grads vs
    the exact bf16-free f32 reference, on skewed inputs."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((128, 64)) *
                     rng.uniform(0.05, 3.0, (128, 64))).astype(np.float32))

    def loss_exact(xx, ww):
        return jnp.mean(jnp.square(xx @ ww))

    def loss_quant(xx, ww):
        h = jnp.zeros((amax_hist_len(),), jnp.float32)
        out, _, _ = scaled_gemm(xx, ww, h, h, dtype=dtype)
        return jnp.mean(jnp.square(out))

    le, (gxe, gwe) = jax.value_and_grad(loss_exact, argnums=(0, 1))(x, w)
    lq, (gxq, gwq) = jax.value_and_grad(loss_quant, argnums=(0, 1))(x, w)
    le, lq = float(le), float(lq)
    loss_err = abs(lq - le) / max(abs(le), 1e-9)

    def _gerr(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.mean(np.abs(a - b)) / max(np.mean(np.abs(b)), 1e-9))

    grad_err = max(_gerr(gxq, gxe), _gerr(gwq, gwe))
    ok = bool(np.isfinite(lq)) and loss_err < tol and grad_err < 5 * tol
    return ok, loss_err, grad_err


def quant_gate_report(tol=None, dtype=None):
    """Run (or fetch the cached) parity probe: dict with ``ok``, ``tol``,
    ``max_rel_err``, ``dtype``. Measured drift warns and reports not-ok
    (default-off); a probe that crashes raises — same contract as
    ``int8_head_gate``."""
    if tol is None:
        tol = float(os.environ.get("PTPU_QUANT_GATE_TOL", "0.02"))
    dtype = dtype or quant_dtype()
    key = (round(tol, 9), dtype)
    if key not in _GATE_CACHE:
        # the first caller is usually the model's own trace (engagement
        # resolves at trace time): evaluate the probe eagerly there too,
        # or its values are tracers of the step being traced
        with jax.ensure_compile_time_eval():
            ok, loss_err, grad_err = _gate_probe(tol, dtype)
        if not ok:
            warnings.warn(
                "quant-compute parity probe drift (loss "
                f"{loss_err:.4f} vs tol={tol}, grad {grad_err:.4f} vs "
                f"{5 * tol}) for dtype={dtype}; scaled GEMMs stay OFF "
                "(force with PTPU_QUANT_COMPUTE=1, or raise "
                "PTPU_QUANT_GATE_TOL)", RuntimeWarning, stacklevel=2)
        _GATE_CACHE[key] = {"ok": ok, "tol": tol, "loss_rel_err": loss_err,
                            "grad_rel_err": grad_err, "grad_tol": 5 * tol,
                            "dtype": dtype}
    return _GATE_CACHE[key]


def quant_gate(tol=None, dtype=None):
    """True iff the cached parity probe passed."""
    return quant_gate_report(tol, dtype)["ok"]


def quant_compute_forced():
    """``PTPU_QUANT_COMPUTE`` set to a truthy value (explicit force-on)."""
    env = os.environ.get("PTPU_QUANT_COMPUTE")
    return env is not None and env.strip().lower() not in _OFF_VALUES


def quant_compute_enabled(requested=False):
    """Master decision, int8-head shaped: ``PTPU_QUANT_COMPUTE`` set
    forces the answer either way; unset, quant runs only when *requested*
    (policy ``quant:`` entries), off CPU, and behind a passing parity
    gate."""
    env = os.environ.get("PTPU_QUANT_COMPUTE")
    if env is not None:
        return env.strip().lower() not in _OFF_VALUES
    if not requested:
        return False
    if jax.default_backend() == "cpu":
        return False
    return quant_gate()


def requested_quant_sites(cfg):
    """Build-time request resolution: which sites this config *asks* for.

    Decides amax-buffer creation, so it deliberately ignores the parity
    gate (a gate flake must not change checkpoint layout). The env force
    with no policy sites means "all"; the env escape hatch (``0``) means
    none — no buffer, programs structurally identical to pre-quant."""
    env = os.environ.get("PTPU_QUANT_COMPUTE")
    if env is not None and env.strip().lower() in _OFF_VALUES:
        return frozenset()
    sites = quant_sites_from_policy(getattr(cfg, "recompute_policy", None))
    if quant_compute_forced():
        return sites or frozenset(GEMM_SITES)
    return sites


def engaged_quant_sites(cfg):
    """Trace-time engagement: requested sites, gated by
    :func:`quant_compute_enabled` (parity probe / CPU default-off)."""
    sites = requested_quant_sites(cfg)
    if not sites:
        return frozenset()
    if not quant_compute_enabled(requested=True):
        return frozenset()
    return sites


# ---------------------------------------------------------------------------
# serving: int8 resident weights + dequant-free int8 x int8 -> int32 GEMM


def quantize_weight_cols_int8(w, eps=SCALE_EPS):
    """Per-output-channel absmax int8 over the contraction axis (-2): one
    f32 scale per output column, so the dequant of ``x_q @ W_q`` is a
    rank-1 rescale (``* sx * sw``) — no per-element dequant pass. Returns
    ``(codes int8 [..., h, n], scales f32 [..., 1, n])``."""
    wf = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / INT8_MAX,
                    eps)
    q = jnp.clip(jnp.round(wf / s), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, s


def int8_weight_matmul(x, codes, scales):
    """``x @ W`` with W pre-quantized by :func:`quantize_weight_cols_int8`:
    activations quantize per-row on the fly, the GEMM runs int8 x int8 with
    an int32 accumulator, and the f32 result is rescaled separably by the
    row scales and the per-column weight scales."""
    xq, sx = quantize_rows_int8(x)
    acc = jnp.matmul(xq, codes, preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * scales).astype(x.dtype)


def _int8_weights_probe_ok():
    """Round-trip probe on skewed per-column magnitudes: the int8 weight
    GEMM must track the exact product within a few percent."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    w *= rng.uniform(0.01, 8.0, (1, 48)).astype(np.float32)
    x = rng.standard_normal((4, 64)).astype(np.float32)
    exact = np.asarray(jnp.asarray(x) @ jnp.asarray(w))
    codes, scales = quantize_weight_cols_int8(jnp.asarray(w))
    got = np.asarray(int8_weight_matmul(jnp.asarray(x), codes, scales))
    if not np.isfinite(got).all():
        return False
    err = np.mean(np.abs(got - exact)) / max(np.mean(np.abs(exact)), 1e-9)
    return bool(err < 0.05)


_INT8_W_PROBE = [None]


def int8_weights_enabled(requested=False):
    """Serving int8-resident-weights gate, shaped like ``int8_kv_enabled``:
    ``PTPU_INT8_WEIGHTS`` forces either way; unset, the engine's request is
    honoured only behind a passing round-trip probe (failure warns loudly
    and falls back to exact weights)."""
    env = os.environ.get("PTPU_INT8_WEIGHTS")
    if env is not None:
        return env.strip().lower() not in _OFF_VALUES
    if not requested:
        return False
    if _INT8_W_PROBE[0] is None:
        try:
            _INT8_W_PROBE[0] = _int8_weights_probe_ok()
        except Exception as e:  # noqa: BLE001
            warnings.warn(f"int8-weights probe crashed ({e!r}); serving "
                          "weights stay exact", RuntimeWarning, stacklevel=2)
            _INT8_W_PROBE[0] = False
    if not _INT8_W_PROBE[0]:
        warnings.warn(
            "int8-weights round-trip probe failed on this backend; serving "
            "weights stay exact (force with PTPU_INT8_WEIGHTS=1)",
            RuntimeWarning, stacklevel=2)
        return False
    return True


# ---------------------------------------------------------------------------
# bench probes: reference-free loss-drift A/B for the QUANT gate


def loss_drift_probe(dtype=None, steps=8, lr=0.05):
    """Tiny deterministic training A/B: fit a 2-GEMM regression with exact
    vs scaled GEMMs (delayed scaling threaded across steps) and return the
    relative final-loss drift. This is the embedded bf16 reference probe
    the bench ``"quant"`` block and tools/bench_gate.py QUANT gate consume
    — self-contained, no baseline file needed."""
    dtype = dtype or quant_dtype()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
    w1_0 = jnp.asarray((rng.standard_normal((64, 64)) * 0.1).astype(np.float32))
    w2_0 = jnp.asarray((rng.standard_normal((64, 32)) * 0.1).astype(np.float32))
    hlen = amax_hist_len()

    def run(quantized):
        w1, w2 = w1_0, w2_0
        hist = jnp.zeros((2, 2, hlen), jnp.float32)

        def loss_fn(w1, w2, hist):
            if quantized:
                h1, nh1x, nh1w = scaled_gemm(x, w1, hist[0, 0], hist[0, 1],
                                             dtype=dtype)
                out, nh2x, nh2w = scaled_gemm(jax.nn.relu(h1), w2,
                                              hist[1, 0], hist[1, 1],
                                              dtype=dtype)
                new_hist = jnp.stack([jnp.stack([nh1x, nh1w]),
                                      jnp.stack([nh2x, nh2w])])
            else:
                out = jax.nn.relu(x @ w1) @ w2
                new_hist = hist
            return jnp.mean(jnp.square(out - y)), new_hist

        loss = None
        for _ in range(steps):
            (loss, hist), (g1, g2) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(w1, w2, hist)
            w1 = w1 - lr * g1
            w2 = w2 - lr * g2
        return float(loss)

    le = run(False)
    lq = run(True)
    return abs(lq - le) / max(abs(le), 1e-9)


# ---------------------------------------------------------------------------
# telemetry: gemm_dtype_mode gauge + quant_gemm_flops_total counter


from .. import telemetry as _telemetry  # noqa: E402

#: 0 = wide (bf16/f32), 1 = int8, 2 = fp8 — per GEMM site and path
_GEMM_MODE = _telemetry.gauge(
    "gemm_dtype_mode",
    "Narrow-GEMM dtype per decoder site (0=wide, 1=int8, 2=fp8)",
    labelnames=("site", "path"))
_QUANT_FLOPS = _telemetry.counter(
    "quant_gemm_flops_total",
    "Cumulative forward FLOPs executed through narrow scaled GEMMs",
    labelnames=("dtype",))

_MODE_VALUE = {"int8": 1.0, "fp8": 2.0}

#: last engagement seen at trace time: (path, dtype, flops_per_token) —
#: TrainStep ticks the flops counter from it per step
_LAST_TRACE = [None]


def note_gemm_mode(path, sites, dtype, flops_per_token=0):
    """Record trace-time engagement: one ``gemm_dtype_mode`` series per
    site (0 for sites staying wide) and the per-token narrow-FLOP rate for
    the step counter."""
    mode = _MODE_VALUE.get(dtype, 0.0)
    for s in GEMM_SITES:
        _GEMM_MODE.set(mode if s in sites else 0.0, labels=(s, path))
    if sites:
        _LAST_TRACE[0] = (path, dtype, float(flops_per_token))
    elif _LAST_TRACE[0] is not None and _LAST_TRACE[0][0] == path:
        _LAST_TRACE[0] = None


def note_step_tokens(tokens):
    """Tick ``quant_gemm_flops_total`` for one executed step of ``tokens``
    tokens, using the FLOP rate recorded by the last engaged trace."""
    info = _LAST_TRACE[0]
    if info is None:
        return
    _, dtype, per_tok = info
    if per_tok > 0:
        _QUANT_FLOPS.inc(per_tok * float(tokens), labels=(dtype,))
