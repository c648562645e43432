"""Chunked, vocab-sharded cross-entropy LM head.

The dense LM-head loss materializes a fp32 ``[tokens, vocab]`` logits
tensor (and its grad twin in backward) — ~1GB per microbatch at
1.3B/seq2048/batch4, the single largest HBM+bandwidth consumer at that
scale. This module computes the same loss **blockwise over vocab
chunks**:

- forward: an online log-sum-exp scan over ``[tokens, chunk]`` logit
  blocks (running max + rescaled sum, plus the target-logit gather), so
  peak extra HBM is ``O(tokens * chunk)`` fp32;
- backward: a ``custom_vjp`` that *recomputes* each chunk's logits and
  contracts ``softmax_chunk - onehot_chunk`` directly into ``dh`` and the
  per-chunk ``dw`` rows — the ``[tokens, vocab]`` grad-logits tensor
  never exists either.

The **vocab-sharded** variant runs the same kernel per tensor-parallel
shard inside ``shard_map``: each shard computes its partial
(max, sumexp, target-logit) triple and the combine is a ``pmax``/``psum``
of *scalars per token* — never a logits all-gather (the fused
computation-collective discipline of arXiv:2305.06942; EQuARX
arXiv:2506.17615 quantizes the collective itself, here the collective is
already 3 floats/token). Both passes are hand-written shard_maps wrapped
in ONE outer ``custom_vjp`` — autodiff never transposes through the
collectives, so the gradients are exact on every jax version's shard_map
semantics.

The optional int8 head path (per-token-row scales on h, per-vocab-row
scales on w, straight-through backward through the REAL weights —
``incubate.nn.functional._int8_head_core``'s recipe) is **default-on when
a numeric parity gate passes** (:func:`int8_head_enabled`); env
``PTPU_INT8_HEAD`` forces it either way.

Knobs (docs/PERF.md):
- ``PTPU_CE_VCHUNK``: vocab chunk size (default 8192, clamped to vocab).
  Also a memory-planner plan dimension (``memory.Candidate.head_chunk``).
- ``PTPU_LOSS_HEAD``: force ``dense`` | ``chunked`` | ``sharded``.
- ``PTPU_INT8_HEAD``: "0" forces fp head, truthy forces int8; unset →
  the parity gate decides.
- ``PTPU_INT8_HEAD_GATE_TOL``: gate loss tolerance (default 0.02).
"""
from __future__ import annotations

import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from ... import telemetry as _telemetry
from ...core.dispatch import apply_op

DEFAULT_VOCAB_CHUNK = 8192

_HEAD_MODE = _telemetry.gauge(
    "loss_head_mode",
    "active LM-head loss path: 1 on the (mode, int8) series that produced "
    "the run's loss (mode: dense|chunked|sharded; int8: on|off)",
    labelnames=("mode", "int8"))
_HEAD_CHUNK_BYTES = _telemetry.gauge(
    "loss_head_chunk_bytes",
    "fp32 bytes of ONE [tokens, chunk] logits block resident per CE scan "
    "step (the chunked head's peak logits footprint; dense = the full "
    "[tokens, vocab] tensor)")


_LAST_HEAD_MODE = [None]


def record_head_mode(mode, int8, tokens, chunk):
    """Set the loss-head telemetry gauges (docs/TELEMETRY.md). Only one
    (mode, int8) series reads 1 at a time — the previously active series
    is zeroed, so an A/B that switches paths mid-process still names the
    path that produced the LAST number."""
    active = (mode, "on" if int8 else "off")
    prev = _LAST_HEAD_MODE[0]
    if prev is not None and prev != active:
        _HEAD_MODE.set(0, labels=prev)
    _HEAD_MODE.set(1, labels=active)
    _LAST_HEAD_MODE[0] = active
    _HEAD_CHUNK_BYTES.set(int(tokens) * int(chunk) * 4)


# ---------------------------------------------------------------------------
# int8-head parity gate
# ---------------------------------------------------------------------------
_GATE_CACHE = {}


def int8_head_gate(tol=None):
    """Run (once per tolerance) the int8-head parity probe: chunked CE
    loss + grads on a deterministic probe batch, fp vs int8. Passes when
    the loss shift is < ``tol`` (default 0.02, env
    ``PTPU_INT8_HEAD_GATE_TOL``) and both grad mean-abs errors are < 5x
    that. This is the default-on criterion for the int8 LM head."""
    if tol is None:
        tol = float(os.environ.get("PTPU_INT8_HEAD_GATE_TOL", "0.02"))
    if tol in _GATE_CACHE:
        return _GATE_CACHE[tol]

    def loss_grads(int8):
        rng = np.random.default_rng(0)
        h = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32) * 0.5)
        w = jnp.asarray(rng.standard_normal((256, 64)).astype(np.float32) * 0.5)
        y = jnp.asarray(rng.integers(0, 256, (64,)).astype(np.int32))

        def f(h, w):
            return chunked_lm_loss_arrays(h, w, y, vocab_chunk=64, int8=int8)

        l, (gh, gw) = jax.value_and_grad(f, argnums=(0, 1))(h, w)
        return float(l), np.asarray(gh), np.asarray(gw)

    # a probe that CRASHES raises (a broken int8 dot is a bug to see,
    # not a default to flip); only measured drift turns the head off.
    # The first caller is usually the loss's own trace: evaluate eagerly
    # there too, or the probe's values are tracers of that trace
    with jax.ensure_compile_time_eval():
        lf, ghf, gwf = loss_grads(False)
        l8, gh8, gw8 = loss_grads(True)
    ok = abs(l8 - lf) / max(abs(lf), 1e-9) < tol
    for g8, gf in ((gh8, ghf), (gw8, gwf)):
        denom = np.abs(gf).mean() + 1e-9
        ok = ok and (np.abs(g8 - gf).mean() / denom < 5 * tol)
    _GATE_CACHE[tol] = bool(ok)
    return _GATE_CACHE[tol]


def int8_head_enabled():
    """Resolve whether the int8 LM head is active: ``PTPU_INT8_HEAD``
    forces it ("0"/"" = off, anything else = on); unset, the parity gate
    (:func:`int8_head_gate`) decides — default-on when it passes. On the
    CPU backend the unforced default stays off: there is no int8 MXU rate
    to win, only quantization noise."""
    env = os.environ.get("PTPU_INT8_HEAD")
    if env is not None:
        return env not in ("", "0")
    import jax

    if jax.default_backend() == "cpu":
        return False
    return int8_head_gate()


# ---------------------------------------------------------------------------
# chunk-scan building blocks (shared by the unsharded + sharded kernels)
# ---------------------------------------------------------------------------
def _quantize_rows(a):
    """Per-row absmax int8: a [R, H] -> (int8 [R, H], f32 scale [R, 1])."""
    s = jnp.maximum(jnp.max(jnp.abs(a.astype(jnp.float32)), -1,
                            keepdims=True) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / s),
                 -127, 127).astype(jnp.int8)
    return q, s


def _chunk_logits(h, wc, int8, qh=None, sh=None):
    """One [N, c] fp32 logits block; int8 runs the quantized matmul
    (weight-chunk rows quantized in-loop — never a full int8 weight copy
    resident)."""
    if int8:
        qw, sw = _quantize_rows(wc)
        acc = jnp.einsum("nh,ch->nc", qh, qw,
                         preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sh * sw.T
    return jnp.einsum("nh,ch->nc", h, wc,
                      preferred_element_type=jnp.float32)


def _scan_stats(h, wp, y, off, *, n_chunks, chunk, vocab, int8):
    """Online-LSE scan over [N, chunk] logit blocks of ``wp`` ([K*c, H],
    zero-padded past ``vocab``): returns per-token (running max, rescaled
    sumexp, target-logit sum). ``off`` is this shard's global vocab
    offset (0 unsharded); labels outside [off, off+vocab) contribute no
    gold here (another shard owns them)."""
    qh = sh = None
    if int8:
        qh, sh = _quantize_rows(h)
    neg = jnp.float32(-np.inf)

    def body(carry, i):
        m, s, gold = carry
        wc = jax.lax.dynamic_slice_in_dim(wp, i * chunk, chunk, 0)
        logits = _chunk_logits(h, wc, int8, qh, sh)
        col = i * chunk + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < vocab, logits, neg)
        m_new = jnp.maximum(m, jnp.max(logits, -1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits - m_new[:, None]), -1))
        yl = y - off - i * chunk
        hit = (yl >= 0) & (yl < chunk)
        g = jnp.take_along_axis(
            logits, jnp.clip(yl, 0, chunk - 1)[:, None], 1)[:, 0]
        gold = gold + jnp.where(hit, g, 0.0)
        return (m_new, s, gold), None

    n = h.shape[0]
    init = (jnp.full((n,), neg), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return m, s, gold


def _scan_grads(h, wp, y, off, lse, coeff, *, n_chunks, chunk, vocab, int8):
    """Backward chunk scan: recompute each [N, c] logits block, contract
    ``(softmax - onehot) * coeff`` into (dh [N, H] f32, dw [K*c, H] f32).
    The grad-logits block dies with its scan iteration."""
    qh = sh = None
    if int8:
        qh, sh = _quantize_rows(h)
    hf = h.astype(jnp.float32)
    neg = jnp.float32(-np.inf)

    def body(dh, i):
        wc = jax.lax.dynamic_slice_in_dim(wp, i * chunk, chunk, 0)
        logits = _chunk_logits(h, wc, int8, qh, sh)
        col = i * chunk + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < vocab, logits, neg)
        p = jnp.exp(logits - lse[:, None])           # softmax block
        yl = (y - off)[:, None]
        onehot = (col[None, :] == yl) & (yl >= 0) & (yl < vocab)
        q = (p - onehot.astype(jnp.float32)) * coeff[:, None]
        # straight-through: contractions use the REAL operands even when
        # the forward logits were int8
        dh = dh + jnp.einsum("nc,ch->nh", q, wc.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dwc = jnp.einsum("nc,nh->ch", q, hf,
                         preferred_element_type=jnp.float32)
        return dh, dwc

    dh0 = jnp.zeros(h.shape, jnp.float32)
    dh, dwc = jax.lax.scan(body, dh0, jnp.arange(n_chunks))
    return dh, dwc.reshape(n_chunks * chunk, h.shape[1])


def resolve_vocab_chunk(vocab, vocab_chunk=None):
    """Effective chunk: explicit arg > PTPU_CE_VCHUNK > default, clamped
    to [1, vocab]."""
    c = vocab_chunk or int(os.environ.get("PTPU_CE_VCHUNK", "0")) \
        or DEFAULT_VOCAB_CHUNK
    return max(1, min(int(c), int(vocab)))


def _pad_rows(w2, rows):
    if w2.shape[0] == rows:
        return w2
    return jnp.concatenate(
        [w2, jnp.zeros((rows - w2.shape[0], w2.shape[1]), w2.dtype)])


# ---------------------------------------------------------------------------
# unsharded kernel: custom_vjp over the chunk scans
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _chunked_ce_fn(n_chunks, chunk, vocab, int8):
    """Masked-sum chunked CE for a static (K, c, V) chunking:
    f(h [N,H], wp [K*c,H] zero-padded, y [N] int32, mask [N] f32) -> sum.
    The full [N, vocab] logits/grad-logits tensor exists in NEITHER pass.
    """
    dims = dict(n_chunks=n_chunks, chunk=chunk, vocab=vocab, int8=int8)

    @jax.custom_vjp
    def ce_sum(h, wp, y, mask):
        m, s, gold = _scan_stats(h, wp, y, 0, **dims)
        return jnp.sum((m + jnp.log(s) - gold) * mask)

    def ce_fwd(h, wp, y, mask):
        m, s, gold = _scan_stats(h, wp, y, 0, **dims)
        lse = m + jnp.log(s)
        return jnp.sum((lse - gold) * mask), (h, wp, y, mask, lse)

    def ce_bwd(res, g):
        h, wp, y, mask, lse = res
        coeff = (g * mask).astype(jnp.float32)
        dh, dw = _scan_grads(h, wp, y, 0, lse, coeff, **dims)
        return (dh.astype(h.dtype), dw.astype(wp.dtype),
                np.zeros(y.shape, jax.dtypes.float0), jnp.zeros_like(mask))

    ce_sum.defvjp(ce_fwd, ce_bwd)
    return ce_sum


def chunked_ce_sum(h, w2, y, mask, *, vocab_chunk=None, int8=False):
    """Masked-sum chunked CE on arrays. h [N, H]; w2 [V, H] vocab-major;
    y [N] int; mask [N] f32. Divide by the mask count outside for the
    mean."""
    vocab = w2.shape[0]
    c = resolve_vocab_chunk(vocab, vocab_chunk)
    k = -(-vocab // c)
    fn = _chunked_ce_fn(k, c, vocab, bool(int8))
    # pad OUTSIDE the custom_vjp: jnp.pad's own vjp slices dw back to [V]
    return fn(_ensure_2d(h), _pad_rows(w2, k * c),
              y.astype(jnp.int32), mask)


def _ensure_2d(h):
    return h if h.ndim == 2 else h.reshape(-1, h.shape[-1])


# ---------------------------------------------------------------------------
# vocab-sharded kernel: custom_vjp AROUND hand-written shard_maps
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _sharded_ce_fn(mesh, axis, n_chunks, chunk, v_local, int8):
    """Masked-sum CE with w vocab-sharded over ``axis``: forward combines
    per-shard (max, sumexp, gold) via pmax/psum of per-token scalars;
    backward psums the per-shard dh partials in-ring and emits each
    shard's own dw rows. Both passes are explicit shard_maps — jax never
    differentiates through the collectives, so the semantics don't depend
    on shard_map's transpose rules.

    Each shard's vocab OFFSET rides in as a length-1 slice of a sharded
    iota (in_spec P(axis)) instead of ``lax.axis_index`` — axis_index
    lowers to a PartitionId instruction that this XLA rejects under
    partial-manual SPMD when auto axes remain."""
    from jax.sharding import PartitionSpec as P

    dims = dict(n_chunks=n_chunks, chunk=chunk, vocab=v_local, int8=int8)
    rows = n_chunks * chunk
    tp = int(mesh.shape[axis])
    # numpy, not jnp: the factory is cached across traces, so a staged
    # array here would leak a tracer out of its first jit scope
    offsets = np.arange(tp, dtype=np.int32) * v_local    # [tp] -> [1]/shard

    def _fwd_body(h, wl, y, mask, offs):
        off = offs[0]
        m, s, gold = _scan_stats(h, _pad_rows(wl, rows), y, off, **dims)
        big_m = jax.lax.pmax(m, axis)
        big_s = jax.lax.psum(s * jnp.exp(m - big_m), axis)
        lse = big_m + jnp.log(big_s)
        gold = jax.lax.psum(gold, axis)
        return jnp.sum((lse - gold) * mask), lse

    def _run_fwd(h, w2, y, mask):
        return jax.shard_map(
            _fwd_body, mesh=mesh,
            in_specs=(P(), P(axis), P(), P(), P(axis)),
            out_specs=(P(), P()), axis_names={axis},
        )(h, w2, y, mask, offsets)

    def _bwd_body(h, wl, y, mask, lse, g, offs):
        off = offs[0]
        coeff = (g * mask).astype(jnp.float32)
        dh, dwl = _scan_grads(h, _pad_rows(wl, rows), y, off, lse, coeff,
                              **dims)
        # dh is partial over the tp shards (each saw only its vocab rows)
        return jax.lax.psum(dh, axis), dwl[:v_local]

    def _run_bwd(h, w2, y, mask, lse, g):
        return jax.shard_map(
            _bwd_body, mesh=mesh,
            in_specs=(P(), P(axis), P(), P(), P(), P(), P(axis)),
            out_specs=(P(), P(axis)), axis_names={axis},
        )(h, w2, y, mask, lse, g, offsets)

    @jax.custom_vjp
    def ce_sum(h, w2, y, mask):
        return _run_fwd(h, w2, y, mask)[0]

    def ce_fwd(h, w2, y, mask):
        total, lse = _run_fwd(h, w2, y, mask)
        return total, (h, w2, y, mask, lse)

    def ce_bwd(res, g):
        h, w2, y, mask, lse = res
        dh, dw = _run_bwd(h, w2, y, mask, lse,
                          jnp.asarray(g, jnp.float32))
        return (dh.astype(h.dtype), dw.astype(w2.dtype),
                np.zeros(y.shape, jax.dtypes.float0), jnp.zeros_like(mask))

    ce_sum.defvjp(ce_fwd, ce_bwd)
    return ce_sum


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def _flatten(h, y, ignore_index):
    hf = h.reshape(-1, h.shape[-1])
    yf = y.reshape(-1).astype(jnp.int32)
    valid = (yf != ignore_index)
    # clamp masked labels into range so no shard's gather sees them
    return hf, jnp.where(valid, yf, 0), valid.astype(jnp.float32)


def chunked_lm_loss_arrays(h, w, y, *, transpose_y=True, vocab_chunk=None,
                           ignore_index=-100, int8=False):
    """Mean chunked CE on raw arrays (jit-traceable; used by models and
    tests). h [..., H]; w [V, H] (transpose_y) or [H, V]; y [...] int."""
    w2 = w if transpose_y else w.T
    hf, yf, mask = _flatten(h, y, ignore_index)
    total = chunked_ce_sum(hf, w2, yf, mask, vocab_chunk=vocab_chunk,
                           int8=int8)
    return total / jnp.maximum(mask.sum(), 1.0)


def sharded_lm_loss_arrays(h, w, y, mesh, axis="mp", *, transpose_y=True,
                           vocab_chunk=None, ignore_index=-100, int8=False):
    """Vocab-sharded chunked CE: w's vocab dim is sharded over ``axis``;
    each shard runs the chunked kernel on its local rows and the combine
    is pmax/psum of (max, sumexp, gold) scalars per token. Runs as a
    PARTIAL shard_map over ``axis`` only, so dp/pp placements of h stay
    visible to GSPMD (the pipeline's last stage holds a SHARD of the
    head, not a replica). Must be called under jit."""
    jax_mesh = getattr(mesh, "jax_mesh", mesh)
    tp = jax_mesh.shape[axis]
    w2 = w if transpose_y else w.T
    vocab = w2.shape[0]
    if vocab % tp != 0:
        raise ValueError(
            f"vocab ({vocab}) must divide over tp axis {axis!r} (size {tp})")
    v_local = vocab // tp
    c = resolve_vocab_chunk(v_local, vocab_chunk)
    k = -(-v_local // c)
    fn = _sharded_ce_fn(jax_mesh, axis, k, c, v_local, bool(int8))
    hf, yf, mask = _flatten(h, y, ignore_index)
    return fn(hf, w2, yf, mask) / jnp.maximum(mask.sum(), 1.0)


def fused_chunked_cross_entropy(x, weight, labels, transpose_y=True,
                                vocab_chunk=None, ignore_index=-100,
                                int8=None, mesh=None, tp_axis=None,
                                name=None):
    """Paddle-level fused chunked CE LM head (Tensor in, Tensor out).

    ``int8=None`` resolves via :func:`int8_head_enabled` (parity-gated
    default-on). ``mesh``/``tp_axis`` select the vocab-sharded variant.
    """
    if int8 is None:
        int8 = int8_head_enabled()
    vocab = weight.shape[0] if transpose_y else weight.shape[-1]
    n_tokens = 1
    for s in labels.shape:
        n_tokens *= int(s)
    if tp_axis is not None:
        jm = getattr(mesh, "jax_mesh", mesh)
        vocab //= int(jm.shape[tp_axis])
    record_head_mode("sharded" if tp_axis else "chunked", int8, n_tokens,
                     resolve_vocab_chunk(vocab, vocab_chunk))

    if tp_axis is not None:
        def _run(h, w, y):
            return sharded_lm_loss_arrays(
                h, w, y, mesh, tp_axis, transpose_y=transpose_y,
                vocab_chunk=vocab_chunk, ignore_index=ignore_index,
                int8=int8)
    else:
        def _run(h, w, y):
            return chunked_lm_loss_arrays(
                h, w, y, transpose_y=transpose_y, vocab_chunk=vocab_chunk,
                ignore_index=ignore_index, int8=int8)

    return apply_op(_run, x, weight, labels,
                    _op_name="fused_chunked_cross_entropy")
