"""Continuous-batching LLM serving over paged KV caches.

Capability slot: the reference's LLM serving stack (the C++ side of
`block_multi_head_attention` + the fastdeploy/serving slot managers that
drive it). TPU-native design:

- KV lives in PAGES `[num_pages, Hkv, page_size, D]` per layer; a
  `PagePool` hands pages to sequences on admission and reclaims them on
  completion, so memory scales with live tokens, not max_seq * slots.
- `ContinuousBatchingEngine` drives the vLLM-style loop: admit waiting
  requests into free slots (prefill writes the prompts' KV into their
  pages), then run ONE batched decode step for every live slot per
  `step()` — new requests join mid-flight without stalling running ones,
  finished slots free their pages immediately. A decode tick is
  launched from the device's own next tokens BEFORE the tick ahead of
  it is fetched, so fetch, emit, retire, admission and the caller's
  loop run under the device's work (docs/SERVING.md "The step's
  order").
- Admission prefills ALL newly admitted prompts as one padded batch —
  one pass over the weights per admission group, not per request.
- The decode step's attention is the pallas paged kernel
  (`ops/pallas/decode_attention.paged_attention`): block tables via
  scalar prefetch, so only the pages a sequence owns are fetched.
- Sampling runs inside the jitted decode step: per-request temperature /
  top-k / top-p (temperature 0 = greedy, the default). Per-token
  streaming callbacks fire as tokens are emitted.
- Admission reserves only prefill pages; decode pages are allocated as
  sequences grow. On pool exhaustion the youngest request is preempted:
  policy "recompute" (default) folds its tokens into the resume prompt,
  "swap" round-trips its KV through host memory (measured tradeoffs in
  docs/ROUND5_RESPONSE.md).
- `enable_prefix_cache=True` adds automatic prefix caching: pages are
  content-addressed by sha1 block-hash chains and reused read-only
  across requests sharing a prompt prefix (~2x TTFT on long shared
  system prompts, measured).

Weights are packed into an explicit pytree passed to the jitted step (not
closed-over constants), so `reload_weights()` on a live engine takes
effect without recompilation.

Works with the GPT/LLaMA stacked-weights families (anything exposing
`_decode_params()` — llama.py:66).
"""
from __future__ import annotations

import math
import os
import time
import warnings
from collections import deque, namedtuple

import numpy as np

from .. import telemetry as _telemetry
from ..telemetry import trace as _trace

__all__ = ["PagePool", "ContinuousBatchingEngine", "int8_kv_enabled"]

# serving metrics (names/labels contract: docs/TELEMETRY.md). Gauges are
# refreshed once per step(); counters tick at the event sites.
_TELEMETRY_REG = _telemetry.get_registry()
_QUEUE_DEPTH = _telemetry.gauge(
    "serving_queue_depth", "requests waiting for admission")
_SLOTS_OCCUPIED = _telemetry.gauge(
    "serving_slots_occupied", "engine slots holding a live request")
_BATCH_OCCUPANCY = _telemetry.histogram(
    "serving_batch_occupancy", "live slots / max_slots per decode tick",
    buckets=tuple(i / 8 for i in range(1, 9)))
_KV_UTIL = _telemetry.gauge(
    "serving_kv_page_utilization", "fraction of KV pages allocated")
_ADMISSIONS = _telemetry.counter(
    "serving_admissions_total", "requests admitted into slots",
    labelnames=("kind",))
_PREEMPTIONS = _telemetry.counter(
    "serving_preemptions_total", "requests evicted under page pressure",
    labelnames=("policy",))
_STEPS = _telemetry.counter(
    "serving_steps_total", "engine decode ticks")
_REQ_LATENCY = _telemetry.histogram(
    "serving_request_latency_seconds", "submit-to-completion wall time")
_TTFT = _telemetry.histogram(
    "serving_ttft_seconds", "submit-to-first-token wall time")
_REF_UNDERFLOWS = _telemetry.counter(
    "serving_page_ref_underflows_total",
    "KV page refcount decremented below zero (double-release bug)")
_CANCELLATIONS = _telemetry.counter(
    "serving_cancellations_total",
    "requests cancelled before completion (docs/SERVING.md)",
    labelnames=("reason",))
_SPEC_TICKS = _telemetry.counter(
    "serving_spec_ticks_total",
    "decode ticks under a draft model: 'spec' ran draft+verify, "
    "'fallback' took the plain single-token path (sampled rows live)",
    labelnames=("mode",))
_DECODE_TICKS = _telemetry.counter(
    "serving_decode_ticks_total",
    "plain decode ticks by how they were launched: 'ahead' while the "
    "tick before was still unfetched (its tokens went from program to "
    "program on the device), 'settled' with nothing in flight",
    labelnames=("mode",))
_DISCARDED = _telemetry.counter(
    "serving_decode_discarded_tokens_total",
    "tokens of a tick launched ahead that were never emitted: the row "
    "had 'ended' at the tick before (eos, a lowered max_new_cap) or was "
    "'withdrawn' (cancelled) while the tick was in flight",
    labelnames=("reason",))
_SPEC_DRAFTED = _telemetry.counter(
    "serving_spec_draft_tokens_total",
    "draft tokens proposed to the verifier")
_SPEC_ACCEPTED = _telemetry.counter(
    "serving_spec_accepted_tokens_total",
    "draft tokens accepted by the target verify pass")
_INT8_KV = _telemetry.gauge(
    "serving_int8_kv_active",
    "1 when the engine stores paged KV as blockwise int8 (+fp32 "
    "per-row scales in the page table) — docs/SERVING.md")
_WEIGHT_BYTES = _telemetry.gauge(
    "serving_weight_bytes",
    "resident packed decode-weight bytes per storage dtype "
    "(docs/QUANT.md: int8-packed replicas report the reduced footprint)",
    labelnames=("dtype",))
_CACHE_BYTES = _telemetry.gauge(
    "serving_cache_bytes",
    "bytes of the cache: 'pool' is what the paged pools take on the "
    "device, 'algorithm' what the model kind must keep for as many "
    "tokens (a latent row of 576 values lies in 640 lanes); for a kind "
    "that keeps a state by SLOT beside its pages, 'slot_store' is what "
    "that store takes and 'slot_algorithm' what the algorithm must keep "
    "for as many slots",
    labelnames=("kind",))
_PREFILL_PASSES = _telemetry.counter(
    "serving_prefill_passes_total",
    "chunked prefill passes launched, by the row count the pass was "
    "compiled at (a step of the engine's ladder, docs/SERVING.md)",
    labelnames=("rows",))
_DECODE_ROWS = _telemetry.counter(
    "serving_decode_rows_total",
    "plain decode ticks launched, by the row count the tick was compiled "
    "at (a step of the engine's decode ladder, docs/SERVING.md)",
    labelnames=("rows",))
_PROGRAM_TEMP_BYTES = _telemetry.gauge(
    "serving_program_temp_bytes",
    "temporary device bytes of each compiled serving program, from its "
    "memory_analysis() at warmup: under one layer's K/V slab while the "
    "pool stays in place (docs/SERVING.md)",
    labelnames=("program",))


# ---------------------------------------------------------------- int8 KV
#: relative round-trip error the int8-KV parity probe tolerates
#: (PTPU_INT8_KV_TOL overrides). Row-absmax int8 holds ~1/254 of the
#: row range per element; 2% is an order of magnitude of headroom, so a
#: probe failure means the quantizer itself drifted, not noise.
KV_QUANT_TOL = 0.02


def _int8_kv_probe_ok():
    """Numeric parity probe over the REAL paged-KV quantization path
    (memory.quantize_rows_int8 / dequantize_rows_int8) on a skewed
    tensor with outlier rows — the int8-LM-head gate discipline: the
    probe exercises the same code every int8 cache write runs, so a
    monkeypatched/broken quantizer fails the gate instead of serving
    drifted KV."""
    import jax.numpy as jnp

    from ..memory import dequantize_rows_int8, quantize_rows_int8

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 64)).astype(np.float32)
    x[0] *= 1e3        # large-magnitude row
    x[1] *= 1e-3       # tiny row (scale epsilon path)
    x[2, 5] = 400.0    # in-row outlier (worst case for absmax grids)
    q, s = quantize_rows_int8(jnp.asarray(x))
    rt = np.asarray(dequantize_rows_int8(q, s))
    absmax = np.maximum(np.abs(x).max(-1, keepdims=True), 1e-12)
    err = float(np.max(np.abs(rt - x) / absmax))
    tol = float(os.environ.get("PTPU_INT8_KV_TOL", KV_QUANT_TOL))
    return err <= tol


def int8_kv_enabled(requested=False):
    """Resolve the int8 paged-KV mode (docs/SERVING.md numerics
    contract). ``PTPU_INT8_KV`` forces: ``0`` is the exact escape hatch
    (bf16/f32 pages, bitwise the pre-int8 engine), ``1`` forces int8 on.
    Unset: the mode engages only when the constructor ``requested`` it
    AND the parity probe passes — a drifting quantizer defaults the
    engine OFF (loudly) instead of serving approximate KV."""
    env = os.environ.get("PTPU_INT8_KV", "").strip().lower()
    if env != "":
        return env not in ("0", "off", "false")
    if not requested:
        return False
    if _int8_kv_probe_ok():
        return True
    warnings.warn(
        "int8_kv requested but the paged-KV quantization parity probe "
        "FAILED its round-trip tolerance — serving with exact "
        f"(non-quantized) KV instead (tol {KV_QUANT_TOL}, "
        "PTPU_INT8_KV=1 forces; docs/SERVING.md)")
    return False


def _int8_paged_kernel_mode():
    """Resolve ``PTPU_PAGED_INT8_KERNEL`` — HOW an already-engaged int8
    paged cache is read (rides ON TOP of the ``int8_kv_enabled`` parity
    gate). Returns one of:

    - ``"kernel"``: the Pallas int8-page kernel
      (``ops/pallas/decode_attention.paged_attention_int8``);
    - ``"interpret"``: the same kernel forced through the Pallas
      interpreter (the CPU parity tests drive the real kernel code);
    - ``"off"``: the HBM gather+dequant reference path.

    Unset/``auto`` resolves to ``kernel`` on real TPU devices and
    ``off`` elsewhere (off-TPU the kernel would silently run in the
    interpreter — orders of magnitude slower). Unknown values are a
    hard error: a mistyped knob must not masquerade as a measured
    configuration (the ``_block_for`` discipline)."""
    env = os.environ.get("PTPU_PAGED_INT8_KERNEL", "").strip().lower()
    if env in ("0", "off", "false"):
        return "off"
    if env == "interpret":
        return "interpret"
    if env in ("", "auto"):
        from ..ops.pallas import on_tpu_device

        return "kernel" if on_tpu_device() else "off"
    raise ValueError(
        f"PTPU_PAGED_INT8_KERNEL={env!r}: expected auto|interpret|0 "
        "(docs/SERVING.md)")


def _int8_paged_kernel_active():
    return _int8_paged_kernel_mode() != "off"


# ------------------------------------------------------- KV cache helpers
# A cache is ONE stacked array [L, Hkv, num_pages+1, page, D] (exact
# mode) or a (codes int8 [L, Hkv, num_pages+1, page, D],
# scales f32 [L, Hkv, num_pages+1, page, 1]) pair (int8 mode) — the
# fp32 per-row scales ride NEXT TO the page payload, addressed by the
# same page table. That is the pool's ONE layout, and it never moves: a
# program takes the whole pool, writes into it in place
# (`_kv_write_run`, the one writer of rows; `_swap_scatter` restores
# whole pages), reads pages out of it by (layer, page), and returns the
# same buffer. No consumer slices a layer's slab out or stacks one
# back. The helpers below are tuple-aware so every cache consumer
# (decode, chunked prefill, swap, handoff) is written once.

def _kv_map(fn, c):
    return tuple(fn(x) for x in c) if isinstance(c, tuple) else fn(c)


def _kv_map2(fn, a, b):
    if isinstance(a, tuple):
        return tuple(fn(x, y) for x, y in zip(a, b))
    return fn(a, b)


def _kv_write_run(cache, li, tables, pos0, nvalid, vals):
    """Write each slot's run of consecutive positions into layer ``li``
    of the stacked cache, where it lies: slot ``b`` puts rows
    ``vals[b, :nvalid[b]]`` (``vals`` [B, c, Hkv, D] at the compute
    dtype; ``nvalid`` [B], or one count for every slot) at positions
    ``pos0[b]..`` through its page table ``tables[b]``. Every writer writes such runs: a decode tick one row
    a slot, a verify or draft window C, a prefill chunk or a whole
    prompt up to c. ``li`` is a python int (eager prefill, the unrolled
    walk) or the layer scan's counter; on the scan's carry, and on a
    donated buffer, XLA performs the scatter in place.

    The pages a run touches are read, the new rows laid over them, and
    the pages written back whole, one (page, D) tile-aligned window per
    (head, page): the pool keeps the layout the kernels read, and a
    chunk of 128 rows costs 96 page updates a head, not 128 rows' (row
    by row the chip takes 72 ns a row, 2.4 ms a chunk write; with the
    heads as the scatter's window XLA relays the whole pool heads-minor
    and back, every layer). A frame page no row of the run falls in,
    and every page of a slot with ``nvalid`` 0, goes to the pool's last
    page, the scratch page nothing reads; positions past the table are
    dropped. Two slots never touch one page with different rows (pages
    are exclusively owned past a shared prefix; a padded decode row
    writes the scratch page). int8 caches quantize each
    row (one fp32 scale per head_dim row — the block the page table
    addresses) at the write."""
    import jax.numpy as jnp

    B, c = vals.shape[:2]
    pps = tables.shape[1]
    nvalid = jnp.reshape(jnp.asarray(nvalid), (-1, 1, 1))

    def put(x, v):
        hkv, page = x.shape[1], x.shape[3]
        npg = (c + page - 2) // page + 1      # pages a run of c can touch
        j0 = pos0 // page
        lp = j0[:, None] + jnp.arange(npg)[None]                # [B, npg]
        # frame row t of slot b is position j0*page + t: run row r
        r = (jnp.arange(npg * page)[None]
             - (pos0 - j0 * page)[:, None]).reshape(B, npg, page)
        new = (r >= 0) & (r < nvalid) & (lp < pps)[..., None]
        pid = jnp.where(new.any(-1),
                        jnp.take_along_axis(tables, jnp.minimum(lp, pps - 1),
                                            1),
                        x.shape[2] - 1)
        # three ADJACENT advanced indices: [Hkv, B, npg] pages of
        # [page, D] each, out and back in
        at = (li, jnp.arange(hkv)[:, None, None], pid[None])
        rows = jnp.take_along_axis(
            v, jnp.clip(r, 0, c - 1).reshape(B, -1, 1, 1), 1)
        rows = jnp.moveaxis(rows, 2, 0).reshape(hkv, B, npg, page, -1)
        return x.at[at].set(jnp.where(new[None, ..., None],
                                      rows.astype(x.dtype), x[at]))

    if isinstance(cache, tuple):
        from ..memory import quantize_rows_int8

        return _kv_map2(put, cache, quantize_rows_int8(vals))
    return put(cache, vals)


def _kv_gather_rows(cache, li, idx, dtype):
    """Gather pages by id out of layer ``li`` of the stacked cache, in
    one indexing operation (no slab is sliced out first) ->
    [Hkv, *idx.shape, page, D] at the engine's logical ``dtype``. int8
    caches dequantize (codes * scales) on the way out; exact caches
    return their storage as-is."""
    import jax.numpy as jnp

    def g(x):
        # three ADJACENT advanced indices: the result keeps them in place
        heads = jnp.arange(x.shape[1]).reshape((-1,) + (1,) * idx.ndim)
        return x[li, heads, idx[None]]

    if isinstance(cache, tuple):
        q, s = cache
        return (g(q).astype(jnp.float32) * g(s)).astype(dtype)
    return g(cache)


def _kv_nbytes(c):
    leaves = c if isinstance(c, tuple) else (c,)
    return sum(int(np.asarray(x).nbytes if not hasattr(x, "nbytes")
                   else x.nbytes) for x in leaves)


_DECODE_WEIGHT_NAMES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg",
                        "wu", "wd")
#: the 7 projection slabs eligible for int8-resident packing (norms stay
#: exact: they are cheap, and their dynamic range is the worst int8 fit)
_QUANT_WEIGHT_NAMES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def _wmat(x, w):
    """``x @ W`` for one packed decode weight: exact slabs multiply
    directly; int8-resident ``(codes, scales)`` pairs take the
    dequant-free int8 x int8 -> int32 GEMM (quant.int8_weight_matmul) —
    the weights are never expanded back to wide dtype."""
    if isinstance(w, tuple):
        from ..quant import int8_weight_matmul

        return int8_weight_matmul(x, *w)
    return x @ w


def _layer_slice(w, li):
    """Per-layer view of one stacked weight entry (tuple-aware: an
    int8-packed entry slices codes and scales together)."""
    if isinstance(w, tuple):
        return (w[0][li], w[1][li])
    return w[li]


def _weight_nbytes(weights):
    """Resident bytes of the packed decode tree, keyed by storage dtype —
    the ``serving_weight_bytes{dtype}`` footprint. int8-packed layers
    split between their int8 codes and f32 scale rows."""
    out = {}

    def add(a):
        if a is None:
            return
        if isinstance(a, tuple):
            for x in a:
                add(x)
            return
        key = str(a.dtype)
        out[key] = out.get(key, 0) + int(a.nbytes)

    for w in weights["layers"]:
        add(w)
    for n in ("embed", "fnorm", "head"):
        add(weights[n])
    return out


def _run_layer_stack(scan_layers, layers, x, layer_fn, cache, base=0):
    """THE scan-or-unrolled walker over one GROUP of like layers, a
    [n, ...]-stacked weight tuple: ``layer_fn(lp, li, x, cache) ->
    (x, cache)`` over the WHOLE stacked pools (``cache`` is the tuple of
    them: K and V for the dense decoder, one latent pool for a latent
    model). ``li`` is the layer's index in the pools: ``base`` is where
    this group starts, so the groups of a stack of unlike layers are
    walked one after another and the pool's layer index runs on. Shared
    by the engine's decode/prefill/verify programs AND the spec-decode
    DraftRunner, so the pool discipline cannot drift between target and
    draft. Scanned: the pools ride the scan's CARRY beside ``x`` (any
    pytree) and only the weights and the layer counter are ``xs`` (a
    scan cannot alias ``xs`` to ``ys``: caches there cost a second pool
    and a slab copied in and out per layer); compile flat in depth (the
    replica cold-start win). Unrolled (``PTPU_SCAN_LAYERS=0``): ``li``
    is a python int and the same ``layer_fn`` runs; bitwise identical,
    compile linear in depth."""
    import jax
    import jax.numpy as jnp

    n = layers[0].shape[0]
    if scan_layers:
        def step(carry, per):
            lp, li = per
            return layer_fn(lp, li, *carry), None

        (x, cache), _ = jax.lax.scan(
            step, (x, cache),
            (layers, jnp.arange(base, base + n, dtype=jnp.int32)))
        return x, cache
    for li in range(n):
        x, cache = layer_fn(tuple(_layer_slice(w, li) for w in layers),
                            base + li, x, cache)
    return x, cache


def _pack_weights_stacked(model):
    """Decode weight tree: {"layers": 9x [L, ...] stacked arrays,
    "embed", "fnorm", "head"} — shared by the engine and the spec-decode
    DraftRunner so target and draft numerics come off one packer."""
    import jax.numpy as jnp

    core = model.model if hasattr(model, "model") else model
    head = getattr(model, "lm_head", None)
    L = model.config.num_layers
    dec = getattr(model, "decoder", None)
    if dec is not None and all(
            getattr(getattr(dec, n, None), "_data", None) is not None
            and getattr(dec, n)._data.shape[0] == L
            for n in _DECODE_WEIGHT_NAMES):
        # natively-stacked family (GPTForCausalLMPipe): reference, don't
        # copy — a live-engine reload is free of the sliced-copy peak
        layers = tuple(getattr(dec, n)._data for n in _DECODE_WEIGHT_NAMES)
    else:
        params = model._decode_params()
        layers = tuple(
            jnp.stack([params[li][n]._data for li in range(L)])
            for n in _DECODE_WEIGHT_NAMES)
    return {
        "layers": layers,
        "embed": core.embed_tokens.weight._data,
        "fnorm": core.final_norm.weight._data,
        "head": head.weight._data if head is not None else None,
    }


class PagePool:
    """Free-list page allocator (the block manager)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = deque(range(num_pages))

    def alloc(self, n: int):
        if n > len(self._free):
            raise MemoryError(
                f"PagePool: need {n} pages, {len(self._free)} free")
        return [self._free.popleft() for _ in range(n)]

    def free(self, pages):
        self._free.extend(pages)

    @property
    def available(self):
        return len(self._free)


def _pass_row_ladder(max_slots):
    """The row counts a chunked prefill pass is compiled at: the powers
    of two below ``max_slots``, then ``max_slots`` itself (1, 2, 4, 6
    for six slots). A pass runs at the smallest step that holds its
    prefilling rows, so it pays for under twice the rows it has."""
    steps, n = [], 1
    while n < max_slots:
        steps.append(n)
        n *= 2
    return (*steps, max_slots)


def _decode_row_ladder(max_slots):
    """The row counts a decode tick is compiled at: the powers of eight
    below ``max_slots``, then ``max_slots`` itself (1, 8, 32 for 32
    slots; 1, 6 for six). A tick runs at the smallest step that holds
    its live rows. Sparser than the prefill pass's ladder: a padded
    decode row costs only its per-row fusions, while each step is one
    more program to compile and warm."""
    steps, n = [], 1
    while n < max_slots:
        steps.append(n)
        n *= 8
    return (*steps, max_slots)


#: a decode tick launched and not yet fetched: its ``nxt`` on the device,
#: [(slot, request)] of its rows, {id(request): row} (not by rid: a fleet
#: replays a cancelled request under its old rid), its ``decode_tick`` span
_Tick = namedtuple("_Tick", "nxt live row_of span")


class _Request:
    __slots__ = ("rid", "prompt", "generated", "length", "pages",
                 "temperature", "top_k", "top_p", "on_token",
                 "prefill_pos", "seq_tokens", "admit_seq", "swapped",
                 "submit_t", "first_token_t", "deadline",
                 "queued_t", "queue_s", "admit_t")

    def __init__(self, rid, prompt, temperature=0.0, top_k=0, top_p=1.0,
                 on_token=None, deadline=None):
        self.rid = rid
        self.prompt = list(prompt)
        self.generated = []
        self.length = 0          # tokens currently in the kv pages
        self.pages = []
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.on_token = on_token
        self.prefill_pos = 0     # tokens already written to kv (chunked)
        # the tokens prefill must (re)build KV for: the prompt initially;
        # after a preemption, prompt + generated-so-far (the resume prefix)
        self.seq_tokens = self.prompt
        self.admit_seq = -1      # admission order (preemption victims =
                                 # youngest first, vLLM recompute policy)
        self.swapped = None      # host-side KV snapshot (swap policy)
        self.submit_t = time.perf_counter()   # latency telemetry anchors
        self.first_token_t = None
        self.deadline = deadline  # absolute perf_counter() cancel point
        # TTFT anatomy, kept on the request so it holds whether or not
        # the tracer was on at submit: when it last entered the waiting
        # queue, the seconds it has waited there (summed over requeues)
        # and its newest admission
        self.queued_t = self.submit_t
        self.queue_s = 0.0
        self.admit_t = None


def _sample_rows(jax, jnp, logits, temps, top_ks, top_ps, key):
    """Per-row temperature / top-k / top-p sampling; temp<=0 rows take
    argmax. Runs inside the jitted decode step."""
    f32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(f32, -1).astype(jnp.int32)
    # temperature scales BEFORE the filters (HF/vLLM order): the nucleus is
    # computed on the distribution actually sampled from, so high
    # temperature widens it and low temperature narrows it
    scaled = f32 / jnp.maximum(temps[:, None], 1e-6)
    V = scaled.shape[-1]
    srt = jnp.flip(jnp.sort(scaled, -1), -1)                  # desc [B, V]
    k_eff = jnp.where(top_ks > 0, top_ks, V)
    kth = jnp.take_along_axis(
        srt, jnp.clip(k_eff - 1, 0, V - 1)[:, None], 1)       # [B, 1]
    topk_sorted = jnp.where(srt < kth, -jnp.inf, srt)
    probs_sorted = jax.nn.softmax(topk_sorted, -1)
    csum = jnp.cumsum(probs_sorted, -1)
    # nucleus: keep the smallest prefix with cumulative mass >= top_p
    # (the first token is always kept: csum - p_i < p holds at i=0)
    keep = (csum - probs_sorted) < top_ps[:, None]
    thr = jnp.min(jnp.where(keep, topk_sorted, jnp.inf), -1, keepdims=True)
    # a logit survives only if it passes BOTH filters (max of thresholds);
    # keep[:, 0] is always True so thr is finite
    masked = jnp.where(scaled < jnp.maximum(kth, thr), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, masked, -1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _rope(x, pos):
    """Shared framework rope (models/gpt.py) — serving stays
    bit-identical to training/generate."""
    from ..models.gpt import _rope_at_positions

    return _rope_at_positions(x, pos)


class DenseDecoderServing:
    """What the engine asks of a model kind (docs/SERVING.md "Model
    kinds and cache geometry"), answered for the dense rmsnorm + swiglu +
    rope decoder, MHA or GQA: the cache's geometry (two pools, K and V
    per head), the packed weights (one group of like layers), the
    layer's mathematics and the attention over the pools — a decode
    tick, a prefill chunk, a speculative verify window and the eager
    group prefill. Any model with the decode contract
    (``_decode_params()``, or natively stacked) is served through it; a
    model of another kind hands over its own ``serving_arch()``
    (models/latent_moe.LatentMoEServing). A pool that comes as a
    (codes, scales) pair is the int8 page format."""

    cache_names = ("k", "v")
    #: the cache leaves addressed by SLOT, not by page: none
    slot_cache_names = ()
    #: why a request cannot leave this engine for another: it can
    no_handoff = None
    #: engine features this model kind refuses at construction, by name
    refuses = {}

    def __init__(self, model):
        self.model, self.cfg = model, model.config
        self.hd = self.cfg.hidden_size // self.cfg.num_heads
        self.hkv = self.cfg.num_kv_heads

    # -- geometry and weights ---------------------------------------------
    def cache_shapes(self, num_pages, page):
        """K and V in the stacked KERNEL layout [L, Hkv, num_pages,
        page, D]: a layer's pages are exactly what paged_attention
        consumes (no per-step transposes), and the leading L axis is
        what the layer scan counts."""
        shape = (self.cfg.num_layers, self.hkv, num_pages + 1, page,
                 self.hd)
        return shape, shape

    def cache_token_bytes(self, itemsize):
        """Bytes the algorithm must keep a token, over all layers."""
        return 2 * self.cfg.num_layers * self.hkv * self.hd * itemsize

    def pack(self, int8_weights=False):
        """{"layers": 9 LEADING-AXIS-STACKED arrays [L, ...] in _block
        order, "embed", "fnorm", "head"}. Stacked models
        (GPTForCausalLMPipe / StackedDecoder) pack ZERO-COPY; per-layer
        models stack their slices (one transient per-layer copy during
        the stack, then only the stacked copy is retained).
        ``int8_weights``: the 7 projection slabs are re-packed as
        (codes int8 [L, h, n], scales f32 [L, 1, n]) tuples — embed,
        norms and head stay exact (embed also fixes the engine's KV
        dtype); the zero-copy reference is given up for ~4x less
        resident bytes."""
        w = _pack_weights_stacked(self.model)
        if int8_weights:
            from ..quant import quantize_weight_cols_int8

            w["layers"] = tuple(
                quantize_weight_cols_int8(arr)
                if name in _QUANT_WEIGHT_NAMES else arr
                for name, arr in zip(_DECODE_WEIGHT_NAMES, w["layers"]))
        return w

    def groups(self, weights):
        """[(stacked leaves, layer forward)]: one group."""
        return [(weights["layers"], self.layer_forward)]

    def carry_in(self, x):
        """The walker's carry: the hidden state alone."""
        return x

    def carry_out(self, x):
        """(hidden state, the program's counts for the host: none)."""
        return x, None

    # -- layer mathematics ------------------------------------------------
    def layer_forward(self, li, lp, x, pos0, attend):
        """One decoder layer: projections + rope + ``attend(li, q, k,
        v)`` (which owns cache writes and the attention math) + MLP.
        Shared by the compiled programs and the eager group prefill so
        their numerics can never diverge."""
        import jax

        from ..models.gpt import _rms_pure

        ln1, wq, wk, wv, wo, ln2, wg, wu, wd = lp
        B, S = x.shape[:2]
        h = _rms_pure(x, ln1)
        q = _wmat(h, wq).reshape(B, S, self.cfg.num_heads, self.hd)
        k = _wmat(h, wk).reshape(B, S, self.hkv, self.hd)
        v = _wmat(h, wv).reshape(B, S, self.hkv, self.hd)
        q, k = _rope(q, pos0), _rope(k, pos0)
        o = attend(li, q, k, v)                       # [B, S, Hq, D]
        x = x + _wmat(o.reshape(B, S, -1), wo)
        h2 = _rms_pure(x, ln2)
        return x + _wmat(jax.nn.silu(_wmat(h2, wg)) * _wmat(h2, wu), wd)

    # -- attention over the K and V pools ---------------------------------
    def paged_attend(self, q, kc, vc, li, tables, lens):
        """Single-position paged attention over layer ``li`` of the
        stacked caches: q [B, Hq, D] -> [B, Hq, D]. Exact caches take
        the Pallas paged kernel, which reads its pages out of the pool
        by (layer, page); int8 caches take the int8-page Pallas kernel
        (``paged_attention_int8``: (codes, scales) dequantized in VMEM
        per fetched page — the PR 12 named follow-up) when the device
        gate allows, else gather the owned pages, dequantize in HBM,
        and run the masked reference attention (docs/SERVING.md). Both
        int8 paths read the SAME codes*scales values; the int8 mode
        itself engages only behind the quantizer parity gate
        (``int8_kv_enabled``)."""
        import jax
        import jax.numpy as jnp

        if not isinstance(kc, tuple):
            from ..ops.pallas.decode_attention import paged_attention

            return paged_attention(q, kc, vc, tables, lens, layer=li)
        mode = _int8_paged_kernel_mode()
        if mode != "off":
            from ..ops.pallas.decode_attention import paged_attention_int8

            return paged_attention_int8(
                q, *kc, *vc, tables, lens, layer=li,
                interpret=True if mode == "interpret" else None)
        b, hq, hd = q.shape
        S = tables.shape[1] * kc[0].shape[3]
        ck = _kv_gather_rows(kc, li, tables, q.dtype).reshape(
            self.hkv, b, S, hd)
        cv = _kv_gather_rows(vc, li, tables, q.dtype).reshape(
            self.hkv, b, S, hd)
        rep = hq // self.hkv
        if rep > 1:
            ck = jnp.repeat(ck, rep, 0)
            cv = jnp.repeat(cv, rep, 0)
        scale = 1.0 / math.sqrt(hd)
        logits = jnp.einsum("bhd,hbsd->bhs",
                            (q * scale).astype(jnp.float32),
                            ck.astype(jnp.float32))
        mask = jnp.arange(S)[None, None, :] < lens[:, None, None]
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        o = jnp.einsum("bhs,hbsd->bhd", probs, cv.astype(jnp.float32))
        return o.astype(q.dtype)

    def decode_attend(self, tables, lens, slots=None):
        """A decode tick's attention: this token's KV row written, then
        read back with the rest (``slots`` is for a kind that keeps a
        state by slot: unused here)."""
        def attend(li, q, k, v, cache):
            kc, vc = cache
            kc = _kv_write_run(kc, li, tables, lens, 1, k)
            vc = _kv_write_run(vc, li, tables, lens, 1, v)
            o = self.paged_attend(q[:, 0], kc, vc, li, tables, lens + 1)
            return o[:, None], (kc, vc)               # [B, 1, Hq, D]

        return attend

    def verify_attend(self, tables, lens, C):
        """A speculative verify window's attention: the C rows written,
        then the SAME per-position `paged_attend` a plain decode tick at
        that position would run — position i reads lens+i+1 valid rows,
        the earlier window rows having just been written with the
        identical values sequential ticks would have written."""
        import jax.numpy as jnp

        def attend(li, q, k, v, cache):
            kc, vc = cache
            kc = _kv_write_run(kc, li, tables, lens, C, k)
            vc = _kv_write_run(vc, li, tables, lens, C, v)
            o = [self.paged_attend(q[:, i], kc, vc, li, tables,
                                   lens + i + 1) for i in range(C)]
            return jnp.stack(o, 1), (kc, vc)          # [B, C, Hq, D]

        return attend

    def chunk_attend(self, hist, pos0, nvalid, chunk, page, slots=None):
        """A prefill chunk's attention ([B, chunk] positions from
        ``pos0``): chunk rows attend to [cached prefix + own chunk]
        causally, against the gathered history."""
        import jax
        import jax.numpy as jnp

        B = hist.shape[0]
        S = hist.shape[1] * page
        scale = 1.0 / math.sqrt(self.hd)
        rep = self.cfg.num_heads // self.hkv
        row_pos = pos0[:, None] + jnp.arange(chunk)[None, :]  # [B, c]
        cols = jnp.arange(S)
        mask = cols[None, None, :] <= row_pos[:, :, None]     # [B, c, S]

        def attend(li, q, k, v, cache):
            # write the chunk's kv FIRST, then gather the prefix back
            # (one source of truth for the attention operands; in int8
            # mode both the own-chunk and prefix reads come back
            # dequantized — identical to what decode will see)
            kc, vc = cache
            kc = _kv_write_run(kc, li, hist, pos0, nvalid, k)
            vc = _kv_write_run(vc, li, hist, pos0, nvalid, v)
            ck = _kv_gather_rows(kc, li, hist, q.dtype).reshape(
                self.hkv, B, S, self.hd)
            cv = _kv_gather_rows(vc, li, hist, q.dtype).reshape(
                self.hkv, B, S, self.hd)
            if rep > 1:
                ck = jnp.repeat(ck, rep, 0)
                cv = jnp.repeat(cv, rep, 0)
            logits = jnp.einsum("bchd,hbsd->bhcs",
                                (q * scale).astype(jnp.float32),
                                ck.astype(jnp.float32))
            logits = jnp.where(mask[:, None], logits, -1e30)
            probs = jax.nn.softmax(logits, -1)
            o = jnp.einsum("bhcs,hbsd->bchd", probs,
                           cv.astype(jnp.float32))
            return o.astype(q.dtype), (kc, vc)           # [B, c, Hq, D]

        return attend

    def group_attend(self, tables, nvalid, S):
        """The eager group prefill's attention: every prompt from
        position 0, padded to ``S``, causal over its own rows; each
        prompt's valid k/v rows go into the pages it owns."""
        import jax
        import jax.numpy as jnp

        scale = 1.0 / math.sqrt(self.hd)
        rep = self.cfg.num_heads // self.hkv
        mask = jnp.tril(jnp.ones((S, S), bool))
        pos0 = jnp.zeros(nvalid.shape, jnp.int32)

        def attend(li, q, k, v, cache):
            kc, vc = cache
            if isinstance(kc, tuple):
                # round-trip k/v through the page quantizer BEFORE both
                # the attention math and the cache write: group prefill,
                # chunked prefill, and decode all read the SAME
                # quantized KV (re-quantizing a round-tripped row is
                # exact — the absmax element always maps to code 127,
                # so the recomputed scale is identical)
                from ..memory import (dequantize_rows_int8,
                                      quantize_rows_int8)

                k = dequantize_rows_int8(*quantize_rows_int8(k), k.dtype)
                v = dequantize_rows_int8(*quantize_rows_int8(v), v.dtype)
            ck = jnp.repeat(k, rep, 2) if rep > 1 else k
            cv = jnp.repeat(v, rep, 2) if rep > 1 else v
            logits = jnp.einsum("bthd,bshd->bhts",
                                (q * scale).astype(jnp.float32),
                                ck.astype(jnp.float32))
            logits = jnp.where(mask[None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, -1)
            o = jnp.einsum("bhts,bshd->bthd", probs,
                           cv.astype(jnp.float32)).astype(q.dtype)
            kc = _kv_write_run(kc, li, tables, pos0, nvalid, k)
            vc = _kv_write_run(vc, li, tables, pos0, nvalid, v)
            return o, (kc, vc)

        return attend


class ContinuousBatchingEngine:
    def __init__(self, model, max_slots=4, page_size=64, num_pages=None,
                 max_seq_len=None, max_new_tokens=32, eos_token_id=None,
                 seed=0, prefill_chunk=None, preempt_policy="recompute",
                 enable_prefix_cache=False, int8_kv=False,
                 int8_weights=False, draft_model=None, spec_tokens=4,
                 prefill_only=False, rid_base=0):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        cfg = model.config
        self.cfg = cfg
        self.page = page_size
        self.max_seq = max_seq_len or cfg.max_seq_len
        self.pages_per_seq = (self.max_seq + page_size - 1) // page_size
        self.max_slots = max_slots
        self.max_new_tokens = max_new_tokens
        self.eos = eos_token_id
        num_pages = num_pages or (max_slots * self.pages_per_seq + 2)
        self.pool = PagePool(num_pages)
        # one extra non-allocable scratch page: the BATCHED chunked
        # prefill routes padded rows' cache writes there
        self._trash_page = num_pages

        # Model kinds (docs/SERVING.md "Model kinds and cache geometry"):
        # the engine asks the model's ``serving_arch()`` for the cache's
        # geometry, the packed weights by group of like layers, each
        # group's layer mathematics and the attention over its pools;
        # a model with the dense decoder's decode contract is answered
        # for by :class:`DenseDecoderServing`. A feature a kind does not
        # have refuses here, by name.
        self._arch = self._arch_of(model)
        asked = {"int8_kv": int8_kv, "int8_weights": int8_weights,
                 "draft_model": draft_model is not None,
                 "group prefill (prefill_chunk=None)":
                     prefill_chunk is None,
                 "enable_prefix_cache": enable_prefix_cache}
        for name, why in self._arch.refuses.items():
            if asked.get(name):
                raise ValueError(
                    f"{type(model).__name__} does not serve with "
                    f"{name}: {why}")

        # int8 resident weights (docs/QUANT.md): the 7 projection slabs
        # pack as per-output-column int8 codes + f32 scales and every
        # decode/prefill GEMM runs int8 x int8 -> int32 without ever
        # dequantizing the weights (~4x less weight HBM per replica vs
        # f32). Engages only behind the round-trip probe;
        # PTPU_INT8_WEIGHTS=0 is the exact escape hatch. Resolved BEFORE
        # the pack below, which reads the flag.
        from ..quant import int8_weights_enabled

        self.int8_weights = ("int8_weights" not in self._arch.refuses
                             and int8_weights_enabled(int8_weights))

        self._model = model
        self._weights = self._pack_weights(model)
        self._key = jax.random.PRNGKey(seed)

        # scan-over-layers decode (docs/SERVING.md cold start): the ONE
        # models.gpt resolver decides — the decode/prefill programs
        # compile as a lax.scan over the [L, ...]-stacked weights+caches
        # (depth-flat build time, the PR 7 discipline) unless
        # PTPU_SCAN_LAYERS=0 keeps the python-unrolled loop, the bitwise
        # escape hatch (proven: greedy streams identical either way).
        from ..models.gpt import scan_layers_enabled

        self._scan_layers = scan_layers_enabled()

        # int8 paged KV (docs/SERVING.md): pages stored as int8 codes +
        # fp32 per-row scales riding in the page table, ~half the exact
        # mode's KV HBM. Engages only behind the parity probe;
        # PTPU_INT8_KV=0 is the exact escape hatch.
        self.int8_kv = ("int8_kv" not in self._arch.refuses
                        and int8_kv_enabled(int8_kv))

        # paged caches: ``self.cache`` is the tuple of the model kind's
        # pools, named by ``self.cache_names`` (K and V per head for the
        # dense decoder, one pool of latent rows for a latent model),
        # each [L, *, num_pages + 1, page, *]. Every pool is addressed
        # by the same page tables, and every consumer (programs, swap,
        # handoff, prefix export) goes through the tuple. int8 pools are
        # (codes, fp32 per-row scales) pairs. A kind that keeps a state
        # of fixed size a request (a recurrent layer's) names the leaves
        # that hold it in ``slot_cache_names``: they come LAST in the
        # tuple, each [L, max_slots + 1, ...], addressed by a request's
        # SLOT; the last slot is the trash slot, which padded rows name
        # (docs/SERVING.md "Caches by page and by slot").
        dt = self._weights["embed"].dtype
        self.cache_names = tuple(self._arch.cache_names)
        self._slot_names = tuple(self._arch.slot_cache_names)
        self._n_paged = len(self.cache_names) - len(self._slot_names)
        if self.cache_names[self._n_paged:] != self._slot_names:
            raise ValueError(
                f"{type(self._arch).__name__}: the leaves addressed by slot "
                f"{self._slot_names} come last in cache_names "
                f"{self.cache_names}")
        self._trash_slot = max_slots
        shapes = self._arch.cache_shapes(num_pages, page_size)
        if self.int8_kv:
            self.cache = tuple(
                (jnp.zeros(shape, jnp.int8),
                 jnp.zeros(shape[:-1] + (1,), jnp.float32))
                for shape in shapes)
        else:
            self.cache = tuple(jnp.zeros(shape, dt) for shape in shapes)
        token_bytes = self._arch.cache_token_bytes(
            1 if self.int8_kv else dt.itemsize)
        # what the pools take on the device, and what the algorithm must
        # keep for as many tokens (a pool's padding to the tile shows as
        # the difference)
        _CACHE_BYTES.set(float(sum(_kv_nbytes(c) for c in self.cache)),
                         labels=("pool",))
        _CACHE_BYTES.set(float(token_bytes * (num_pages + 1) * page_size),
                         labels=("algorithm",))
        # padding of a decode batch, for every kind: a row of its own,
        # length 0, every page the trash page, on the trash slot. It
        # writes and reads the trash page alone, steps no live state,
        # and costs the attention kernels one page
        self._pad_row = _Request(-1, [0])
        self._pad_row.generated = [0]
        self._pad_row.pages = [self._trash_page] * self.pages_per_seq
        if self._slot_names:
            store = tuple(
                jnp.zeros(shape, dtype) for shape, dtype in
                self._arch.slot_cache_shapes(max_slots, dt))
            self.cache += store
            _CACHE_BYTES.set(float(sum(_kv_nbytes(c) for c in store)),
                             labels=("slot_store",))
            _CACHE_BYTES.set(
                float(self._arch.slot_cache_bytes(dt.itemsize)
                      * (max_slots + 1)), labels=("slot_algorithm",))

        # prefill_only: this engine is the PREFILL half of a
        # disaggregated pair (fleet.disagg) — step() admits and prefills
        # but never runs a decode tick; completed-prefill requests wait
        # in their slots for extract()
        self.prefill_only = bool(prefill_only)

        self._slots: list[_Request | None] = [None] * max_slots
        self._waiting: deque[_Request] = deque()
        # rid_base: fleet routers give each replica a disjoint id space
        # so request trace trees (docs/TELEMETRY.md Tracing) never
        # collide across replicas
        self._next_rid = int(rid_base)
        # weights are argument 0 — NOT closed-over jit constants — so a
        # reload on a live engine feeds the already-compiled step
        self._decode_jit = jax.jit(self._decode_step, donate_argnums=(4,),
                                   static_argnums=(11,))
        # a decode tick is as wide as the step of this ladder that holds
        # its live rows: one compiled program a step
        self._decode_rows = _decode_row_ladder(max_slots)
        # the decode tick launched and not yet fetched, a ``_Tick``
        # (docs/SERVING.md "The step's order"). The next tick takes its
        # tokens from that tick's ``nxt`` on the device; ``_settle()``
        # brings them to the host. With nothing in flight a tick reads
        # ``_no_tick`` in its place, a vector of nxt's shape (the
        # tokens, then what the model kind counts of a tick)
        self._in_flight = None
        _, counts = jax.eval_shape(
            lambda x: self._arch.carry_out(self._arch.carry_in(x)),
            jax.ShapeDtypeStruct((1, 1, 1), jnp.float32))
        self._no_tick = jnp.zeros(
            (max_slots + (0 if counts is None else counts.shape[0]),),
            jnp.int32)
        self.decode_ticks = {"ahead": 0, "settled": 0}
        self.discarded_tokens = {"ended": 0, "withdrawn": 0}
        self.prefill_batches = 0      # observability: admission group count
        self.preemptions = 0          # pages reclaimed from the youngest
        self._admit_counter = 0
        self._tick = 0           # step() calls so far: names a tick in
                                 # the trace (engine_step, first_token)
        # preempt_policy: what happens to a victim's KV state.
        #   "recompute" — drop pages, fold generated tokens into the resume
        #     prompt, rebuild KV by re-prefilling on re-admission (vLLM
        #     recompute; the r5 default).
        #   "swap" — copy the victim's pages to HOST memory, free the
        #     device pages, and scatter the snapshot back on re-admission
        #     (vLLM swap / the reference block-table cache-offload shape):
        #     no prefill FLOPs are re-paid, at the price of two
        #     host<->device transfers of the live KV. Greedy outputs are
        #     bitwise identical either way (bf16 round-trips exactly
        #     through the host copy); tests assert both.
        if preempt_policy not in ("recompute", "swap"):
            raise ValueError(
                f"preempt_policy must be 'recompute' or 'swap', "
                f"got {preempt_policy!r}")
        self.preempt_policy = preempt_policy
        # enable_prefix_cache=True: automatic prefix caching (vLLM APC /
        # SGLang radix-cache shape). KV pages are content-addressed by
        # their token-prefix chain; a new request whose prompt shares a
        # full-page-aligned prefix with any previously computed sequence
        # REUSES those pages (read-only, refcounted) and prefills only
        # the tail. Released pages are retained "free-but-cached": they
        # are reclaimed lazily (cache eviction, FIFO over ref-0 entries)
        # only when the pool runs short. Matching is capped one token
        # below the prompt end so a fully-cached prompt still computes
        # its first-token logits. Sound because KV at position i is a
        # pure function of tokens[0..i]; writes only ever target
        # positions past the matched prefix (page-granular match), so
        # shared pages are never written. Requires chunked prefill (the
        # tail prefill starts mid-prompt) and the recompute preemption
        # policy (swap restore scatters into pages, which must stay
        # exclusive).
        if enable_prefix_cache:
            if prefill_chunk is None:
                raise ValueError("enable_prefix_cache requires chunked "
                                 "prefill (prefill_chunk=...)")
            if preempt_policy != "recompute":
                raise ValueError("enable_prefix_cache composes only with "
                                 "preempt_policy='recompute'")
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self._prefix_cache = {}       # token-chain digest -> page id
        self._cached_pages = set()    # page ids held by the cache (O(1)
                                      # membership on the release path)
        self._page_ref = {}           # page id -> live-request refcount
        self.prefix_cache_hits = 0    # pages reused instead of prefilled
        self.prefix_cache_evictions = 0
        self.prefix_tokens_skipped = 0
        self.prefix_pages_exported = 0  # shipped to a drain destination
        self.prefix_pages_imported = 0  # warmed from a draining peer
        self._cache_admit_floor = 0   # requests admitted before a
                                      # reload_weights hold stale KV and
                                      # must not register pages
        self.swaps_out = 0            # victims snapshotted to host
        self.swaps_in = 0             # snapshots restored to device
        # fixed-shape ([pages_per_seq] page vector, trash-padded) so each
        # compiles ONCE; swap-in donates the caches (no double buffering)
        self._swap_out_jit = jax.jit(self._swap_gather)
        self._swap_in_jit = jax.jit(self._swap_scatter,
                                    donate_argnums=(0,))
        # chunked prefill (vLLM-style): admit immediately, write the
        # prompt's KV `prefill_chunk` tokens per TICK so long prompts
        # don't stall the decode latency of running requests
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        self.prefills_completed = 0   # per-request (both prefill modes)
        # batched chunked prefill: ONE jitted pass advances every
        # prefilling slot by up to prefill_chunk tokens per tick
        # (VERDICT r3 item 7 — the eager per-request chunk loop paid the
        # ~2.5ms/dispatch host cost per layer per request), as wide as
        # the step of the row ladder that holds the rows with a chunk:
        # one function, one compiled program a step
        self._prefill_jit = jax.jit(self._prefill_chunk_step,
                                    donate_argnums=(5,))
        self._pass_rows = _pass_row_ladder(max_slots)
        self.prefill_chunk_steps = 0  # observability: jitted pass count
        # row count -> a greedy program's sampling operands, made here for
        # every step of the decode ladder: no tick makes an array
        self._greedy_consts = {}
        for b in self._decode_rows:
            self._greedy_operands(b)
        self._first_token_jit = jax.jit(self._first_token_step,
                                        static_argnums=(6,))
        self._stats_pending = deque()  # (prefill_tick span, its counts)
        # -- request deadlines / cancellation (docs/SERVING.md) --
        self.cancelled = {}           # rid -> reason, drained by callers
        self.cancellations = 0
        # -- draft-model speculative decoding (fleet.spec_decode) --
        # draft K tokens per tick, verify in ONE target forward,
        # accept-prefix; bitwise-greedy-exact vs plain decode (the
        # verify pass runs the SAME per-position paged kernel)
        self.spec_tokens = int(spec_tokens)
        self._draft = None
        if draft_model is not None:
            if self.spec_tokens < 1:
                raise ValueError("spec_tokens must be >= 1 with a "
                                 f"draft model, got {spec_tokens}")
            from .fleet.spec_decode import DraftRunner

            self._draft = DraftRunner(self, draft_model)
            self._verify_jit = jax.jit(self._spec_verify,
                                       donate_argnums=(4,))
        self.spec_ticks = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        # pages each decoding slot must hold BEFORE a tick: a spec tick
        # writes K drafts + the carry token past `length`, a plain tick
        # writes one
        self._lookahead = (self.spec_tokens + 1 if self._draft is not None
                           else 1)
        self.build_seconds = None     # set by warmup() (cold-start gate)
        # each program warmup() builds: its trace, lowering, load and
        # first-run seconds, published into the trace by the first
        # traced step() after warmup() (docs/TELEMETRY.md)
        from ..jit import SetupRecord

        self.setup_record = SetupRecord("engine")
        # program name -> {"temp", "alias"} bytes, read off each compiled
        # program's memory_analysis() at warmup(): temp under one layer's
        # slab says no pool-sized buffer is left in the program
        self.program_bytes = {}
        # -- brownout degradation knobs (fleet.overload, docs/SERVING.md
        # "Overload & degradation") — reversible service caps the fleet
        # brownout ladder sets under sustained pressure and restores on
        # recovery. All-default = full service, behavior unchanged.
        self.max_new_cap = None       # L1: cap on tokens to generate
        self.spec_paused = False      # L2: skip speculative ticks
                                      #     (greedy-output-invariant)
        self.prefill_chunk_cap = None  # L3: per-tick prefill token
                                       #     budget (output-invariant)

    @staticmethod
    def _arch_of(model):
        return (model.serving_arch() if hasattr(model, "serving_arch")
                else DenseDecoderServing(model))

    def _pack_weights(self, model):
        # the model kind's packed tree ({"layers", "embed", "fnorm",
        # "head"}; what "layers" holds is the kind's, see its ``pack``);
        # the per-dtype footprint lands in self.weight_bytes and
        # serving_weight_bytes{dtype}
        if model is not self._arch.model:
            self._arch = self._arch_of(model)     # a reload's new model
        w = self._arch.pack(self.int8_weights)
        self.weight_bytes = _weight_nbytes(w)
        for dt, nb in self.weight_bytes.items():
            _WEIGHT_BYTES.set(float(nb), labels=(dt,))
        return w

    # -- the pools by name ---------------------------------------------------
    def _pool(self, name):
        if name not in self.cache_names:
            raise AttributeError(
                f"this engine's cache has no {name!r} pool: its pools are "
                f"{self.cache_names} (docs/SERVING.md, model kinds)")
        return self.cache[self.cache_names.index(name)]

    def _set_pool(self, name, value):
        i = self.cache_names.index(name)
        self.cache = self.cache[:i] + (value,) + self.cache[i + 1:]

    kc = property(lambda self: self._pool("k"),
                  lambda self, v: self._set_pool("k", v),
                  doc="the dense decoder's K pool")
    vc = property(lambda self: self._pool("v"),
                  lambda self, v: self._set_pool("v", v),
                  doc="the dense decoder's V pool")

    def reload_weights(self, model=None):
        """Re-read weights from the model (e.g. after an in-place update);
        the compiled decode step picks them up on the next tick. Any
        cached prefix KV is invalidated (it was computed under the old
        weights): ref-0 cached pages are freed now, in-use ones when
        their readers release them; requests already admitted are barred
        from registering their (stale) pages.

        The old packed weights are released BEFORE repacking: with the
        lazy per-layer slicing of the stacked models (gpt.py
        _decode_params), a live-engine reload peaks at stacked + new
        slices + one in-flight layer instead of holding old and new
        sliced copies side by side (ADVICE r5). The release is what buys
        the headroom, so a mid-pack failure cannot fall back to the old
        weights — it raises loudly and the engine stays weightless until
        a reload succeeds (serving on half-reloaded state would be
        worse). A tick in flight reads the old weights and is settled
        first, or the release would free nothing yet."""
        self._settle()
        self._weights = None
        try:
            self._weights = self._pack_weights(model or self._model)
        except Exception as e:
            raise RuntimeError(
                "reload_weights failed mid-pack; the old weights were "
                "already released (HBM headroom), so the engine has no "
                "weights until a reload_weights() succeeds") from e
        if self.enable_prefix_cache:
            for key in list(self._prefix_cache):
                pg = self._prefix_cache.pop(key)
                self._cached_pages.discard(pg)
                if self._page_ref.get(pg, 0) == 0:
                    self._page_ref.pop(pg, None)
                    self.pool.free([pg])
            self._cache_admit_floor = self._admit_counter

    # -- model math ---------------------------------------------------------
    def _head_tokens(self, last, reqs):
        """final-norm'd last hidden rows [B, H] -> first token per req."""
        jax, jnp = self._jax, self._jnp
        w = self._weights
        lg = (last @ w["head"] if w["head"] is not None
              else last @ w["embed"].T)
        self._key, sub = jax.random.split(self._key)
        if any(r.temperature > 0.0 for r in reqs):
            toks = _sample_rows(
                jax, jnp, lg,
                jnp.asarray([r.temperature for r in reqs], jnp.float32),
                jnp.asarray([r.top_k for r in reqs], jnp.int32),
                jnp.asarray([r.top_p for r in reqs], jnp.float32), sub)
        else:
            toks = jnp.argmax(lg.astype(jnp.float32), -1)
        return [int(t) for t in np.asarray(toks)]

    def _prefill_group(self, reqs):
        """Run ALL newly admitted prompts as ONE padded batch: write each
        prompt's KV into its pages, return the first generated token per
        request.

        One pass over the weights per admission group (the reference's
        serving stack batches prefill the same way before handing slots to
        the decode loop). Runs eagerly: page-cache writes copy the pool
        once per layer per GROUP; jitting would retrace per padded length
        (bucket lengths first if admission cost ever dominates)."""
        jnp = self._jnp
        from ..models.gpt import _rms_pure

        self.prefill_batches += 1
        self.prefills_completed += len(reqs)
        w = self._weights
        B = len(reqs)
        lens = np.asarray([len(r.seq_tokens) for r in reqs])
        S = int(lens.max())
        ids_np = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            ids_np[i, : lens[i]] = r.seq_tokens
        ids = jnp.asarray(ids_np)
        x = w["embed"][ids]                                  # [B, S, H]
        pos0 = jnp.zeros((B,), jnp.int32)
        attend = self._arch.group_attend(
            jnp.asarray(self._table_rows(reqs)),
            jnp.asarray(lens, jnp.int32), S)
        # eagerly and unrolled (a scan would compile a padded length)
        x, self.cache = self._run_layers(w, self._arch.carry_in(x), pos0,
                                         self.cache, attend, scan=False)
        x = _rms_pure(self._arch.carry_out(x)[0], w["fnorm"])
        last = x[jnp.arange(B), jnp.asarray(lens - 1)]       # [B, H]
        toks = self._head_tokens(last, reqs)
        for i, r in enumerate(reqs):
            r.length = int(lens[i])
            # group prefill wrote the whole prompt: keep prefill_pos in
            # lockstep so a later swap snapshot is classified decode-phase
            # (its restore must reserve the growth page, not the prompt)
            r.prefill_pos = int(lens[i])
        if self._draft is not None:
            # the draft's KV for these prompts (same pages/page table)
            self._draft.prefill(reqs, [r.seq_tokens for r in reqs])
        return toks

    def _run_layers(self, weights, x, pos0, cache, attend, scan=None):
        """Run every decoder layer over the whole stacked pools through
        the shared :func:`_run_layer_stack` walker (scan-over-layers per
        the models.gpt resolver; ``PTPU_SCAN_LAYERS=0``, and the eager
        group prefill's ``scan=False``, unroll bitwise —
        docs/SERVING.md), one GROUP of like layers after another, the
        pools' layer index running on. ``x`` is the model kind's carry
        (``carry_in``). ``attend(li, *operands, cache) -> (o, cache)``
        owns the layer's cache writes and its attention; the rest of
        the layer is the group's forward, the model kind's own, shared
        by every program so their numerics can never drift apart."""
        scan = self._scan_layers if scan is None else scan
        base = 0
        for layers, forward in self._arch.groups(weights):
            def layer_fn(lp, li, x, cache, forward=forward):
                def inner(li, *operands):
                    nonlocal cache
                    o, cache = attend(li, *operands, cache)
                    return o

                x = forward(li, lp, x, pos0, inner)
                return x, cache

            x, cache = _run_layer_stack(scan, layers, x, layer_fn, cache,
                                        base)
            base += layers[0].shape[0]
        return x, cache

    def _head_logits(self, weights, x):
        return (x @ weights["head"] if weights["head"] is not None
                else x @ weights["embed"].T)

    def _choose(self, lg, temps, top_ks, top_ps, key, do_sample):
        """A token a row from its logits, inside a compiled program."""
        jax, jnp = self._jax, self._jnp
        if do_sample:
            return _sample_rows(jax, jnp, lg, temps, top_ks, top_ps, key)
        # greedy-only: skip the full-vocab sort/cumsum entirely
        return jnp.argmax(lg.astype(jnp.float32), -1).astype(jnp.int32)

    def _decode_step(self, weights, tokens, lens, tables, cache,
                     temps, top_ks, top_ps, key, prev, src,
                     do_sample=False, slots=None):
        """ONE batched decode: tokens [B] (last emitted), lens [B] tokens
        already cached, tables [B, pages_per_seq]. A row whose last
        token the host has not read yet takes it from ``prev``, the
        ``next`` of the tick before, at row ``src`` [B] (-1: the host's
        ``tokens`` holds it). ``slots`` [B] is each row's slot, for a
        kind that keeps a state by slot (None, and no operand of the
        program, for every other kind). Returns (next, the pools): next
        is [max_slots] (zeros past B, so that ``prev`` has one shape
        whatever the width of either tick), and what the model kind
        counts of a tick (``carry_out``: an expert layer's routed pairs)
        is appended to it: it comes to the host in the tokens' own
        fetch. B is a step of the engine's decode ladder
        (``_decode_rows``)."""
        jnp = self._jnp
        from ..models.gpt import _rms_pure

        tokens = jnp.where(src >= 0, prev[src], tokens)
        x = weights["embed"][tokens][:, None]                # [B, 1, H]
        attend = self._arch.decode_attend(tables, lens, slots)
        x, cache = self._run_layers(weights, self._arch.carry_in(x), lens,
                                    cache, attend)
        x, stats = self._arch.carry_out(x)
        x = _rms_pure(x, weights["fnorm"])[:, 0]
        nxt = self._choose(self._head_logits(weights, x), temps, top_ks,
                           top_ps, key, do_sample)
        if nxt.shape[0] < self.max_slots:
            nxt = jnp.pad(nxt, (0, self.max_slots - nxt.shape[0]))
        if stats is not None:
            nxt = jnp.concatenate([nxt, stats])
        return nxt, cache

    def _spec_verify(self, weights, toks, lens, tables, cache):
        """Speculative-decoding verify: ONE target forward over the
        C = K+1 token window [carry, d1..dK] at positions
        lens..lens+K, returning the target's greedy token at EVERY
        position (t1..t_{K+1}) plus the updated caches.

        Bitwise-greedy-exact by construction (the acceptance contract,
        docs/SERVING.md): projections/norms/rope/MLP are row-local ops
        (batching over positions cannot change a row's value), and
        attention runs the SAME per-position `paged_attend` with the
        same operands a plain decode tick at that position would see —
        position i reads lens+i+1 valid rows, the earlier window rows
        having just been written with the identical values sequential
        ticks would have written."""
        jnp = self._jnp
        from ..models.gpt import _rms_pure

        C = toks.shape[1]
        x = weights["embed"][toks]                           # [B, C, H]
        attend = self._arch.verify_attend(tables, lens, C)
        x, cache = self._run_layers(weights, x, lens, cache, attend)
        x = _rms_pure(x, weights["fnorm"])                   # [B, C, H]
        lg = self._head_logits(weights, x)
        t = jnp.argmax(lg.astype(jnp.float32), -1).astype(jnp.int32)
        return t, cache

    # -- engine surface -----------------------------------------------------
    def submit(self, prompt_ids, temperature=0.0, top_k=0, top_p=1.0,
               on_token=None, deadline_seconds=None, rid=None) -> int:
        """Queue a request. ``temperature=0`` decodes greedily; otherwise
        softmax sampling with optional top_k / top_p truncation.
        ``on_token(rid, token_id)`` streams each generated token.
        ``deadline_seconds`` cancels the request (queued OR running —
        pages freed, ``serving_cancellations_total{reason="deadline"}``)
        once that much wall time has passed since submit. ``rid`` lets a
        fleet router assign globally-unique ids (trace trees must not
        collide across replicas); the caller owns uniqueness."""
        if len(prompt_ids) == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "token to prefill")
        total = len(prompt_ids) + self.max_new_tokens
        if total > self.max_seq:
            raise ValueError(
                f"request needs {total} tokens (prompt "
                f"{len(prompt_ids)} + max_new {self.max_new_tokens}) > "
                f"max_seq_len {self.max_seq}")
        if self._draft is not None and total + self.spec_tokens > self.max_seq:
            raise ValueError(
                f"speculative decoding writes up to {self.spec_tokens} "
                f"draft tokens of KV past the sequence end: request "
                f"needs {total} + {self.spec_tokens} spec headroom > "
                f"max_seq_len {self.max_seq}")
        # feasibility must cover the speculative lookahead too: the
        # grow-pages no-deadlock invariant ("a lone request always
        # fits") prices length + K + 1 tokens under a draft model
        spec_pad = self.spec_tokens if self._draft is not None else 0
        need = (total + spec_pad + self.page - 1) // self.page
        if need > self.pool.num_pages:
            raise ValueError(
                f"request needs {need} pages (incl. {spec_pad} tokens "
                f"of speculative headroom) > pool size "
                f"{self.pool.num_pages}")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            rid = int(rid)
            self._next_rid = max(self._next_rid, rid + 1)
        deadline = (time.perf_counter() + float(deadline_seconds)
                    if deadline_seconds is not None else None)
        self._waiting.append(_Request(
            rid, [int(t) for t in prompt_ids], temperature, top_k, top_p,
            on_token, deadline=deadline))
        # request span tree (docs/TELEMETRY.md Tracing): the async
        # "request" span covers submit → retire; "queue" covers
        # submit → admission (re-opened on preemption requeue)
        _trace.async_begin("request", rid,
                           {"prompt_tokens": len(prompt_ids)})
        _trace.async_begin("queue", rid)
        return rid

    # -- cancellation / deadlines ------------------------------------------
    def _cancel_req(self, req, reason, slot_idx=None):
        """Tear a request out of the engine: release pages (completed
        prefix pages still register into the prefix cache — their KV is
        valid), drop any host snapshot, close its trace spans, count
        it. The request lands in ``self.cancelled`` (rid -> reason) for
        callers that track outcomes."""
        if slot_idx is not None:
            self._slots[slot_idx] = None
            if req.first_token_t is None:
                _trace.async_end("prefill", req.rid, {"cancelled": reason})
        else:
            try:
                self._waiting.remove(req)
            except ValueError:
                pass
            _trace.async_end("queue", req.rid, {"cancelled": reason})
        if req.pages:
            self._release_pages(req, register=True)
        req.swapped = None
        self.cancelled[req.rid] = reason
        self.cancellations += 1
        _CANCELLATIONS.inc(labels=(reason,))
        _trace.async_end("request", req.rid, {"cancelled": reason})

    def cancel(self, rid, reason="user") -> bool:
        """Cancel a queued or running request by id. Returns True if it
        was found live; its pages return to the pool immediately."""
        for i, r in enumerate(self._slots):
            if r is not None and r.rid == rid:
                self._cancel_req(r, reason, slot_idx=i)
                return True
        for r in list(self._waiting):
            if r.rid == rid:
                self._cancel_req(r, reason)
                return True
        return False

    def _sweep_deadlines(self):
        """Cancel every request whose deadline has passed — queued AND
        running (a stuck client must not hold KV pages forever). A
        request that already FINISHED generating is not cancelled: its
        tokens were all delivered, so the retire loop (which runs right
        after this sweep) returns it as a completion."""
        now = time.perf_counter()
        for i, r in enumerate(list(self._slots)):
            if (r is not None and r.deadline is not None
                    and now >= r.deadline and not self._finished(r)):
                self._cancel_req(r, "deadline", slot_idx=i)
        for r in [r for r in self._waiting
                  if r.deadline is not None and now >= r.deadline]:
            self._cancel_req(r, "deadline")

    def _emit(self, req, tok):
        if req.first_token_t is None:
            req.first_token_t = time.perf_counter()
            ttft = req.first_token_t - req.submit_t
            _TTFT.observe(ttft)
            if _trace.enabled():
                # everything of the TTFT that was not waiting for a slot
                # is prefill, a preempted first attempt included
                _trace.async_end("prefill", req.rid)
                _trace.async_instant("first_token", req.rid, {
                    "queue_ms": 1e3 * req.queue_s,
                    "prefill_ms": 1e3 * (ttft - req.queue_s),
                    "tick": self._tick})
        req.generated.append(tok)
        if req.on_token is not None:
            req.on_token(req.rid, tok)

    def _mark_admitted(self, req, kind):
        """A request leaves the waiting queue for a slot: its wait is
        added to what it has waited before, on the request itself."""
        req.admit_t = time.perf_counter()
        req.queue_s += req.admit_t - req.queued_t
        if _trace.enabled():
            _trace.async_end("queue", req.rid)
            _trace.async_instant("admitted", req.rid,
                                 {"kind": kind, "tick": self._tick})

    def _admit(self):
        group = []
        for i in range(self.max_slots):
            if self._slots[i] is not None or not self._waiting:
                continue
            req = self._waiting[0]
            if req.swapped is not None:
                # swap policy re-admission: restore the host KV snapshot
                # into freshly allocated pages — no prefill re-run. For a
                # decode-phase snapshot, also reserve THIS tick's growth
                # page up front: restoring with exactly n pages when
                # length is page-aligned would hand _grow_pages a starved
                # youngest request and swap it straight back out (a full
                # round-trip per tick with zero progress).
                snap = req.swapped
                n = snap["n"]
                # restore the FULL reservation, not just the snapshot
                # pages: a mid-prefill victim needs its whole prompt's
                # pages back for _prefill_tick's scatter targets, and a
                # decode-phase one needs this tick's growth page (without
                # it a page-aligned restoree would be the starved
                # youngest and swap straight back out)
                if snap["prefill_pos"] < len(req.seq_tokens):
                    need = max(n, (len(req.seq_tokens) + self.page - 1)
                               // self.page)
                else:
                    need = max(n, (snap["length"] + self.page) // self.page)
                if need > self.pool.available:
                    break  # head-of-line waits for pages
                self._waiting.popleft()
                req.pages = self.pool.alloc(need)
                # stage the n-page snapshot into fresh fixed-shape host
                # buffers (no zeroing — the padded rows scatter into the
                # scratch page, so their uninitialized contents are
                # irrelevant; the padded h2d volume is the price of the
                # compile-once scatter)
                staged = tuple(
                    _kv_map(self._jnp.asarray,
                            self._swap_stage(snap[name], n))
                    for name in self.cache_names[:self._n_paged]) + tuple(
                    self._jnp.asarray(snap[name])
                    for name in self._slot_names)
                self.cache = self._swap_in_jit(
                    self.cache, self._padded_page_vec(req.pages[:n]),
                    staged, i if self._slot_names else None)
                req.prefill_pos = snap["prefill_pos"]
                req.length = snap["length"]
                req.swapped = None
                self.swaps_in += 1
                req.admit_seq = self._admit_counter
                self._admit_counter += 1
                self._slots[i] = req
                if (self._draft is not None
                        and req.prefill_pos >= len(req.seq_tokens)):
                    # a decode-phase snapshot (a disagg handoff, or a
                    # swap-policy victim) carries no draft KV — rebuild
                    # it for the restored context so acceptance doesn't
                    # collapse (mid-prefill snapshots rebuild at the
                    # prefill-completion hook instead)
                    self._draft.prefill(
                        [req],
                        [(req.prompt + req.generated)[:req.length]])
                _ADMISSIONS.inc(labels=("swap_restore",))
                self._mark_admitted(req, "swap_restore")
                if req.first_token_t is None:
                    # a mid-prefill swap victim resumes its prefill
                    # phase here — re-open the span so the restore-to-
                    # first-token segment stays in the TTFT anatomy
                    _trace.async_begin(
                        "prefill", req.rid,
                        {"kind": "swap_restore",
                         "resume_tokens": len(req.seq_tokens)})
                continue  # not part of any prefill group
            # reserve only what PREFILL writes (the resume prefix); decode
            # pages are allocated as the sequence grows, with preemption
            # under pressure — block-table growth semantics of the
            # reference's block_multi_head_attention serving path (vs the
            # r4 worst-case prompt+max_new reservation that capped batch
            # width at a fraction of pool capacity). With the prefix
            # cache on, pages holding an already-computed prefix of this
            # prompt are REUSED (read-only) and only the tail is
            # reserved + prefilled.
            shared = self._match_prefix(req.seq_tokens)
            need = ((len(req.seq_tokens) + self.page - 1) // self.page
                    - len(shared))
            if self.enable_prefix_cache:
                # PIN the matched pages before any eviction runs: a ref-0
                # free-but-cached prefix page is otherwise a legal FIFO
                # eviction victim, and reclaiming it here would alias one
                # physical page into prefix-read and tail-write roles
                for pg in shared:
                    self._page_ref[pg] = self._page_ref.get(pg, 0) + 1
                if not self._free_pages_for(need):
                    for pg in shared:  # unpin; retry next tick
                        self._page_ref[pg] -= 1
                    break  # head-of-line waits for pages
            elif need > self.pool.available:
                break  # head-of-line waits for pages
            self._waiting.popleft()
            if self.enable_prefix_cache:
                req.pages = shared + self._alloc_ref(need)
                if shared:
                    req.prefill_pos = max(req.prefill_pos,
                                          len(shared) * self.page)
                    self.prefix_cache_hits += len(shared)
                    self.prefix_tokens_skipped += len(shared) * self.page
            else:
                req.pages = self.pool.alloc(need)
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            self._slots[i] = req
            _ADMISSIONS.inc(labels=("prefill",))
            self._mark_admitted(req, "prefill")
            if req.first_token_t is None:
                _trace.async_begin(
                    "prefill", req.rid,
                    {"resume_tokens": len(req.seq_tokens)})
            group.append(req)
        if not group:
            return
        if self.prefill_chunk is None:
            with _trace.span("prefill_group",
                             attrs={"requests": len(group)}, cat="serve"):
                first = self._prefill_group(group)
            for req, tok in zip(group, first):
                self._emit(req, tok)
        # chunked mode: KV fills incrementally in step()

    def _prefill_chunk_step(self, weights, ids, pos0, nvalid, hist, cache,
                            slots=None):
        """ONE jitted chunk pass over the B rows it is handed, the
        prefilling slots packed into the first of them: ids [B, c] chunk
        tokens (zero-padded), pos0 [B] absolute start, nvalid [B] real
        tokens this chunk (0 for a row with none: its writes go to the
        scratch page), hist [B, pages_per_seq] page tables, slots [B]
        each row's slot for a kind that keeps a state by slot (the trash
        slot for a row with no chunk; None for every other kind). Returns
        (final-normed last-valid hidden [max_slots, H], the pools), and
        after the hidden rows the model kind's counts if it keeps any.
        B is a step of the engine's row ladder (``_pass_rows``), chunk
        and pages_per_seq are engine constants: one compile a step,
        every one of them made by ``warmup()``. The hidden rows come
        back at the full ``max_slots`` (zeros past B), so the
        first-token program has one shape whatever the pass's width."""
        jnp = self._jnp
        from ..models.gpt import _rms_pure

        B, c = ids.shape
        x = weights["embed"][ids]                            # [B, c, H]
        attend = self._arch.chunk_attend(hist, pos0, nvalid, c, self.page,
                                         slots)
        x, cache = self._run_layers(weights, self._arch.carry_in(x), pos0,
                                    cache, attend)
        x, stats = self._arch.carry_out(x)
        last_rows = jnp.clip(nvalid - 1, 0, c - 1)
        last = _rms_pure(x[jnp.arange(B), last_rows], weights["fnorm"])
        if B < self.max_slots:
            last = jnp.pad(last, ((0, self.max_slots - B), (0, 0)))
        return (last, cache) if stats is None else (last, stats, cache)

    def _prefill_tick(self, span):
        """Chunked prefill: advance EVERY prefilling slot by up to
        `prefill_chunk` prompt tokens in one jitted batched pass, so
        running requests keep decoding every tick while long prompts fill
        incrementally (the reference serving stack's chunked-prefill /
        mixed-batch scheduling over block_multihead_attention; r3's
        eager per-request loop paid the per-dispatch host cost per layer
        per request). The pass is as wide as the smallest step of the
        row ladder that holds the rows with a chunk: a lone prompt runs
        a one-row program, a full house the ``max_slots``-row one.
        ``span`` is the tick's ``prefill_tick`` span: a pass that
        launches annotates it with its width and with how many of the
        positions it computes are real."""
        jnp = self._jnp
        at = [i for i, r in enumerate(self._slots)
              if r is not None and r.prefill_pos < len(r.seq_tokens)]
        if not at:
            return
        reqs = [self._slots[i] for i in at]
        B = next(n for n in self._pass_rows if n >= len(reqs))
        c = self.prefill_chunk
        with _trace.span("prefill_build", cat="serve"):
            # brownout L3: a live chunk cap shrinks the per-tick prefill
            # token budget WITHOUT recompiling — the jitted pass keeps
            # its [B, c] shapes and simply sees fewer valid tokens per row
            c_eff = (c if self.prefill_chunk_cap is None
                     else max(1, min(c, self.prefill_chunk_cap)))
            ids_np = np.zeros((B, c), np.int32)
            pos0 = np.zeros(B, np.int32)
            nvalid = np.zeros(B, np.int32)
            hist = np.zeros((B, self.pages_per_seq), np.int32)
            slots = self._slot_vec(at, B)
            for i, r in enumerate(reqs):
                pos = r.prefill_pos
                n = min(c_eff, len(r.seq_tokens) - pos)
                ids_np[i, :n] = r.seq_tokens[pos:pos + n]
                pos0[i], nvalid[i] = pos, n
                hist[i, :len(r.pages)] = r.pages[:self.pages_per_seq]
        if _trace.enabled():
            span.annotate(rows=len(reqs), pass_rows=B,
                          valid_tokens=int(nvalid.sum()),
                          computed_tokens=B * c)
        _PREFILL_PASSES.inc(labels=(str(B),))
        with _trace.span("prefill_launch", cat="serve"):
            last, *stats, self.cache = self._prefill_jit(
                self._weights, jnp.asarray(ids_np), jnp.asarray(pos0),
                jnp.asarray(nvalid), jnp.asarray(hist), self.cache, slots)
        if stats:
            # the model kind's counts of a pass are read once the pass
            # has ended, at a later fetch: no tick waits for them
            self._stats_pending.append((span, stats[0]))
        self.prefill_chunk_steps += 1
        completed = []
        for i, r in enumerate(reqs):
            r.prefill_pos += int(nvalid[i])
            if r.prefill_pos == len(r.seq_tokens):
                completed.append((i, r))
        if completed:
            with _trace.span("first_token_fetch", cat="serve"):
                toks = self._first_tokens(last, completed)
            self._drain_stats()
            for (i, r), tok in zip(completed, toks):
                self.prefills_completed += 1
                r.length = len(r.seq_tokens)
                self._emit(r, tok)
            if self._draft is not None:
                done_reqs = [r for _, r in completed]
                self._draft.prefill(done_reqs,
                                    [r.seq_tokens for r in done_reqs])

    def _swap_gather(self, cache, pages, slot=None):
        """Every layer's rows for `pages`, of every pool -> a tuple of
        [L, Hkv, P, page, D] (P = pages_per_seq, trash-padded; int8
        caches yield a (codes, scales) leaf pair), then every layer's
        entry of ``slot`` of each leaf addressed by slot ([L, ...]). One
        jitted dispatch per swap-out, then a single host transfer."""
        g = lambda c: c[:, :, pages]
        n = self._n_paged
        return (tuple(_kv_map(g, c) for c in cache[:n])
                + tuple(c[:, slot] for c in cache[n:]))

    def _swap_scatter(self, cache, pages, snap, slot=None):
        """Scatter a host snapshot (one entry a pool) back into the
        pools at `pages` (trash-padded rows land in the scratch page —
        harmless by definition), and the entries of the leaves addressed
        by slot into ``slot``. Donates the pools."""
        sc = lambda c, s: c.at[:, :, pages].set(s)
        n = self._n_paged
        return (tuple(_kv_map2(sc, c, s)
                      for c, s in zip(cache[:n], snap[:n]))
                + tuple(c.at[:, slot].set(s)
                        for c, s in zip(cache[n:], snap[n:])))

    def _slot_vec(self, at, width):
        """[width] int32: the slots ``at`` of a program's first rows,
        the trash slot for the rest; None for a kind that keeps nothing
        by slot (its programs have no such operand)."""
        if not self._slot_names:
            return None
        slots = np.full(width, self._trash_slot, np.int32)
        slots[:len(at)] = at
        return slots

    def _padded_page_vec(self, pages):
        pad = np.full(self.pages_per_seq, self._trash_page, np.int32)
        pad[: len(pages)] = pages
        return self._jnp.asarray(pad)

    def _snapshot_to_host(self, r):
        """Build ``r.swapped`` — THE host KV snapshot format the
        swap-restore admission path consumes — shared by swap-policy
        preemption and the disagg ``extract()`` seam so the two can
        never drift. Sliced device-side to pages holding LIVE tokens
        before the host copy: the retained snapshot and the d2h
        transfer scale with written KV, not the page reservation (a
        mid-prefill victim's untouched prompt pages and grown-but-empty
        decode pages never leave the device; restore re-allocates the
        full reservation from prefill_pos/length bookkeeping)."""
        self._settle()
        got = self._swap_out_jit(
            self.cache, self._padded_page_vec(r.pages),
            self._slots.index(r) if self._slot_names else None)
        written = max(r.length, r.prefill_pos)
        n = min((written + self.page - 1) // self.page, len(r.pages))
        cut = lambda c: np.asarray(c[:, :, :n])
        # one entry a pool, under the pool's name ("k" and "v" for the
        # dense decoder, "latent" for a latent model); a leaf addressed
        # by slot is snapshotted whole (its size does not grow)
        k = self._n_paged
        r.swapped = {name: _kv_map(cut, g)
                     for name, g in zip(self.cache_names[:k], got)}
        r.swapped.update((name, np.asarray(g))
                         for name, g in zip(self._slot_names, got[k:]))
        r.swapped.update(n=n, prefill_pos=r.prefill_pos, length=r.length)
        return r.swapped

    # -- prefix cache (content-addressed KV pages) --------------------------
    def _chain_keys(self, tokens, n_pages):
        """Chain digests of pages 0..n_pages-1: key_i =
        sha1(key_{i-1} || tokens of page i) — O(1) bytes per cache
        entry regardless of prefix depth (the vLLM block-hash-chain
        discipline; 160-bit collision space is identity in practice)."""
        import hashlib

        keys, prev = [], b""
        for i in range(n_pages):
            block = np.asarray(
                tokens[i * self.page: (i + 1) * self.page],
                np.int64).tobytes()
            prev = hashlib.sha1(prev + block).digest()
            keys.append(prev)
        return keys

    def _evictable(self):
        return [k for k, pg in self._prefix_cache.items()
                if self._page_ref.get(pg, 0) == 0]

    def _free_pages_for(self, n):
        """True if n pages can be allocated, evicting ref-0 cached pages
        (FIFO) as needed. Callers must PIN (incref) any matched shared
        pages before calling, or eviction could reclaim them."""
        while self.pool.available < n:
            victims = self._evictable()
            if not victims:
                return False
            key = victims[0]
            page = self._prefix_cache.pop(key)
            self._cached_pages.discard(page)
            self._page_ref.pop(page, None)
            self.pool.free([page])
            self.prefix_cache_evictions += 1
        return True

    def _alloc_ref(self, n):
        pages = self.pool.alloc(n)
        for pg in pages:
            self._page_ref[pg] = self._page_ref.get(pg, 0) + 1
        return pages

    def _release_pages(self, req, register):
        """Drop req's claim on its pages. Own pages whose content is a
        complete, deterministic token-prefix page are REGISTERED into the
        prefix cache (retained, lazily evictable) instead of freed; the
        rest return to the pool. Without the cache enabled this is
        exactly pool.free."""
        if not self.enable_prefix_cache:
            self.pool.free(req.pages)
            req.pages = []
            return
        register = register and req.admit_seq >= self._cache_admit_floor
        written = max(req.length, req.prefill_pos)
        full = req.prompt + req.generated
        n_complete = min(written // self.page, len(req.pages))
        keys = (self._chain_keys(full, n_complete)
                if register and n_complete else [])
        freed = []
        for i, pg in enumerate(req.pages):
            ref = self._page_ref.get(pg, 0) - 1
            if ref < 0:
                # a page released more times than it was claimed is a
                # double-release: silently clamping to zero masked the bug
                # (ADVICE r5) — count it and fail loudly
                _REF_UNDERFLOWS.inc()
                raise RuntimeError(
                    f"PagePool refcount underflow: page {pg} released by "
                    f"request {req.rid} but holds no claim — double "
                    "release (see serving_page_ref_underflows_total)")
            self._page_ref[pg] = ref
            if ref > 0:
                continue  # another live request still reads it
            if pg in self._cached_pages:
                continue  # retained by the cache (free-but-cached)
            if i < len(keys) and keys[i] not in self._prefix_cache:
                self._prefix_cache[keys[i]] = pg
                self._cached_pages.add(pg)
                continue
            freed.append(pg)
            self._page_ref.pop(pg, None)
        self.pool.free(freed)
        req.pages = []

    def _match_prefix(self, tokens):
        """Longest cached full-page chain strictly shorter than the
        prompt (>=1 token always left to prefill). Returns the shared
        page list."""
        if not self.enable_prefix_cache:
            return []
        max_pages = (len(tokens) - 1) // self.page
        shared = []
        for key in self._chain_keys(tokens, max_pages):
            pg = self._prefix_cache.get(key)
            if pg is None:
                break
            shared.append(pg)
        return shared

    def _swap_stage(self, snap, n):
        """FRESH host staging buffers per restore at the fixed
        [L, Hkv, P, page, D] scatter shape (leaf-wise over int8
        code/scale pairs), filled with the n-page snapshot. A reused
        buffer is unsound: on backends that zero-copy host arrays into
        the program (jax CPU aliases numpy memory instead of copying at
        dispatch), overwriting the staging buffer for restore N+1 races
        the still in-flight transfer of restore N. Fresh arrays make
        each restore's payload immutable for the lifetime of its
        dispatch; allocation cost is noise next to the h2d transfer."""

        def stage(leaf):
            shape = leaf.shape[:2] + (self.pages_per_seq,) + leaf.shape[3:]
            buf = np.empty(shape, leaf.dtype)
            buf[:, :, :n] = leaf
            return buf

        return _kv_map(stage, snap)

    def _preempt(self, slot_idx):
        """Evict a running request and requeue it at the FRONT of the
        waiting queue. Policy "recompute": free the pages and fold the
        generated tokens into the resume prompt — re-admission rebuilds
        the KV by prefilling prompt+generated. Policy "swap": snapshot
        the pages to host first — re-admission restores the KV with zero
        recompute. Correctness is bitwise for greedy decodes under both
        policies (asserted by tests)."""
        self._settle()
        r = self._slots[slot_idx]
        if self.preempt_policy == "swap" and r.pages:
            # NOTE: the gather materialises [L, Hkv, P, page, D] on device
            # before the host copy. Pool exhaustion here is a logical
            # page-budget limit, not physical HBM exhaustion, so the
            # transient is safe; a deployment sized to true HBM capacity
            # would gather layer-by-layer instead.
            self._snapshot_to_host(r)
            self.swaps_out += 1
            self.pool.free(r.pages)
            r.pages = []
        else:
            # release BEFORE resetting the bookkeeping: registration
            # needs the written-token count, and caching the victim's
            # completed pages makes the recompute resume nearly free
            # (re-admission matches its own prefix)
            self._release_pages(r, register=True)
            r.seq_tokens = r.prompt + r.generated
            r.prefill_pos = 0
            r.length = 0
        self._slots[slot_idx] = None
        self._waiting.appendleft(r)
        r.queued_t = time.perf_counter()
        self.preemptions += 1
        _PREEMPTIONS.inc(labels=(self.preempt_policy,))
        if r.first_token_t is None:
            _trace.async_end("prefill", r.rid, {"preempted": True})
        _trace.async_instant("preempt", r.rid,
                             {"policy": self.preempt_policy})
        _trace.async_begin("queue", r.rid, {"requeue": True})

    def _grow_pages(self, newly):
        """Ensure every decoding slot owns pages for this tick's token.
        On pool exhaustion, preempt the YOUNGEST running request (its
        oldest peers keep their pages and finish first — guaranteed
        progress, no deadlock: a lone request always fits by the submit()
        feasibility check). Under a draft model the reservation covers
        the whole speculative window (K drafts + carry) instead of one
        token; a prefill-only engine never grows (its admissions reserve
        every page chunked prefill will write). A row's length was
        advanced when its tick was launched, so the pages follow the
        launches, not the fetches. Before a victim is chosen the tick
        in flight is settled and what it finished is retired into
        ``newly``: a row that only waited for its last token gives its
        pages back instead of being evicted with them."""
        if self.prefill_only:
            return
        while True:
            # oldest-first service order
            live = sorted(
                ((i, r) for i, r in enumerate(self._slots)
                 if r is not None and self._decodes(r)),
                key=lambda ir: ir[1].admit_seq)
            short = None
            for i, r in live:
                need = (r.length + self._lookahead
                        + self.page - 1) // self.page
                grow = need - len(r.pages)
                if grow <= 0:
                    continue
                ok = (self._free_pages_for(grow)
                      if self.enable_prefix_cache
                      else grow <= self.pool.available)
                if ok:
                    r.pages.extend(self._alloc_ref(grow)
                                   if self.enable_prefix_cache
                                   else self.pool.alloc(grow))
                else:
                    short = (i, r)
                    break
            if short is None:
                return
            if self._in_flight is not None:
                self._settle()
                self._retire_finished(newly)
                continue
            # youngest victim across ALL occupied slots — a just-admitted
            # mid-prefill request is younger than any decoding one, so
            # the oldest running requests keep their pages and finish
            # first; only if the starved request IS the youngest does it
            # preempt itself (re-runs when pages free up)
            occupied = [(i, r) for i, r in enumerate(self._slots)
                        if r is not None]
            victim = max(occupied, key=lambda ir: ir[1].admit_seq)
            self._preempt(victim[0])

    def _finished(self, r, pending=0):
        """True when a request has nothing left to generate: max_new
        reached, or its newest token is eos. THE completion predicate —
        retire, the decode-tick live filter, and the disagg handoff
        sweep all share it. ``pending`` counts a token launched and not
        yet fetched (``_pending``): by count, the host knows a row's
        last tick when it launches it. A live brownout L1 cap
        (``max_new_cap``) lowers the limit for every request still
        generating; restoring the cap to None restores the full
        budget."""
        limit = self.max_new_tokens
        if self.max_new_cap is not None:
            limit = min(limit, self.max_new_cap)
        return (len(r.generated) + pending >= limit
                or (self.eos is not None and bool(r.generated)
                    and r.generated[-1] == self.eos))

    def _pending(self, r):
        """1 while a token of ``r`` is in flight (launched, unfetched)."""
        return int(self._in_flight is not None
                   and id(r) in self._in_flight.row_of)

    def _decodes(self, r):
        """True for a slot's request that the next decode tick carries:
        its prompt is cached, and it is not finished by anything the
        host knows, the count of a token in flight included. Pages grow
        for these rows and no others."""
        return (bool(r.generated) and r.length > 0
                and not self._finished(r, self._pending(r)))

    def _retire(self, req: _Request):
        _REQ_LATENCY.observe(time.perf_counter() - req.submit_t)
        self._release_pages(req, register=True)
        if _trace.enabled():
            _trace.async_end("request", req.rid,
                             {"generated_tokens": len(req.generated)})
        return req.prompt + req.generated

    def _retire_finished(self, newly):
        """Retire every slot whose request the host knows to be
        finished, into ``newly`` ({rid: full ids})."""
        for i, r in enumerate(self._slots):
            if r is not None and self._finished(r):
                newly[r.rid] = self._retire(r)
                self._slots[i] = None

    def step(self):
        """Admit, launch one batched decode tick, then bring the tick
        BEFORE it to the host and emit its tokens (docs/SERVING.md "The
        step's order"): the device computes a tick while the host reads
        the last one, so ``on_token`` hears a token at most one tick
        after the device made it. Returns {rid: full_ids} for the
        requests retired in this call: a request is retired by the
        first ``step()`` that begins with its last token on the host,
        which is the call after the one that emitted it, or the same
        call when the engine had nothing left to launch (the pipeline
        drains: an idle engine holds no token back). With the tracer
        on, the call is one ``engine_step`` span whose children are its
        phases (docs/TELEMETRY.md Tracing)."""
        self._tick += 1
        traced = _trace.enabled()
        if traced:
            self.setup_record.publish()
        with _trace.span("engine_step", {"tick": self._tick} if traced
                         else None, cat="serve"):
            return self._step()

    def _step(self):
        jax = self._jax
        newly = {}
        self._drain_stats()
        with _trace.span("retire", cat="serve"):
            # deadlines sweep FIRST: an expired request must not occupy
            # a slot (or pages) for even one more tick
            self._sweep_deadlines()
            # retire next: a finishing slot frees pages and a slot for
            # this very tick's admissions
            self._retire_finished(newly)
        with _trace.span("admission", cat="serve"):
            self._admit()
        if self.prefill_chunk is not None:
            with _trace.span("prefill_tick", cat="serve") as span:
                self._prefill_tick(span)
        with _trace.span("grow_pages", cat="serve"):
            self._grow_pages(newly)
        # a request that hit max_new/eos at prefill completion THIS
        # tick must not decode once more before next tick's retire —
        # the off-by-one emitted max_new+1 tokens (and a token PAST
        # eos) whenever completion landed on the prefill path
        live = ([] if self.prefill_only else
                [(i, r) for i, r in enumerate(self._slots)
                 if r is not None and self._decodes(r)])
        if _TELEMETRY_REG.enabled:
            _STEPS.inc()
            _QUEUE_DEPTH.set(len(self._waiting))
            occupied = sum(1 for s in self._slots if s is not None)
            _SLOTS_OCCUPIED.set(occupied)
            _KV_UTIL.set(1.0 - self.pool.available / self.pool.num_pages)
            _INT8_KV.set(1.0 if self.int8_kv else 0.0)
            if live:
                _BATCH_OCCUPANCY.observe(len(live) / self.max_slots)
        if not live:
            # nothing to launch: the pipeline drains, and what its last
            # tokens finish is retired in this very call
            if self._in_flight is not None:
                self._settle()
                with _trace.span("retire", cat="serve"):
                    self._retire_finished(newly)
            return newly
        # static greedy/sampling mode: one retrace per mode, and the
        # default all-greedy workload never pays the vocab sort
        do_sample = any(r.temperature > 0.0 for _, r in live)
        if (self._draft is not None and not do_sample
                and not self.spec_paused):
            # speculative tick: draft K, verify in one target forward
            self._spec_tick(live)
            return newly
        if self._draft is not None:
            _SPEC_TICKS.inc(labels=("fallback",))
        before = self._in_flight
        ahead = before is not None
        with _trace.span("decode_build", cat="serve"):
            # as wide as the smallest step of the decode ladder that
            # holds the live rows, padded with ``_pad_row`` (length 0,
            # the trash page, the trash slot), its results past the live
            # rows discarded. A row of the tick in flight takes its
            # token from that tick's row on the device (``src``); a row
            # that joined since has it on the host
            B = next(n for n in self._decode_rows if n >= len(live))
            rows = [r for _, r in live] + [self._pad_row] * (B - len(live))
            slots = self._slot_vec([i for i, _ in live], B)
            came = before.row_of if ahead else {}
            src = np.asarray([came.get(id(r), -1) for r in rows], np.int32)
            host = (
                np.asarray([0 if k >= 0 else r.generated[-1]
                            for k, r in zip(src, rows)], np.int32),
                np.asarray([r.length for r in rows], np.int32),
                self._table_rows(rows), src, slots)
            if do_sample:
                host += (
                    np.asarray([r.temperature for r in rows], np.float32),
                    np.asarray([r.top_k for r in rows], np.int32),
                    np.asarray([r.top_p for r in rows], np.float32))
        with _trace.span("decode_upload", cat="serve"):
            # one batched transfer of what the tick reads. A greedy tick
            # reads no sampling operand and draws no key: it is handed
            # the same device constants every tick (the host's serial
            # work is what a decode tick of a few ms waits on)
            if do_sample:
                tokens, lens, tables, src, slots, temps, top_ks, top_ps = (
                    jax.device_put(host))
                self._key, sub = jax.random.split(self._key)
            else:
                tokens, lens, tables, src, slots = jax.device_put(host)
                temps, top_ks, top_ps, sub = self._greedy_operands(B)
        _DECODE_ROWS.inc(labels=(str(B),))
        with _trace.span("decode_tick",
                         {"live": len(live), "rows": B, "ahead": int(ahead),
                          "ticks": 1, "discarded": 0}
                         if _trace.enabled() else None,
                         cat="serve") as tick_span:
            with _trace.span("decode_launch", cat="serve"):
                nxt, self.cache = self._decode_jit(
                    self._weights, tokens, lens, tables, self.cache,
                    temps, top_ks, top_ps, sub,
                    before.nxt if ahead else self._no_tick, src,
                    do_sample, slots)
        # what the next tick needs of this one the host knows without
        # its tokens: every row is one token longer
        for _, r in live:
            r.length += 1
        self._in_flight = _Tick(
            nxt, live, {id(r): j for j, (_, r) in enumerate(live)},
            tick_span)
        mode = "ahead" if ahead else "settled"
        self.decode_ticks[mode] += 1
        _DECODE_TICKS.inc(labels=(mode,))
        if ahead:
            # the device finished the tick before this one before it
            # began this one: the wait is what was left of that tick
            self._fetch(before)
        if self._draft is not None:
            # fallback tick under a draft: mirror the carry token into
            # the draft's KV (proposal discarded) so the draft cache
            # stays hole-free — without this, every sampled tick leaves
            # a permanently stale draft row and speculative acceptance
            # silently collapses once greedy ticks resume. A draft
            # engine's next tick may be a speculative one, which builds
            # its window from the host's tokens: nothing stays in flight
            self._draft.catch_up(tokens, lens, tables)
            self._settle()
        return newly

    def _settle(self):
        """Bring the decode tick in flight, if any, to the host and emit
        its tokens (docs/SERVING.md "The step's order"). Runs before
        anything that moves a request's host state out of its slot
        (preemption, ``extract``), before the weights change, when a
        step has nothing to launch, and after a plain tick under a
        draft model."""
        if self._in_flight is not None:
            tick, self._in_flight = self._in_flight, None
            self._fetch(tick)

    def _fetch(self, tick):
        """Fetch one launched tick's tokens and emit them. A row that
        the tick before showed to be finished (eos, a lowered cap:
        'ended'), or that left its slot while the tick was in flight
        (cancelled: 'withdrawn'), has its token discarded: the tick was
        launched before the host could know."""
        # the host fetch is the tick's real sync point
        with _trace.span("decode_fetch", cat="serve"):
            nxt = np.asarray(tick.nxt)
        if len(nxt) > self.max_slots:
            # the model kind's counts rode behind the tokens
            self._note_stats(tick.span, nxt[self.max_slots:])
            self._drain_stats()
        ended = withdrawn = 0
        with _trace.span("emit", cat="serve"):
            for j, (i, r) in enumerate(tick.live):
                if self._finished(r):
                    ended += 1
                elif self._slots[i] is not r:
                    withdrawn += 1
                else:
                    self._emit(r, int(nxt[j]))
        for reason, n in (("ended", ended), ("withdrawn", withdrawn)):
            if n:
                self.discarded_tokens[reason] += n
                _DISCARDED.inc(n, labels=(reason,))
        if ended and _trace.enabled():
            tick.span.annotate(discarded=ended)

    def _first_token_step(self, weights, last, temps, top_ks, top_ps, key,
                          do_sample=False):
        """The head and the choice of a first token for EVERY row a
        prefill pass hands back ([max_slots, H] final-normed hidden
        rows, zeros past the pass's own width), one fixed shape: the
        host keeps the rows that finished their prompt."""
        return self._choose(self._head_logits(weights, last), temps, top_ks,
                            top_ps, key, do_sample)

    def _first_tokens(self, last, completed):
        """First tokens of the rows ``completed`` ([(row, request)]) of a
        chunked prefill pass, through ONE compiled program over
        ``max_slots`` rows whatever the pass's width (``last`` comes
        padded from the pass: the head reads its weights once either
        way), not an eager program a count of finished rows, each with
        its own compiles: 64 slots were 580 of them."""
        jax = self._jax
        do_sample = any(r.temperature > 0.0 for _, r in completed)
        if do_sample:
            b = self.max_slots
            temps, top_ks = np.zeros(b, np.float32), np.zeros(b, np.int32)
            top_ps = np.ones(b, np.float32)
            for i, r in completed:
                temps[i], top_ks[i], top_ps[i] = (r.temperature, r.top_k,
                                                  r.top_p)
            self._key, sub = jax.random.split(self._key)
            operands = (*jax.device_put((temps, top_ks, top_ps)), sub)
        else:
            operands = self._greedy_operands()
        toks = np.asarray(self._first_token_jit(
            self._weights, last, *operands, do_sample))
        return [int(toks[i]) for i, _ in completed]

    def _drain_stats(self):
        """Note the counts of the prefill passes that have ended (their
        arrays are ready: nothing is waited for)."""
        while self._stats_pending and self._stats_pending[0][1].is_ready():
            span, stats = self._stats_pending.popleft()
            self._note_stats(span, np.asarray(stats))

    def _note_stats(self, span, stats):
        """What a program of the model kind counted of itself (it rode
        behind the tokens, or beside a prefill pass's hidden rows): the
        kind names and checks its own counts and keeps its own
        counters; the engine puts them on the tick's span."""
        attrs = self._arch.note_stats(stats)
        if _trace.enabled():
            span.annotate(**attrs)

    def _table_rows(self, rows):
        """Fixed-shape [B, pages_per_seq] page tables (zero-padded; the
        kernels clamp + length-mask padded entries), on the host."""
        table_rows = []
        for r in rows:
            row = list(r.pages) + [0] * (self.pages_per_seq - len(r.pages))
            table_rows.append(row[: self.pages_per_seq])
        return np.asarray(table_rows, np.int32)

    def _spec_tick(self, live):
        """Draft-model speculative decode tick (docs/SERVING.md): the
        draft proposes K greedy tokens per live row, the target verifies
        all of them in ONE forward (`_spec_verify`), and the longest
        draft prefix matching the target's own greedy tokens is emitted
        plus the target's bonus token — 1..K+1 tokens per tick, every
        one bitwise-identical to what plain greedy decode would emit."""
        jnp = self._jnp
        K = self.spec_tokens
        pad_to = self.max_slots
        rows = [r for _, r in live] + [live[0][1]] * (pad_to - len(live))
        lens_np = np.asarray([r.length for r in rows], np.int32)
        lens = jnp.asarray(lens_np)
        tables = jnp.asarray(self._table_rows(rows))

        def ctx_tok(r, i):
            # context token i without materializing prompt+generated
            # (O(seq) per row per tick on the hot path otherwise)
            n = len(r.prompt)
            return r.prompt[i] if i < n else r.generated[i - n]

        # context[length] is the carry token (generated[-1]);
        # context[length-1] re-primes the draft's previous position —
        # always a rewrite of the same value EXCEPT after a fully-
        # accepted window, where it fills the draft-KV hole for the
        # token the draft proposed but never consumed
        prev = np.asarray([ctx_tok(rows[j], int(lens_np[j]) - 1)
                           for j in range(pad_to)], np.int32)
        cur = np.asarray([ctx_tok(rows[j], int(lens_np[j]))
                          for j in range(pad_to)], np.int32)
        with _trace.span("spec_draft", attrs={"k": K}, cat="serve"):
            d_toks = self._draft.propose(prev, cur, lens, tables, K)
        toks = np.concatenate([cur[:, None], d_toks], 1)     # [B, K+1]
        with _trace.span("spec_verify",
                         attrs={"live": len(live), "k": K},
                         cat="serve"):
            t_out, self.cache = self._verify_jit(
                self._weights, jnp.asarray(toks), lens, tables, self.cache)
            t_np = np.asarray(t_out)
        accepted_total = 0
        for j, (i, r) in enumerate(live):
            drafts, targets = d_toks[j], t_np[j]
            m = 0
            while m < K and int(drafts[m]) == int(targets[m]):
                m += 1
            accepted_total += m
            out = []
            for t in [int(x) for x in drafts[:m]] + [int(targets[m])]:
                out.append(t)
                if self.eos is not None and t == self.eos:
                    break
                if len(r.generated) + len(out) >= self.max_new_tokens:
                    break
            r.length += len(out)
            for t in out:
                self._emit(r, t)
        self.spec_ticks += 1
        self.spec_draft_tokens += K * len(live)
        self.spec_accepted_tokens += accepted_total
        if _TELEMETRY_REG.enabled:
            _SPEC_TICKS.inc(labels=("spec",))
            _SPEC_DRAFTED.inc(K * len(live))
            _SPEC_ACCEPTED.inc(accepted_total)
        _trace.instant("spec_accept",
                       {"accepted": accepted_total,
                        "drafted": K * len(live)}, cat="serve")

    @property
    def spec_acceptance_rate(self):
        """Fraction of drafted tokens the target verify accepted."""
        return (self.spec_accepted_tokens
                / max(1, self.spec_draft_tokens))

    def run_until_complete(self, max_ticks=10000):
        done = {}
        for _ in range(max_ticks):
            done.update(self.step())
            if not self._waiting and all(s is None for s in self._slots):
                return done
        raise TimeoutError("serving loop did not drain")

    # -- fleet surface (router / disaggregated serving) ---------------------
    def load(self):
        """Live load signals for an admission router (docs/SERVING.md):
        queue depth, slot occupancy, and KV headroom — the same state
        the per-tick telemetry gauges publish, read synchronously."""
        occupied = sum(1 for s in self._slots if s is not None)
        return {
            "queue_depth": len(self._waiting),
            "occupied_slots": occupied,
            "free_slots": self.max_slots - occupied,
            "kv_free_fraction": self.pool.available / self.pool.num_pages,
            # per-replica resident decode-weight footprint by storage
            # dtype (docs/QUANT.md): int8-packed replicas report the
            # reduced bytes a placement router can pack against
            "int8_weights": self.int8_weights,
            "weight_bytes": dict(self.weight_bytes),
        }

    def prefix_match_pages(self, tokens):
        """How many full KV pages of this prompt's prefix the engine's
        prefix cache already holds — the prefix-affinity routing signal.
        0 when the cache is off (match is read-only: nothing is pinned)."""
        return len(self._match_prefix([int(t) for t in tokens]))

    def extract(self, slot_idx):
        """Disaggregated-serving handoff seam (fleet.disagg): snapshot a
        slot's KV pages + resume state to host exactly like a swap-out,
        release the slot and its pages, and return the request. A
        decode engine `inject()`s the request; its swap-restore
        admission path scatters the pages back — bitwise (exact caches
        round-trip unchanged; int8 caches move their raw codes+scales).
        Unlike `_preempt(policy="swap")`, this works with ANY preempt
        policy and registers completed prefix pages into this engine's
        prefix cache (the prefill worker keeps the warm prefix)."""
        self._refuse_handoff("extract")
        r = self._slots[slot_idx]
        if r is None:
            raise ValueError(f"slot {slot_idx} is empty")
        self._snapshot_to_host(r)
        self._release_pages(r, register=True)
        self._slots[slot_idx] = None
        return r

    def inject(self, req):
        """Accept a request extracted from another engine (the decode
        half of a disaggregated pair). Its host snapshot restores
        through the standard swap-restore admission path. Both engines
        must share the page geometry (page_size, pages_per_seq) and KV
        mode; the disagg wrapper enforces this."""
        self._refuse_handoff("inject")
        self._next_rid = max(self._next_rid, req.rid + 1)
        req.queued_t = time.perf_counter()
        self._waiting.append(req)

    def _refuse_handoff(self, what):
        why = self._arch.no_handoff
        if why:
            raise NotImplementedError(
                f"{type(self._model).__name__} does not {what} a request "
                f"between engines: {why}")

    def export_prefix_pages(self, max_pages=None):
        """Serialize prefix-cache entries — (chain key, one-page KV
        snapshot) pairs, in cache insertion order so every chain ships
        head-first — for a drain destination to warm its cache from
        before this engine retires. ``max_pages`` caps the payload; a
        chain cut mid-way imports as a valid shorter prefix (a shipped
        tail whose head was cut is unreachable by ``_match_prefix`` and
        simply evicts under pressure)."""
        if not self.enable_prefix_cache:
            return []
        keys = list(self._prefix_cache)
        if max_pages is not None:
            keys = keys[: int(max_pages)]
        entries = []
        for start in range(0, len(keys), self.pages_per_seq):
            chunk = keys[start: start + self.pages_per_seq]
            pages = [self._prefix_cache[k] for k in chunk]
            got = self._swap_out_jit(self.cache,
                                     self._padded_page_vec(pages))
            for i, key in enumerate(chunk):
                cut = lambda c, i=i: np.asarray(c[:, :, i: i + 1])
                entries.append({"key": bytes(key), **{
                    name: _kv_map(cut, g)
                    for name, g in zip(self.cache_names, got)}})
        self.prefix_pages_exported += len(entries)
        return entries

    def import_prefix_pages(self, entries):
        """Install exported prefix pages: allocate a page, scatter the
        snapshot into the caches, register key -> page at refcount 0 —
        free-but-cached, evictable under pressure like any cached page.
        Known keys are skipped; import never evicts anything (free-pool
        pages only: warming must not cannibalize live or warmer state).
        Returns the number of pages imported."""
        if not self.enable_prefix_cache:
            return 0
        n = 0
        for e in entries:
            key = bytes(e["key"])
            if key in self._prefix_cache:
                continue
            if self.pool.available == 0:
                break
            pg = self.pool.alloc(1)[0]
            pages = self._jnp.asarray(np.asarray([pg], np.int32))
            self.cache = self._swap_scatter(
                self.cache, pages, tuple(e[n] for n in self.cache_names))
            self._prefix_cache[key] = pg
            self._cached_pages.add(pg)
            self._page_ref[pg] = 0
            n += 1
        self.prefix_pages_imported += n
        return n

    def _greedy_operands(self, b=None):
        """The sampling operands of a greedy program of ``b`` rows
        (``max_slots`` by default; unread by the program): device
        constants, made once a row count."""
        b = b or self.max_slots
        if b not in self._greedy_consts:
            jax, jnp = self._jax, self._jnp
            self._greedy_consts[b] = (
                jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
                jnp.ones((b,), jnp.float32),
                jax.random.PRNGKey(0))  # never touches self._key's stream
        return self._greedy_consts[b]

    def _dummy_decode_operands(self, do_sample=False, b=None):
        """Decode-tick operands of ``b`` rows (``max_slots`` by default)
        whose cache writes land in the scratch page — what
        :meth:`warmup` compiles against."""
        jnp = self._jnp
        b = b or self.max_slots
        return (self._weights, jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.full((b, self.pages_per_seq), self._trash_page,
                         jnp.int32),
                self.cache, *self._greedy_operands(b), self._no_tick,
                jnp.full((b,), -1, jnp.int32), do_sample,
                self._slot_vec([], b))

    def decode_program_text(self):
        """StableHLO text of the greedy decode tick (trace + lower, no
        compile, nothing executed). The paged-attention Mosaic kernel
        appears in it as a ``tpu_custom_call`` carrying its
        ``kernel_name`` — chip_smoke.py's proof that decode runs the
        kernel, not a reference."""
        return self._decode_jit.lower(
            *self._dummy_decode_operands()).as_text()

    def _build(self, name, jitted, *operands):
        """Build one program on dummy operands through the jit layer's
        timed build, its phases into ``setup_record`` under ``name``
        (no ``hlo_program_bytes``: nothing here reads a program's
        size). Returns the executable."""
        from ..jit import timed_lower_compile

        return timed_lower_compile(jitted, name, *operands, hlo_bytes=False,
                                   record=self.setup_record)

    def _warm(self, name, jitted, *operands):
        """Build one program that holds the pool (:meth:`_build`), keep
        what its ``memory_analysis()`` says under ``program_bytes[name]``
        (and in the ``serving_program_temp_bytes`` gauge and a
        ``program_memory`` instant of the trace), and run it once, timed
        through to its results. The run finds the executable the build
        compiled: one compile a program."""
        mem = self._build(name, jitted, *operands).memory_analysis()
        nb = {"temp": int(mem.temp_size_in_bytes),
              "alias": int(mem.alias_size_in_bytes)}
        self.program_bytes[name] = nb
        _PROGRAM_TEMP_BYTES.set(float(nb["temp"]), labels=(name,))
        if _trace.enabled():
            _trace.instant("program_memory", {"program": name, **nb},
                           cat="serve")
        return self.setup_record.first_run(jitted, *operands)

    def warmup(self, sample=False):
        """Compile the engine's programs on dummy operands (cache writes
        land in the scratch page) and record the wall time in
        ``self.build_seconds`` — the replica cold-start number the
        serving bench records and bench_gate gates (docs/SERVING.md) —
        each program's temporary and aliased bytes in
        ``self.program_bytes``, and its trace, lowering, load and first-run
        seconds in ``self.setup_record`` (the first-token program's too).
        The decode tick and the chunked prefill pass are compiled and run
        at EVERY step of their row ladders
        (``decode`` and ``prefill`` at ``max_slots`` rows,
        ``decode_r<N>`` and ``prefill_r<N>`` below it), so that no
        arrival pattern compiles later. Greedy programs only unless
        ``sample=True`` (the first sampled tick otherwise pays its own
        compile). A ``prefill_only`` engine compiles only its prefill
        programs — the decode/verify programs never run there, and
        charging their compile into the gated cold-start number would
        overstate real spin-up cost."""
        jax, jnp = self._jax, self._jnp
        t0 = time.perf_counter()
        b = self.max_slots
        lens = jnp.zeros((b,), jnp.int32)
        tables = jnp.full((b, self.pages_per_seq), self._trash_page,
                          jnp.int32)
        modes = () if self.prefill_only else (
            (False, True) if sample else (False,))
        for do_sample in modes:
            name = "decode_sample" if do_sample else "decode"
            for B in self._decode_rows:
                _, self.cache = self._warm(
                    name if B == b else f"{name}_r{B}", self._decode_jit,
                    *self._dummy_decode_operands(do_sample, B))
        if self.prefill_chunk is not None:
            c = self.prefill_chunk
            for B in self._pass_rows:
                last, *_stats, self.cache = self._warm(
                    "prefill" if B == b else f"prefill_r{B}",
                    self._prefill_jit,
                    self._weights, jnp.zeros((B, c), jnp.int32),
                    jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                    tables[:B], self.cache, self._slot_vec([], B))
            # the first-token program holds no pool: built and run as
            # the others are, not among ``program_bytes``
            ops = (self._weights, last, *self._greedy_operands(), False)
            self._build("first_token", self._first_token_jit, *ops)
            self.setup_record.first_run(self._first_token_jit, *ops)
        if self._draft is not None and not self.prefill_only:
            _, self.cache = self._warm(
                "verify", self._verify_jit,
                self._weights,
                jnp.zeros((b, self.spec_tokens + 1), jnp.int32),
                lens, tables, self.cache)
            self._draft.warmup(tables)
        self.setup_record.warm_end = time.perf_counter()
        self.build_seconds = self.setup_record.warm_end - t0
        return self.build_seconds
