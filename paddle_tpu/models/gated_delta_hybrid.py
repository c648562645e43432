"""A stacked decoder of linear-attention layers (Gated DeltaNet: a
recurrent state a head, short convolutions, an output gate) and
full-attention layers in a repeating period, for the serving engine.

The Olmo-Hybrid line: ``layer_types`` is a pattern (three
``linear_attention``, one ``full_attention``) repeated. A linear layer
keeps, a request, a state ``S`` [heads, d_k, d_v] in float32 and the last
``kernel - 1`` inputs of its convolutions, whatever the length; a full
layer keeps K and V a token (no rotary embedding: position reaches it
through the recurrent layers before it). The block's norm follows the
sublayer (``x += rms(mixer(x))``, ``x += rms(swiglu(x))``), and a full
layer's q and k pass an rmsnorm over the whole projection.

The model is natively stacked BY PERIOD (``lin.*`` leaves ``[periods,
linear layers of a period, ...]``, ``full.*`` likewise), so the engine's
packed tree references the parameters and the weights exist once, and the
engine's layer walk scans ONE group whose element is a period.

What the engine asks of the model kind is :class:`GatedDeltaHybridServing`
(``model.serving_arch()``): a cache of two kinds (K and V by page; the
recurrent state and the convolutions' tails by slot), the packed weights,
a period's mathematics, and each cache's reads and writes in a decode
tick (the state stepped in place by ``ops/pallas/gated_delta``) and in a
prefill chunk (the delta rule's chunkwise form).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu import nn
from paddle_tpu import telemetry as _telemetry
from paddle_tpu.core.tensor import Parameter

_STATE_STEPS = _telemetry.counter(
    "serving_state_steps_total",
    "positions a recurrent state was advanced over, a request's row "
    "counted once whatever the number of linear layers: 'decode' one a "
    "live row a tick, 'prefill' the valid positions of a chunk",
    labelnames=("phase",))

FFN_LEAVES = ("ln2", "fg", "fu", "fd")
LIN_LEAVES = ("ln1", "wq", "wk", "wv", "wg", "wo", "wa", "wb", "a_log",
              "dt_bias", "cq", "ck", "cv", "onorm") + FFN_LEAVES
FULL_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "qn", "kn") + FFN_LEAVES
L2_EPS = 1e-6


class GatedDeltaHybridConfig:
    """Sizes under the published names of the family's ``config.json``.
    ``layer_types`` is the whole stack served here; its shortest
    repeating pattern is the period the weights are stacked by."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 layer_types, num_heads, num_kv_heads, linear_num_heads,
                 linear_key_head_dim, linear_value_head_dim,
                 linear_conv_kernel_dim=4, max_seq_len=2048,
                 dtype="float32"):
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.intermediate_size = intermediate_size
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.linear_num_heads = linear_num_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.max_seq_len, self.dtype = max_seq_len, dtype
        bad = set(self.layer_types) - {"linear_attention", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        n = self.num_layers
        p = next(p for p in range(1, n + 1) if n % p == 0
                 and self.layer_types == self.layer_types[:p] * (n // p))
        self.pattern, self.periods = self.layer_types[:p], n // p
        self.lin_per_period = self.pattern.count("linear_attention")
        self.full_per_period = self.pattern.count("full_attention")
        if not self.lin_per_period or not self.full_per_period:
            raise ValueError(
                "a gated-delta hybrid stack has linear_attention AND "
                f"full_attention layers; layer_types {self.layer_types}")

    @property
    def num_linear_layers(self):
        return self.periods * self.lin_per_period

    @property
    def num_full_layers(self):
        return self.periods * self.full_per_period

    @property
    def conv_channels(self):
        """q, k and v side by side: what the convolutions run over."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)

    def leaf_shapes(self):
        """group -> leaf -> shape, a layer leaf stacked [periods, layers
        of its kind in a period, ...]; plus the three top leaves."""
        h, m = self.hidden_size, self.intermediate_size
        lh, dk, dv = (self.linear_num_heads, self.linear_key_head_dim,
                      self.linear_value_head_dim)
        kw, hd = self.linear_conv_kernel_dim, self.head_dim
        ffn = {"ln2": (h,), "fg": (h, m), "fu": (h, m), "fd": (m, h)}
        lin = dict(ffn, ln1=(h,), wq=(h, lh * dk), wk=(h, lh * dk),
                   wv=(h, lh * dv), wg=(h, lh * dv), wo=(lh * dv, h),
                   wa=(h, lh), wb=(h, lh), a_log=(lh,), dt_bias=(lh,),
                   cq=(kw, lh * dk), ck=(kw, lh * dk), cv=(kw, lh * dv),
                   onorm=(dv,))
        full = dict(ffn, ln1=(h,), wq=(h, self.num_heads * hd),
                    wk=(h, self.num_kv_heads * hd),
                    wv=(h, self.num_kv_heads * hd),
                    wo=(self.num_heads * hd, h), qn=(self.num_heads * hd,),
                    kn=(self.num_kv_heads * hd,))
        lead = lambda n, d: {k: (self.periods, n) + s for k, s in d.items()}
        return {"lin": lead(self.lin_per_period, lin),
                "full": lead(self.full_per_period, full),
                "top": {"embed": (self.vocab_size, h), "fnorm": (h,),
                        "head": (h, self.vocab_size)}}


class _Group(nn.Layer):
    """The layers of one kind: their leaves stacked by period."""


class GatedDeltaHybridForCausalLM(nn.Layer):
    """The stacked model ON ``weights`` ({"lin": {leaf: [periods, n,
    ...]}, "full": {...}, "embed", "fnorm", "head"}): referenced, never
    copied, so a caller that made them on the device holds them once."""

    def __init__(self, config, weights):
        super().__init__()
        self.config = config
        shapes = config.leaf_shapes()
        for group in ("lin", "full", "top"):
            layer = _Group()
            src = weights if group == "top" else weights[group]
            for leaf, shape in shapes[group].items():
                if tuple(src[leaf].shape) != shape:
                    raise ValueError(f"{group}.{leaf}: {src[leaf].shape} "
                                     f"!= {shape}")
                setattr(layer, leaf, Parameter(src[leaf], trainable=False))
            setattr(self, group, layer)

    def serving_arch(self):
        return GatedDeltaHybridServing(self)


# ------------------------------------------------ the delta rule by chunks
def chunk_delta_rule(q, k, v, g, beta, s0, block):
    """The gated delta rule over a chunk in its chunkwise (WY) form:
    q, k [B, T, H, d_k] (k unit, q unit and scaled), v [B, T, H, d_v],
    g [B, T, H] the log of the decay, beta [B, T, H], s0 [B, H, d_k, d_v],
    all float32 -> (o [B, T, H, d_v], the state after position T - 1).
    Equal to stepping ``S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T
    k_t)^T``, ``o_t = S_t^T q_t`` position by position. A position with
    ``g`` 0 and ``beta`` 0 leaves the state as it was.

    Inside a block of ``block`` positions the pseudo-values ``U`` solve
    the unit lower-triangular system ``(I + tril(diag(beta) (K K^T *
    decay), -1)) U = diag(beta) (V - diag(e^G) K S)`` once; the blocks
    are chained through ``S``: T / block dependent steps, not T."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, t, h, dv = v.shape
    nb = t // block
    # [B, T, H, x] -> [nb, B, H, block, x]
    blocks = lambda a: jnp.moveaxis(
        a.reshape(b, nb, block, h, -1), (1, 3), (0, 2))
    lower = jnp.tril(jnp.ones((block, block), bool))
    strict = jnp.tril(jnp.ones((block, block), bool), -1)

    def step(s, xs):
        q, k, v, g, beta = xs
        gc = jnp.cumsum(g[..., 0], -1)                   # [B, H, C]
        beta = beta[..., 0]
        decay = jnp.exp(jnp.where(
            lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        a = jnp.where(strict, beta[..., None] * decay * jnp.einsum(
            "bhtd,bhid->bhti", k, k, precision=hi), 0.0)
        rhs = jnp.concatenate(
            [beta[..., None] * v, (beta * jnp.exp(gc))[..., None] * k], -1)
        sol = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(block, dtype=a.dtype), rhs, lower=True,
            unit_diagonal=True)
        u = sol[..., :dv] - jnp.einsum("bhtd,bhdv->bhtv", sol[..., dv:], s,
                                       precision=hi)
        o = (jnp.einsum("bhtd,bhdv->bhtv", q * jnp.exp(gc)[..., None], s,
                        precision=hi)
             + jnp.einsum("bhti,bhiv->bhtv", decay * jnp.einsum(
                 "bhtd,bhid->bhti", q, k, precision=hi), u, precision=hi))
        last = gc[..., -1:]
        s = (jnp.exp(last)[..., None] * s
             + jnp.einsum("bhtd,bhtv->bhdv",
                          k * jnp.exp(last - gc)[..., None], u,
                          precision=hi))
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(
        blocks(a) for a in (q, k, v, g[..., None], beta[..., None])))
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t, h, dv), s


# -------------------------------------------------- what the engine asks
class GatedDeltaHybridServing:
    """The engine's view of the model (inference/serving.py, "Model kinds
    and cache geometry" in docs/SERVING.md)."""

    #: the pools addressed by page, then the leaves addressed by slot
    cache_names = ("k", "v", "state", "conv")
    slot_cache_names = ("state", "conv")
    #: positions of a prefill chunk whose triangular system is solved at
    #: once (a chunk that is no multiple of it is one block)
    prefill_block = 64
    #: rows of a prefill chunk whose full-attention scores are held at
    #: once ([rows, heads, chunk, max_seq] float32)
    prefill_rows = 16
    #: the state's step through the Pallas kernel (True), plain jax.numpy
    #: (False), or by the device (None: the kernel on a TPU)
    step_kernel = None
    #: engine features this model kind refuses at construction, by name
    refuses = {
        "int8_kv": "the recurrent state has no int8 format, and the K/V "
        "pool of the full layers is not served apart from it",
        "int8_weights": "the linear layers' slabs have no int8 packing",
        "draft_model": "a verify window would have to roll a recurrent "
        "state back, and a DraftRunner walks dense layers",
        "group prefill (prefill_chunk=None)": "the hybrid model prefills "
        "by chunks only",
        "enable_prefix_cache": "a prefix hit needs the recurrent state at "
        "the page boundary, and the state store keeps a row's newest only",
    }
    #: why a request cannot leave this engine for another
    no_handoff = ("the state store (the linear layers' recurrent state "
                  "and convolution tails, by slot) has no wire format")

    def __init__(self, model):
        from ..inference.serving import DenseDecoderServing
        from ..ops.pallas.gated_delta import heads_per_lane_row

        self.model, self.cfg = model, model.config
        #: the full layers' K and V pools are the dense kind's
        self._kv = DenseDecoderServing(model)
        self.pack_g = heads_per_lane_row(self.cfg.linear_num_heads,
                                         self.cfg.linear_value_head_dim)

    # -- geometry and weights ---------------------------------------------
    def cache_shapes(self, num_pages, page):
        """K and V of the FULL layers only, in the dense kind's layout."""
        cfg = self.cfg
        shape = (cfg.num_full_layers, cfg.num_kv_heads, num_pages + 1,
                 page, cfg.head_dim)
        return shape, shape

    def slot_cache_shapes(self, max_slots, dtype):
        """((shape, dtype)) of the leaves addressed by slot, the slot on
        axis 1 and one more than ``max_slots`` of them (the last is the
        trash slot). ``state``: float32, packed ``pack_g`` heads to a
        lane row (ops/pallas/gated_delta). ``conv``: the last ``kernel -
        1`` inputs of the three convolutions, flat, at the model's
        dtype."""
        cfg = self.cfg
        g = self.pack_g
        return (((cfg.num_linear_layers, max_slots + 1,
                  cfg.linear_num_heads // g, cfg.linear_key_head_dim,
                  g * cfg.linear_value_head_dim), np.dtype("float32")),
                ((cfg.num_linear_layers, max_slots + 1,
                  (cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels),
                 np.dtype(dtype)))

    def cache_token_bytes(self, itemsize):
        """Bytes the algorithm must keep a token, over the full layers."""
        cfg = self.cfg
        return (2 * cfg.num_full_layers * cfg.num_kv_heads * cfg.head_dim
                * itemsize)

    def slot_cache_bytes(self, itemsize):
        """Bytes the algorithm must keep a slot, over the linear layers."""
        cfg = self.cfg
        return cfg.num_linear_layers * (
            cfg.linear_num_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim * 4
            + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels
            * itemsize)

    def pack(self, int8_weights=False):
        """{"layers": the linear then the full leaves, each [periods,
        ...], "embed", "fnorm", "head"}: the parameters themselves, no
        copy (``int8_weights`` is refused at construction)."""
        m = self.model
        return {
            "layers": (tuple(getattr(m.lin, k)._data for k in LIN_LEAVES)
                       + tuple(getattr(m.full, k)._data
                               for k in FULL_LEAVES)),
            "embed": m.top.embed._data, "fnorm": m.top.fnorm._data,
            "head": m.top.head._data,
        }

    def groups(self, weights):
        """[(stacked leaves, forward)]: ONE group, whose element is a
        period; the pools' layer indices are counted inside it. The
        walk's ``xs`` is the period's number alone: the layers' leaves
        stay whole beside it (closed over, as the latent kind keeps its
        experts) and a layer reads its matrix at (period, layer) where
        it lies. Sliced as ``xs``, a period's [3, h, n] stack would be
        copied out of the weights every period of every tick (1.3 GB a
        period at the published widths)."""
        import functools

        import jax.numpy as jnp

        return [((jnp.arange(self.cfg.periods, dtype=jnp.int32),),
                 functools.partial(self.period_forward,
                                   leaves=weights["layers"]))]

    def carry_in(self, x):
        """The walker's carry: the hidden state beside the program's
        counts (rows whose state it stepped, rows it was launched over,
        positions it advanced over, 1 for a prefill pass), each summed
        over the linear layers."""
        import jax.numpy as jnp

        return x, jnp.zeros((4,), jnp.int32)

    def carry_out(self, carry):
        return carry

    def note_stats(self, stats):
        """One program's counts, on the host: onto the counter, and back
        as the span attrs of docs/TELEMETRY.md."""
        rows, launched, tokens, prefill = (
            int(v) // self.cfg.num_linear_layers for v in stats)
        _STATE_STEPS.inc(tokens, labels=("prefill" if prefill
                                         else "decode",))
        return {"state_rows": rows, "state_rows_launched": launched,
                "state_tokens": tokens}

    # -- layer mathematics ------------------------------------------------
    def period_forward(self, li, _lp, carry, pos0, attend, leaves):
        """One period: its layers in the pattern's order. ``li`` counts
        periods; a layer's index in its pools is counted from it, and
        its leaves are read at ``[li, j]`` of the stacked ``leaves``."""
        cfg = self.cfg
        x, stats = carry
        lin = dict(zip(LIN_LEAVES, leaves[:len(LIN_LEAVES)]))
        full = dict(zip(FULL_LEAVES, leaves[len(LIN_LEAVES):]))
        at = {"lin": 0, "full": 0}
        for kind in cfg.pattern:
            if kind == "linear_attention":
                j = at["lin"]
                x, n = self._linear_layer(
                    {k: v[li, j] for k, v in lin.items()},
                    li * cfg.lin_per_period + j, x, attend)
                stats = stats + n
                at["lin"] += 1
            else:
                j = at["full"]
                x = self._full_layer({k: v[li, j] for k, v in full.items()},
                                     li * cfg.full_per_period + j, x, attend)
                at["full"] += 1
        return x, stats

    @staticmethod
    def _ffn(p, x):
        import jax

        from .gpt import _rms_pure

        y = (jax.nn.silu(x @ p["fg"]) * (x @ p["fu"])) @ p["fd"]
        return x + _rms_pure(y, p["ln2"])

    def _linear_layer(self, p, li, x, attend):
        import jax
        import jax.numpy as jnp

        from .gpt import _rms_pure

        b, s = x.shape[:2]
        f32 = jnp.float32
        xc = jnp.concatenate([x @ p["wq"], x @ p["wk"], x @ p["wv"]], -1)
        taps = jnp.concatenate([p["cq"], p["ck"], p["cv"]], -1)
        beta = 2.0 * jax.nn.sigmoid((x @ p["wb"]).astype(f32))
        g = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            (x @ p["wa"]).astype(f32) + p["dt_bias"].astype(f32))
        o, n = attend(li, "lin", xc, taps, g, beta)      # [B, S, H, dv] f32
        gate = jax.nn.silu(x @ p["wg"]).reshape(o.shape)
        y = (_rms_pure(o, p["onorm"].astype(f32)) * gate).astype(x.dtype)
        x = x + _rms_pure(y.reshape(b, s, -1) @ p["wo"], p["ln1"])
        return self._ffn(p, x), n

    def _full_layer(self, p, li, x, attend):
        from .gpt import _rms_pure

        cfg = self.cfg
        b, s = x.shape[:2]
        q = _rms_pure(x @ p["wq"], p["qn"]).reshape(b, s, cfg.num_heads, -1)
        k = _rms_pure(x @ p["wk"], p["kn"]).reshape(b, s, cfg.num_kv_heads,
                                                    -1)
        v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, -1)
        o = attend(li, "full", q, k, v)                  # [B, S, Hq, D]
        x = x + _rms_pure(o.reshape(b, s, -1) @ p["wo"], p["ln1"])
        return self._ffn(p, x)

    def _conv_qkv(self, window, taps):
        """The causal depthwise convolutions over ``window`` [B, T + K -
        1, C] (the K - 1 inputs before the chunk first), then silu, in
        float32 -> unit q (scaled), unit k, v: [B, T, H, *]."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        lh, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        kw = taps.shape[0]
        t = window.shape[1] - (kw - 1)
        f32 = jnp.float32
        y = sum(window[:, j:j + t].astype(f32) * taps[j].astype(f32)
                for j in range(kw))
        y = jax.nn.silu(y)
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
        b = y.shape[0]
        q = unit(y[..., :lh * dk].reshape(b, t, lh, dk)) * dk ** -0.5
        k = unit(y[..., lh * dk:2 * lh * dk].reshape(b, t, lh, dk))
        return q, k, y[..., 2 * lh * dk:].reshape(b, t, lh, dv)

    # -- the caches' reads and writes -------------------------------------
    @staticmethod
    def _real_only(real, g, beta):
        """The log-decay and the step of a chunk's positions [B, c, H],
        0 and 0 where a position is padding (``real`` [B, c] False): the
        delta rule then leaves the state as it was over it."""
        import jax.numpy as jnp

        return (jnp.where(real[..., None], g, 0.0),
                jnp.where(real[..., None], beta, 0.0))

    @staticmethod
    def _rows(store, li, slots):
        """Layer ``li``'s entries of ``slots`` [B] of a leaf addressed
        by slot -> [B, ...]: the layer first, then its rows. Gathered
        in one step (``store[li, slots]``), XLA copies the whole store
        out of the layer scan's carry (1.7 GB a prefill pass)."""
        import jax
        import jax.numpy as jnp

        return jnp.take(jax.lax.dynamic_index_in_dim(store, li, 0,
                                                     keepdims=False),
                        slots, axis=0)

    @staticmethod
    def _put_rows(store, li, slots, rows):
        """``store[li, slots[b]] = rows[b]`` for a leaf addressed by slot,
        as ONE update of layer ``li``'s whole slab: each slot takes the
        row that names it or keeps what it held (rows that share a slot,
        padding on the trash slot, leave one of them there). Scattered
        row by row XLA loops over the rows: nine operations a row a layer
        a tick, 2.6 of a decode tick's 31 ms at 64 rows and 12 layers.
        For a leaf whose slab is small (the convolutions' tails: 4.5 MB)."""
        import jax
        import jax.numpy as jnp

        named = slots[None, :] == jnp.arange(store.shape[1])[:, None]
        slab = jax.lax.dynamic_index_in_dim(store, li, 0, keepdims=False)
        slab = jnp.where(
            named.any(1).reshape((-1,) + (1,) * (slab.ndim - 1)),
            jnp.take(rows, jnp.argmax(named, 1), axis=0), slab)
        return jax.lax.dynamic_update_index_in_dim(store, slab, li, 0)

    @staticmethod
    def _fresh(first, held):
        """What a row starts a chunk from: what its slot ``held`` [B,
        ...], or zeros where the chunk is the row's first. So no program
        zeroes a slot at admission, and a slot handed to a new request
        while a tick launched ahead still steps the old one is right by
        the order of the programs on the device."""
        import jax.numpy as jnp

        return jnp.where(first.reshape((-1,) + (1,) * (held.ndim - 1)),
                         jnp.zeros((), held.dtype), held)

    def decode_attend(self, tables, lens, slots):
        """A decode tick. Full layers: the dense kind's (this token's K/V
        row written, then ``paged_attention`` over the row's pages).
        Linear layers: the convolutions' tail and the state of each
        row's SLOT read, stepped one position and written back where they
        lie; a row on the trash slot (padding) steps nothing live."""
        import jax.numpy as jnp

        from ..ops.pallas import on_tpu_device
        from ..ops.pallas.gated_delta import (gdn_decode_step,
                                              gdn_decode_step_reference)

        kv_attend = self._kv.decode_attend(tables, lens)
        kernel = (on_tpu_device() if self.step_kernel is None
                  else self.step_kernel)

        def attend(li, kind, *operands):
            *operands, (kc, vc, state, conv) = operands
            if kind == "full":
                o, (kc, vc) = kv_attend(li, *operands, (kc, vc))
                return o, (kc, vc, state, conv)
            xc, taps, g, beta = operands                 # xc [B, 1, C]
            b = xc.shape[0]
            tail = self._rows(conv, li, slots).reshape(
                b, taps.shape[0] - 1, -1)
            window = jnp.concatenate([tail, xc], 1)
            conv = self._put_rows(conv, li, slots,
                                  window[:, 1:].reshape(b, -1))
            q, k, v = self._conv_qkv(window, taps)
            args = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                    beta[:, 0], state, slots, li)
            o, state = (gdn_decode_step(*args, g=self.pack_g) if kernel
                        else gdn_decode_step_reference(*args, self.pack_g))
            live = jnp.sum(slots != state.shape[1] - 1).astype(jnp.int32)
            n = jnp.stack([live, jnp.int32(b), live, jnp.int32(0)])
            return (o[:, None], n), (kc, vc, state, conv)

        return attend

    def chunk_attend(self, hist, pos0, nvalid, chunk, page, slots):
        """A prefill chunk ([B, chunk] positions from ``pos0``, the first
        ``nvalid`` of them real). Full layers: the chunk's K/V rows
        written, then ``prefill_rows`` rows at a time attend their
        gathered history causally (no rope). Linear layers: a row's tail
        and state come from its slot, or are ZERO when the chunk is the
        row's first (``pos0`` 0), whatever the slot held; the chunk goes
        through the delta rule's chunkwise form; positions at or past
        ``nvalid`` leave the state and the tail as they were."""
        import jax
        import jax.numpy as jnp

        from ..inference.serving import _kv_gather_rows, _kv_write_run
        from ..ops.pallas.gated_delta import pack_state, unpack_state

        cfg = self.cfg
        b = hist.shape[0]
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        rep = cfg.num_heads // hkv
        s_len = hist.shape[1] * page
        nvalid = jnp.broadcast_to(jnp.asarray(nvalid, jnp.int32), (b,))
        first = (pos0 == 0)
        real = jnp.arange(chunk)[None, :] < nvalid[:, None]     # [B, c]
        rows = min(self.prefill_rows, b)
        while b % rows:
            rows -= 1
        block = (self.prefill_block if chunk % self.prefill_block == 0
                 else chunk)
        split = lambda a: a.reshape((b // rows, rows) + a.shape[1:])

        def full(li, q, k, v, kc, vc):
            kc = _kv_write_run(kc, li, hist, pos0, nvalid, k)
            vc = _kv_write_run(vc, li, hist, pos0, nvalid, v)
            # the history of every row, gathered HERE: a pool read inside
            # the row groups' loop is copied whole into it (1.5 GB)
            rows_of = lambda pool: split(jnp.moveaxis(
                _kv_gather_rows(pool, li, hist, q.dtype).reshape(
                    hkv, b, s_len, hd), 1, 0))       # [., rows, Hkv, S, D]

            def some_rows(args):
                qg, ck, cv, p0 = args
                if rep > 1:
                    ck, cv = jnp.repeat(ck, rep, 1), jnp.repeat(cv, rep, 1)
                logits = jnp.einsum(
                    "bchd,bhsd->bhcs", qg * (hd ** -0.5), ck,
                    preferred_element_type=jnp.float32)
                at = p0[:, None] + jnp.arange(chunk)[None, :]
                mask = jnp.arange(s_len)[None, None] <= at[:, :, None]
                probs = jax.nn.softmax(
                    jnp.where(mask[:, None], logits, -1e30), -1)
                return jnp.einsum("bhcs,bhsd->bchd", probs.astype(cv.dtype),
                                  cv, preferred_element_type=jnp.float32
                                  ).astype(qg.dtype)

            o = jax.lax.map(some_rows, (split(q), rows_of(kc), rows_of(vc),
                                        split(pos0)))
            return o.reshape((b,) + o.shape[2:]), kc, vc

        def lin(li, xc, taps, g, beta, state, conv):
            kw = taps.shape[0]
            tail = self._fresh(first, self._rows(conv, li, slots)).reshape(
                b, kw - 1, -1)
            s0 = self._fresh(first, self._rows(state, li, slots))

            def some_rows(args):
                xc, tail, g, beta, real, nvalid, s0 = args   # [rows, ...]
                window = jnp.concatenate([tail, xc], 1)  # [., c + K-1, C]
                # the last K - 1 inputs up to the chunk's last REAL
                # position
                keep = nvalid[:, None] + jnp.arange(kw - 1)[None, :]
                tail = jnp.take_along_axis(window, keep[..., None], 1)
                q, k, v = self._conv_qkv(window, taps)
                o, s = chunk_delta_rule(
                    q, k, v, *self._real_only(real, g, beta),
                    unpack_state(s0, self.pack_g), block)
                return o, pack_state(s, self.pack_g), tail

            # ``prefill_rows`` rows at a time: the float32 q, k, v and
            # the blocks' products of all the rows at once are gigabytes
            o, s, tail = jax.lax.map(some_rows, tuple(
                split(a) for a in (xc, tail, g, beta, real, nvalid, s0)))
            join = lambda a: a.reshape((b,) + a.shape[2:])
            state = state.at[li, slots].set(join(s))
            conv = self._put_rows(conv, li, slots,
                                  join(tail).reshape(b, -1))
            stepped = jnp.sum(nvalid > 0).astype(jnp.int32)
            n = jnp.stack([stepped, jnp.int32(b),
                           jnp.sum(nvalid).astype(jnp.int32), jnp.int32(1)])
            return (join(o), n), state, conv

        def attend(li, kind, *operands):
            *operands, (kc, vc, state, conv) = operands
            if kind == "full":
                o, kc, vc = full(li, *operands, kc, vc)
            else:
                o, state, conv = lin(li, *operands, state, conv)
            return o, (kc, vc, state, conv)

        return attend
