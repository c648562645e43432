"""A stacked decoder of latent attention (MLA) and grouped-sigmoid routed
experts, for the serving engine.

The DeepSeek-V3 family's language model (dots.vlm1, and its kin): every
layer attends through a low-rank latent (the cache row of a token is the
normed latent ``c_kv`` beside ONE roped key part shared by all heads, not
K and V per head); the first layers' feed-forward is a dense swiglu, the
rest are routed experts (sigmoid scores, group-limited top-k on biased
scores, weights normalised and scaled) beside one shared expert.

The model is natively stacked BY GROUP of like layers (``dense.*`` and
``moe.*``, each leaf ``[layers of the group, ...]``), so the engine's
packed tree references the parameters and the weights exist once. It is
told which routed experts it holds (``experts_held = (lo, hi)``): the
router scores all of them, the held ones' part of the sum is computed,
what the others would add is not (incubate/distributed/models/moe/
grouped.py).

What the engine asks of a model that is not the dense decoder is
:class:`LatentMoEServing` (``model.serving_arch()``): the cache's
geometry, the packed weights by group, each group's layer mathematics, and
the two attention paths over the latent pool (absorbed decode through the
``mla_paged_attention`` kernel; a prefill chunk, absorbed too, against
the gathered latent history by row groups and history blocks under a
running softmax).
"""
from __future__ import annotations

import math

import numpy as np

from paddle_tpu import nn
from paddle_tpu import telemetry as _telemetry
from paddle_tpu.core.tensor import Parameter

_MOE_LOCAL_PAIRS = _telemetry.counter(
    "serving_moe_local_pairs_total",
    "(token, expert) pairs routed to experts the served model holds, "
    "over every expert layer of every tick")
_MOE_DROPPED = _telemetry.counter(
    "serving_moe_dropped_total",
    "routed pairs to held experts that the grouped GEMM was not handed "
    "(local pairs less the group sizes it was given, block by block): 0, "
    "and the model kind raises on any other reading")

ATTN_LEAVES = ("ln1", "wqa", "qln", "wqb", "wkva", "kvln", "wkb", "wvb",
               "wo", "ln2")
DENSE_LEAVES = ATTN_LEAVES + ("wg", "wu", "wd")
MOE_LEAVES = ATTN_LEAVES + ("router", "bias", "sg", "su", "sd", "eg", "eu",
                            "ed")


class LatentMoEConfig:
    """Sizes under the published names of the family's ``config.json``
    where it has one. ``n_routed_experts`` is the ROUTER's width;
    ``experts_held`` the half-open range of them this model holds."""

    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, n_group, topk_group,
                 first_k_dense=1, n_shared_experts=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 experts_held=None, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=2048, dtype="float32"):
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.num_layers, self.num_heads = num_layers, num_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group, self.topk_group = n_group, topk_group
        self.first_k_dense = first_k_dense
        self.n_shared_experts = n_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.experts_held = tuple(experts_held or (0, n_routed_experts))
        self.rope_theta, self.rope_scaling = rope_theta, rope_scaling
        self.max_seq_len, self.dtype = max_seq_len, dtype
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside the "
                             f"router's {n_routed_experts} experts")
        if not 0 < first_k_dense < num_layers:
            raise ValueError("a latent MoE stack has leading dense layers "
                             "and expert layers after them")

    @property
    def cache_row(self):
        """Values of one token's cache row a layer (the algorithm's)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self):
        """Lanes of the pool's row: the cache row padded to the tile."""
        return -(-self.cache_row // 128) * 128

    def leaf_shapes(self):
        """group -> leaf -> shape, each leaf stacked over its group's
        layers; plus the three top leaves."""
        h, nh = self.hidden_size, self.num_heads
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        qr, r = self.q_lora_rank, self.kv_lora_rank
        m, me = self.intermediate_size, self.moe_intermediate_size
        ms = me * self.n_shared_experts
        held = self.experts_held[1] - self.experts_held[0]
        attn = {"ln1": (h,), "wqa": (h, qr), "qln": (qr,),
                "wqb": (qr, nh * (dn + dr)), "wkva": (h, r + dr),
                "kvln": (r,), "wkb": (nh, dn, r), "wvb": (nh, r, dv),
                "wo": (nh * dv, h), "ln2": (h,)}
        dense = dict(attn, wg=(h, m), wu=(h, m), wd=(m, h))
        moe = dict(attn, router=(h, self.n_routed_experts),
                   bias=(self.n_routed_experts,), sg=(h, ms), su=(h, ms),
                   sd=(ms, h), eg=(held, h, me), eu=(held, h, me),
                   ed=(held, me, h))
        nd, nm = self.first_k_dense, self.num_layers - self.first_k_dense
        return {"dense": {k: (nd,) + s for k, s in dense.items()},
                "moe": {k: (nm,) + s for k, s in moe.items()},
                "top": {"embed": (self.vocab_size, h), "fnorm": (h,),
                        "head": (h, self.vocab_size)}}


# ------------------------------------------------------------------ rope
def yarn_inv_freq(dim, base, scaling):
    """Rotary frequencies [dim/2]: plain, or YaRN's blend of the plain and
    the interpolated ones, fixed at every length (the family blends once,
    not by the running length)."""
    inv = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return inv.astype(np.float32)
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp                   # 1: the plain frequency stays
    return (inv / factor * (1 - keep) + inv * keep).astype(np.float32)


def yarn_mscale(scaling, key):
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling.get(key, 1.0) * math.log(scaling["factor"]) + 1.0


def softmax_scale(cfg):
    """``(d_nope + d_rope)^-0.5 * mscale^2`` with YaRN's mscale_all_dim."""
    m = yarn_mscale(cfg.rope_scaling, "mscale_all_dim")
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope_at(x, pos, inv_freq, mscale=1.0):
    """Half-split rotation of [B, T, H, D] at positions ``pos[b] + t``."""
    import jax.numpy as jnp

    from .gpt import _rope_rotate

    p = (pos[:, None] + jnp.arange(x.shape[1])[None, :]).astype(jnp.float32)
    f = p[..., None] * jnp.asarray(inv_freq)
    sin = (jnp.sin(f) * mscale)[:, :, None, :]
    cos = (jnp.cos(f) * mscale)[:, :, None, :]
    return _rope_rotate(x, sin, cos)


# ----------------------------------------------------------------- model
class _Group(nn.Layer):
    """One group of like layers: its leaves stacked on a leading axis."""


class LatentMoEForCausalLM(nn.Layer):
    """The stacked model. ``weights`` ({"dense": {leaf: [n, ...]}, "moe":
    {...}, "embed", "fnorm", "head"}) are referenced, never copied, so a
    caller that made them on the device holds them once; without them the
    leaves are drawn from ``seed`` (tests, tiny sizes)."""

    def __init__(self, config, weights=None, seed=0):
        super().__init__()
        self.config = config
        shapes = config.leaf_shapes()
        if weights is None:
            weights = _random_weights(config, shapes, seed)
        for group in ("dense", "moe"):
            layer = _Group()
            for leaf, shape in shapes[group].items():
                arr = weights[group][leaf]
                if tuple(arr.shape) != shape:
                    raise ValueError(f"{group}.{leaf}: {arr.shape} != "
                                     f"{shape}")
                setattr(layer, leaf, Parameter(arr, trainable=False))
            setattr(self, group, layer)
        top = _Group()
        for leaf, shape in shapes["top"].items():
            if tuple(weights[leaf].shape) != shape:
                raise ValueError(f"{leaf}: {weights[leaf].shape} != {shape}")
            setattr(top, leaf, Parameter(weights[leaf], trainable=False))
        self.top = top

    def serving_arch(self):
        return LatentMoEServing(self)


def _random_weights(cfg, shapes, seed):
    """Leaves from a seed: norm gains near one, the router's bias small
    and non-zero, matrices normal at fan-in^-1/2."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    out = {"dense": {}, "moe": {}}
    for gi, group in enumerate(("dense", "moe", "top")):
        for li, (leaf, shape) in enumerate(shapes[group].items()):
            k = jax.random.fold_in(jax.random.fold_in(key, gi), li)
            z = jax.random.normal(k, shape, jnp.float32)
            if leaf in ("ln1", "qln", "kvln", "ln2", "fnorm"):
                arr = (1.0 + 0.1 * z).astype(dt)
            elif leaf == "bias":
                arr = 0.05 * z
            elif leaf == "embed":
                arr = z.astype(dt)
            else:
                arr = (z * shape[-2] ** -0.5).astype(dt)
            (out if group == "top" else out[group])[leaf] = arr
    return out


# -------------------------------------------------- what the engine asks
class LatentMoEServing:
    """The engine's view of the model (inference/serving.py, "Model kinds
    and cache geometry" in docs/SERVING.md)."""

    cache_names = ("latent",)
    #: the cache leaves addressed by SLOT, not by page: none
    slot_cache_names = ()
    #: why a request cannot leave this engine for another: it can
    no_handoff = None
    #: the prefill chunk's attention through the Pallas kernel (True), the
    #: gather path (False), or by the device (None: the kernel on a TPU)
    prefill_kernel = None
    #: engine features this model kind refuses at construction, by name
    refuses = {
        "int8_kv": "the latent pool has no int8 row format",
        "int8_weights": "the latent and expert slabs have no int8 packing",
        "draft_model": "a DraftRunner walks dense layers and a K/V pool",
        "group prefill (prefill_chunk=None)": "the latent model prefills "
        "by chunks only",
    }

    def __init__(self, model):
        self.model, self.cfg = model, model.config
        cfg = self.cfg
        self.inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                                      cfg.rope_scaling)
        self.rope_mscale = (yarn_mscale(cfg.rope_scaling, "mscale")
                            / yarn_mscale(cfg.rope_scaling,
                                          "mscale_all_dim"))
        self.scale = softmax_scale(cfg)
        #: rows of a prefill chunk attended together (the queries in the
        #: latent space are rows x chunk x heads x 640), and the history
        #: positions a running-softmax step of the gather path takes
        #: (its scores are rows x heads x chunk x block float32)
        self.prefill_rows, self.prefill_block = 16, 512

    # -- geometry and weights ---------------------------------------------
    def cache_shapes(self, num_pages, page):
        """One pool: a token's row a layer is ``cache_row`` values, laid
        in ``cache_width`` lanes (512 + 64 meet the 128-lane tile as 640:
        the pad is the pool's cost over the algorithm's bytes)."""
        cfg = self.cfg
        return ((cfg.num_layers, 1, num_pages + 1, page, cfg.cache_width),)

    def cache_token_bytes(self, itemsize):
        """Bytes the algorithm must keep a token, over all layers."""
        return self.cfg.num_layers * self.cfg.cache_row * itemsize

    def pack(self, int8_weights=False):
        """{"layers": (dense leaves, moe leaves), "embed", "fnorm",
        "head"}: the parameters themselves, no copy (``int8_weights``
        is refused at construction)."""
        m = self.model
        return {
            "layers": (tuple(getattr(m.dense, k)._data
                             for k in DENSE_LEAVES),
                       tuple(getattr(m.moe, k)._data for k in MOE_LEAVES)),
            "embed": m.top.embed._data, "fnorm": m.top.fnorm._data,
            "head": m.top.head._data,
        }

    def groups(self, weights):
        """[(stacked leaves, layer forward)] in stack order."""
        import functools

        dense, moe = weights["layers"]
        # the experts' stacks stay whole beside the walk (the grouped GEMM
        # reads a layer's experts where they lie); the rest is walked
        return [(dense, self.dense_layer),
                (moe[:-3], functools.partial(self.moe_layer,
                                             experts=moe[-3:]))]

    def carry_in(self, x):
        """The walker's carry: the hidden state beside the program's
        expert counts (local pairs, experts hit, the fullest expert's
        pairs, local pairs the grouped GEMM was not handed), each summed
        over the expert layers."""
        import jax.numpy as jnp

        return x, jnp.zeros((4,), jnp.int32)

    def carry_out(self, carry):
        """(hidden state, the counts: the engine brings them to the host
        with the program's tokens and hands them to ``note_stats``)."""
        return carry

    def note_stats(self, stats):
        """One program's counts, on the host: onto the counters, and back
        as the span attrs of docs/TELEMETRY.md. A local pair the grouped
        GEMM was not handed is a dropped token: routing here drops none,
        and a reading that says otherwise raises."""
        pairs, hit, load_max, dropped = (int(v) for v in stats)
        _MOE_LOCAL_PAIRS.inc(pairs)
        _MOE_DROPPED.inc(dropped)
        if dropped:
            raise RuntimeError(
                f"the expert layers' grouped GEMM was handed {dropped} "
                f"fewer rows than the {pairs} pairs routed to held "
                "experts: routing drops nothing (moe/grouped.py)")
        lo, hi = self.cfg.experts_held
        return {"local_pairs": pairs, "experts_hit": hit,
                "expert_load_max": load_max,
                "expert_load_mean": pairs / (hi - lo),
                "dropped_tokens": dropped}

    # -- layer mathematics ------------------------------------------------
    def _attention(self, li, lp, x, pos0, attend):
        import jax.numpy as jnp

        from .gpt import _rms_pure

        cfg = self.cfg
        ln1, wqa, qln, wqb, wkva, kvln, wkb, wvb, wo = lp[:9]
        b, s = x.shape[:2]
        dn, dr, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank)
        h1 = _rms_pure(x, ln1)
        q = (_rms_pure(h1 @ wqa, qln) @ wqb).reshape(b, s, cfg.num_heads,
                                                     dn + dr)
        kva = h1 @ wkva
        ckv = _rms_pure(kva[..., :r], kvln)
        k_r = rope_at(kva[..., None, r:], pos0, self.inv_freq,
                      self.rope_mscale)
        q_r = rope_at(q[..., dn:], pos0, self.inv_freq, self.rope_mscale)
        row = jnp.concatenate(
            [ckv, k_r[:, :, 0],
             jnp.zeros((b, s, cfg.cache_width - cfg.cache_row), x.dtype)],
            -1)[:, :, None]                          # [B, S, 1, width]
        o = attend(li, q[..., :dn], q_r, row, wkb, wvb)   # [B, S, H*dv]
        return x + o @ wo

    def dense_layer(self, li, lp, carry, pos0, attend):
        import jax

        from .gpt import _rms_pure

        x, stats = carry
        x = self._attention(li, lp, x, pos0, attend)
        ln2, wg, wu, wd = lp[9:]
        h2 = _rms_pure(x, ln2)
        return x + (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd, stats

    def moe_layer(self, li, lp, carry, pos0, attend, experts):
        import jax
        import jax.numpy as jnp

        from ..incubate.distributed.models.moe.grouped import (
            HI, grouped_sigmoid_route, held_expert_ffn)
        from .gpt import _rms_pure

        cfg = self.cfg
        x, stats = carry
        x = self._attention(li, lp, x, pos0, attend)
        ln2, router, bias, sg, su, sd = lp[9:]
        b, s, h = x.shape
        flat = _rms_pure(x, ln2).reshape(b * s, h)
        logits = jnp.matmul(flat.astype(jnp.float32),
                            router.astype(jnp.float32), precision=HI)
        idx, w = grouped_sigmoid_route(
            logits, bias, top_k=cfg.num_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob)
        y, st = held_expert_ffn(flat, idx, w, *experts, cfg.experts_held,
                                layer=li - cfg.first_k_dense)
        shared = (jax.nn.silu(flat @ sg) * (flat @ su)) @ sd
        y = (y + shared.astype(jnp.float32)).astype(x.dtype)
        return x + y.reshape(b, s, h), stats + st

    # -- attention over the latent pool -----------------------------------
    def _latent_q(self, q_nope, q_r, wkb):
        """Queries into the latent space, [..., H, width]: ``q_nope
        W_kvb,K^T`` beside the roped part, zeros over the pad."""
        import jax.numpy as jnp

        cfg = self.cfg
        q_lat = jnp.einsum("...hd,hdr->...hr", q_nope, wkb)
        pad = jnp.zeros(q_lat.shape[:-1] + (cfg.cache_width - cfg.cache_row,),
                        q_lat.dtype)
        return jnp.concatenate([q_lat, q_r, pad], -1)

    def decode_attend(self, tables, lens, slots=None):
        """A decode tick's attention: the token's row written, then every
        head attends the latent rows themselves (absorbed form) through
        the paged kernel; ``W_kvb,V`` after."""
        import jax.numpy as jnp

        from ..inference.serving import _kv_write_run
        from ..ops.pallas.decode_attention import mla_paged_attention

        cfg = self.cfg

        def attend(li, q_nope, q_r, row, wkb, wvb, cache):
            (pool,) = cache
            pool = _kv_write_run(pool, li, tables, lens, 1, row)
            qf = self._latent_q(q_nope[:, 0], q_r[:, 0], wkb)
            o_lat = mla_paged_attention(qf, pool, tables, lens + 1, layer=li,
                                        rank=cfg.kv_lora_rank,
                                        scale=self.scale)
            o = jnp.einsum("bhr,hrv->bhv", o_lat, wvb)
            return o.reshape(o.shape[0], 1, -1), (pool,)

        return attend

    def chunk_attend(self, hist, pos0, nvalid, chunk, page, slots=None):
        """A prefill chunk's attention, absorbed too (at chunk 128 the
        absorbed scores cost less than up-projecting the history, and the
        history stays one 640-wide row a token): the chunk's rows
        written, then ``prefill_rows`` rows at a time attend the paged
        latent history. On a TPU through the ``mla_paged_prefill`` kernel
        (scores stay in VMEM under a running softmax, pages past the
        chunk's end are not fetched); elsewhere the gathered history,
        ``prefill_block`` positions a step under the same running
        softmax, as far as the longest row reaches."""
        import jax
        import jax.numpy as jnp

        from ..inference.serving import _kv_gather_rows, _kv_write_run
        from ..ops.pallas import on_tpu_device
        from ..ops.pallas.decode_attention import mla_paged_prefill

        cfg = self.cfg
        r = cfg.kv_lora_rank
        pages_per_seq = hist.shape[1]
        kernel = (on_tpu_device() if self.prefill_kernel is None
                  else self.prefill_kernel)
        ppb = max(1, min(pages_per_seq, self.prefill_block // page))
        blk = ppb * page
        nblk = -(-pages_per_seq // ppb)
        # history blocks the longest row needs
        need = (jnp.max(pos0 + nvalid) + blk - 1) // blk
        scale = np.float32(self.scale)

        def attend(li, q_nope, q_r, row, wkb, wvb, cache):
            (pool,) = cache
            pool = _kv_write_run(pool, li, hist, pos0, nvalid, row)
            b, c, nh = q_nope.shape[:3]
            g = min(self.prefill_rows, b)
            while b % g:
                g -= 1

            def by_kernel(args):
                qn, qr, tb, p0, nv = args           # [g, c, H, *]
                # head-major tiles ([g, H, c, width]): a block of heads
                # x the chunk's positions is contiguous
                qf = jnp.swapaxes(self._latent_q(qn, qr, wkb), 1, 2)
                o_lat = mla_paged_prefill(
                    qf.reshape(g, nh * c, -1), pool, tb, p0, nv, layer=li,
                    rank=r, scale=self.scale, chunk=c)
                return jnp.einsum("ghcr,hrv->gchv",
                                  o_lat.reshape(g, nh, c, r), wvb)

            def by_gather(args):
                qn, qr, tb, p0, _ = args
                # pad the tables to whole blocks (the pad reads the
                # scratch page, masked out below)
                tb = jnp.pad(tb, ((0, 0), (0, nblk * ppb - pages_per_seq)),
                             constant_values=pool.shape[2] - 1)
                qf = self._latent_q(qn, qr, wkb)     # [g, c, H, width]
                qpos = p0[:, None] + jnp.arange(c)[None, :]      # [g, c]

                def step(j, carry):
                    acc, m, l = carry
                    ids = jax.lax.dynamic_slice_in_dim(tb, j * ppb, ppb, 1)
                    kv = _kv_gather_rows(pool, li, ids, qf.dtype)[0]
                    kv = kv.reshape(g, blk, -1)      # [g, blk, width]
                    s = jnp.einsum("gchw,gsw->ghcs", qf, kv,
                                   preferred_element_type=jnp.float32)
                    kpos = j * blk + jnp.arange(blk)
                    ok = kpos[None, None, :] <= qpos[:, :, None]
                    s = jnp.where(ok[:, None], s * scale, -1e30)
                    m_new = jnp.maximum(m, jnp.max(s, -1))
                    p = jnp.exp(s - m_new[..., None])
                    alpha = jnp.exp(m - m_new)
                    l = alpha * l + jnp.sum(p, -1)
                    acc = acc * alpha[..., None] + jnp.einsum(
                        "ghcs,gsr->ghcr", p.astype(kv.dtype), kv[..., :r],
                        preferred_element_type=jnp.float32)
                    return acc, m_new, l

                acc = jnp.zeros((g, nh, c, r), jnp.float32)
                m = jnp.full((g, nh, c), -1e30, jnp.float32)
                l = jnp.zeros((g, nh, c), jnp.float32)
                acc, m, l = jax.lax.fori_loop(0, need, step, (acc, m, l))
                o_lat = (acc / jnp.where(l == 0.0, 1.0, l)[..., None]
                         ).astype(qf.dtype)
                return jnp.einsum("ghcr,hrv->gchv", o_lat, wvb)

            split = lambda a: a.reshape((b // g, g) + a.shape[1:])
            o = jax.lax.map(by_kernel if kernel else by_gather,
                            (split(q_nope), split(q_r), split(hist),
                             split(pos0), split(jnp.broadcast_to(
                                 jnp.asarray(nvalid), (b,)))))
            return o.reshape(b, c, -1), (pool,)

        return attend
