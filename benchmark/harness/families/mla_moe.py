"""The latent-attention + routed-experts decoder family (the DeepSeek-V3
line: dots.vlm1's language model and its kin), as ONE CHIP'S SHARE of a
deployment: the experts ``experts_held`` of the router's
``router_experts``, a slice of the vocabulary, every other width whole.

What the harness knows of this shape is here: its plain reference in
straightforward jax.numpy (float32, matmuls at HIGHEST, no cache, no
kernel, attention NOT absorbed: the latent is up-projected to keys and
values per head; every held expert applied to every token under a mask;
what the experts held elsewhere would add is left out, as in the program),
how the program's model is built on the seed's weights, and the
arithmetic. ``make_weights`` and ``forward_logits`` import nothing of
paddle_tpu. The family is served only: the training names exist and
raise.

Each layer, on x [T, h]:
  c_q = rms(x W_qa); q = c_q W_qb -> H x (d_nope + d_rope)
  (c_kv, k_r) = x W_kva; c_kv = rms(c_kv); k_r = rope(k_r), one for all H
  k_nope = c_kv W_kb; v = c_kv W_vb   (kv_b_proj held as its two halves)
  scores = (q_nope . k_nope + rope(q_r) . k_r) s,  s = (d_nope+d_rope)^-1/2
           * mscale^2, mscale = 0.1 mscale_all_dim ln(factor) + 1 (YaRN)
  x += softmax(scores) v W_o
  dense layers: x += swiglu(rms(x))
  expert layers: sigma = sigmoid(rms(x) W_g) in float32; choice on
    sigma + b: a group's score the sum of its two best, topk_group groups
    kept, the num_experts_per_tok best inside them; weights the chosen
    sigma, normalised, x routed_scaling_factor;
    x += sum_{i held} w_i E_i + S   (S the shared expert)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import HI, _leaf_values, mm, rms, seed_key

BIAS_STD = 0.02     # the router's selection bias: small, never zero
HEAD_BLOCK = 16     # heads attended together in the reference


# ------------------------------------------------------------------ shapes
def held(cfg):
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"], "n_routed_experts counts " \
        "the experts held here"
    return lo, hi


def leaf_shapes(cfg):
    """group -> leaf -> shape, layer leaves stacked over their group."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    m, me = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    ms = me * cfg["n_shared_experts"]
    e, nheld = cfg["router_experts"], cfg["n_routed_experts"]
    attn = {"ln1": (h,), "wqa": (h, qr), "qln": (qr,),
            "wqb": (qr, nh * (dn + dr)), "wkva": (h, r + dr), "kvln": (r,),
            "wkb": (nh, dn, r), "wvb": (nh, r, dv), "wo": (nh * dv, h),
            "ln2": (h,)}
    dense = dict(attn, wg=(h, m), wu=(h, m), wd=(m, h))
    moe = dict(attn, router=(h, e), bias=(e,), sg=(h, ms), su=(h, ms),
               sd=(ms, h), eg=(nheld, h, me), eu=(nheld, h, me),
               ed=(nheld, me, h))
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    v = cfg["vocab_size"]
    return {"dense": {k: (nd,) + s for k, s in dense.items()},
            "moe": {k: (nm,) + s for k, s in moe.items()},
            "top": {"embed": (v, h), "fnorm": (h,), "head": (h, v)}}


# ----------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind"))
def _make_leaf(key, shape, dtype, kind):
    """One leaf on the device: its leading slices drawn one after another
    (each from its own key), so the float32 draw is one slice's."""
    # leading axes drawn slice by slice: none of a top leaf, the layers
    # of a layer leaf, the layers and the experts of an expert leaf
    lead = 0 if kind == "top" else (2 if len(shape) == 4 else 1)
    n = int(np.prod(shape[:lead]))

    def one(i):
        k = jax.random.fold_in(key, i)
        if kind == "bias":
            return BIAS_STD * jax.random.normal(k, shape[lead:], jnp.float32)
        return _leaf_values(k, shape[lead:]).astype(dtype)

    return jax.lax.map(one, jnp.arange(n)).reshape(shape)


def make_weights(cfg, seed, dtype):
    """{"dense": {leaf: [n, ...]}, "moe": {...}, "embed", "fnorm",
    "head"} on the device, a jitted call a leaf. Matrices are normal at
    the harness's INIT_STD, norm gains near one on the bf16 grid, the
    router's bias float32, small and non-zero."""
    key = seed_key(seed)
    shapes = leaf_shapes(cfg)
    out = {"dense": {}, "moe": {}}
    for gi, group in enumerate(("dense", "moe", "top")):
        for li, (leaf, shape) in enumerate(sorted(shapes[group].items())):
            k = jax.random.fold_in(jax.random.fold_in(key, gi), li)
            kind = "bias" if leaf == "bias" else group
            arr = _make_leaf(k, shape, jnp.float32 if leaf == "bias"
                             else jnp.dtype(dtype), kind)
            (out if group == "top" else out[group])[leaf] = arr
    return out


# ---------------------------------------------------------------- reference
def yarn_inv_freq(cfg):
    """Rotary frequencies of the rope part: YaRN's blend of plain and
    interpolated, made once (the family does not rescale by length)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return inv.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), dim - 1)
    span = high - low if high != low else 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / span, 0, 1)
    return (inv / sc["factor"] * ramp + inv * (1 - ramp)).astype(np.float32)


def _mscale(cfg, key):
    sc = cfg.get("rope_scaling")
    if not sc or sc["factor"] <= 1:
        return 1.0
    return 0.1 * sc.get(key, 1.0) * math.log(sc["factor"]) + 1.0


def rope(x, inv_freq, mscale):
    """Half-split rotation of [T, H, D] at positions 0..T-1."""
    f = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    sin = (jnp.sin(f) * mscale)[:, None, :]
    cos = (jnp.cos(f) * mscale)[:, None, :]
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, cfg, mode):
    """Latent attention of one layer on x [T, h], not absorbed."""
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)
    inv = jnp.asarray(yarn_inv_freq(cfg))
    ms = _mscale(cfg, "mscale") / _mscale(cfg, "mscale_all_dim")
    scale = (dn + dr) ** -0.5 * _mscale(cfg, "mscale_all_dim") ** 2
    h1 = rms(x, f32(p["ln1"]), eps)
    cq = rms(mm(h1, f32(p["wqa"]), mode), f32(p["qln"]), eps)
    q = mm(cq, f32(p["wqb"]), mode).reshape(t, nh, dn + dr)
    kva = mm(h1, f32(p["wkva"]), mode)
    ckv = rms(kva[:, :r], f32(p["kvln"]), eps)
    k_r = rope(kva[:, None, r:], inv, ms)                    # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv, ms)], -1)
    k_nope = mm(ckv, f32(p["wkb"]).reshape(nh * dn, r).T, mode).reshape(
        t, nh, dn)
    v = mm(ckv, f32(p["wvb"]).transpose(1, 0, 2).reshape(r, nh * dv),
           mode).reshape(t, nh, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (t, nh, dr))], -1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def heads(args):                   # a block of heads at a time
        qb, kb, vb = args                                    # [hb, T, *]
        sc = jnp.einsum("hqd,hkd->hqk", qb, kb, precision=HI) * scale
        sc = jnp.where(causal, sc, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1), vb,
                          precision=HI)

    hb = math.gcd(nh, HEAD_BLOCK)
    blocks = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (nh // hb, hb) + (t, a.shape[-1]))
    o = jax.lax.map(heads, (blocks(q), blocks(k), blocks(v)))
    o = jnp.moveaxis(o.reshape(nh, t, dv), 0, 1).reshape(t, nh * dv)
    return x + mm(o, f32(p["wo"]), mode)


def route(logits, bias, cfg):
    """-> (chosen experts [T, k], their weights [T, k]) over ALL of the
    router's experts: the bias moves the choice, not the weight."""
    t, e = logits.shape
    ng, per = cfg["n_group"], logits.shape[1] // cfg["n_group"]
    sigma = jax.nn.sigmoid(logits)
    choice = sigma + bias
    best2 = jnp.sort(choice.reshape(t, ng, per), -1)[..., -2:].sum(-1)
    kept_groups = jnp.argsort(-best2, -1)[:, :cfg["topk_group"]]
    kept = (jnp.arange(ng)[None, :, None] == kept_groups[:, None, :]).any(-1)
    choice = jnp.where(jnp.repeat(kept, per, 1), choice, -jnp.inf)
    idx = jnp.argsort(-choice, -1)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(sigma, idx, 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def swiglu(x, wg, wu, wd, mode):
    f32 = lambda a: a.astype(jnp.float32)
    return mm(jax.nn.silu(mm(x, f32(wg), mode)) * mm(x, f32(wu), mode),
              f32(wd), mode)


def experts_part(p, h2, cfg, mode):
    """The held experts' part of the routed sum, every held expert applied
    to every token and weighted by the token's weight for it (0 where the
    token did not choose it), plus the shared expert."""
    lo, _ = held(cfg)
    logits = jnp.matmul(h2, p["router"].astype(jnp.float32), precision=HI)
    idx, w = route(logits, p["bias"].astype(jnp.float32), cfg)

    def one(y, ew):
        e, wg, wu, wd = ew
        gate = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1)   # [T]
        return y + gate[:, None] * swiglu(h2, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h2), (
        jnp.arange(p["eg"].shape[0]), p["eg"], p["eu"], p["ed"]))
    return y + swiglu(h2, p["sg"], p["su"], p["sd"], mode)


def block(p, x, cfg, mode, kind):
    x = attention(p, x, cfg, mode)
    h2 = rms(x, p["ln2"].astype(jnp.float32), cfg["rms_norm_eps"])
    if kind == "dense":
        return x + swiglu(h2, p["wg"], p["wu"], p["wd"], mode)
    return x + experts_part(p, h2, cfg, mode)


def forward_logits(weights, ids, cfg, mode="f32"):
    """Full forward of one sequence [T]: logits [T, V] over the slice of
    the vocabulary held here."""
    x = weights["embed"][ids].astype(jnp.float32)
    for kind in ("dense", "moe"):
        def body(x, p, kind=kind):
            return block(p, x, cfg, mode, kind), None

        x, _ = jax.lax.scan(body, x, weights[kind])
    x = rms(x, weights["fnorm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return mm(x, weights["head"].astype(jnp.float32), mode)


# ----------------------------------------------------- the program's model
def model_config(cfg):
    from paddle_tpu.models.latent_moe import LatentMoEConfig

    return LatentMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        first_k_dense=cfg["first_k_dense_replace"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"], experts_held=held(cfg),
        rope_theta=cfg["rope_theta"], rope_scaling=cfg.get("rope_scaling"),
        max_seq_len=cfg["deployment"]["engine"]["max_seq_len"],
        dtype=cfg["torch_dtype"])


def serving_model(cfg, seed):
    """The stacked model ON the seed's weights (referenced, not copied:
    the weights exist once on the device)."""
    from paddle_tpu.models.latent_moe import LatentMoEForCausalLM

    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's norm epsilon is the constant 1e-6")
    weights = make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    return LatentMoEForCausalLM(model_config(cfg), weights=weights)


def _served_only(*_a, **_k):
    raise NotImplementedError(
        "the mla_moe family is served only: one chip's share of its "
        "experts does not train here (PERF.md section 4)")


class RefTrainer:
    __init__ = _served_only


TRAIN_PARAMS = {}
training_model = load_training_weights = seed_param = _served_only
train_flops_per_token = _served_only


# ----------------------------------------------------------- the arithmetic
def attention_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * qr + qr * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_expected(cfg):
    """(token, held expert) pairs a token a layer, if routing were even."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def matmul_params(cfg):
    """Parameters that multiply a token on this chip, the held experts by
    the pairs an even router would send them; without the head."""
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + nd * 3 * h * cfg["intermediate_size"]
            + nm * (h * cfg["router_experts"]
                    + cfg["n_shared_experts"] * expert_params(cfg)
                    + pairs_expected(cfg) * expert_params(cfg)))


def recorded_pairs():
    """Sum of the ``local_pairs`` the program's ticks recorded since the
    tracer's last reset (the window's start), or None without them."""
    from paddle_tpu.telemetry import trace

    got = [e["attrs"]["local_pairs"] for e in trace.events()
           if e.get("ph") == "X" and e.get("name") in ("decode_tick",
                                                       "prefill_tick")
           and "local_pairs" in (e.get("attrs") or {})]
    return sum(got) if got else None


def serve_flops(cfg, positions):
    """Forward FLOPs THIS CHIP computes for tokens at the given absolute
    positions: attention with its context term (per head d_nope + d_rope
    for the scores and d_v for the values, as the reference computes
    them), dense FFN, router, shared expert, the held experts by the
    pairs the program recorded (a traced run) or the even router's
    expectation, and the sliced head where a position can only be a
    sampled one: at or past the longest prompt's last (max_seq_len -
    max_new_tokens - 1); a shorter prompt's sampled positions go
    uncounted, none is counted twice."""
    n, ctx = len(positions), sum(positions)
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    per_ctx = 2 * nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                        + cfg["v_head_dim"])
    pairs = recorded_pairs()
    if pairs is None:
        pairs = pairs_expected(cfg) * nm * n
    eng = cfg["deployment"]["engine"]
    first_sampled = eng["max_seq_len"] - eng["max_new_tokens"] - 1
    sampled = sum(p >= first_sampled for p in positions)
    return (2 * n * (cfg["num_hidden_layers"] * attention_params(cfg)
                     + nd * 3 * h * cfg["intermediate_size"]
                     + nm * (h * cfg["router_experts"]
                             + cfg["n_shared_experts"] * expert_params(cfg)))
            + cfg["num_hidden_layers"] * per_ctx * ctx
            + 2 * pairs * expert_params(cfg)
            + 2 * sampled * h * cfg["vocab_size"])


def cache_bytes_per_token(cfg):
    """One latent row (c_kv and the shared roped key, 2 bytes each) of one
    token in every layer: what the algorithm must keep and move."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2)


def mla_paged_attention_work(cfg, batch, seq):
    """One absorbed decode call over ``batch`` rows of ``seq`` cached
    tokens: (flops, bytes). Every head scores the 576-value row and sums
    its 512 latent values; the row is read once for all heads; queries in
    and latent outputs out."""
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    row = r + cfg["qk_rope_head_dim"]
    flops = 2 * batch * seq * nh * (row + r)
    nbytes = 2 * batch * (seq * row + nh * (row + r))
    return flops, nbytes


KERNEL_WORK = {"mla_paged_attention": mla_paged_attention_work}
