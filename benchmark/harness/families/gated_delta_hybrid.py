"""The gated-delta hybrid decoder family (Olmo-Hybrid and its kin): a
repeating period of linear-attention layers (Gated DeltaNet, Yang, Kautz,
Hatamizadeh, arXiv:2412.06464, with short convolutions and an output
gate) and full-attention layers, as ONE PIPELINE STAGE: the first
``num_hidden_layers`` entries of ``layer_types``, every width whole.

What the harness knows of this shape is here: its plain reference in
straightforward jax.numpy (float32, matmuls at HIGHEST, no cache, no
kernel, the recurrence a ``lax.scan`` over positions exactly as the
equation reads, attention a full causal softmax), how the program's model
is built on the seed's weights, and the arithmetic. ``make_weights`` and
``forward_logits`` import nothing of paddle_tpu. The family is served
only: the training names exist and raise.

A linear layer, on x [T, h] (H heads of d_k keys and d_v values):
  q = silu(conv(x W_q)), k = silu(conv(x W_k)), v = silu(conv(x W_v))
      conv: causal, depthwise, ``linear_conv_kernel_dim`` wide, no bias
  q^ = q / |q|_2 d_k^-1/2,  k^ = k / |k|_2          (per head)
  beta = 2 sigmoid(x W_b)   (the 2: linear_allow_neg_eigval)
  alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))      float32
  S_t = alpha_t S_{t-1} + beta_t k^_t (v_t - alpha_t S_{t-1}^T k^_t)^T
  o_t = S_t^T q^_t                       S in R^{d_k x d_v}, from zero
  y = [rms_head(o) * silu(x W_g)] W_o    rms_head: over a head's d_v
A full layer: q = rms(x W_q), k = rms(x W_k) over the whole projection,
  v = x W_v; causal softmax at d^-1/2, NO rotary embedding; W_o.
The block (the family's convention): x += rms(mixer(x)); x += rms(swiglu(x)).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import HI, _leaf_values, mm, rms, seed_key

L2_EPS = 1e-6       # under the root of q's and k's norms


# ------------------------------------------------------------------ shapes
def layer_types(cfg):
    """The stage's layers: the first ``num_hidden_layers`` of the list."""
    types = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    if len(types) < cfg["num_hidden_layers"]:
        raise ValueError("layer_types is shorter than num_hidden_layers")
    bad = set(types) - {"linear_attention", "full_attention"}
    if bad:
        raise ValueError(f"unknown layer types {sorted(bad)}")
    return types


def period(cfg):
    """(the shortest pattern the stage repeats, how often)."""
    types = layer_types(cfg)
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return types[:p], len(types) // p


def dims(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    lh = cfg["linear_num_value_heads"]
    assert lh == cfg["linear_num_key_heads"], "one key head a value head"
    return dict(h=h, nh=nh, hkv=cfg["num_key_value_heads"], hd=h // nh,
                lh=lh, dk=cfg["linear_key_head_dim"],
                dv=cfg["linear_value_head_dim"],
                kw=cfg["linear_conv_kernel_dim"],
                m=cfg["intermediate_size"], v=cfg["vocab_size"])


def leaf_shapes(cfg):
    """group -> leaf -> shape; a layer leaf is stacked [periods, layers
    of its kind in a period, ...]."""
    d = dims(cfg)
    h, m = d["h"], d["m"]
    ffn = {"ln2": (h,), "fg": (h, m), "fu": (h, m), "fd": (m, h)}
    lin = dict(ffn, ln1=(h,), wq=(h, d["lh"] * d["dk"]),
               wk=(h, d["lh"] * d["dk"]), wv=(h, d["lh"] * d["dv"]),
               wg=(h, d["lh"] * d["dv"]), wo=(d["lh"] * d["dv"], h),
               wa=(h, d["lh"]), wb=(h, d["lh"]), a_log=(d["lh"],),
               dt_bias=(d["lh"],), cq=(d["kw"], d["lh"] * d["dk"]),
               ck=(d["kw"], d["lh"] * d["dk"]),
               cv=(d["kw"], d["lh"] * d["dv"]), onorm=(d["dv"],))
    full = dict(ffn, ln1=(h,), wq=(h, d["nh"] * d["hd"]),
                wk=(h, d["hkv"] * d["hd"]), wv=(h, d["hkv"] * d["hd"]),
                wo=(d["nh"] * d["hd"], h), qn=(d["nh"] * d["hd"],),
                kn=(d["hkv"] * d["hd"],))
    pat, n = period(cfg)
    nl, nf = pat.count("linear_attention"), pat.count("full_attention")
    return {"lin": {k: (n, nl) + s for k, s in lin.items()},
            "full": {k: (n, nf) + s for k, s in full.items()},
            "top": {"embed": (d["v"], h), "fnorm": (h,),
                    "head": (h, d["v"])}}


# ----------------------------------------------------------------- weights
def _draw(key, shape, dtype, kind):
    """One leaf on the device, its leading slices drawn one after another
    (each from its own key), so the float32 draw is one slice's."""
    lead = 0 if kind == "top" else 2
    n = int(np.prod(shape[:lead]))

    def one(i):
        k = jax.random.fold_in(key, i)
        if kind == "a_log":      # the published layer's: log U(0, 16)
            return jnp.log(jax.random.uniform(
                k, shape[lead:], jnp.float32, 1e-3, 16.0))
        if kind == "dt_bias":    # softplus^-1 of exp U(log .001, log .1)
            dt = jnp.exp(jax.random.uniform(
                k, shape[lead:], jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if kind == "taps":       # a depthwise Conv1d's: U(+-K^-1/2)
            bound = shape[lead] ** -0.5
            return jax.random.uniform(k, shape[lead:], jnp.float32, -bound,
                                      bound).astype(dtype)
        return _leaf_values(k, shape[lead:]).astype(dtype)

    return jax.lax.map(one, jnp.arange(n)).reshape(shape)


_make_leaf = jax.jit(_draw, static_argnames=("shape", "dtype", "kind"))


def make_weights(cfg, seed, dtype):
    """{"lin": {leaf: [periods, n, ...]}, "full": {...}, "embed", "fnorm",
    "head"} on the device, a jitted call a leaf. Matrices are normal at
    the harness's INIT_STD, norm gains near one on the bf16 grid; what
    the published layer initialises in its own way is drawn that way:
    ``a_log`` and ``dt_bias`` (float32), so that a decay is neither 0 nor
    1, and the convolutions' taps, so that q, k and v leave silu's
    linear stretch."""
    key = seed_key(seed)
    shapes = leaf_shapes(cfg)
    out = {"lin": {}, "full": {}}
    for gi, group in enumerate(("lin", "full", "top")):
        for li, (leaf, shape) in enumerate(sorted(shapes[group].items())):
            k = jax.random.fold_in(jax.random.fold_in(key, gi), li)
            f32 = leaf in ("a_log", "dt_bias")
            kind = (leaf if f32 else
                    "taps" if leaf in ("cq", "ck", "cv") else group)
            arr = _make_leaf(k, shape, jnp.float32 if f32
                             else jnp.dtype(dtype), kind)
            (out if group == "top" else out[group])[leaf] = arr
    return out


# ---------------------------------------------------------------- reference
def _f32(a):
    return a.astype(jnp.float32)


def causal_conv(x, taps):
    """y_t = sum_j taps[j] x_{t - (K-1) + j} on x [T, C], zeros before
    the sequence; taps [K, C]."""
    kw = taps.shape[0]
    xp = jnp.pad(x, ((kw - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * taps[j] for j in range(kw))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def swiglu(p, x, mode):
    return mm(jax.nn.silu(mm(x, _f32(p["fg"]), mode))
              * mm(x, _f32(p["fu"]), mode), _f32(p["fd"]), mode)


def delta_mixer(p, x, cfg, mode):
    """A Gated DeltaNet layer's mixer on x [T, h]: the recurrence a scan
    over positions, exactly as the equation reads. In a lower-precision
    control ("bf16", "int8") the state is kept in bfloat16, the nearest
    precision below the float32 the configuration states for it."""
    d = dims(cfg)
    t, lh, dk, dv = x.shape[0], d["lh"], d["dk"], d["dv"]
    act = lambda w, c: jax.nn.silu(causal_conv(mm(x, _f32(p[w]), mode),
                                               _f32(p[c])))
    q = l2norm(act("wq", "cq").reshape(t, lh, dk)) * dk ** -0.5
    k = l2norm(act("wk", "ck").reshape(t, lh, dk))
    v = act("wv", "cv").reshape(t, lh, dv)
    beta = 2.0 * jax.nn.sigmoid(mm(x, _f32(p["wb"]), mode))       # [T, H]
    alpha = jnp.exp(-jnp.exp(p["a_log"]) * jax.nn.softplus(
        mm(x, _f32(p["wa"]), mode) + p["dt_bias"]))               # [T, H]
    keep = ((lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
            if mode != "f32" else (lambda s: s))

    def step(s, at):                       # s [H, dk, dv]
        qt, kt, vt, a, b = at
        s = a[:, None, None] * s
        u = vt - jnp.einsum("hkv,hk->hv", s, kt, precision=HI)
        s = keep(s + b[:, None, None] * kt[:, :, None] * u[:, None, :])
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((lh, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    o = rms(o, _f32(p["onorm"]), cfg["rms_norm_eps"])             # per head
    gate = jax.nn.silu(mm(x, _f32(p["wg"]), mode)).reshape(t, lh, dv)
    return mm((o * gate).reshape(t, lh * dv), _f32(p["wo"]), mode)


def attention_mixer(p, x, cfg, mode):
    """A full layer's mixer: q and k normed over the whole projection, a
    causal softmax over every earlier position, no rotary embedding."""
    d = dims(cfg)
    t, nh, hkv, hd = x.shape[0], d["nh"], d["hkv"], d["hd"]
    eps = cfg["rms_norm_eps"]
    q = rms(mm(x, _f32(p["wq"]), mode), _f32(p["qn"]), eps)
    k = rms(mm(x, _f32(p["wk"]), mode), _f32(p["kn"]), eps)
    v = mm(x, _f32(p["wv"]), mode)
    q = q.reshape(t, nh, hd)
    k = jnp.repeat(k.reshape(t, hkv, hd), nh // hkv, 1)
    v = jnp.repeat(v.reshape(t, hkv, hd), nh // hkv, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    return mm(o.reshape(t, nh * hd), _f32(p["wo"]), mode)


def block(p, x, cfg, mode, kind):
    eps = cfg["rms_norm_eps"]
    mixer = delta_mixer if kind == "linear_attention" else attention_mixer
    x = x + rms(mixer(p, x, cfg, mode), _f32(p["ln1"]), eps)
    return x + rms(swiglu(p, x, mode), _f32(p["ln2"]), eps)


def forward_logits(weights, ids, cfg, mode="f32"):
    """Full forward of one sequence [T]: logits [T, V]."""
    pat, _ = period(cfg)
    x = _f32(weights["embed"][ids])

    def one_period(x, p):
        at = {"lin": 0, "full": 0}
        for kind in pat:
            g = "lin" if kind == "linear_attention" else "full"
            layer = jax.tree_util.tree_map(lambda a: a[at[g]], p[g])
            x = block(layer, x, cfg, mode, kind)
            at[g] += 1
        return x, None

    x, _ = jax.lax.scan(one_period, x, {"lin": weights["lin"],
                                        "full": weights["full"]})
    x = rms(x, _f32(weights["fnorm"]), cfg["rms_norm_eps"])
    return mm(x, _f32(weights["head"]), mode)


# ----------------------------------------------------- the program's model
def model_config(cfg):
    from paddle_tpu.models.gated_delta_hybrid import GatedDeltaHybridConfig

    d = dims(cfg)
    return GatedDeltaHybridConfig(
        vocab_size=d["v"], hidden_size=d["h"], intermediate_size=d["m"],
        layer_types=layer_types(cfg), num_heads=d["nh"],
        num_kv_heads=d["hkv"], linear_num_heads=d["lh"],
        linear_key_head_dim=d["dk"], linear_value_head_dim=d["dv"],
        linear_conv_kernel_dim=d["kw"],
        max_seq_len=cfg["deployment"]["engine"]["max_seq_len"],
        dtype=cfg["torch_dtype"])


def serving_model(cfg, seed):
    """The stacked model ON the seed's weights (referenced, not copied:
    the weights exist once on the device)."""
    from paddle_tpu.models.gated_delta_hybrid import (
        GatedDeltaHybridForCausalLM)

    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's norm epsilon is the constant 1e-6")
    if cfg.get("rope_parameters", {}).get("rope_theta") is not None:
        raise ValueError("the family's full layers carry no rotary "
                         "embedding (rope_theta null)")
    weights = make_weights(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    return GatedDeltaHybridForCausalLM(model_config(cfg), weights=weights)


def _served_only(*_a, **_k):
    raise NotImplementedError(
        "the gated_delta_hybrid family is served only: a stage of the "
        "model does not train on one chip (PERF.md section 4)")


class RefTrainer:
    __init__ = _served_only


TRAIN_PARAMS = {}
training_model = load_training_weights = seed_param = _served_only
train_flops_per_token = _served_only


# ----------------------------------------------------------- the arithmetic
def _counts(cfg):
    types = layer_types(cfg)
    return types.count("linear_attention"), types.count("full_attention")


def layer_matmul_params(cfg):
    """(a linear layer's, a full layer's) parameters that multiply a
    token: the projections and the feed-forward, not norms or taps."""
    d = dims(cfg)
    h, ffn = d["h"], 3 * d["h"] * d["m"]
    lin = (2 * h * d["lh"] * d["dk"] + 3 * h * d["lh"] * d["dv"]
           + 2 * h * d["lh"] + ffn)
    full = 2 * h * d["nh"] * d["hd"] + 2 * h * d["hkv"] * d["hd"] + ffn
    return lin, full


def matmul_params(cfg):
    """Parameters that multiply a token on this chip, without the head."""
    (nl, nf), (lin, full) = _counts(cfg), layer_matmul_params(cfg)
    return nl * lin + nf * full


def state_step_flops(cfg):
    """One position of one linear layer's recurrence: alpha S, S^T k, the
    rank-one update and S^T q over H heads of d_k x d_v (6 a cell)."""
    d = dims(cfg)
    return 6 * d["lh"] * d["dk"] * d["dv"]


def serve_flops(cfg, positions):
    """Forward FLOPs for tokens at the given absolute positions: 2 x the
    layers' matmul parameters a position, the recurrence of every linear
    layer, the full layers' scores and values against the context (4 x
    heads x head size a cached position), and the head where a position
    can only be a sampled one (at or past the longest prompt's last,
    max_seq_len - max_new_tokens - 1)."""
    d = dims(cfg)
    n, ctx = len(positions), sum(positions)
    nl, nf = _counts(cfg)
    eng = cfg["deployment"]["engine"]
    first_sampled = eng["max_seq_len"] - eng["max_new_tokens"] - 1
    sampled = sum(p >= first_sampled for p in positions)
    return (2 * n * matmul_params(cfg) + n * nl * state_step_flops(cfg)
            + nf * 4 * d["nh"] * d["hd"] * ctx
            + 2 * sampled * d["h"] * d["v"])


def cache_bytes_per_token(cfg):
    """K and V (2 bytes a value) of one token in every FULL layer: what
    grows with the length. The linear layers keep ``state_bytes_per_row``
    whatever the length."""
    d = dims(cfg)
    return _counts(cfg)[1] * 2 * d["hkv"] * d["hd"] * 2


def state_bytes_per_row(cfg):
    """The float32 recurrent state of one request over every linear
    layer: what a decode tick reads and writes once each."""
    d = dims(cfg)
    return _counts(cfg)[0] * d["lh"] * d["dk"] * d["dv"] * 4


def gdn_decode_step_work(cfg, batch, seq):
    """One call (one layer, ``batch`` rows): (flops, bytes). The state is
    read and written once; q, k, v and o are 1/96 of it and not counted."""
    d = dims(cfg)
    return (batch * state_step_flops(cfg),
            2 * batch * d["lh"] * d["dk"] * d["dv"] * 4)


KERNEL_WORK = {"gdn_decode_step": gdn_decode_step_work}
