"""The dense decoder family: rmsnorm + swiglu + rope, MHA or GQA, tied or
untied head (InternLM2 and its kin). What the harness knows of
this shape is here and nowhere else: its plain reference in straightforward
jax.numpy (float32, matmuls at HIGHEST: forward, loss, gradients and the
AdamW step, on weights made from the seed alone; it imports nothing of
paddle_tpu), how the program's models are built and given those weights,
and the arithmetic of its FLOPs, its cache and its kernels' work.

Storage types are the configuration's (bf16 parameters and first moment,
no master copy), so a parameter is rounded to its storage type once per
step exactly as the configuration states; all arithmetic between is f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..reference import HI, _f32, _leaf_values, mm, rms, rope, seed_key

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


# ------------------------------------------------------------------ weights
def leaf_shapes(cfg):
    """name -> shape of every leaf, layer leaves stacked [L, ...]."""
    v, h, n = cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = h // cfg["num_attention_heads"]
    kv, m = cfg["num_key_value_heads"] * hd, cfg["intermediate_size"]
    shapes = {"embed": (v, h), "ln1": (n, h), "wq": (n, h, h),
              "wk": (n, h, kv), "wv": (n, h, kv), "wo": (n, h, h),
              "ln2": (n, h), "wg": (n, h, m), "wu": (n, h, m),
              "wd": (n, m, h), "fnorm": (h,)}
    if not cfg["tie_word_embeddings"]:
        shapes["head"] = (v, h)
    return shapes


def make_leaf(key, name, shape, dtype, layer=None):
    """One leaf from the seed key. Layer leaves draw each layer from its
    own key, so ``layer=l`` gives exactly row l of the stacked leaf."""
    k = jax.random.fold_in(key, sorted(
        LAYER_LEAVES + ("embed", "fnorm", "head")).index(name))
    if name not in LAYER_LEAVES:
        return _leaf_values(k, shape).astype(dtype)
    if layer is not None:
        return _leaf_values(jax.random.fold_in(k, layer),
                            shape[1:]).astype(dtype)
    return jax.vmap(lambda l: _leaf_values(jax.random.fold_in(k, l),
                                           shape[1:]))(
        jnp.arange(shape[0])).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make_weights(key, shapes, dtype):
    return {n: make_leaf(key, n, s, dtype) for n, s in shapes}


def make_weights(cfg, seed, dtype):
    """Every leaf, stacked, on the device in one jitted call."""
    return _make_weights(seed_key(seed),
                         tuple(sorted(leaf_shapes(cfg).items())), dtype)


# ---------------------------------------------------------------- reference
def block(p, x, cfg, mode):
    """One decoder layer on f32 arrays; p holds the nine layer leaves."""
    b, s, h = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    h1 = rms(x, p["ln1"], eps)
    q = rope(mm(h1, p["wq"], mode).reshape(b, s, nh, hd), base)
    k = rope(mm(h1, p["wk"], mode).reshape(b, s, nkv, hd), base)
    v = mm(h1, p["wv"], mode).reshape(b, s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(b, s, h)
    x = x + mm(o, p["wo"], mode)
    h2 = rms(x, p["ln2"], eps)
    ffn = jax.nn.silu(mm(h2, p["wg"], mode)) * mm(h2, p["wu"], mode)
    return x + mm(ffn, p["wd"], mode)


def head_loss(x, fnorm, head, labels, cfg, mode):
    """Mean next-token cross entropy of final-normed x against labels."""
    logits = mm(rms(x, fnorm, cfg["rms_norm_eps"]), head.T, mode)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


# ------------------------------------------------------------ training step
NORM_LEAVES = ("ln1", "ln2")  # [L, h]: the optimizer sees them stacked


class RefTrainer:
    """Follows the program's first steps: state in its storage types on
    the device, each layer's forward, backward and update run on their own
    so that one layer's float32 temporaries are all that is live. ``opt``
    states the optimizer: lr, beta1, beta2, eps, weight_decay, and
    factored (rank-1 second moment over the last two dims of each leaf as
    it is stored, in f32; the [L, h] norm gains are such a leaf) or plain
    (second moment in the storage type). Layer l's state and work live on
    local device l mod n, so four chips' memory holds what one cannot."""

    def __init__(self, cfg, seed, opt, store_dtype, mode="f32"):
        self.cfg, self.opt, self.mode = cfg, opt, mode
        self.dt = jnp.dtype(store_dtype)
        self.key = seed_key(seed)
        self.shapes = leaf_shapes(cfg)
        self.tied = "head" not in self.shapes
        self.mats = tuple(k for k in LAYER_LEAVES if k not in NORM_LEAVES)
        devs = jax.local_devices()
        self.dev = [devs[l % len(devs)]
                    for l in range(cfg["num_hidden_layers"])]
        self.layers = [
            jax.device_put({k: self._with_slots(self._seed_leaf(k, l))
                            for k in self.mats}, d)
            for l, d in enumerate(self.dev)]
        self.top = {k: self._with_slots(self._seed_leaf(k))
                    for k in self.shapes if k not in self.mats}
        self.t = 0
        self._fwd = jax.jit(lambda p, x: block(_f32(p), x, cfg, mode))
        self._bwd = jax.jit(self._block_bwd)
        self._upd = jax.jit(self._update, donate_argnums=(0,))
        self._head = jax.jit(self._head_grads)

    def _seed_leaf(self, k, layer=None):
        return make_leaf(self.key, k, self.shapes[k], self.dt, layer)

    # state of a leaf: {"p", "m", and "v" or ("vr", "vc")}
    def _with_slots(self, p):
        st = {"p": p, "m": jnp.zeros(p.shape, self.dt)}
        if self.opt["factored"] and p.ndim >= 2:
            st["vr"] = jnp.zeros(p.shape[:-1], jnp.float32)
            st["vc"] = jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
        else:
            st["v"] = jnp.zeros(p.shape, self.dt)
        return st

    def _layer_params(self, l):
        p = {k: self.layers[l][k]["p"] for k in self.mats}
        p.update(jax.device_put({k: self.top[k]["p"][l]
                                 for k in NORM_LEAVES}, self.dev[l]))
        return p

    def _block_bwd(self, p, x, dy):
        _, vjp = jax.vjp(lambda pp, xx: block(pp, xx, self.cfg, self.mode),
                         _f32(p), x)
        gp, dx = vjp(dy)
        return dx, gp

    def _head_grads(self, x, fnorm, head, labels):
        f = lambda xx, fn, hd: head_loss(xx, fn, hd, labels, self.cfg,
                                         self.mode)
        loss, (dx, gfn, ghd) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            x, fnorm.astype(jnp.float32), head.astype(jnp.float32))
        return loss, dx, gfn, ghd

    def _update(self, st, g, t):
        """AdamW on one leaf: decoupled decay, bias-corrected moments,
        the result rounded once to the storage type."""
        o = self.opt
        b1, b2 = o["beta1"], o["beta2"]
        p = st["p"].astype(jnp.float32)
        m = b1 * st["m"].astype(jnp.float32) + (1 - b1) * g
        new = {"m": m.astype(self.dt)}
        if "vr" in st:
            g2 = g * g
            new["vr"] = vr = b2 * st["vr"] + (1 - b2) * jnp.mean(g2, -1)
            new["vc"] = vc = b2 * st["vc"] + (1 - b2) * jnp.mean(g2, -2)
            rmean = jnp.maximum(jnp.mean(vr, -1, keepdims=True), 1e-30)
            v = vr[..., :, None] * vc[..., None, :] / rmean[..., None]
        else:
            v = b2 * st["v"].astype(jnp.float32) + (1 - b2) * g * g
            new["v"] = v.astype(self.dt)
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + o["eps"])
        new["p"] = (p * (1 - o["lr"] * o["weight_decay"])
                    - o["lr"] * upd).astype(self.dt)
        return new, jnp.sum(g * g)

    def step(self, ids, labels):
        """One step on int arrays [B, S]; returns (loss, per-leaf squared
        gradient norms)."""
        self.t += 1
        n = len(self.layers)
        embed = self.top["embed"]["p"]
        head = embed if self.tied else self.top["head"]["p"]
        x = embed[ids].astype(jnp.float32)
        xs = []
        for l in range(n):
            x = jax.device_put(x, self.dev[l])
            xs.append(x)
            x = self._fwd(self._layer_params(l), x)
        top_dev = jax.local_devices()[0]
        loss, dx, g_fnorm, g_head = self._head(
            jax.device_put(x, top_dev), self.top["fnorm"]["p"], head, labels)
        sumsq = {k: 0.0 for k in self.shapes}
        g_norms = {k: [None] * n for k in NORM_LEAVES}
        for l in reversed(range(n)):
            dx, gp = self._bwd(self._layer_params(l), xs.pop(),
                               jax.device_put(dx, self.dev[l]))
            for k in NORM_LEAVES:
                g_norms[k][l] = jax.device_put(gp[k], top_dev)
            for k in self.mats:
                self.layers[l][k], s = self._upd(self.layers[l][k], gp[k],
                                                 self.t)
                sumsq[k] += float(s)
        g_top = {k: jnp.stack(v) for k, v in g_norms.items()}
        g_top["fnorm"] = g_fnorm
        g_top["embed"] = jnp.zeros(embed.shape, jnp.float32).at[ids].add(
            jax.device_put(dx, top_dev))
        if self.tied:
            g_top["embed"] += g_head
        else:
            g_top["head"] = g_head
        for k in self.top:
            self.top[k], sumsq[k] = self._upd(self.top[k], g_top[k], self.t)
        return float(loss), {k: float(v) for k, v in sumsq.items()}

    def change_sumsq(self):
        """Per-leaf squared norm of (parameters now - parameters at the
        seed), over the stacked leaf."""
        d = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        out = {k: float(d(st["p"], self._seed_leaf(k)))
               for k, st in self.top.items()}
        for k in self.mats:
            out[k] = sum(float(d(st[k]["p"], jax.device_put(
                self._seed_leaf(k, l), self.dev[l])))
                for l, st in enumerate(self.layers))
        return out


# ------------------------------------------------------------------ serving
def forward_logits(weights, ids, cfg, mode="f32"):
    """Full forward of one sequence [T] over the stacked weights: logits
    [T, V], each position's prediction of the next token."""
    x = weights["embed"][ids][None].astype(jnp.float32)
    n = cfg["num_hidden_layers"]

    def body(x, p):
        return block(_f32(p), x, cfg, mode), None

    x, _ = jax.lax.scan(body, x, {k: weights[k] for k in LAYER_LEAVES},
                        length=n)
    head = weights["embed"] if "head" not in weights else weights["head"]
    x = rms(x[0], weights["fnorm"].astype(jnp.float32),
            cfg["rms_norm_eps"])
    return mm(x, head.astype(jnp.float32).T, mode)


# ----------------------------------------------------- the program's models
def serving_model(cfg, seed):
    """The decoder as a fleet worker builds it, the seed's weights in it."""
    from tools.serve_bench import build_decoder

    dtype = jnp.dtype(cfg["torch_dtype"])
    cfg_kw = dict(vocab_size=cfg["vocab_size"],
                  hidden_size=cfg["hidden_size"],
                  num_layers=cfg["num_hidden_layers"],
                  num_heads=cfg["num_attention_heads"],
                  num_kv_heads=cfg["num_key_value_heads"],
                  intermediate_size=cfg["intermediate_size"],
                  max_seq_len=cfg["deployment"]["engine"]["max_seq_len"],
                  dropout=0.0, tie_embeddings=cfg["tie_word_embeddings"])
    model = build_decoder(cfg_kw, seed=0, bf16=dtype == jnp.bfloat16)
    load_weights(model, make_weights(cfg, seed, dtype))
    return model


def _put(params, name, arr):
    if tuple(params[name]._data.shape) != tuple(arr.shape):
        raise RuntimeError(f"{name}: {params[name]._data.shape} != "
                           f"{arr.shape}")
    params[name]._data = arr


def load_weights(model, w):
    """Canonical stacked leaves into LlamaForCausalLM's per-layer
    parameters (nn.Linear holds [in, out], as the leaves do)."""
    from paddle_tpu.models.gpt import _BLOCK_PARAM_FIELDS

    params = dict(model.named_parameters())
    _put(params, "model.embed_tokens.weight", w["embed"])
    _put(params, "model.final_norm.weight", w["fnorm"])
    if "head" in w:
        _put(params, "lm_head.weight", w["head"].T)
    for leaf, suffix in _BLOCK_PARAM_FIELDS:
        for l in range(w[leaf].shape[0]):
            _put(params, f"model.layers.{l}.{suffix}", w[leaf][l])


#: reading name (a canonical leaf) -> parameter name of GPTForCausalLMPipe
TRAIN_PARAMS = {"embed": "embed_tokens.weight", "fnorm": "final_norm.weight",
                **{k: f"decoder.{k}" for k in LAYER_LEAVES}}


def training_model(cfg, mix):
    """The trainer's stacked layer-scan model at the mix's sequence
    length, before any seed's weights."""
    import bench
    from paddle_tpu.models.gpt import GPTConfig

    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=mix["seq"], dropout=0.0, dtype=cfg["torch_dtype"],
        recompute=True, tie_embeddings=cfg["tie_word_embeddings"])
    return bench.build_model(
        gcfg, bf16=jnp.dtype(cfg["torch_dtype"]) == jnp.bfloat16)


def load_training_weights(model, weights):
    """The stacked leaves are the stacked model's parameters as they are."""
    params = dict(model.named_parameters())
    for leaf, name in TRAIN_PARAMS.items():
        _put(params, name, weights.pop(leaf))


def seed_param(cfg, key, name, dtype):
    """What the parameter that a reading names held at the seed."""
    return make_leaf(key, name, leaf_shapes(cfg)[name], dtype)


# ----------------------------------------------------------- the arithmetic
def matmul_params(cfg):
    """Parameters that multiply every token: the projections of every
    layer and the head (the embedding lookup multiplies nothing)."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * m
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * h


def train_flops_per_token(cfg, seq):
    """6 N for the matmuls forward and backward, plus causal attention
    (two matmuls forward, four backward, over half the square): 6 L h S.
    Recomputed operations do not count."""
    return (6 * matmul_params(cfg)
            + 6 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def serve_flops(cfg, positions):
    """Forward FLOPs of tokens processed at the given absolute positions
    (prefill or decode alike): 2 N each, plus attention over the context
    before it, 4 L h per position of context."""
    n, ctx = len(positions), sum(positions)
    return (2 * matmul_params(cfg) * n
            + 4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * ctx)


def cache_bytes_per_token(cfg):
    """K and V rows (2 bytes each) of one token in every layer."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd * 2 * 2


# ------------------------------------------------- kernel work, per call
def flash_fwd_work(cfg, batch, seq):
    """Causal flash forward of one layer: (flops, bytes). QK^T and PV over
    half the square; reads q, k, v and writes o (bf16) and the lse (f32)."""
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    flops = 2 * batch * seq * seq * h
    nbytes = 2 * batch * seq * (2 * h + 2 * kv) \
        + 4 * batch * seq * cfg["num_attention_heads"]
    return flops, nbytes


def flash_bwd_work(cfg, batch, seq):
    """Causal flash backward: five matmuls (scores again, dv, dp, dq, dk)
    over half the square; reads q, k, v, o, do, lse and writes dq, dk, dv."""
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    flops = 5 * batch * seq * seq * h
    nbytes = 2 * batch * seq * (4 * h + 4 * kv) \
        + 4 * batch * seq * cfg["num_attention_heads"]
    return flops, nbytes


KERNEL_WORK = {"flash_fwd": flash_fwd_work, "flash_bwd": flash_bwd_work}
