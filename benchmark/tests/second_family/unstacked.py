"""A second family, which exists only for the tests: what a model_config
PR adds beside its configuration, here outside benchmark/harness/ so that
the tests can show that no file there has to change. It wraps the same
program models as ``dense_gqa`` and is deliberately unlike it wherever the
harness must not look: its weights are a nested tree with one dict a
layer (nothing stacked), its leaves have other names and draw from other
keys, and its reference trainer differentiates the whole model at once.
Test sizes only: nothing here is blocked to fit a chip.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

from ..reference import HI, _f32, _leaf_values, mm, rms, rope, seed_key

#: a layer's leaf -> (the trainer's stacked field, the decoder's parameter)
LAYER = {"norm_a": ("ln1", "input_norm.weight"),
         "q": ("wq", "attn.q_proj.weight"), "k": ("wk", "attn.k_proj.weight"),
         "v": ("wv", "attn.v_proj.weight"), "o": ("wo", "attn.o_proj.weight"),
         "norm_f": ("ln2", "post_attn_norm.weight"),
         "gate": ("wg", "mlp.gate_proj.weight"),
         "up": ("wu", "mlp.up_proj.weight"),
         "down": ("wd", "mlp.down_proj.weight")}


# ------------------------------------------------------------------ weights
def _shapes(cfg):
    """(top leaves, one layer's leaves): name -> shape."""
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    m = cfg["intermediate_size"]
    top = {"tok": (v, h), "norm": (h,)}
    if not cfg["tie_word_embeddings"]:
        top["out"] = (v, h)
    layer = {"norm_a": (h,), "q": (h, h), "k": (h, kv), "v": (h, kv),
             "o": (h, h), "norm_f": (h,), "gate": (h, m), "up": (h, m),
             "down": (m, h)}
    return top, layer


def _draw(key, path, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return _leaf_values(k, shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _make_weights(key, cfg_items, dtype):
    cfg = dict(cfg_items)
    top, layer = _shapes(cfg)
    w = {n: _draw(key, n, s, dtype) for n, s in top.items()}
    w["layers"] = [{n: _draw(key, f"{l}/{n}", s, dtype)
                    for n, s in layer.items()}
                   for l in range(cfg["num_hidden_layers"])]
    return w


_SHAPE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "num_key_value_heads",
               "intermediate_size", "tie_word_embeddings")


def make_weights(cfg, seed, dtype):
    return _make_weights(seed_key(seed),
                         tuple((k, cfg[k]) for k in _SHAPE_KEYS), dtype)


# ---------------------------------------------------------------- reference
def _block(p, x, cfg, mode):
    b, s, h = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    eps, base = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = rms(x, p["norm_a"], eps)
    q = rope(mm(a, p["q"], mode).reshape(b, s, nh, hd), base)
    k = rope(mm(a, p["k"], mode).reshape(b, s, nkv, hd), base)
    v = mm(a, p["v"], mode).reshape(b, s, nkv, hd)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                     precision=HI).reshape(b, s, h)
    x = x + mm(att, p["o"], mode)
    f = rms(x, p["norm_f"], eps)
    return x + mm(jax.nn.silu(mm(f, p["gate"], mode)) * mm(f, p["up"], mode),
                  p["down"], mode)


def _logits(w, ids, cfg, mode):
    """[B, S] ids -> [B, S, V] over an f32 tree."""
    x = w["tok"][ids]
    for p in w["layers"]:
        x = _block(p, x, cfg, mode)
    return mm(rms(x, w["norm"], cfg["rms_norm_eps"]),
              w.get("out", w["tok"]).T, mode)


def forward_logits(weights, ids, cfg, mode="f32"):
    return _logits(_f32(weights), ids[None], cfg, mode)[0]


def _to_params(w):
    """The tree in the optimizer's layout, which is the trainer's: layer
    leaves stacked, under this family's names."""
    out = {k: v for k, v in w.items() if k != "layers"}
    out.update({n: jnp.stack([p[n] for p in w["layers"]]) for n in LAYER})
    return out


def _to_tree(params):
    n = params["q"].shape[0]
    w = {k: v for k, v in params.items() if k not in LAYER}
    w["layers"] = [{k: params[k][l] for k in LAYER} for l in range(n)]
    return w


class RefTrainer:
    """AdamW as the configuration states it (``factored``: rank-1 second
    moment over the last two dims of each parameter as the trainer stores
    it) around one value_and_grad of the whole model."""

    def __init__(self, cfg, seed, opt, store_dtype, mode="f32"):
        self.opt, self.dt = opt, jnp.dtype(store_dtype)
        self.p0 = _to_params(make_weights(cfg, seed, self.dt))
        self.state = {k: self._slots(p) for k, p in self.p0.items()}
        self.t = 0

        def loss(params, ids, labels):
            lg = _logits(_to_tree(params), ids, cfg, mode)
            gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
            return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)

        self._grad = jax.jit(jax.value_and_grad(loss))

    def _slots(self, p):
        st = {"p": p, "m": jnp.zeros(p.shape, self.dt)}
        if self.opt["factored"] and p.ndim >= 2:
            st["vr"] = jnp.zeros(p.shape[:-1], jnp.float32)
            st["vc"] = jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
        else:
            st["v"] = jnp.zeros(p.shape, self.dt)
        return st

    def _update(self, st, g):
        o, t = self.opt, self.t
        b1, b2 = o["beta1"], o["beta2"]
        m = b1 * st["m"].astype(jnp.float32) + (1 - b1) * g
        new = {"m": m.astype(self.dt)}
        if "vr" in st:
            new["vr"] = vr = b2 * st["vr"] + (1 - b2) * jnp.mean(g * g, -1)
            new["vc"] = vc = b2 * st["vc"] + (1 - b2) * jnp.mean(g * g, -2)
            rmean = jnp.maximum(jnp.mean(vr, -1, keepdims=True), 1e-30)
            v = vr[..., :, None] * vc[..., None, :] / rmean[..., None]
        else:
            v = b2 * st["v"].astype(jnp.float32) + (1 - b2) * g * g
            new["v"] = v.astype(self.dt)
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + o["eps"])
        new["p"] = (st["p"].astype(jnp.float32)
                    * (1 - o["lr"] * o["weight_decay"])
                    - o["lr"] * upd).astype(self.dt)
        return new

    def step(self, ids, labels):
        self.t += 1
        loss, g = self._grad(_f32({k: st["p"] for k, st in
                                   self.state.items()}), ids, labels)
        self.state = {k: self._update(st, g[k])
                      for k, st in self.state.items()}
        return float(loss), {k: float(jnp.sum(v * v)) for k, v in g.items()}

    def change_sumsq(self):
        return {k: float(jnp.sum(jnp.square(
            st["p"].astype(jnp.float32) - self.p0[k].astype(jnp.float32))))
            for k, st in self.state.items()}


# ----------------------------------------------------- the program's models
def _program_keys(cfg, max_seq_len):
    return dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                intermediate_size=cfg["intermediate_size"],
                max_seq_len=max_seq_len, dropout=0.0,
                tie_embeddings=cfg["tie_word_embeddings"])


def _put(params, name, arr):
    if tuple(params[name]._data.shape) != tuple(arr.shape):
        raise RuntimeError(f"{name}: {params[name]._data.shape} != "
                           f"{arr.shape}")
    params[name]._data = arr


def serving_model(cfg, seed):
    from tools.serve_bench import build_decoder

    dtype = jnp.dtype(cfg["torch_dtype"])
    model = build_decoder(
        _program_keys(cfg, cfg["deployment"]["engine"]["max_seq_len"]),
        seed=0, bf16=dtype == jnp.bfloat16)
    w = make_weights(cfg, seed, dtype)
    params = dict(model.named_parameters())
    _put(params, "model.embed_tokens.weight", w["tok"])
    _put(params, "model.final_norm.weight", w["norm"])
    if "out" in w:
        _put(params, "lm_head.weight", w["out"].T)
    for l, p in enumerate(w["layers"]):
        for leaf, (_, suffix) in LAYER.items():
            _put(params, f"model.layers.{l}.{suffix}", p[leaf])
    return model


TRAIN_PARAMS = {"tok": "embed_tokens.weight", "norm": "final_norm.weight",
                **{k: f"decoder.{field}" for k, (field, _) in LAYER.items()}}


def training_model(cfg, mix):
    import bench
    from paddle_tpu.models.gpt import GPTConfig

    gcfg = GPTConfig(dtype=cfg["torch_dtype"], recompute=True,
                     **_program_keys(cfg, mix["seq"]))
    return bench.build_model(
        gcfg, bf16=jnp.dtype(cfg["torch_dtype"]) == jnp.bfloat16)


def load_training_weights(model, weights):
    params = dict(model.named_parameters())
    for leaf, arr in _to_params(weights).items():
        _put(params, TRAIN_PARAMS[leaf], arr)


def seed_param(cfg, key, name, dtype):
    top, layer = _shapes(cfg)
    if name in top:
        return _draw(key, name, top[name], dtype)
    return jnp.stack([_draw(key, f"{l}/{name}", layer[name], dtype)
                      for l in range(cfg["num_hidden_layers"])])


# ----------------------------------------------------------- the arithmetic
def matmul_params(cfg):
    _, layer = _shapes(cfg)
    per_layer = sum(a * b for a, b in (s for s in layer.values()
                                       if len(s) == 2))
    return (cfg["num_hidden_layers"] * per_layer
            + cfg["vocab_size"] * cfg["hidden_size"])


def train_flops_per_token(cfg, seq):
    return (6 * matmul_params(cfg)
            + 6 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def serve_flops(cfg, positions):
    return (2 * matmul_params(cfg) * len(positions)
            + 4 * cfg["num_hidden_layers"] * cfg["hidden_size"]
            * sum(positions))


def cache_bytes_per_token(cfg):
    _, layer = _shapes(cfg)
    return cfg["num_hidden_layers"] * 2 * layer["k"][1] * 2


KERNEL_WORK = {}
