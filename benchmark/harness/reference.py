"""What any family's plain reference is made of: the key a seed gives, the
values a leaf draws, the matmul and its modes, rmsnorm and rope, in
straightforward jax.numpy, float32 with matmuls at HIGHEST precision.
Imports nothing of paddle_tpu. A family's own reference (its layers, its
loss, its optimizer step, its weights' tree) is in harness/families/.

``mode`` selects the matmul precision: "f32" is the reference, "bf16" and
"int8" (per-tensor symmetric fake quantisation of both operands of every
projection and of the head) are the lower-precision controls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02


# ------------------------------------------------------------------ weights
def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf_values(key, shape):
    if len(shape) == 1:  # norm gains near one, so that a dropped gain
        # shows; on the bf16 grid (steps of 1/128), so that every program
        # that makes them from the seed rounds them alike
        return 1.0 + jax.random.randint(key, shape, -16, 17).astype(
            jnp.float32) / 128.0
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


# --------------------------------------------------------------------- math
def _fake_int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def mm(x, w, mode):
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":
        x, w = _fake_int8(x), _fake_int8(w)
    return jnp.matmul(x, w, precision=HI)


def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, base):
    """Half-split rotation on [B, S, H, D] at positions 0..S-1."""
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(f)[None, :, None, :], jnp.cos(f)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
