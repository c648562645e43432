"""Fused residual-add + RMS norm as a Pallas TPU kernel.

Capability parity: the reference's fused residual+norm CUDA kernels
(``phi/kernels/fusion/gpu/fused_layernorm_kernel.cu`` — residual_bias_add
+ norm in one pass). The transformer block computes ``y = x + attn_out``
followed by ``rms(y)``; unfused, ``y`` makes an HBM round-trip between
the add and the norm's read (plus a second read for the norm's variance
pass when XLA doesn't fuse across the reduce). This kernel streams row
blocks through VMEM once and emits BOTH tensors the block needs: the new
residual stream ``y`` and the normalised ``o``.

Backward reuses the forward's rstd residual (closed-form jnp, XLA-fused)
and returns the ONE shared cotangent for x and r — the caller adds the
downstream residual gradient itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rows_block(n: int) -> int:
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def _fwd_kernel(x_ref, r_ref, w_ref, y_ref, o_ref, rstd_ref, *, eps):
    y32 = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    y_ref[:] = y32.astype(y_ref.dtype)
    # norm reads the ROUNDED residual stream (bf16), matching the unfused
    # reference `rms(x + r)` where the add materialises in model dtype
    yn = y_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(yn), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (yn * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _fwd(x2, r2, w, eps, interpret):
    n, h = x2.shape
    br = _rows_block(n)
    with jax.enable_x64(False):
        y, o, rstd = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            name="add_rms_norm_fwd",
            grid=(n // br,),
            in_specs=[
                pl.BlockSpec((br, h), lambda i: (i, 0)),
                pl.BlockSpec((br, h), lambda i: (i, 0)),
                pl.BlockSpec((h,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((br, h), lambda i: (i, 0)),
                pl.BlockSpec((br, h), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, h), x2.dtype),
                jax.ShapeDtypeStruct((n, h), x2.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            interpret=interpret,
        )(x2, r2, w)
    return y, o, rstd[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _add_rms(x2, r2, w, eps, interpret):
    y, o, _ = _fwd(x2, r2, w, eps, interpret)
    return y, o


def _add_rms_fwd(x2, r2, w, eps, interpret):
    y, o, rstd = _fwd(x2, r2, w, eps, interpret)
    # named residuals: under selective remat, policies saving
    # "addrms_y"/"rms_rstd" let the backward reuse them instead of
    # re-running this kernel
    from jax.ad_checkpoint import checkpoint_name

    y = checkpoint_name(y, "addrms_y")
    rstd = checkpoint_name(rstd, "rms_rstd")
    return (y, o), (y, w, rstd)


def _add_rms_bwd(eps, interpret, res, gs):
    y, w, rstd = res
    gy, go = gs
    yf = y.astype(jnp.float32)
    gf = go.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    r = rstd[:, None]
    yhat = yf * r
    gw_ = gf * wf
    dnorm = r * (gw_ - yhat * jnp.mean(gw_ * yhat, axis=-1, keepdims=True))
    dy = gy.astype(jnp.float32) + dnorm
    dw = jnp.sum(gf * yhat, axis=0)
    dy = dy.astype(y.dtype)
    return dy, dy, dw.astype(w.dtype)


_add_rms.defvjp(_add_rms_fwd, _add_rms_bwd)


def add_rms_norm(x, residual, weight, epsilon=1e-6, interpret=None):
    """Fused ``y = x + residual; o = rms_norm(y) * weight``.

    Returns ``(y, o)`` — the updated residual stream and the normalised
    activations. Shapes: x/residual [..., H], weight [H].
    """
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = residual.reshape(-1, shape[-1])
    y, o = _add_rms(x2, r2, weight, float(epsilon), bool(interpret))
    return y.reshape(shape), o.reshape(shape)
