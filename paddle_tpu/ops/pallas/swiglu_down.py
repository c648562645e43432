"""Fused swiglu + down-projection as a Pallas TPU megakernel.

The norm→ffn seam of a decoder block ends in
``(silu(gate) * up) @ wd`` — unfused, the ``[tokens, intermediate]``
swiglu product makes a full HBM round-trip between the elementwise pass
and the down matmul (~45MB per microbatch at 1.3B/b4, 2x that at
LLaMA-7B widths where intermediate=11008). This kernel streams
(gate, up, wd) blocks through VMEM, applies silu*mul on the VPU, and
feeds the MXU dot directly — the product never exists in HBM
(FlashFuser-style seam fusion; docs/SCAN.md).

Backward is a hand-written custom_vjp (residuals: gate, up, wd — gate/up
already carry the ``ffn_gate``/``ffn_up`` remat anchors at the call
site, so a save policy controls their lifetime, not this kernel): the
swiglu product is rebuilt in XLA-fused elementwise math for the wd
weight-grad contraction, mirroring the int8-FFN vjp discipline
(models/gpt.py::_ffn_i8_bwd) without the quantization round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: row/contraction block sizes: rows feed the MXU 128-wide; the K blocks
#: walk the intermediate dim so wd never needs more than [bk, h] VMEM
_BLOCK_ROWS = 256
_BLOCK_K = 512


def _rows_block(n):
    for b in (_BLOCK_ROWS, 128, 64, 32, 16, 8):
        if n % b == 0:
            return b
    return None


def _k_block(m):
    for b in (_BLOCK_K, 256, 128):
        if m % b == 0:
            return b
    return None


def swiglu_down_supported(gate_shape, wd_shape):
    """Mosaic-tileable shapes: rows divisible by a sublane block, the
    intermediate dim by a K block, and lane-aligned trailing dims."""
    rows = 1
    for s in gate_shape[:-1]:
        rows *= int(s)
    m, h = int(wd_shape[0]), int(wd_shape[1])
    return (int(gate_shape[-1]) == m
            and _rows_block(rows) is not None
            and _k_block(m) is not None
            and h % 128 == 0 and m % 128 == 0)


def _fwd_kernel(g_ref, u_ref, wd_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    g32 = g_ref[:].astype(jnp.float32)
    u32 = u_ref[:].astype(jnp.float32)
    ffn = (g32 * jax.lax.logistic(g32) * u32).astype(g_ref.dtype)
    acc_ref[:] += jnp.dot(ffn, wd_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _fwd(g2, u2, wd, interpret):
    rows, m = g2.shape
    h = wd.shape[1]
    br = _rows_block(rows)
    bk = _k_block(m)
    nk = m // bk
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, nk=nk),
            name="swiglu_down_fwd",
            grid=(rows // br, nk),
            in_specs=[
                pl.BlockSpec((br, bk), lambda i, k: (i, k)),
                pl.BlockSpec((br, bk), lambda i, k: (i, k)),
                pl.BlockSpec((bk, h), lambda i, k: (k, 0)),
            ],
            out_specs=pl.BlockSpec((br, h), lambda i, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, h), g2.dtype),
            scratch_shapes=[pltpu.VMEM((br, h), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=2 * rows * m * h + 4 * rows * m,
                bytes_accessed=(2 * rows * m + m * h + rows * h)
                * g2.dtype.itemsize,
                transcendentals=rows * m,
            ),
            interpret=interpret,
        )(g2, u2, wd)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _swiglu_down(g2, u2, wd, interpret):
    return _fwd(g2, u2, wd, interpret)


def _swiglu_down_fwd(g2, u2, wd, interpret):
    return _fwd(g2, u2, wd, interpret), (g2, u2, wd)


def _swiglu_down_bwd(interpret, res, g):
    g2, u2, wd = res
    gate = g2.astype(jnp.float32)
    up = u2.astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    silu = gate * sig
    dsilu = sig * (1.0 + gate * (1.0 - sig))
    ffn = (silu * up).astype(g2.dtype)
    dffn = g @ wd.T
    dwd = jnp.einsum("rm,rh->mh", ffn, g).astype(wd.dtype)
    gf = dffn.astype(jnp.float32)
    dgate = (gf * up * dsilu).astype(g2.dtype)
    dup = (gf * silu).astype(u2.dtype)
    return dgate, dup, dwd


_swiglu_down.defvjp(_swiglu_down_fwd, _swiglu_down_bwd)


def swiglu_down(gate, up, wd, interpret=None):
    """Fused ``(silu(gate) * up) @ wd``. gate/up [..., M], wd [M, H] ->
    [..., H]; the swiglu product never materializes in HBM. Callers gate
    on :func:`swiglu_down_supported` — unsupported shapes raise here
    (loud, per the kernel-dispatch discipline in models/gpt.py)."""
    from . import use_interpret

    if interpret is None:
        interpret = use_interpret()
    if not swiglu_down_supported(gate.shape, wd.shape):
        raise ValueError(
            f"swiglu_down: untileable shapes gate={tuple(gate.shape)} "
            f"wd={tuple(wd.shape)} — guard with swiglu_down_supported")
    shape = gate.shape
    g2 = gate.reshape(-1, shape[-1])
    u2 = up.reshape(-1, shape[-1])
    out = _swiglu_down(g2, u2, wd, bool(interpret))
    return out.reshape(shape[:-1] + (wd.shape[1],))
