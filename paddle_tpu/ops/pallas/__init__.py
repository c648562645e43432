"""Pallas TPU kernels — the capability slot the reference fills with
hand-written CUDA fusions (``phi/kernels/fusion/gpu``, ``phi/kernels/gpu/
flash_attn_kernel.cu``).

Design stance (TPU-first): only ops that XLA cannot already fuse optimally
get a Pallas kernel. Flash attention (tiled online-softmax over VMEM blocks)
and row-normalisation (rms/layer norm over long rows) qualify; elementwise
chains like rope/swiglu/bias-act do NOT — XLA fuses those into the
surrounding matmuls, and a Pallas kernel would break that fusion.

All kernels run in interpret mode on CPU (tests) and compiled on TPU.
"""
from __future__ import annotations

import logging

import jax

_log = logging.getLogger("paddle_tpu.pallas")
_tpu_cache = [None]


def on_tpu_device() -> bool:
    """True when device 0 is a TPU, i.e. Mosaic kernels compile. Gated on
    the *device* platform; a failing device query propagates."""
    if _tpu_cache[0] is None:
        _tpu_cache[0] = jax.devices()[0].platform == "tpu"
    return _tpu_cache[0]


def use_interpret() -> bool:
    """Interpret-mode on non-TPU backends so the same kernel code is tested
    on the CPU mesh (SURVEY §4: fake-backend strategy)."""
    return not on_tpu_device()


_path_logged = set()


def log_path_once(op: str, path: str) -> None:
    """One-line record of which implementation served an op (pallas vs xla),
    so benchmarks can prove the fast path engaged. Keyed on (op, path): a
    mid-run path switch (shape-dependent fallback) is logged too. INFO level:
    a caller raises this logger to INFO to record the path."""
    if (op, path) not in _path_logged:
        _path_logged.add((op, path))
        _log.info("paddle_tpu dispatch path: %s -> %s", op, path)


from .flash_attention import flash_attention, flash_attention_fwd  # noqa: E402
from .rms_norm import rms_norm  # noqa: E402
from .swiglu_down import swiglu_down, swiglu_down_supported  # noqa: E402

__all__ = ["flash_attention", "flash_attention_fwd", "rms_norm",
           "swiglu_down", "swiglu_down_supported", "use_interpret"]
