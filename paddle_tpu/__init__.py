"""paddle_tpu — a TPU-native deep-learning framework.

Capability parity with PaddlePaddle's public API surface
(``python/paddle/__init__.py``), built from scratch on jax/XLA/Pallas:
eager ops dispatch to XLA (debug path), training loops compile through
``jax.jit``/pjit (perf path), parallelism maps onto ``jax.sharding.Mesh``.
"""
from __future__ import annotations

import jax as _jax

# float64 capability parity with the reference (x64 must be on before tracing)
_jax.config.update("jax_enable_x64", True)
# keep python-float default at float32 (paddle semantics) via weak types.

from . import dtypes as _dtype_module
from .dtypes import (  # noqa: F401
    DType,
    bool_,
    uint8,
    int8,
    int16,
    int32,
    int64,
    float16,
    bfloat16,
    float32,
    float64,
    float8_e4m3fn,
    float8_e5m2,
    complex64,
    complex128,
    iinfo,
    finfo,
    promote_types,
)

dtype = DType  # paddle.dtype is the dtype class

from .device import (  # noqa: F401
    Place,
    TPUPlace,
    CPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
    XPUPlace,
    CustomPlace,
    set_device,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_rocm,
    is_compiled_with_xpu,
    is_compiled_with_tpu,
)

from .framework import (  # noqa: F401
    no_grad,
    enable_grad,
    set_grad_enabled,
    is_grad_enabled,
    set_default_dtype,
    get_default_dtype,
    seed,
    get_rng_state,
    set_rng_state,
    in_dynamic_mode,
    in_dynamic_or_pir_mode,
    Generator,
)

from .core.tensor import Tensor, Parameter  # noqa: F401

# ops: importing patches Tensor methods
from .ops import *  # noqa: F401,F403
from . import ops as _ops

from .autograd import grad, PyLayer  # noqa: F401

# numeric constants (parity: paddle.pi / e / inf / nan / newaxis)
import math as _math

import numpy as _np_mod

bool = _np_mod.bool_  # paddle.bool dtype alias (shadows builtins.bool here only)
pstring = "pstring"   # string-tensor dtype tag (reference: phi StringTensor)
raw = "raw"           # raw dtype tag (reference: DataType::UNDEFINED carrier)

pi = _math.pi
e = _math.e
inf = float("inf")
nan = float("nan")
newaxis = None


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def disable_signal_handler():
    pass


def check_shape(tensor):
    return list(tensor.shape)


def get_cuda_rng_state():
    from . import framework as _fw

    return _fw.get_rng_state()


def set_cuda_rng_state(state):
    from . import framework as _fw

    _fw.set_rng_state(state)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    from .nn.layer.layers import Layer

    holder = Layer.__new__(Layer)
    Layer.__init__(holder)
    return holder.create_parameter(shape, attr=attr, dtype=dtype,
                                   is_bias=is_bias,
                                   default_initializer=default_initializer)


class LazyGuard:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def batch(reader, batch_size, drop_last=False):
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched

from . import autograd  # noqa: F401

# subpackages (populated progressively; import lazily where heavy)
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import amp  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from . import distributed  # noqa: F401
from . import device  # noqa: F401
from . import utils  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import telemetry  # noqa: F401
from . import memory  # noqa: F401
from . import static  # noqa: F401
from . import sparse  # noqa: F401
from . import strings  # noqa: F401
from . import distribution  # noqa: F401
from . import linalg_ns as linalg  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import onnx  # noqa: F401
from . import text  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import quantization  # noqa: F401
from . import autograd  # noqa: F401
from .hapi.model import Model, summary  # noqa: F401
from .framework_io import save, load  # noqa: F401
from .framework_io import async_save, clear_async_save_task_queue  # noqa: F401
from .ops.compat import to_dlpack, from_dlpack  # noqa: F401
from .distributed.data_parallel import DataParallel  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401

from . import version  # noqa: F401
from . import inference  # noqa: F401
from . import callbacks  # noqa: F401
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from . import hub  # noqa: F401
from . import api_tracer  # noqa: F401
from . import cost_model  # noqa: F401
from . import ir  # noqa: F401
from . import tensorrt  # noqa: F401

__version__ = version.full_version


def disable_static(place=None):
    return None


def enable_static():
    raise NotImplementedError(
        "paddle_tpu is dynamic-first; use paddle_tpu.jit.to_static for "
        "compiled execution."
    )


def is_grad_enabled_():
    return is_grad_enabled()


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.summary import flops as _flops

    return _flops(net, input_size, custom_ops, print_detail)


def get_flags(flags):
    from .utils import flags as _flags

    return _flags.get_flags(flags)


def set_flags(flags):
    from .utils import flags as _flags

    return _flags.set_flags(flags)


def synchronize():
    """Block until all enqueued device work completes."""
    try:
        _jax.effects_barrier()
    except Exception:
        pass


class CUDAGraph:  # capability slot: jit already gives whole-step graphs on TPU
    def __init__(self, *a, **k):
        raise NotImplementedError("Use paddle_tpu.jit — XLA compiles whole-step graphs.")

from . import cinn  # noqa: F401
