"""Device / Place abstraction.

Parity target: the reference's ``phi::Place`` (``paddle/phi/common/place.h:31``)
and ``paddle.device`` python API.  On TPU there is a single accelerator type;
``TPUPlace`` is first-class (the reference survey calls for a new enum value),
``CPUPlace`` maps to the XLA CPU client, and CUDA aliases are accepted for
source compatibility but resolve to the default accelerator.
"""
from __future__ import annotations

import os
import threading

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_gpu_place(self):
        return False


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"


class CUDAPlace(TPUPlace):
    """Source-compat alias: code written for GPU runs on the accelerator."""

    device_type = "tpu"


class CUDAPinnedPlace(CPUPlace):
    device_type = "cpu"


class XPUPlace(TPUPlace):
    device_type = "tpu"


class CustomPlace(TPUPlace):
    device_type = "tpu"

    def __init__(self, dev_type="tpu", device_id=0):
        super().__init__(device_id)


_state = threading.local()
_platform_cache = [None]


def cpu_requested() -> bool:
    """True when the caller asked for the CPU: ``JAX_PLATFORMS`` names it
    FIRST (``tpu,cpu`` — the TPU machine's own setting — asks for the TPU
    and merely keeps a CPU backend beside it). The CPU is something a
    caller asks for — the tests and tier-1 do — never something the
    program discovers and settles for."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def _accelerator_platform():
    """The current jax platform name — WITHOUT initializing device backends.

    Querying jax.default_backend() creates the PJRT client (on real TPU pods
    that can block on the fabric); we answer from JAX_PLATFORMS when set and
    only fall back to a real (cached) backend query on explicit demand.
    """
    env = os.environ.get("JAX_PLATFORMS", "")
    if env:
        return env.split(",")[0].strip() or "cpu"
    if _platform_cache[0] is None:
        _platform_cache[0] = jax.default_backend()
    return _platform_cache[0]


def require_accelerator(what="this entry point"):
    """The measurement entry points' platform check: returns ``True`` on a
    TPU and ``False`` only when the caller asked for the CPU
    (:func:`cpu_requested`). A machine where JAX found no TPU and nobody
    asked for the CPU raises — no result may come from a device the
    caller did not name."""
    d = jax.devices()[0]
    if d.platform == "tpu":
        return True
    if d.platform == "cpu" and cpu_requested():
        return False
    raise RuntimeError(
        f"{what}: JAX found no TPU (platform={d.platform!r}, "
        f"device_kind={d.device_kind!r}, count={len(jax.devices())}) and "
        "JAX_PLATFORMS does not ask for the CPU — refusing to run on a "
        "device nobody named. Set JAX_PLATFORMS=cpu for a CPU smoke run.")


def device_record():
    """``{"platform", "kind", "count"}`` as JAX reports the devices —
    every benchmark line carries it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- chip peaks ---------------------------------------------------------------
#: Published per-chip peaks, keyed by ``jax.Device.device_kind``. ONE table:
#: MFU/roofline denominators (jit cost summaries), the memory
#: planner's HBM fallback and the layout autotuner's link term all read it.
#: v5e: Google Cloud documentation, "TPU v5e" system architecture — 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
#: inter-chip interconnect per chip. A TPU kind missing here is an error,
#: not a default: add its row with the source of the figures.
CHIP_PEAKS = {
    "TPU v5 lite": {  # what a v5e reports as device_kind
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_sec": 819e9,
        "ici_bytes_per_sec": 1600e9 / 8,
    },
}

#: the CPU has no published peak: these are placeholders so CPU tests can
#: exercise the cost-summary plumbing; every reader carries the flag and
#: no CPU number is ever reported as a utilization
_CPU_PLACEHOLDER = {
    "bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes": 16e9,
    "hbm_bytes_per_sec": 100e9, "ici_bytes_per_sec": 10e9,
}


def chip_peaks(device=None):
    """``(peaks, placeholder)`` for ``device`` (default: device 0):
    the :data:`CHIP_PEAKS` row of its ``device_kind``. CPU devices get the
    flagged placeholder row; any other kind missing from the table raises
    ``KeyError`` naming it."""
    d = device if device is not None else jax.devices()[0]
    if d.platform == "cpu":
        return dict(_CPU_PLACEHOLDER), True
    try:
        return dict(CHIP_PEAKS[d.device_kind]), False
    except KeyError:
        raise KeyError(
            f"device_kind {d.device_kind!r} (platform {d.platform!r}) has "
            "no row in paddle_tpu.device.CHIP_PEAKS — add its published "
            "peaks with their source; an unknown chip never borrows "
            "another chip's numbers") from None


# -- compile cache ------------------------------------------------------------
def compile_cache_dir():
    """Place JAX's persistent compilation cache; returns the directory.

    Called ONCE by each entry point that compiles for the chip
    (chip_smoke.py, bench.py, tools/serve_bench.py, profile_bench.py, the
    fleet workers) and never at ``import paddle_tpu``. When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, so this sets
    nothing and returns it; otherwise the cache goes to
    ``<checkout>/.jax_cache``, resolved from this package's location —
    the path is part of the cache key, so it must not move. A caller that
    asked for the CPU gets no cache (returns ``None``): deserialized CPU
    executables that hold collectives deadlock in this jaxlib
    (tests/conftest.py)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if cpu_requested():
        return None
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_device(device: str):
    """paddle.device.set_device — accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'...

    GPU/XPU/custom names are treated as the accelerator for compatibility.
    Asking for the accelerator where the caller pinned JAX to the CPU
    raises — a TPU place never quietly resolves to a CPU device.
    """
    device = str(device)
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("cpu",):
        _state.place = CPUPlace(idx)
    else:
        if _accelerator_platform() == "cpu":
            raise RuntimeError(
                f"set_device({device!r}): this process runs on the CPU "
                "(JAX_PLATFORMS / backend) — there is no accelerator to "
                "select; use set_device('cpu')")
        _state.place = TPUPlace(idx)
    return get_device()


def get_device() -> str:
    p = _current_place()
    return f"{p.device_type}:{p.get_device_id()}"


def _current_place() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        plat = _accelerator_platform()
        p = CPUPlace(0) if plat == "cpu" else TPUPlace(0)
        _state.place = p
    return p


def jax_device_for(place: Place | None = None):
    """Map a Place to a concrete jax.Device, or None for "default device".

    Returning None lets callers skip jax.device_put entirely — arrays land on
    the default device lazily without forcing backend initialization. An
    accelerator place on a CPU-only backend, or a device id past the
    device count, raises.
    """
    if place is None:
        return None
    devs = jax.devices("cpu") if place.is_cpu_place() else jax.devices()
    if not place.is_cpu_place() and devs[0].platform == "cpu":
        raise RuntimeError(
            f"{place!r}: the default backend's devices are CPUs — an "
            "accelerator place never resolves to a CPU device")
    idx = place.get_device_id()
    if not 0 <= idx < len(devs):
        raise IndexError(
            f"{place!r}: device id {idx} out of range for {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs[idx]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def cuda_device_count() -> int:  # compat
    return 0


def get_all_device_type():
    return ["cpu", "tpu"]


def get_available_device():
    return [f"tpu:{i}" for i in range(device_count())]


# -- stream/event surface (parity: python/paddle/device) --------------------
# XLA owns scheduling on TPU; streams/events are API-compatible no-ops that
# preserve program semantics (synchronize flushes pending dispatch).

class Stream:
    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    _current_stream = stream
    return stream


import contextlib as _ctx


@_ctx.contextmanager
def stream_guard(stream):
    old = current_stream()
    set_stream(stream)
    try:
        yield
    finally:
        set_stream(old)


def synchronize(device=None):
    """Block until all dispatched work completes."""
    import jax

    (jax.device_put(0.0) + 0).block_until_ready()


def get_cudnn_version():
    return None


class IPUPlace:
    pass


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type=None):
    return False


def get_all_custom_device_type():
    return []


def get_available_custom_device():
    return []
