"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's fake-backend strategy (SURVEY §4: custom_cpu plugin
runs the distributed suite on CPU-only hosts) — XLA-CPU with
xla_force_host_platform_device_count=8 is our fake multi-chip TPU.
"""
import os

# Force CPU: jax reads these two variables when it creates its backend
# (lazily), so setting them before `import jax` is enough.
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# NOTE: do NOT enable jax_compilation_cache_dir here. Deserialized cached
# executables containing CPU collectives deadlock in
# InProcessCommunicator::AllGather on this jax version (reproduced on the
# ZeRO-3 scan program: cold compile passes, warm cache aborts with
# "AwaitAndLogIfStuck").
# Fail fast (and eagerly pin the CPU backend) rather than silently running
# the suite on an accelerator if a backend was already instantiated.
assert jax.default_backend() == "cpu", jax.default_backend()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield
