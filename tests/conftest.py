"""Test rig: force JAX onto a virtual 8-device CPU mesh.

SURVEY.md §4: the TPU-native distributed-test strategy is JAX's CPU backend
with ``--xla_force_host_platform_device_count=8`` — real SPMD on one host.
The tests never touch an accelerator: ``JAX_PLATFORMS=cpu`` is set here,
before jax is imported, whatever the caller's environment says, and the
Pallas kernels run in interpret mode.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Pallas kernels run in interpret mode on CPU.
os.environ.setdefault("VLLM_TPU_PALLAS_INTERPRET", "1")
# Tests must not append to the real ~/.config usage log (the telemetry
# test overrides the path explicitly).
os.environ.setdefault("VLLM_TPU_NO_USAGE_STATS", "1")

import jax  # noqa: E402

# Also for a jax that something imported before this file set the variable.
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: XLA recompiles dominate suite runtime on
# the CPU backend; cached executables survive across pytest runs. Same
# rule and same directory as the worker's (JAX_COMPILATION_CACHE_DIR if
# set, else the checkout's fixed path).
from vllm_tpu.utils.compile_cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {devices}"
    return devices
