"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware isn't available in CI; XLA's host platform can fake
N devices, which exercises the exact same SPMD partitioner + collective
lowering paths our Mesh code uses on a real pod.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np
import pytest

import jax

# the suite is a CPU suite wherever it runs: pin the platform before any
# backend client exists, so a machine with a chip does not hand it to
# pytest (chip_smoke.py is what runs there)
jax.config.update("jax_platforms", "cpu")

# test-only: exact f32 matmuls so numerical comparisons vs numpy are tight
# (the production TPU path keeps the fast default so the MXU runs bf16)
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def cache_config():
    """Whatever a test does to the process-wide compile-cache settings
    (jax's persistent cache and the AOT layer) is undone after it: a test
    that left them switched off changed how every later test compiles."""
    from paddle_tpu.runtime import aot

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    aot_before = aot.configured()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    aot.configure(aot_before)
