"""What must hold where there is no chip: the device entry point
refuses to run, the compile cache goes where the environment says, and
the peaks table invents nothing. (What holds ON the chip is
``python chip_smoke.py`` itself, run through the chip tool.)"""
import os
import subprocess
import sys

import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import runtime
from paddle_tpu.core.device import CHECKOUT_CACHE_DIR
from paddle_tpu.obs import mfu
from paddle_tpu.runtime import aot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_accelerator_means_no_result():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr or "platform='cpu'" in r.stderr
    assert r.stdout.strip() == "", "printed something that reads as a result"


def test_env_names_the_compile_cache(monkeypatch, tmp_path, cache_config):
    env_dir, other = str(tmp_path / "from_env"), str(tmp_path / "other")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    assert pt.set_compilation_cache(other) == env_dir
    assert jax.config.jax_compilation_cache_dir == before  # left alone
    assert aot.active_cache().dir == env_dir   # both layers, one directory
    assert not os.path.exists(other)


def test_default_compile_cache_is_in_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == os.path.join(ROOT, ".xla_cache")
    assert pt.set_compilation_cache() == CHECKOUT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
    assert aot.active_cache().dir == CHECKOUT_CACHE_DIR


def test_peaks_table_has_no_default():
    assert mfu.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="TPU v99"):
        mfu.peak_flops("TPU v99")
    assert mfu.peak_flops() is None   # this suite runs on the cpu backend


def test_failed_native_build_is_said_once(monkeypatch, capfd):
    def no_compiler():
        raise subprocess.CalledProcessError(
            1, ["g++"], stderr=b"ptruntime.cc:1: error: no such thing")

    monkeypatch.setattr(runtime, "_lib", None)
    monkeypatch.setattr(runtime, "_build_error", None)
    monkeypatch.setattr(runtime, "_SO", "/nonexistent/libptruntime.so")
    monkeypatch.setattr(runtime, "_build", no_compiler)
    assert runtime.native_status() == "python"
    assert runtime.get_lib() is None
    err = capfd.readouterr().err
    assert err.count("native library unavailable") == 1
    assert "no such thing" in err
