"""Device management.

TPU-native analog of the reference's ``paddle/fluid/platform/place.h``
(CPUPlace/CUDAPlace/CUDAPinnedPlace) and ``device_context.{h,cc}``.
On TPU there is no per-op stream management — XLA owns scheduling — so a
"place" reduces to a jax.Device plus helpers for host staging.
"""
from __future__ import annotations

import functools
import os
import time

import jax

from ..obs import metrics as _metrics, trace as _trace


class Place:
    """A device placement (ref: platform::Place)."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))

    @property
    def jax_device(self):
        devs = [d for d in jax.devices() if _kind_of(d) == self.kind]
        if self.index >= len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} {self.kind} "
                f"device(s) (backend {jax.default_backend()})")
        return devs[self.index]


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


# The reference exposes CUDAPlace; accepting the name keeps recipes portable.
def CUDAPlace(index: int = 0) -> Place:  # pragma: no cover - alias
    return TPUPlace(index)


def _kind_of(dev) -> str:
    plat = getattr(dev, "platform", "cpu")
    return "tpu" if plat not in ("cpu",) else "cpu"


_CURRENT = [None]


def set_device(device) -> Place:
    """set_device("tpu"), set_device("cpu"), set_device("tpu:0")."""
    if isinstance(device, Place):
        _CURRENT[0] = device
        return device
    name, _, idx = str(device).partition(":")
    if name in ("gpu", "cuda", "xpu"):
        name = "tpu"
    place = Place(name, int(idx) if idx else 0)
    _CURRENT[0] = place
    return place


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.index}"


def current_place() -> Place:
    if _CURRENT[0] is None:
        _CURRENT[0] = Place(_kind_of(jax.devices()[0]), 0)
    return _CURRENT[0]


@functools.lru_cache(maxsize=None)
def device_count(kind: str = None) -> int:
    if kind is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if _kind_of(d) == kind])


def is_compiled_with_tpu() -> bool:
    return any(_kind_of(d) == "tpu" for d in jax.devices())


def device_identity() -> dict:
    """Where a number came from, as jax reports it: every result a
    benchmark prints carries these three fields."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}


# ---- jax's own compile events, as the program's records ---------------------
# jax.monitoring is the only view of the dozens of small programs a process
# traces, lowers, compiles or loads beside its step (an optimizer's slots,
# lr and key, an initializer). Each duration event becomes a phase record
# on the obs ring (obs.trace: always written, a parent by what is open on
# the thread), each cache answer an obs.metrics counter.
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    # covers the persistent cache's read too: jax.cache_load is its child
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}
# jax 0.9.0 records cache_misses where it WRITES an entry (a compile over
# jax_persistent_cache_min_compile_time_secs), not for every program it
# compiles without asking the cache
_JAX_COUNTERS = {
    "/jax/compilation_cache/cache_hits": _metrics.counter("jax.cache.hits"),
    "/jax/compilation_cache/cache_misses":
        _metrics.counter("jax.cache.misses"),
}


def _on_jax_duration(event, duration, **kwargs):
    name = _JAX_DURATIONS.get(event)
    if name is not None:
        end = time.perf_counter()
        attrs = {"event": event}
        if "fun_name" in kwargs:
            attrs["fun_name"] = kwargs["fun_name"]
        _trace.record(name, end - duration, end, **attrs)


def _on_jax_event(event, **_):
    counter = _JAX_COUNTERS.get(event)
    if counter is not None:
        counter.inc()


# once a process: a module is imported once. Never
# jax.monitoring.clear_event_listeners(): other listeners are not ours
jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
jax.monitoring.register_event_listener(_on_jax_event)


# where the compile cache lives when the environment names no directory:
# a fixed path inside the checkout (the path is part of jax's cache key,
# so a directory that moves never hits); listed in .gitignore
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def set_compilation_cache(directory=CHECKOUT_CACHE_DIR,
                          min_compile_time_secs=1.0):
    """Persist compiled XLA executables across processes (the TPU analog
    of the reference's program/kernel caches). Two layers share one
    directory:

    - jax's native persistent compilation cache (every jit/pjit whose
      compile took >= ``min_compile_time_secs``) — but jax declines to
      write it on some backends (notably host CPU), so
    - the framework's own AOT executable cache
      (``paddle_tpu.runtime.aot``) is activated on the SAME directory:
      every Executor/TrainStep/Predictor/ServeEngine compile is then
      serialized as a content-addressed envelope and hydrated by the
      next process, on every backend.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache: jax already reads it, this function sets no other, and
    ``directory`` is ignored. Otherwise ``directory`` is used, by
    default ``<checkout>/.xla_cache``. Pass ``None`` to disable both
    layers. Returns the directory in force."""
    from ..runtime import aot as _aot

    if directory is None:
        jax.config.update("jax_enable_compilation_cache", False)
        # force-off, masking an env PADDLE_TPU_AOT_CACHE too — "pass
        # None to disable both" must hold however the cache came on
        _aot.disable()
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    directory = os.path.abspath(env_dir or str(directory))
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    _aot.configure(directory)
    return directory
