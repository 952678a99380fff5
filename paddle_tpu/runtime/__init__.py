"""Native host runtime bindings (ctypes over libptruntime.so).

See cc/ptruntime.cc for what each piece replaces in the reference. The
library is compiled on first use with the baked g++ toolchain and cached
next to the source (never committed). Without a working compiler the
pure-Python data path takes over, and says so once with the compiler's
own output; ``native_status()`` tells which of the two is in use.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

_HERE = os.path.dirname(__file__)
_SO = os.path.join(_HERE, "libptruntime.so")
_SRC = os.path.join(_HERE, "cc", "ptruntime.cc")

_lib = None
_lib_lock = threading.Lock()
_build_error = None   # why the native library is unavailable, once known


def _build():
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", _SO]
    subprocess.run(cmd, check=True, capture_output=True)


def _unavailable(err):
    """Record (and report, once) why the pure-Python path is in use."""
    global _build_error
    detail = getattr(err, "stderr", None)
    detail = detail.decode(errors="replace").strip() if detail else str(err)
    _build_error = f"{type(err).__name__}: {detail}"
    print(f"paddle_tpu.runtime: native library unavailable, using the "
          f"pure-Python data path ({_build_error})", file=sys.stderr)
    return None


def native_status():
    """``"built"`` when libptruntime.so is loaded, else ``"python"``
    (the build or load failed; the reason went to stderr)."""
    return "built" if get_lib() is not None else "python"


def get_lib():
    """Load (building if needed) the native runtime; None if unavailable."""
    global _lib
    if _lib is not None or _build_error is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            if not os.path.exists(_SO) or \
                    os.path.getmtime(_SO) < os.path.getmtime(_SRC):
                _build()
            lib = ctypes.CDLL(_SO)
            if not hasattr(lib, "pt_multislot_parse"):
                # stale .so from older source with equal/newer mtime
                # (docker COPY / zip extraction): rebuild once
                _build()
                lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            return _unavailable(e)
        # signatures (a missing symbol means an unusable lib:
        # fall back to pure Python rather than crash consumers)
        try:
            # signatures
            lib.pt_arena_new.restype = ctypes.c_void_p
            lib.pt_arena_new.argtypes = [ctypes.c_size_t]
            lib.pt_arena_alloc.restype = ctypes.c_void_p
            lib.pt_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
            lib.pt_arena_reset.argtypes = [ctypes.c_void_p]
            lib.pt_arena_free.argtypes = [ctypes.c_void_p]
            lib.pt_arena_stats.argtypes = [ctypes.c_void_p] + \
                [ctypes.POINTER(ctypes.c_uint64)] * 4
            lib.pt_ring_new.restype = ctypes.c_void_p
            lib.pt_ring_new.argtypes = [ctypes.c_size_t]
            lib.pt_ring_push.restype = ctypes.c_int
            lib.pt_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_size_t]
            lib.pt_ring_pop.restype = ctypes.c_int
            lib.pt_ring_pop.argtypes = [ctypes.c_void_p,
                                        ctypes.POINTER(ctypes.c_void_p),
                                        ctypes.POINTER(ctypes.c_size_t),
                                        ctypes.c_long]
            lib.pt_blob_free.argtypes = [ctypes.c_void_p]
            lib.pt_ring_close.argtypes = [ctypes.c_void_p]
            lib.pt_ring_len.restype = ctypes.c_size_t
            lib.pt_ring_len.argtypes = [ctypes.c_void_p]
            lib.pt_ring_free.argtypes = [ctypes.c_void_p]
            lib.pt_rec_writer_open.restype = ctypes.c_void_p
            lib.pt_rec_writer_open.argtypes = [ctypes.c_char_p]
            lib.pt_rec_write.restype = ctypes.c_int
            lib.pt_rec_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_uint32]
            lib.pt_rec_writer_close.restype = ctypes.c_uint64
            lib.pt_rec_writer_close.argtypes = [ctypes.c_void_p]
            lib.pt_shard_reader_start.restype = ctypes.c_void_p
            lib.pt_shard_reader_start.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
                ctypes.c_size_t]
            lib.pt_shard_reader_ring.restype = ctypes.c_void_p
            lib.pt_shard_reader_ring.argtypes = [ctypes.c_void_p]
            lib.pt_shard_reader_errors.restype = ctypes.c_int
            lib.pt_shard_reader_errors.argtypes = [ctypes.c_void_p]
            lib.pt_shard_reader_free.argtypes = [ctypes.c_void_p]
            lib.pt_shuffle_new.restype = ctypes.c_void_p
            lib.pt_shuffle_new.argtypes = [ctypes.c_size_t, ctypes.c_uint64]
            lib.pt_shuffle_push.restype = ctypes.c_int
            lib.pt_shuffle_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_size_t]
            lib.pt_shuffle_pop.restype = ctypes.c_int
            lib.pt_shuffle_pop.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(ctypes.c_void_p),
                                           ctypes.POINTER(ctypes.c_size_t),
                                           ctypes.c_size_t, ctypes.c_long]
            lib.pt_shuffle_len.restype = ctypes.c_size_t
            lib.pt_shuffle_len.argtypes = [ctypes.c_void_p]
            lib.pt_shuffle_close.argtypes = [ctypes.c_void_p]
            lib.pt_shuffle_free.argtypes = [ctypes.c_void_p]
            lib.pt_multislot_parse.restype = ctypes.c_long
            lib.pt_multislot_parse.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_int,
                ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_long]
        except AttributeError as e:
            return _unavailable(e)
        _lib = lib
        return _lib


class RingBuffer:
    """Blocking byte-blob channel; native when possible, queue fallback."""

    def __init__(self, capacity=8):
        lib = get_lib()
        self._lib = lib
        if lib is not None:
            self._h = lib.pt_ring_new(capacity)
            self._q = None
        else:  # pure-python fallback
            import queue

            self._h = None
            self._q = queue.Queue(maxsize=capacity)
        self._closed = False

    def push(self, data: bytes) -> bool:
        if self._h is not None:
            return self._lib.pt_ring_push(self._h, data, len(data)) == 0
        try:
            while True:
                if self._closed:
                    return False
                try:
                    self._q.put(data, timeout=0.1)
                    return True
                except Exception:
                    continue
        except Exception:
            return False

    def pop(self, timeout_ms=-1):
        """bytes, or None when closed-and-drained."""
        if self._h is not None:
            p = ctypes.c_void_p()
            n = ctypes.c_size_t()
            rc = self._lib.pt_ring_pop(self._h, ctypes.byref(p),
                                       ctypes.byref(n), timeout_ms)
            if rc == -1:
                return None
            if rc == -3:
                raise TimeoutError("ring pop timed out")
            data = ctypes.string_at(p.value, n.value)
            self._lib.pt_blob_free(p)
            return data
        import queue

        deadline = None if timeout_ms < 0 else timeout_ms / 1000.0
        while True:
            try:
                return self._q.get(timeout=0.1 if deadline is None else deadline)
            except queue.Empty:
                if self._closed and self._q.empty():
                    return None
                if deadline is not None:
                    raise TimeoutError("ring pop timed out")

    def __len__(self):
        if self._h is not None:
            return self._lib.pt_ring_len(self._h)
        return self._q.qsize()

    def close(self):
        self._closed = True
        if self._h is not None:
            self._lib.pt_ring_close(self._h)

    def __del__(self):
        try:
            if self._h is not None and self._lib is not None:
                self._lib.pt_ring_free(self._h)
                self._h = None
        except Exception:
            pass


class Arena:
    """Host staging allocator with stats (ref: memory/allocation)."""

    def __init__(self, block_size=1 << 20):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.pt_arena_new(block_size)

    def alloc(self, n) -> int:
        return self._lib.pt_arena_alloc(self._h, n)

    def reset(self):
        self._lib.pt_arena_reset(self._h)

    def stats(self):
        vals = [ctypes.c_uint64() for _ in range(4)]
        self._lib.pt_arena_stats(self._h, *[ctypes.byref(v) for v in vals])
        return {"total_allocated": vals[0].value, "in_use": vals[1].value,
                "peak": vals[2].value, "alloc_count": vals[3].value}

    def __del__(self):
        try:
            if getattr(self, "_h", None) is not None:
                self._lib.pt_arena_free(self._h)
                self._h = None
        except Exception:
            pass


class RecordWriter:
    """Length-prefixed CRC'd record shard writer."""

    def __init__(self, path):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.pt_rec_writer_open(os.fsencode(path))
        if not self._h:
            raise OSError(f"cannot open {path}")

    def write(self, data: bytes):
        if self._lib.pt_rec_write(self._h, data, len(data)) != 0:
            raise OSError("record write failed")

    def close(self) -> int:
        n = self._lib.pt_rec_writer_close(self._h)
        self._h = None
        return n

    def __enter__(self):
        return self

    def __exit__(self, *a):
        if self._h:
            self.close()


class ShardReader:
    """Threaded readahead over record shards; iterates raw record bytes."""

    def __init__(self, paths, n_threads=2, ring_capacity=64):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        arr = (ctypes.c_char_p * len(paths))(
            *[os.fsencode(p) for p in paths])
        self._h = lib.pt_shard_reader_start(arr, len(paths), n_threads,
                                            ring_capacity)
        self._ring = lib.pt_shard_reader_ring(self._h)

    def __iter__(self):
        return self

    def __next__(self):
        p = ctypes.c_void_p()
        n = ctypes.c_size_t()
        rc = self._lib.pt_ring_pop(self._ring, ctypes.byref(p),
                                   ctypes.byref(n), -1)
        if rc == -1:
            if self._lib.pt_shard_reader_errors(self._h):
                raise OSError("shard reader encountered corrupt records")
            raise StopIteration
        data = ctypes.string_at(p.value, n.value)
        self._lib.pt_blob_free(p)
        return data

    def close(self):
        if getattr(self, "_h", None):
            self._lib.pt_shard_reader_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class ShufflePool:
    """Bounded reservoir with uniform random pops — the native analog of
    the buffered shuffle reader (cc: PtShufflePool); python-queue-free
    so producers can feed it from worker threads without GIL churn.
    Falls back to a pure-python reservoir when the library is absent."""

    def __init__(self, capacity=1024, seed=0, min_fill=None):
        self._min_fill = min(min_fill if min_fill is not None
                             else capacity // 2, capacity)
        lib = get_lib()
        self._lib = lib
        import threading as _t

        # liveness guard: counts callers inside native push/pop so free
        # can wait for them (mirrors the C-side inflight drain; this
        # layer also stops NEW callers once the handle is retired)
        self._guard = _t.Condition()
        self._users = 0
        if lib is not None:
            self._h = lib.pt_shuffle_new(capacity, seed or 0)
        else:
            import random

            self._h = None
            self._pool = []
            self._rng = random.Random(seed)
            self._cap = capacity
            self._closed = False
            self._cv = _t.Condition()

    def _enter(self):
        """Claim the native handle for one call; None once retired."""
        with self._guard:
            if self._h is None:
                return None
            self._users += 1
            return self._h

    def _exit(self):
        with self._guard:
            self._users -= 1
            if self._users == 0:
                self._guard.notify_all()

    def push(self, data: bytes) -> bool:
        h = self._enter()
        if h is not None:
            try:
                rc = self._lib.pt_shuffle_push(h, data, len(data))
            finally:
                self._exit()
            if rc == -2:  # malloc failure is an error, not a quiet stop
                raise MemoryError("ShufflePool: native allocation failed")
            return rc == 0
        if self._lib is not None:
            return False  # native pool already freed
        with self._cv:
            while len(self._pool) >= self._cap and not self._closed:
                self._cv.wait(0.1)
            if self._closed:
                return False
            self._pool.append(bytes(data))
            self._cv.notify_all()
            return True

    def pop(self, timeout_ms=-1):
        """A uniformly random blob; None when closed and drained; raises
        TimeoutError when ``timeout_ms`` elapses first (a slow producer
        is not end-of-stream)."""
        h = self._enter() if self._lib is not None else None
        if h is not None:
            try:
                data = ctypes.c_void_p()
                size = ctypes.c_size_t()
                rc = self._lib.pt_shuffle_pop(h, ctypes.byref(data),
                                              ctypes.byref(size),
                                              self._min_fill, timeout_ms)
            finally:
                self._exit()
            if rc == 1:
                raise TimeoutError(
                    f"ShufflePool.pop: no sample within {timeout_ms}ms")
            if rc != 0:
                return None
            out = ctypes.string_at(data, size.value)
            self._lib.pt_blob_free(data)
            return out
        if self._lib is not None:
            return None  # native pool already freed
        import time as _time

        deadline = None if timeout_ms < 0 \
            else _time.monotonic() + timeout_ms / 1000.0
        with self._cv:
            while True:
                ready = len(self._pool) >= (1 if self._closed
                                            else max(self._min_fill, 1))
                if ready or (self._closed and not self._pool):
                    break
                if deadline is not None and _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"ShufflePool.pop: no sample within {timeout_ms}ms")
                self._cv.wait(0.1)
            if not self._pool:
                return None
            i = self._rng.randrange(len(self._pool))
            self._pool[i], self._pool[-1] = self._pool[-1], self._pool[i]
            out = self._pool.pop()
            self._cv.notify_all()
            return out

    def __len__(self):
        h = self._enter() if self._lib is not None else None
        if h is not None:
            try:
                return self._lib.pt_shuffle_len(h)
            finally:
                self._exit()
        if self._lib is not None:
            return 0
        with self._cv:
            return len(self._pool)

    def close(self):
        h = self._enter() if self._lib is not None else None
        if h is not None:
            try:
                self._lib.pt_shuffle_close(h)
            finally:
                self._exit()
            return
        if self._lib is not None:
            return
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def __del__(self):
        try:
            if self._lib is None or self._h is None:
                return
            # retire the handle first so no NEW caller can enter, then
            # wait for in-flight push/pop to leave; the C free() adds a
            # second drain (closed + inflight cv) for non-python callers
            with self._guard:
                h, self._h = self._h, None
                self._lib.pt_shuffle_close(h)  # wakes blocked callers
                while self._users:
                    self._guard.wait(0.1)
            self._lib.pt_shuffle_free(h)
        except Exception:
            pass


def multislot_parse(text, slot_sizes, slot_is_float):
    """Native MultiSlot sample parsing (the reference data_feed.cc role:
    MultiSlotDataFeed::ParseOneInstance). ``text``: bytes of one file's
    samples; returns a list of sample-major arrays, one per slot
    (float32 or int64, shape (n_samples, slot_size)), or None when the
    native library is unavailable (caller falls back to Python parsing).
    Raises ValueError with the 0-based line index on a format error.
    """
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    if isinstance(text, str):
        text = text.encode()
    # upper bound on samples: number of newlines + 1
    max_samples = text.count(b"\n") + 1
    n = len(slot_sizes)
    sizes = (ctypes.c_long * n)(*[int(s) for s in slot_sizes])
    isf = (ctypes.c_int * n)(*[1 if f else 0 for f in slot_is_float])
    bufs = [np.empty((max_samples, int(sz)),
                     np.float32 if f else np.int64)
            for sz, f in zip(slot_sizes, slot_is_float)]
    outs = (ctypes.c_void_p * n)(*[b.ctypes.data for b in bufs])
    got = lib.pt_multislot_parse(text, len(text), n, sizes, isf, outs,
                                 max_samples)
    if got < 0:
        raise ValueError(f"malformed MultiSlot sample at line {-got - 1}")
    return [b[:got] for b in bufs]
