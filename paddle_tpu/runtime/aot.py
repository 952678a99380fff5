"""AOT compile-and-ship: a content-addressed on-disk executable cache.

Every compile site in the framework — ``Executor._build`` (static path,
fused ``steps=K`` and plan-carrying entries included), ``TrainStep``
(eager path), the inference ``Predictor``, and ``ServeEngine``'s
prefill/decode buckets — pays a full XLA compile on a process's first
request. For a serving replica, an elastic relaunch, or a fleet
``verify_plan`` probe that compile IS the cold-start latency: it caps
autoscaling speed, and every replica pays it again for the same program.
This module is the full-program compile-once-run-anywhere stance of the
Julia-to-TPU work (PAPERS.md, arXiv 1810.09868) applied to the whole
framework: serialize the compiled executable ONCE, hydrate it from disk
everywhere else.

Design:

- **Key = content, never identity.** The cache key is a SHA-256 over the
  environment fingerprint (jax/jaxlib versions, backend platform, device
  kinds + count, ``XLA_FLAGS``, the relevant ``PADDLE_TPU_*`` knobs),
  the site kind, and the full StableHLO text of the *lowered* module —
  which already bakes in shapes, dtypes, shardings, donation
  (``jax.buffer_donor`` arg attributes), optimization level (the
  analysis passes rewrote the ops before tracing), fused step count (the
  scan is in the module), and program constants. Any ``CacheKey`` drift
  — a changed feed shape, plan, comm layout, or steps=K — produces a
  different module and therefore a clean MISS; a stale hit is
  structurally impossible, not merely checked for.
- **Fingerprint verified twice.** The fingerprint participates in the
  digest AND is stored verbatim in the envelope and re-compared at load:
  deserializing an executable produced by a different jaxlib can
  crash rather than error, so a mismatched envelope is rejected before
  any bytes reach ``deserialize_and_load`` (journaled as an ``aot``
  event with the reason).
- **Bitwise-identical by construction.** A hit deserializes the exact
  executable a local ``lowered.compile()`` would have produced (same
  module, same compile options), so outputs are bitwise identical and
  ``input_output_alias`` donation survives the round-trip —
  ``tools/perf_gate.donation_stats`` reads it straight off the hydrated
  executable.
- **Opt-in and fail-open.** With no cache configured every site keeps
  today's lazy ``jax.jit`` behavior. Any cache failure (serialization
  unsupported, torn file, tampered envelope) falls back to an in-process
  compile and journals why — the cache can make a run faster, never
  break it. A program the compiler refuses is not a cache failure: it
  raises.

Activation: ``configure(dir)`` (process-wide), env
``PADDLE_TPU_AOT_CACHE=dir``, ``paddle_tpu.set_compilation_cache(dir)``
(which also enables jax's native persistent cache), or per-instance
``ServeEngine(..., aot_cache_dir=...)`` / ``Config.aot_cache_dir``.
``tools/aot_cache.py`` lists/verifies/evicts entries and runs warmup
probes from a saved inference model.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import threading
import time

from ..obs import lockdep as _lockdep, metrics as _metrics, trace as _trace

__all__ = [
    "AOTCache", "configure", "configured", "active_cache",
    "resolve_cache", "fingerprint", "fingerprint_digest",
    "load_or_compile", "cache_stats", "warm_inference_model",
    "shared_cache_env", "ENV_DIR", "FORMAT_VERSION",
]

ENV_DIR = "PADDLE_TPU_AOT_CACHE"
FORMAT_VERSION = 2   # 2: header records the executable's device ids
_SUFFIX = ".aot"
_MAGIC = b"PTAOT1\n"

# PADDLE_TPU_* knobs that change what gets COMPILED (not just how a run
# behaves). OPT_LEVEL rewrites the op list before tracing — it is
# already visible in the module text, but keeping it here makes the
# fingerprint self-describing in `aot_cache.py --list` output.
_FINGERPRINT_KNOBS = ("PADDLE_TPU_OPT_LEVEL",)

_DISABLED = object()      # configure-level mask over the env fallback
_ACTIVE = [None]          # configure()'d cache, None (defer to env),
                          # or _DISABLED (force-off, env masked too)
_BY_DIR = {}              # dir -> AOTCache (per-instance caches share)
_LOCK = _lockdep.lock("aot.registry")
# every AOTCache's answers in this process, where the registry's readers
# see them (``obs.snapshot()``, the exporter; the benchmark's
# ``cache_misses`` and ``chip_smoke.py``'s cache line): the same increments
# as an object's own ``hits`` / ``misses``, which are its directory's
_HITS = _metrics.counter("aot.cache.hits")
_MISSES = _metrics.counter("aot.cache.misses")


def fingerprint():
    """Everything OUTSIDE the lowered module that the executable bytes
    depend on. Touches ``jax.devices()`` — call at compile time only
    (the backend exists there); never from import paths."""
    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devs[0].platform,
        "device_count": len(devs),
        "device_kinds": sorted({str(d.device_kind) for d in devs}),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "knobs": {k: os.environ.get(k, "") for k in _FINGERPRINT_KNOBS},
    }


def fingerprint_digest(fp=None):
    fp = fp if fp is not None else fingerprint()
    return hashlib.sha256(
        repr(sorted(fp.items())).encode()).hexdigest()


def _journal_event(**fields):
    """One ``aot`` journal event; inert without an active journal (the
    standard ``if ACTIVE`` hook pattern)."""
    try:
        from ..obs import journal as _journal

        if _journal.ACTIVE is not None:
            _journal.ACTIVE.event("aot", **fields)
    except Exception:
        pass


# -- entry file format --------------------------------------------------------
# <digest>.aot = MAGIC | u64 header_len | JSON header | trees | payload
#
# The header (fingerprint verbatim, digest, kind/label, meta, section
# lengths) is plain JSON so verification and listing NEVER unpickle
# untrusted bytes: a tampered or foreign file is rejected on the header
# alone, and only a fingerprint-verified entry has its (pickled)
# treedefs and serialized-executable payload read at all. Writes are
# atomic (tmp + rename) so a killed writer leaves no torn entry.


def _write_entry(path, header, trees, payload):
    hjson = json.dumps(header, sort_keys=True, default=str).encode()
    # tmp name unique per process AND thread: two threads racing the
    # same digest must not interleave writes into one tmp file (the
    # os.replace of interleaved bytes would publish a torn envelope
    # under a valid digest name)
    tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(struct.pack(">Q", len(hjson)))
            f.write(hjson)
            f.write(trees)
            f.write(payload)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _read_header(f):
    """Parse MAGIC + JSON header from an open entry file, leaving the
    position at the trees section. Raises ValueError on a file that is
    not (or no longer) an AOT envelope."""
    if f.read(len(_MAGIC)) != _MAGIC:
        raise ValueError("not an AOT envelope")
    (hlen,) = struct.unpack(">Q", f.read(8))
    if hlen > 1 << 24:  # a sane header is KBs; refuse absurd lengths
        raise ValueError("oversized header")
    header = json.loads(f.read(hlen))
    if not isinstance(header, dict):
        raise ValueError("header is not an object")
    return header


def _read_entry(path, want_body=True):
    """(header, trees, payload); the latter two ``None`` when
    ``want_body`` is False (listing/verify read metadata only)."""
    with open(path, "rb") as f:
        header = _read_header(f)
        if not want_body:
            return header, None, None
        trees = f.read(int(header["trees_len"]))
        payload = f.read(int(header["payload_len"]))
        if len(trees) != int(header["trees_len"]) or \
                len(payload) != int(header["payload_len"]):
            raise ValueError("truncated entry")
    return header, trees, payload


class AOTCache:
    """One on-disk cache directory of serialized executables.

    Entry file = ``<digest>.aot``: a JSON header holding the
    fingerprint (verbatim, re-verified at load), the site kind/label,
    the ids of the devices the executable runs on, and meta (original
    compile_ms, creation time), followed by the
    pickled in/out pytree defs and the serialized executable payload
    (``jax.experimental.serialize_executable``)."""

    def __init__(self, directory):
        self.dir = os.path.abspath(str(directory))
        os.makedirs(self.dir, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.rejects = 0   # present-but-refused entries (stale/torn)
        self._lock = _lockdep.lock("aot.cache")

    # -- keys -----------------------------------------------------------------
    def key_for(self, lowered, kind, extra=""):
        """Content digest for one lowered computation: fingerprint +
        site kind + the full StableHLO module text. ``extra`` folds in
        anything the module can't see (none needed today; kept for
        forward compatibility)."""
        h = hashlib.sha256()
        h.update(fingerprint_digest().encode())
        h.update(b"\x00" + str(kind).encode())
        h.update(b"\x00" + repr(extra).encode())
        h.update(b"\x00" + lowered.as_text().encode())
        return h.hexdigest()

    def _path(self, digest):
        return os.path.join(self.dir, digest + _SUFFIX)

    # -- load -----------------------------------------------------------------
    def load(self, digest):
        """(Compiled, meta) on a verified hit; (None, reason) otherwise.
        A present-but-wrong entry NEVER reaches a deserializer — pickle
        included: the JSON header's stored fingerprint and digest must
        match the live ones before the treedef/payload bytes are even
        read (defends against env drift the digest didn't cover — and
        against a tampered or hash-collided file)."""
        path = self._path(digest)
        if not os.path.exists(path):
            with self._lock:
                self.misses += 1
            _MISSES.inc()
            return None, "miss"
        try:
            with open(path, "rb") as f:
                header = _read_header(f)
                reason = self._verify_header(header, digest)
                if reason is None:
                    trees = f.read(int(header["trees_len"]))
                    payload = f.read(int(header["payload_len"]))
                    if len(trees) != int(header["trees_len"]) or \
                            len(payload) != int(header["payload_len"]):
                        reason = "truncated entry"
        except Exception as e:
            reason = f"unreadable envelope ({type(e).__name__})"
        if reason is not None:
            with self._lock:
                self.rejects += 1
            _journal_event(action="reject", digest=digest, reason=reason)
            return None, reason
        try:
            import jax
            from jax.experimental import serialize_executable as _se

            # hydrate onto the devices the executable was compiled for:
            # left to its default, jax loads over EVERY local device and
            # a one-device program then demands one shard per device
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in header["device_ids"]]
            in_tree, out_tree = pickle.loads(trees)
            exe = _se.deserialize_and_load(payload, in_tree, out_tree,
                                           execution_devices=devices)
        except Exception as e:
            with self._lock:
                self.rejects += 1
            _journal_event(action="reject", digest=digest,
                           reason=f"deserialize failed: "
                                  f"{type(e).__name__}")
            return None, f"deserialize failed ({type(e).__name__})"
        with self._lock:
            self.hits += 1
        _HITS.inc()
        return exe, header.get("meta", {})

    def _verify_header(self, header, digest=None, live=None):
        """None when the entry header is trustworthy, else the refusal
        reason. ``live`` lets batch callers (verify()) compute the
        live fingerprint once instead of per entry."""
        if header.get("format") != FORMAT_VERSION:
            return f"format {header.get('format')} != {FORMAT_VERSION}"
        if digest is not None and header.get("digest") != digest:
            return "digest mismatch (renamed or tampered entry)"
        live = live if live is not None else fingerprint()
        stored = header.get("fingerprint")
        if stored != live:
            drift = sorted(k for k in set(live) | set(stored or {})
                           if (stored or {}).get(k) != live.get(k))
            return f"fingerprint drift: {drift}"
        for k in ("trees_len", "payload_len"):
            if not isinstance(header.get(k), int) or header[k] <= 0:
                return f"missing {k}"
        ids = header.get("device_ids")
        if not isinstance(ids, list) or not ids or \
                not all(isinstance(i, int) for i in ids):
            return "missing device_ids"
        return None

    # -- store ----------------------------------------------------------------
    def store(self, digest, exe, kind, label=None, meta=None):
        """Serialize + atomically publish one compiled executable.
        Returns True on publish; False (journaled) when the backend
        can't serialize this executable — the run continues on the
        in-process compile either way."""
        try:
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(exe)
        except Exception as e:
            _journal_event(action="store_failed", digest=digest,
                           reason=f"serialize: {type(e).__name__}")
            return False
        trees = pickle.dumps((in_tree, out_tree), protocol=4)
        payload = bytes(payload)
        header = {
            "format": FORMAT_VERSION,
            "digest": digest,
            "fingerprint": fingerprint(),
            "kind": str(kind),
            "label": label,
            "meta": dict(meta or {}, created=time.time()),
            "device_ids": [d.id for d in
                           exe.runtime_executable().local_devices()],
            "trees_len": len(trees),
            "payload_len": len(payload),
        }
        try:
            _write_entry(self._path(digest), header, trees, payload)
        except Exception as e:
            _journal_event(action="store_failed", digest=digest,
                           reason=f"write: {type(e).__name__}")
            return False
        with self._lock:
            self.stores += 1
        return True

    # -- introspection (tools/aot_cache.py) -----------------------------------
    def entries(self):
        """Metadata of every entry from the JSON header alone — the
        (possibly multi-MB) executable payload is never read: digest,
        kind/label, bytes on disk, age, fingerprint summary, original
        compile_ms. Unreadable files are listed with an ``error`` field
        instead of being skipped silently."""
        out = []
        for name in sorted(os.listdir(self.dir)):
            if not name.endswith(_SUFFIX):
                continue
            path = os.path.join(self.dir, name)
            rec = {"digest": name[:-len(_SUFFIX)],
                   "bytes": os.path.getsize(path),
                   "age_s": max(0.0, time.time() - os.path.getmtime(path))}
            try:
                header, _, _ = _read_entry(path, want_body=False)
                rec.update({
                    "kind": header.get("kind"),
                    "label": header.get("label"),
                    "compile_ms": (header.get("meta") or {}).get(
                        "compile_ms"),
                    "jax": (header.get("fingerprint") or {}).get("jax"),
                    "platform": (header.get("fingerprint") or {}).get(
                        "platform"),
                })
            except Exception as e:
                rec["error"] = f"{type(e).__name__}"
            out.append(rec)
        return out

    def verify(self):
        """Re-check every entry's header against the live fingerprint
        (headers only — no payload read, nothing unpickled). Returns
        (ok, stale) digest lists — stale entries would refuse to load,
        so ``--evict --stale`` can clear them."""
        ok, stale = [], []
        live = fingerprint()  # once, not per entry (jax.devices())
        for name in sorted(os.listdir(self.dir)):
            if not name.endswith(_SUFFIX):
                continue
            digest = name[:-len(_SUFFIX)]
            try:
                header, _, _ = _read_entry(
                    os.path.join(self.dir, name), want_body=False)
                reason = self._verify_header(header, digest, live=live)
            except Exception:
                reason = "unreadable"
            (ok if reason is None else stale).append(digest)
        return ok, stale

    def evict(self, digests=None, older_than_s=None, stale_only=False):
        """Remove entries: an explicit digest list, everything older
        than ``older_than_s``, only fingerprint-stale ones, or (no
        filter) the whole cache. Returns the number removed."""
        if stale_only:
            _, digests = self.verify()
        removed = 0
        for name in list(os.listdir(self.dir)):
            if not name.endswith(_SUFFIX):
                continue
            digest = name[:-len(_SUFFIX)]
            path = os.path.join(self.dir, name)
            if digests is not None and digest not in digests:
                continue
            if older_than_s is not None and \
                    time.time() - os.path.getmtime(path) < older_than_s:
                continue
            try:
                os.unlink(path)
                removed += 1
            except OSError:
                pass
        return removed

    def stats(self):
        return {"hits": self.hits, "misses": self.misses,
                "stores": self.stores, "rejects": self.rejects,
                "entries": sum(1 for n in os.listdir(self.dir)
                               if n.endswith(_SUFFIX)),
                "dir": self.dir}


# -- process-wide activation --------------------------------------------------


def configure(directory):
    """Activate the process-wide AOT cache. Accepts a directory, an
    ``AOTCache``, or a previous ``configured()`` snapshot to restore
    (including the disabled sentinel). ``None`` clears the explicit
    setting — the env ``PADDLE_TPU_AOT_CACHE`` fallback applies again;
    use ``disable()`` to force-off an env-activated cache too. Returns
    the AOTCache (or None). ``set_compilation_cache`` routes here so
    one call persists BOTH jax's native compilation cache and the
    framework's executable envelopes."""
    if directory is None or directory is _DISABLED:
        _ACTIVE[0] = directory
        return None
    _ACTIVE[0] = directory if isinstance(directory, AOTCache) \
        else _cache_at(directory)
    return _ACTIVE[0]


def disable():
    """Force the AOT cache OFF for this process, masking the env
    ``PADDLE_TPU_AOT_CACHE`` fallback as well — the programmatic off
    switch ``set_compilation_cache(None)`` promises. Undo with
    ``configure(dir)`` or ``configure(None)`` (the latter re-enables
    the env fallback)."""
    _ACTIVE[0] = _DISABLED


def configured():
    """The explicit configure()/disable() state (None when only the
    env var — or nothing — is active): snapshot this before a
    temporary ``configure()`` and pass it back to restore."""
    return _ACTIVE[0]


def _cache_at(directory):
    d = os.path.abspath(str(directory))
    with _LOCK:
        c = _BY_DIR.get(d)
        if c is None:
            c = _BY_DIR[d] = AOTCache(d)
    return c


def active_cache():
    """The cache compile sites should consult: an explicit
    ``configure()``/``disable()`` wins; otherwise env
    ``PADDLE_TPU_AOT_CACHE`` (re-read per call — a subprocess gets it
    from its environment with no Python-side setup); else None
    (lazy-jit behavior everywhere)."""
    a = _ACTIVE[0]
    if a is _DISABLED:
        return None
    if a is not None:
        return a
    d = os.environ.get(ENV_DIR, "")
    return _cache_at(d) if d else None


def resolve_cache(directory=None):
    """Per-instance override hook (``ServeEngine(aot_cache_dir=...)``,
    ``Config.aot_cache_dir``): an explicit directory wins, else the
    process-wide active cache."""
    if directory is not None:
        return _cache_at(directory)
    return active_cache()


def cache_stats():
    """Stats of the active process-wide cache, or None."""
    c = active_cache()
    return c.stats() if c is not None else None


def shared_cache_env(directory):
    """The env block that hands a SHARED executable cache to a fleet of
    worker processes (``serving.fleet.ReplicaPool``): creates the
    directory and returns ``{ENV_DIR: abspath}``. Concurrent workers
    compiling the same digest race only on the atomic tmp+rename
    publish (last writer wins, both envelopes identical), so the first
    incarnation of every replica can warm the cache in parallel and
    every relaunch/scale-up after that hydrates instead of compiling."""
    d = os.path.abspath(str(directory))
    os.makedirs(d, exist_ok=True)
    return {ENV_DIR: d}


# -- the one compile-site flow ------------------------------------------------


def load_or_compile(jit_fn, args, kind, cache=None, label=None):
    """The whole AOT flow for one compile site: trace (cheap), hash the
    module, hydrate from disk or compile + publish.

    Returns ``(compiled, info)`` where ``compiled`` is a
    ``jax.stages.Compiled`` callable with the SAME calling convention
    as ``jit_fn`` (donation and shardings baked in), or ``(None,
    None)`` with no cache active. A program that fails to lower or
    compile raises here, as it would on the lazy path: only the cache's
    own reads and writes fail open. ``info``:

    - ``source``: ``"aot_disk"`` (hydrated) or ``"xla"`` (compiled
      here; published unless ``stored`` is False)
    - ``deserialize_ms`` / ``compile_ms_avoided`` on a hit
    - ``xla_compile_ms`` on a miss (genuine XLA wall time — unlike the
      lazy path's trace-side ``compile_ms``)
    - ``digest``, ``miss_reason``

    On the obs ring (phase records, always written): ``aot.lower``,
    ``aot.key``, then ``aot.load`` on a hit or ``aot.compile`` and
    ``aot.store`` on a miss, each with ``site`` (= ``kind``), ``label``,
    ``digest`` and ``source``; ``deserialize_ms`` and ``xla_compile_ms``
    are the durations of ``aot.load`` and ``aot.compile``.
    """
    cache = cache if cache is not None else active_cache()
    if cache is None:
        return None, None
    import jax

    # each stage is a phase record on the obs ring (always written: once a
    # compile); what the stages learn (digest, source) joins every record
    # of this call through the one ``attrs``
    attrs = {"site": kind, "label": label}
    with _trace.phase("aot.lower", attrs):
        lowered = jit_fn.lower(*args)
    # the input treedef joins the digest: pytree METADATA (e.g. a
    # TrainStep's opt-state dict keyed by param names) is part of
    # the serialized calling convention but invisible in the
    # module text — two builds with identical StableHLO and
    # different dict keys must not share an entry
    with _trace.phase("aot.key", attrs):
        digest = attrs["digest"] = cache.key_for(
            lowered, kind,
            extra=str(jax.tree_util.tree_structure(args)))
    # timed from here: deserialize_ms is the cost of READING the cache
    # (disk + deserialize), not the trace/hash above — both paths pay
    # those identically. A hit is the record ``aot.load``, written from
    # the same two clock reads
    t0 = time.perf_counter()
    exe, meta = cache.load(digest)
    if exe is not None:
        t1 = time.perf_counter()
        attrs["source"] = "aot_disk"
        _trace.record("aot.load", t0, t1, **attrs)
        info = {"source": "aot_disk", "digest": digest,
                "deserialize_ms": (t1 - t0) * 1e3,
                "compile_ms_avoided": (meta or {}).get("compile_ms")}
        _journal_event(action="hit", kind=kind, digest=digest,
                       deserialize_ms=info["deserialize_ms"],
                       compile_ms_avoided=info["compile_ms_avoided"])
        return exe, info
    miss_reason = meta  # load() returns the refusal/miss reason here
    attrs["source"] = "xla"
    with _trace.phase("aot.compile", attrs) as compiling:
        exe = lowered.compile()
    xla_ms = compiling.dur_us / 1e3
    with _trace.phase("aot.store", attrs):
        stored = cache.store(digest, exe, kind, label=label,
                             meta={"compile_ms": xla_ms})
    return exe, {"source": "xla", "digest": digest,
                 "xla_compile_ms": xla_ms, "stored": stored,
                 "miss_reason": miss_reason}


def provenance_fields(info):
    """The journal `compile`-event provenance fields for one
    ``load_or_compile`` info dict: ``via`` ("xla" | "aot_disk") plus
    ``deserialize_ms``/``compile_ms_avoided`` on a hit or
    ``xla_compile_ms`` on a miss. Empty dict for ``info=None`` (AOT
    inactive) so call sites can splat it unconditionally."""
    if not info or not info.get("source"):
        return {}
    prov = info["source"]
    out = {"via": prov}
    if prov == "aot_disk":
        out["deserialize_ms"] = info.get("deserialize_ms")
        if info.get("compile_ms_avoided") is not None:
            out["compile_ms_avoided"] = info["compile_ms_avoided"]
    elif info.get("xla_compile_ms") is not None:
        out["xla_compile_ms"] = info["xla_compile_ms"]
    return out


# -- warmup ------------------------------------------------------------------


def warm_inference_model(path_prefix, buckets=(1,), cache=None):
    """Warm the executable cache from a SAVED inference model: load it
    through the real ``Predictor`` (the exact code path a serving
    replica runs) and drive one zeroed batch per bucket size, so the
    replica's first real request hydrates instead of compiling.
    Returns the number of entries warmed. Feed shapes come from the
    saved program; dynamic non-batch dims make a feed unwarmable (it
    is skipped with a journal event, not an error)."""
    import numpy as np

    from ..inference.predictor import Config, Predictor

    cfg = Config(str(path_prefix))
    if cache is not None:
        cfg.aot_cache_dir = cache.dir if isinstance(cache, AOTCache) \
            else str(cache)
    pred = Predictor(cfg)
    blk = pred._program.global_block
    warmed = 0
    for b in buckets:
        feed = {}
        ok = True
        for name in pred.get_input_names():
            v = blk.vars.get(name)
            if v is None:
                ok = False
                break
            dyn = set(getattr(v, "dynamic_dims", ()) or ())
            if any(d != 0 for d in dyn):
                ok = False  # dynamic non-batch dim: nothing to pad to
                break
            shape = [int(s) for s in v.shape]
            if shape:
                shape[0] = int(b)  # batch dim follows the bucket
            feed[name] = np.zeros(tuple(shape), np.dtype(v._data.dtype))
        if not ok:
            _journal_event(action="warm_skipped", prefix=str(path_prefix),
                           bucket=int(b), reason="dynamic feed dims")
            continue
        try:
            pred.run(feed)
            warmed += 1
        except Exception as e:
            _journal_event(action="warm_failed", prefix=str(path_prefix),
                           bucket=int(b), reason=type(e).__name__)
    return warmed
