"""ServeEngine: continuous-batching generation over the paged KV cache.

The serving data plane. Each ``step()`` takes one ``Scheduler`` batch
and drives it through two compiled executables:

- **prefill** (one per context-length bucket): encode a newly admitted
  (or preemption-resumed) request's context, scatter its K/V into the
  pages the scheduler allocated, and emit the first generated token —
  the TTFT token.
- **decode** (one per batch-size bucket): for every in-flight request,
  embed its newest token, append that token's K/V to its pages, run
  the ragged ``paged_decode_attention`` kernel across the whole mixed
  batch, and emit each request's next token. The K/V pools are
  **donated** through this step (``donate_argnums``), so the pool
  buffer updates in place in HBM every step — ``tools/perf_gate.py``
  asserts the ``input_output_alias`` on the compiled HLO.

Decode semantics follow ``inference.decoder.greedy_search`` (argmax
continuation, EOS stop, fixed ``max_new_tokens`` cap); a ``sample_fn``
swaps the token choice (the beam analog lives in ``inference.decoder``
— beams multiply KV pages per request and stay out of the continuous
batch). Cache pressure reuses the resilience machinery end to end:
page exhaustion surfaces as ``CachePressureError`` (a
``TransientError``), and the engine relieves it inside
``resilience.policy.retry_call`` — preempting the scheduler's chosen
victim per retry under the policy's bounded budget, so every relief
attempt ticks ``resilience.retries`` and journals the same
``resilience.retry`` events a training guard would.

Per-request observability: lifecycle span markers
(``serving.request.{admit,first_token,finish}``), ``serving.*``
metrics (queue-depth gauge; TTFT/TPOT/e2e latency histograms with
p50/p99), and — when a run journal is active — one ``request`` record
per finished request (arrival/admit/first-token/finish timestamps,
pages held, preemptions) that ``tools/run_report.py`` summarizes.
All hooks follow the established zero-overhead contract: inactive
journal = one None check.
"""
from __future__ import annotations

import itertools
import threading
import time
import weakref

import numpy as np

from ..obs import journal as _journal
from ..obs import lockdep as _lockdep
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..obs import usage as _usage
from ..resilience import inject as _inject
from ..resilience.policy import RecoveryPolicy, retry_call
from .kv_cache import (CachePressureError, PagedKVCache,
                       PageAllocationError, write_tokens)
from .scheduler import CANCELLED, FINISHED, RUNNING, Request, Scheduler

__all__ = ["ServeEngine", "TinyLM", "live_engines", "request_phases",
           "preempt_loss_ms"]

# process-wide replica registry: every ServeEngine registers a weakref
# at construction, so the SLO exporter (obs.export.MetricsExporter with
# no explicit engine list) discovers every live replica in the process
# without any wiring. Weak by design — the registry must never keep a
# replaced replica (and its donated KV pools) alive.
_ENGINES_LOCK = _lockdep.lock("serving.engines")
_ENGINES: list = []
_REPLICA_IDS = itertools.count()


def live_engines():
    """Every ServeEngine constructed in this process and still alive,
    oldest first — the default scrape set for ``obs.export``."""
    out = []
    with _ENGINES_LOCK:
        keep = []
        for ref in _ENGINES:
            eng = ref()
            if eng is not None:
                keep.append(ref)
                out.append(eng)
        _ENGINES[:] = keep
    return out

# latency buckets: sub-ms CPU toy decode through multi-second cold
# prefill-compiles; +inf overflow implicit
_LAT_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
                250.0, 500.0, 1000.0, 2500.0, 5000.0, 15000.0, 60000.0)
_M_TTFT = _metrics.histogram("serving.ttft_ms", _LAT_BUCKETS)
_M_TPOT = _metrics.histogram("serving.tpot_ms", _LAT_BUCKETS)
_M_E2E = _metrics.histogram("serving.e2e_ms", _LAT_BUCKETS)
_M_STEP = _metrics.histogram("serving.step_ms", _LAT_BUCKETS)
_M_TOKENS = _metrics.counter("serving.tokens_generated")
_M_FINISHED = _metrics.counter("serving.requests_finished")
_M_CANCELLED = _metrics.counter("serving.requests_cancelled")

_DECODE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def _bucket(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    return n


def _len_bucket(n, floor):
    """Context-length bucket for prefill: next power of two (>= the
    page size). Unlike batch sizes, context lengths are unbounded —
    a fixed table would compile one executable per distinct length
    past its cap (and every preemption-resume depth is a distinct
    length); powers of two bound the cache at log2(max_seq_len)."""
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


class TinyLM:
    """A deterministic one-layer attention LM — the built-in serving
    model for tests and ``tools/serve_bench.py`` (the stand-in for the
    Gemma-class decoder of arXiv 2605.25645's comparison). Tied
    embeddings, one causal attention layer with residual, weights
    drawn from a seeded RNG so every run replays bitwise.

    ``reference_generate`` is the dense oracle: step-by-step greedy
    decode with a contiguous (unpaged) KV history — the engine's
    paged continuous-batching output is pinned token-for-token
    against it.
    """

    def __init__(self, vocab_size=32, num_heads=2, head_dim=8, seed=0):
        import jax.numpy as jnp

        self.vocab_size = int(vocab_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.embed_dim = self.num_heads * self.head_dim
        rng = np.random.RandomState(seed)
        E = self.embed_dim

        def w(*shape):
            return jnp.asarray(
                rng.randn(*shape).astype(np.float32) / np.sqrt(shape[0]))

        self.embedding = w(self.vocab_size, E)
        self.wq, self.wk, self.wv, self.wo = w(E, E), w(E, E), w(E, E), \
            w(E, E)

    def qkv(self, token_ids):
        """(N,) ids -> (emb (N,E), q/k/v (N,H,D))."""
        import jax.numpy as jnp

        emb = jnp.take(self.embedding, token_ids, axis=0)
        N = emb.shape[0]
        shp = (N, self.num_heads, self.head_dim)
        return (emb, (emb @ self.wq).reshape(shp),
                (emb @ self.wk).reshape(shp),
                (emb @ self.wv).reshape(shp))

    def head(self, attn, emb):
        """attention out (N,H,D) + residual -> logits (N,V) (tied)."""
        out = attn.reshape(emb.shape) @ self.wo + emb
        return out @ self.embedding.T

    def reference_generate(self, prompt, max_new_tokens, eos_id=None):
        """Dense greedy decode (contiguous KV, no paging): the oracle."""
        import jax.numpy as jnp

        from ..ops.pallas.paged_attention import dense_decode_reference

        ctx = [int(t) for t in prompt]
        for _ in range(max_new_tokens):
            ids = jnp.asarray(np.asarray(ctx, np.int32))
            emb, q, k, v = self.qkv(ids)
            attn = dense_decode_reference(
                q[-1:], k[None], v[None])[0]           # (1,H,D)
            logits = self.head(attn[None], emb[-1:])
            nxt = int(jnp.argmax(logits[0]))
            ctx.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        return ctx[len(prompt):]


class ServeEngine:
    """Continuous-batching serve loop over a model + paged KV cache.

    >>> eng = ServeEngine(TinyLM(), PagedKVCache(64, 8, 2, 8))
    >>> r = eng.submit([3, 1, 4], max_new_tokens=8)
    >>> eng.run()                       # until idle
    >>> r.generated

    Threading contract: ``step()``/``run()`` belong to ONE serve-loop
    thread. ``submit()`` and ``cancel()`` are safe from other threads
    (scheduler and cache state are lock-protected); a cancel landing
    while its request is inside the current step's batch takes effect
    at the next step boundary.
    """

    def __init__(self, model, cache, scheduler=None, policy=None,
                 sample_fn=None, interpret=None, clock=None,
                 aot_cache_dir=None, replica_id=None):
        self.model = model
        self.cache = cache
        if cache.num_heads != model.num_heads or \
                cache.head_dim != model.head_dim:
            raise ValueError(
                f"cache geometry ({cache.num_heads}h x {cache.head_dim}d)"
                f" != model ({model.num_heads}h x {model.head_dim}d)")
        if cache.num_layers != 1:
            # the engine's compiled steps read/write layer 0 only; a
            # multi-layer pool would silently waste HBM on layers the
            # engine never touches (the allocator keeps the layer axis
            # for models driving the kernel directly)
            raise ValueError(
                f"ServeEngine drives single-layer models; got a "
                f"num_layers={cache.num_layers} pool")
        if scheduler is not None and scheduler.cache is not cache:
            raise ValueError(
                "scheduler wraps a different PagedKVCache than the one "
                "passed to ServeEngine — pages would allocate in one "
                "pool and be read from the other")
        self.scheduler = scheduler or Scheduler(
            cache, clock=clock if clock is not None else time.monotonic)
        if clock is not None and scheduler is not None:
            raise ValueError("pass clock via the Scheduler when you "
                             "construct one yourself")
        self.clock = self.scheduler.clock
        self.policy = policy or RecoveryPolicy(max_retries=3,
                                               sleep=lambda s: None)
        self.sample_fn = sample_fn
        if interpret is None:
            # interpreter only on the host-CPU backend (tests); on an
            # accelerator the paged kernel compiles through Mosaic
            from ..ops import pallas as _pallas

            interpret = _pallas.auto_interpret()
        self._interpret = bool(interpret)
        self._decode_fns = {}    # bucket -> jitted step
        self._prefill_fns = {}   # length bucket -> jitted prefill
        # AOT executable cache (runtime.aot): a replica constructed
        # with aot_cache_dir= (or under PADDLE_TPU_AOT_CACHE /
        # configure()) hydrates its prefill/decode buckets from disk
        # instead of paying XLA compile per bucket on first traffic
        self._aot_cache_dir = aot_cache_dir
        self._compiles = 0
        self._dispatches = 0
        self.finished = []       # completed Request objects, in order
        self._steps = 0
        self._last_emit = {}     # rid -> last token emission time
        # serializes step() against cancel(): a cancel landing while
        # its request is inside the current batch must wait for the
        # step boundary, or the freed rid KeyErrors the batch build
        # Lock order inside a replica: engine.step -> scheduler ->
        # cache (lockdep-checked under PADDLE_TPU_LOCKDEP)
        self._step_lock = _lockdep.rlock("serving.engine.step")
        # SLO-export identity: stable per process, rides the exporter's
        # replica="N" label so multi-replica scrapes stay attributable.
        # A fleet launcher passes the FLEET-assigned id instead — the
        # per-process counter restarts at 0 in every worker process, so
        # two replicas' scrapes would otherwise collide on replica="0"
        self.replica_id = next(_REPLICA_IDS) if replica_id is None \
            else int(replica_id)
        # per-tenant device-second attribution (obs.usage): charged
        # from step() always-on (plain int/dict arithmetic, the same
        # cost class as the step_ms histogram observe); read pull-only
        self.usage = _usage.UsageMeter(replica_id=self.replica_id)
        # requests that finished mid-step: their journal records are
        # deferred to the end of step() so the pass's device-second
        # charge is already in request_ns when the record is written
        self._finished_this_step = []
        with _ENGINES_LOCK:
            _ENGINES.append(weakref.ref(self))

    # -- intake --------------------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, rid=None, eos_id=None,
               arrival_t=None, trace=None, tenant=None):
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      rid=rid, eos_id=eos_id, arrival_t=arrival_t,
                      trace=trace, tenant=tenant)
        if any(not 0 <= t < self.model.vocab_size for t in req.prompt):
            raise ValueError("prompt token out of vocab range")
        # the deepest context this request can reach is
        # prompt + max_new_tokens - 1 (the final token never needs a
        # slot): reject what can NEVER fit, at the door. An oversize
        # request admitted anyway would ValueError mid-decode (killing
        # the loop for every other in-flight request); a
        # budget-unschedulable one would block the FIFO head forever —
        # a silent stall that starves everything queued behind it
        worst = len(req.prompt) + int(max_new_tokens) - 1
        if worst > self.cache.max_seq_len:
            raise ValueError(
                f"request needs up to {worst} cached tokens > "
                f"max_seq_len {self.cache.max_seq_len}")
        if worst > self.scheduler.token_budget:
            raise ValueError(
                f"request may re-prefill up to {worst} tokens > "
                f"token_budget {self.scheduler.token_budget}: it could "
                "never be (re-)admitted")
        return self.scheduler.submit(req)

    def cancel(self, request):
        """Tear down a request wherever it is (the chaos-kill path):
        pages freed, journaled as cancelled — alloc==free still holds.
        No-op on an already-terminal request: the cancel-vs-complete
        race must not double-journal or rewrite FINISHED state. Blocks
        until any in-flight step() completes (the documented next-
        step-boundary semantics) — tearing pages out from under the
        running batch would KeyError the serve loop."""
        with self._step_lock:
            if request.state in (FINISHED, CANCELLED):
                return
            self.scheduler.finish(request, state=CANCELLED)
            self._last_emit.pop(request.rid, None)
            _M_CANCELLED.inc()
            self._journal_request(request)

    # -- compiled steps ------------------------------------------------------
    def _get_prefill_fn(self, bucket_len):
        fn = self._prefill_fns.get(bucket_len)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.paged_attention import dense_decode_reference

        model, page_size = self.model, self.cache.page_size
        n_page_slots = -(-bucket_len // page_size)
        interpret = self._interpret  # noqa: F841 (dense prefill)

        def prefill(k_pages, v_pages, tokens, length, page_ids):
            # tokens (Lb,) padded; length () true context length;
            # page_ids (n_page_slots,) the sequence's pages (null-padded)
            emb, q, k, v = model.qkv(tokens)
            pos = jnp.arange(bucket_len)
            live = pos < length
            pid = jnp.where(live, page_ids[pos // page_size], 0)
            off = pos % page_size
            k_pages, v_pages = write_tokens(
                k_pages, v_pages, k, v, pid, off)
            qlast = jnp.take(q, length - 1, axis=0)        # (H, D)
            attn = dense_decode_reference(
                qlast[None], k[None], v[None],
                lengths=length[None])[0]                   # (H, D)
            logits = model.head(
                attn[None], jnp.take(emb, length - 1, axis=0)[None])[0]
            return logits, k_pages, v_pages

        fn = jax.jit(prefill, donate_argnums=(0, 1))
        struct = jax.ShapeDtypeStruct
        i32 = np.dtype(np.int32)
        pool_s = struct(
            (self.cache.num_layers, self.cache.num_pages,
             self.cache.page_size, self.cache.num_heads,
             self.cache.head_dim), np.dtype(self.cache.dtype))
        fn, aot_info = self._maybe_aot(
            fn, (pool_s, pool_s, struct((bucket_len,), i32),
                 struct((), i32), struct((n_page_slots,), i32)),
            "serve_prefill")
        self._prefill_fns[bucket_len] = fn
        self._compiles += 1
        self._journal_compile("prefill", bucket=bucket_len,
                              aot_info=aot_info)
        return fn

    def _get_decode_fn(self, bucket, width=None):
        # table width is bucketed by the batch's ACTUAL max pages, not
        # the pool-wide maximum: the kernel grid (and the page DMAs it
        # drives) is (B, width), so a pool-wide table would make every
        # token's K/V traffic O(pool) instead of O(context)
        W = min(width or self.cache.table_width, self.cache.table_width)
        key = (bucket, W)
        entry = self._decode_fns.get(key)
        if entry is not None:
            return entry
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.paged_attention import paged_decode_attention

        model, interpret = self.model, self._interpret

        def decode(k_pages, v_pages, tokens, tables, lengths,
                   slot_pages, slot_offs):
            emb, q, k, v = model.qkv(tokens)
            k_pages, v_pages = write_tokens(
                k_pages, v_pages, k, v, slot_pages, slot_offs)
            attn = paged_decode_attention(
                q, k_pages[0], v_pages[0], tables, lengths,
                interpret=interpret)
            return model.head(attn, emb), k_pages, v_pages

        fn = jax.jit(decode, donate_argnums=(0, 1))
        struct = jax.ShapeDtypeStruct
        pool_s = struct(
            (self.cache.num_layers, self.cache.num_pages,
             self.cache.page_size, self.cache.num_heads,
             self.cache.head_dim), np.dtype(self.cache.dtype))
        i32 = np.dtype(np.int32)
        entry = _DecodeEntry(fn, (
            pool_s, pool_s, struct((bucket,), i32),
            struct((bucket, W), i32), struct((bucket,), i32),
            struct((bucket,), i32), struct((bucket,), i32)), bucket, W)
        entry.fn, aot_info = self._maybe_aot(
            entry.fn, entry.arg_structs, "serve_decode")
        self._decode_fns[key] = entry
        self._compiles += 1
        self._journal_compile("decode", bucket=bucket, table_width=W,
                              aot_info=aot_info)
        return entry

    def warm(self, max_batch=8):
        """Compile (or AOT-hydrate) EVERY bucketed step this engine can
        reach up front: all prefill context-length buckets (the
        ``_len_bucket`` power-of-two ladder from ``page_size`` to
        ``max_seq_len``) and every decode (batch-bucket, table-width)
        pair up to ``max_batch`` lanes. With an AOT cache configured
        this is the replica scale-up story: the FIRST incarnation pays
        XLA once and publishes, every later replica (or relaunch)
        hydrates the whole set from disk before its first request — the
        fleet drill asserts a relaunched replica journals zero
        ``via=="xla"`` compiles. Returns the number of entries warmed.
        (Without a cache the jitted steps still compile lazily on first
        dispatch — warming would build jit wrappers, not executables.)"""
        n = 0
        blen = self.cache.page_size
        while True:
            self._get_prefill_fn(_len_bucket(blen, self.cache.page_size))
            n += 1
            if blen >= self.cache.max_seq_len:
                break
            blen *= 2
        # reachable table widths are _len_bucket(pages, 1) clamped to
        # the pool-wide maximum — enumerate exactly that set
        widths, w = [], 1
        while w < self.cache.table_width:
            widths.append(w)
            w *= 2
        widths.append(self.cache.table_width)
        for b in _DECODE_BUCKETS:
            if b > max(int(max_batch), 1):
                break
            for w in widths:
                self._get_decode_fn(b, width=w)
                n += 1
        return n

    def decode_entry(self, bucket=1):
        """The compiled decode step as a perf-gate entry (``fn`` +
        ``arg_structs``): ``tools/perf_gate.check_entry`` lowers it and
        asserts the donated KV pool aliases."""
        return self._get_decode_fn(_bucket(bucket, _DECODE_BUCKETS))

    # -- the serve loop ------------------------------------------------------
    def step(self):
        """One engine iteration: schedule, prefill admissions, decode
        the running set, retire finished requests. Returns the Batch
        served (falsy when idle)."""
        with self._step_lock:   # cancel() waits for the step boundary
            if _inject.ACTIVE and "replica_kill" in _inject.ACTIVE:
                # serve-loop chaos boundary (the elastic.fire_step_chaos
                # twin): lets the fleet drill kill THIS replica mid-step,
                # gated on serve-step count + replica id. Inactive cost:
                # one empty-dict truthiness test
                _inject.fire("replica_kill", step=self._steps + 1,
                             rank=self.replica_id)
            t0 = self.clock()
            batch = self.scheduler.schedule()
            if not batch:
                return batch
            if _journal.ACTIVE is not None and batch.decodes:
                # reqtrace decode-step mark: which requests decoded at
                # which engine clock — the per-step resolution the
                # assembled timelines anchor decode progress on
                _journal.ACTIVE.event(
                    "req.decode_mark", at=t0, step=self._steps + 1,
                    replica=self.replica_id,
                    rids=[r.rid for r in batch.decodes])
            try:
                with _trace.span("serving.step",
                                 prefills=len(batch.prefills),
                                 decodes=len(batch.decodes)):
                    for req in batch.prefills:
                        p0 = self.clock()
                        self._prefill_one(req)
                        self.usage.charge_prefill(req,
                                                  self.clock() - p0)
                    if batch.decodes:
                        d0 = self.clock()
                        survivors = self._decode_batch(
                            [r for r in batch.decodes
                             if r.state == RUNNING])
                        # the span splits across the lanes that
                        # actually decoded; an all-preempted pass
                        # charges nobody
                        self.usage.charge_decode(survivors,
                                                 self.clock() - d0)
            finally:
                # journal finishes only now: the pass's charge is in
                # request_ns, so the record's device_ns is final
                for req in self._finished_this_step:
                    self._journal_request(req)
                del self._finished_this_step[:]
            self._steps += 1
            step_ms = (self.clock() - t0) * 1e3
            _M_STEP.observe(step_ms)
            return batch

    def run(self, max_steps=None):
        """Serve until idle (or ``max_steps``). Returns steps taken."""
        steps = 0
        while not self.scheduler.idle:
            if max_steps is not None and steps >= max_steps:
                break
            if not self.step():
                break  # budget/pool gridlock: nothing schedulable
            steps += 1
        return steps

    # -- prefill -------------------------------------------------------------
    def _prefill_one(self, req):
        import jax.numpy as jnp

        ctx = req.context
        L = len(ctx)
        bucket_len = _len_bucket(L, self.cache.page_size)
        fn = self._get_prefill_fn(bucket_len)
        n_page_slots = -(-bucket_len // self.cache.page_size)
        tokens = np.zeros(bucket_len, np.int32)
        tokens[:L] = ctx
        pages = self.cache.page_table(req.rid)
        page_ids = np.zeros(n_page_slots, np.int32)
        page_ids[:len(pages)] = pages
        with _trace.span("serving.prefill", rid=req.rid, tokens=L):
            logits, k_pages, v_pages = fn(
                self.cache.k_pages, self.cache.v_pages,
                jnp.asarray(tokens), jnp.asarray(np.int32(L)),
                jnp.asarray(page_ids))
            self.cache.set_pools(k_pages, v_pages)
            self._dispatches += 1
            self._emit_token(req, logits, first=req.first_token_t is None)

    # -- decode --------------------------------------------------------------
    def _relieve_pressure(self, req):
        victim = self.scheduler.preempt_for(req)
        if victim is None:
            raise PageAllocationError(
                f"pool too small for {req.rid!r}: nothing to preempt")
        if _journal.ACTIVE is not None:
            _journal.ACTIVE.event("serving.preempt", victim=victim.rid,
                                  for_request=req.rid)

    def _decode_batch(self, decodes):
        import jax.numpy as jnp

        survivors = []
        for r in decodes:
            if r.state != RUNNING:
                continue  # preempted relieving an earlier lane
            if self.cache.length(r.rid) >= self.cache.max_seq_len:
                # belt-and-braces for requests submitted around
                # ``submit()`` (straight to the scheduler): finish
                # truncated instead of letting extend() ValueError
                # take down the whole serve loop
                self._finish(r)
                continue
            try:
                retry_call(lambda: self.scheduler.extend(r, 1),
                           self.policy, describe=f"extend {r.rid}",
                           before_retry=lambda: self._relieve_pressure(r))
                survivors.append(r)
            except (CachePressureError, PageAllocationError):
                # relief budget spent, or no other victim exists
                # (PageAllocationError from _relieve_pressure): r
                # itself yields its pages and requeues
                self.scheduler.preempt(r)
        # relieving a LATER lane may have preempted an earlier survivor
        # (it was the youngest running) — it no longer holds pages
        survivors = [r for r in survivors if r.state == RUNNING]
        if not survivors:
            return survivors
        n = len(survivors)
        bucket = _bucket(n, _DECODE_BUCKETS)
        rids = [r.rid for r in survivors]
        need = max(len(self.cache.page_table(rid)) for rid in rids)
        entry = self._get_decode_fn(bucket, _len_bucket(need, 1))
        W = entry.table_width
        tokens = np.zeros(bucket, np.int32)
        tokens[:n] = [r.context[-1] for r in survivors]
        tables = np.zeros((bucket, W), np.int32)
        tables[:n] = self.cache.padded_page_tables(rids, width=W)
        lengths = np.zeros(bucket, np.int32)
        lengths[:n] = [self.cache.length(rid) for rid in rids]
        slot_pages = np.zeros(bucket, np.int32)   # padding -> null page
        slot_offs = np.zeros(bucket, np.int32)
        sp, so = self.cache.write_slots(rids)
        slot_pages[:n], slot_offs[:n] = sp, so
        with _trace.span("serving.decode", batch=n, bucket=bucket):
            logits, k_pages, v_pages = entry.fn(
                self.cache.k_pages, self.cache.v_pages,
                jnp.asarray(tokens), jnp.asarray(tables),
                jnp.asarray(lengths), jnp.asarray(slot_pages),
                jnp.asarray(slot_offs))
            self.cache.set_pools(k_pages, v_pages)
            self._dispatches += 1
            logits = np.asarray(logits)    # ONE host sync per step
            for i, r in enumerate(survivors):
                self._emit_token(r, logits[i],
                                 first=r.first_token_t is None)
        return survivors

    # -- token plumbing ------------------------------------------------------
    def _choose(self, logits_row):
        if self.sample_fn is not None:
            return int(self.sample_fn(logits_row))
        return int(np.argmax(np.asarray(logits_row)))

    def _emit_token(self, req, logits_row, first=False):
        now = self.clock()
        tok = self._choose(logits_row)
        req.generated.append(tok)
        _M_TOKENS.inc()
        if first:
            req.first_token_t = now
            _M_TTFT.observe((now - req.arrival_t) * 1e3)
            with _trace.span("serving.request.first_token", rid=req.rid):
                pass
        else:
            _M_TPOT.observe((now - self._last_emit.get(req.rid, now))
                            * 1e3)
        self._last_emit[req.rid] = now
        if req.done:
            self._finish(req)

    def _finish(self, req):
        self.scheduler.finish(req, state=FINISHED)
        self._last_emit.pop(req.rid, None)
        self.finished.append(req)
        _M_FINISHED.inc()
        _M_E2E.observe((req.finish_t - req.arrival_t) * 1e3)
        with _trace.span("serving.request.finish", rid=req.rid,
                         tokens=len(req.generated)):
            pass
        # deferred: step() journals after the pass's usage charge lands
        self._finished_this_step.append(req)

    def _maybe_aot(self, fn, structs, kind):
        """Hydrate one jitted bucket step from the AOT executable cache
        (or compile eagerly + publish; a step the compiler refuses
        raises). ``(fn, None)`` unchanged when no cache is active — the
        lazy jit then compiles on first dispatch."""
        from ..runtime import aot as _aot

        cache = _aot.resolve_cache(self._aot_cache_dir)
        if cache is None:
            return fn, None
        exe, info = _aot.load_or_compile(
            fn, structs, kind=kind, cache=cache,
            label=type(self.model).__name__)
        return (exe, info) if exe is not None else (fn, None)

    # -- observability -------------------------------------------------------
    def _journal_compile(self, kind, aot_info=None, **fields):
        if _journal.ACTIVE is not None:
            from ..runtime import aot as _aot

            _journal.ACTIVE.event("compile", source="serving",
                                  entry=kind, **fields,
                                  **_aot.provenance_fields(aot_info))

    def _journal_request(self, req):
        if _journal.ACTIVE is not None:
            extra = request_phases(req)
            if req.trace is not None:
                extra["trace"] = req.trace
            # chargeback extras: resolved tenant + the int-ns device /
            # page integrals, so obs.usage.rollup_requests rebuilds the
            # per-tenant table from journals alone, exact to the ns
            extra["tenant"] = req.tenant or _usage.DEFAULT_TENANT
            extra["device_ns"] = self.usage.request_ns.get(req.rid, 0)
            extra["page_ns"] = self.cache.closed_page_ns(req.rid)
            _journal.ACTIVE.record_request(
                rid=req.rid, state=req.state,
                arrival_t=req.arrival_t, admit_t=req.admit_t,
                first_token_t=req.first_token_t, finish_t=req.finish_t,
                prompt_tokens=len(req.prompt),
                output_tokens=len(req.generated),
                pages_peak=req.pages_peak,
                preemptions=req.preemptions, replica=self.replica_id,
                **extra)

    def stats(self):
        """Engine + pool + latency snapshot (plain data). Latency
        percentiles are computed from THIS engine's finished requests
        (exact, per-instance) — the ``serving.*`` histograms remain
        the process-wide view and would misattribute other engines'
        samples here."""
        from ..obs.metrics import exact_percentile

        snap = {
            "steps": self._steps, "compiles": self._compiles,
            "dispatches": self._dispatches,
            "finished": len(self.finished),
            "queue_depth": self.scheduler.queue_depth,
            "running": len(self.scheduler.running),
            "preemptions": self.scheduler.preemptions,
            "kv": self.cache.stats(),
            "usage": self.usage.snapshot(),
        }
        fin = list(self.finished)
        lat = {
            "ttft_ms": [(r.first_token_t - r.arrival_t) * 1e3
                        for r in fin if r.first_token_t is not None],
            "tpot_ms": [(r.finish_t - r.first_token_t) * 1e3 /
                        (len(r.generated) - 1)
                        for r in fin if len(r.generated) > 1
                        and r.first_token_t is not None],
            "e2e_ms": [(r.finish_t - r.arrival_t) * 1e3 for r in fin
                       if r.finish_t is not None],
        }
        for name, xs in lat.items():
            if xs:
                snap[name] = {"count": len(xs),
                              "p50": exact_percentile(xs, 50),
                              "p99": exact_percentile(xs, 99)}
        # phase attribution sums over finished requests (the numerators
        # of the per-replica phase-share gauges obs.export publishes):
        # queue (arrival->admit) + prefill + preempt + decode == e2e
        phases = {"queue": 0.0, "prefill": 0.0, "preempt": 0.0,
                  "decode": 0.0}
        for r in fin:
            if r.admit_t is not None and r.arrival_t is not None:
                phases["queue"] += (r.admit_t - r.arrival_t) * 1e3
            p = request_phases(r)
            phases["prefill"] += p.get("prefill_ms", 0.0)
            phases["preempt"] += p.get("preempt_ms", 0.0)
            phases["decode"] += p.get("decode_ms", 0.0)
        snap["phase_ms"] = phases
        return snap


def preempt_loss_ms(req):
    """Total wall time ``req`` spent preempted, in ms: every
    ``preempt_ts[i]`` pairs with ``resume_ts[i]`` (the scheduler stamps
    both), and a final unpaired preempt — the request was torn down
    while still PREEMPTED — pairs with ``finish_t``."""
    loss = 0.0
    for i, p in enumerate(req.preempt_ts):
        end = req.resume_ts[i] if i < len(req.resume_ts) else req.finish_t
        if end is not None:
            loss += (end - p) * 1e3
    return loss


def request_phases(req):
    """Engine-side phase decomposition of one terminal request (ms):
    ``prefill_ms`` (admit -> first token), ``preempt_ms`` (total time
    parked by preemption), ``decode_ms`` (first token -> finish, minus
    preemption loss). Together with the ``queue_ms`` the journal
    derives (arrival -> admit) the four telescope exactly to e2e —
    the attribution invariant ``obs.reqtrace`` builds on. Fields are
    emitted only when their stamps exist (a rejected request has no
    admission, a cancelled one may have no first token)."""
    out = {}
    if req.admit_t is not None and req.first_token_t is not None:
        out["prefill_ms"] = (req.first_token_t - req.admit_t) * 1e3
    if req.finish_t is not None:
        if req.preempt_ts:
            out["preempt_ms"] = preempt_loss_ms(req)
        if req.first_token_t is not None:
            out["decode_ms"] = (req.finish_t - req.first_token_t) * 1e3 \
                - out.get("preempt_ms", 0.0)
    return out


class _DecodeEntry:
    """A perf-gate-shaped cache entry (``fn`` + ``arg_structs``) for
    the engine's compiled decode step, mirroring the Executor's
    ``_Compiled`` contract that ``tools/perf_gate.entry_hlo`` reads."""

    def __init__(self, fn, arg_structs, bucket, table_width):
        self.fn = fn
        self.arg_structs = arg_structs
        self.bucket = bucket
        self.table_width = table_width
        self.examples_hint = bucket
