"""Executor: compile + run a Program.

TPU-native analog of ``python/paddle/fluid/executor.py`` +
``paddle/fluid/framework/executor.cc``. The reference walks the program and
launches one kernel per op; here the whole program is replayed into a single
pure jax function and compiled ONCE per (program version, feed shapes) with
``jax.jit`` — persistable buffers are donated so parameter updates happen
in-place in HBM.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..obs import journal as _journal
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..resilience import inject as _chaos
from .program import (Program, default_main_program, global_scope)

__all__ = ["Executor", "CacheKey"]

# interned once: the run/compile paths tick these without touching the
# registry dict (obs.metrics.reset() zeroes in place, so the references
# stay live forever)
_M_CACHE_HITS = _metrics.counter("executor.jit_cache.hits")
_M_CACHE_MISSES = _metrics.counter("executor.jit_cache.misses")
_M_DISPATCHES = _metrics.counter("executor.dispatches")
_M_COMPILE_MS = _metrics.histogram("executor.compile_ms")
_M_RUN_MS = _metrics.histogram("executor.run_ms")
_M_FETCH_MS = _metrics.histogram("executor.fetch_ms")


@dataclasses.dataclass(frozen=True)
class CacheKey:
    """Named executor jit-cache key.

    Replaces the old positional tuple, whose layout was an append-order
    trap: every new axis (optimize level, data parallelism, now fused
    step count) had to slot in at exactly the right position or silently
    alias unrelated entries — and tests pinned magic indices like
    ``k[-2]``. Fields are named; add new axes as new fields.

    ``steps`` is ``None`` for the single-step path and the microbatch
    count K for fused ``lax.scan`` entries (``Executor.run_steps``) —
    the same program at the same feed shapes compiles to a different
    executable per K, so K is a genuine cache axis.

    ``comm`` is ``None`` for the implicit-GSPMD data-parallel path and
    ``CommOptions.cache_axis()`` for comm-efficient entries
    (``dist.gradcomm``): bucket layout / accumulation / quantization
    each change the compiled exchange, so they key distinct
    executables.

    ``plan`` is ``None`` for hand-specified parallelism and
    ``ShardingPlan.cache_axis()`` for ``fleet.auto_parallel`` entries:
    the plan's mesh layout and per-variable PartitionSpecs are baked
    into the executable's shardings, so two different plans over the
    same program/feeds are genuinely different executables.
    """

    program_uid: int
    program_version: int
    feed_names: tuple
    feed_shapes: tuple
    fetch_names: tuple
    optimize_level: int
    steps: int | None
    data_parallel: bool
    allow_replicated_fallback: bool
    comm: tuple | None = None
    plan: tuple | None = None


class _Compiled:
    def __init__(self, fn, feed_names, persist_in, persist_out, fetch_names):
        self.fn = fn
        self.feed_names = feed_names
        self.persist_in = persist_in
        self.persist_out = persist_out
        self.fetch_names = fetch_names


class Executor:
    def __init__(self, place=None, optimize_level=None):
        import os

        self.place = place
        self._cache: dict = {}
        # default pass pipeline level (see analysis.default_optimize_passes):
        # 0 = verify only, 1 = identity forwarding + DCE, 2 = + CSE.
        # Overridable per run() call and via PADDLE_TPU_OPT_LEVEL.
        if optimize_level is None:
            optimize_level = int(os.environ.get("PADDLE_TPU_OPT_LEVEL", "1"))
        self.optimize_level = int(optimize_level)
        self.last_diagnostics = None  # DiagnosticReport of the last compile
        self._cache_hits = 0    # this executor's share of the global
        self._cache_misses = 0  # executor.jit_cache.* counters
        self._dispatches = 0    # compiled-fn calls (run + run_steps);
        # process-wide mirror: obs.metrics executor.dispatches. The
        # perf gates (tools/perf_gate.py) read this to assert "1 compile
        # + 1 dispatch per K fused steps".

    def close(self):
        self._cache.clear()

    @property
    def dispatches(self):
        """Compiled-fn invocations so far (run + run_steps) — the cheap
        public read for compiled-call-count gates; pairs with
        ``cache_stats()['misses']`` (= compiles). Kept OUT of the
        default ``cache_stats()`` dict (its {hits,misses,size} shape is
        a pinned contract) and cheap unlike ``per_entry=True`` (which
        pays the lazy per-entry analysis)."""
        return self._dispatches

    # -- program -> pure function ------------------------------------------
    @staticmethod
    def _run_ops(env, ops, amp_cast):
        """Replay one op list over a name->array environment (the core
        interpreter loop, shared by the whole-program replay and the
        comm-efficient split replay)."""
        for op in ops:
            args = [env[n] if n is not None else None
                    for n in op.input_names]
            if amp_cast is not None:
                args = amp_cast(op.type, args)
            out = op.fn(*args, **op.attrs)
            if isinstance(out, tuple):
                for name, o in zip(op.output_names, out):
                    env[name] = o
            else:
                env[op.output_names[0]] = out
        return env

    @staticmethod
    def _replay_fn(program, ops, feed_names, updated_names, frozen_names,
                   fetch_names):
        ops = list(ops)
        consts = dict(program._constants)
        amp_cast = _amp_cast_fn(getattr(program, "_amp_cfg", None))

        def fn(feeds, updated, frozen):
            env = dict(consts)
            env.update(zip(feed_names, feeds))
            env.update(zip(updated_names, updated))
            env.update(zip(frozen_names, frozen))
            Executor._run_ops(env, ops, amp_cast)
            return ([env[n] for n in fetch_names],
                    [env[n] for n in updated_names])

        return fn

    def _comm_raw(self, program, ops, feed_names, fetch_names, shapes,
                  updated, frozen, steps, comm, mesh, scope, blk):
        """Comm-efficient data-parallel replay (``dist.gradcomm``).

        Instead of replaying the whole program under implicit GSPMD
        (one all-reduce per parameter gradient, placed by the
        partitioner), the op list is split at the backward/update
        boundary: the forward+backward segment runs under ``jax.vmap``
        over an explicit device-major batch axis — embarrassingly
        parallel, zero collectives — producing every gradient as an
        ``(ndev, ...)`` tensor of per-device partial sums; the exchange
        (bucketed / accumulated / int8-quantized all-reduce) is then
        explicit jax code; the update segment runs once on the reduced
        global gradients. Returns ``(raw_fn, state_var_names, plan,
        handles_steps)`` — ``handles_steps`` means the fn already
        consumes the whole stacked ``(K, ...)`` window (the
        accumulate_steps > 1 nested-scan form) and must not be wrapped
        in the generic single-level scan.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..dist import gradcomm as gc

        ndev = int(np.prod(mesh.devices.shape))
        N = int(comm.accumulate_steps)
        if N > 1:
            if not steps:
                raise ValueError(
                    f"accumulate_steps={N} needs the fused path: drive "
                    "the program through Executor.run_steps(steps=K) so "
                    "accumulation lives inside the scan body")
            if int(steps) % N:
                raise ValueError(
                    f"accumulate_steps={N} must divide the fused window "
                    f"(steps={steps}): partial accumulation windows "
                    "would silently change the effective batch")
        persist_set = set(updated) | set(frozen)
        comp_ops, update_ops, cross = gc.split_update_segment(ops)
        if comm.quantize and any(op.type.startswith("amp_")
                                 for op in update_ops):
            raise ValueError(
                "quantize='int8' cannot compose with AMP dynamic loss "
                "scaling: the exchange runs on SCALED gradients, so "
                "error-feedback residuals would live in loss-scale "
                "units and an overflow step would quantize inf into "
                "the persistent residual")
        cross = [n for n in cross if n not in persist_set
                 and n not in program._constants]
        if not cross:
            raise ValueError(
                "comm-efficient DP found no gradients crossing the "
                "backward/update boundary — nothing to exchange")
        grad_dtypes = {n: blk.var(n)._data.dtype for n in cross}
        plan = gc.plan_buckets(
            [(n, tuple(blk.var(n)._data.shape), np.dtype(grad_dtypes[n]))
             for n in cross], comm, ndev)

        # which feeds carry the batch axis (shapes are per-step even on
        # the fused path — same rule as feed_sharding below)
        vmap_feed = [len(s) >= 1 and s[0] > 0 and s[0] % ndev == 0
                     for s, _ in shapes]
        if shapes and not any(vmap_feed):
            dims = {n: s for (s, _), n in zip(shapes, feed_names)}
            raise ValueError(
                f"comm-efficient DP needs a feed whose leading dim "
                f"divides the {ndev}-device data mesh (feed shapes: "
                f"{dims}); there is no gradient exchange to optimize on "
                "a fully replicated step")

        comp_written = set()
        for op in comp_ops:
            comp_written.update(op.output_names)
        comp_persist = [n for n in updated if n in comp_written]
        comp_fetches = [n for n in fetch_names if n in comp_written]
        if N > 1:
            bad = [n for n in fetch_names if n not in comp_written]
            if bad:
                raise ValueError(
                    f"accumulate_steps={N} needs per-microbatch fetches, "
                    f"but {bad} come from the once-per-window update "
                    "segment (fetch forward/backward values instead)")

        consts = dict(program._constants)
        amp_cast = _amp_cast_fn(getattr(program, "_amp_cfg", None))
        need = list(dict.fromkeys(cross + comp_fetches + comp_persist))

        # -- exchange state (quantized path): per-bucket error-feedback
        # residuals + the stochastic-rounding counter, as @comm@*
        # persistables so they ride the donated carry, checkpoints, and
        # the elastic ProgramStateAdapter like any other training state
        state_names = []
        if comm.quantize:
            for i, b in enumerate(plan.buckets):
                name = gc.EF_PREFIX + str(i)
                ex = blk.vars.get(name)
                if ex is None or tuple(ex._data.shape) != (ndev, b.padded):
                    blk.vars.pop(name, None)
                    blk.create_var(name=name, shape=(ndev, b.padded),
                                   dtype="float32", persistable=True)
                    scope.set(name, jax.device_put(
                        jnp.zeros((ndev, b.padded), jnp.float32),
                        NamedSharding(mesh, P("data", None))))
                elif scope.find_var(name) is None:
                    scope.set(name, jax.device_put(
                        jnp.zeros((ndev, b.padded), jnp.float32),
                        NamedSharding(mesh, P("data", None))))
                state_names.append(name)
            # drop leftovers from a previously different bucket layout
            j = plan.n_buckets
            while blk.vars.pop(gc.EF_PREFIX + str(j), None) is not None:
                j += 1
            if not blk.has_var(gc.STEP_VAR):
                blk.create_var(name=gc.STEP_VAR, shape=(), dtype="int32",
                               persistable=True)
            if scope.find_var(gc.STEP_VAR) is None:
                scope.set(gc.STEP_VAR, jnp.int32(0))
            state_names.append(gc.STEP_VAR)
        n_base = len(updated)

        def comp_shard(feed_vals, upd_vals, frz_vals):
            env = dict(consts)
            env.update(zip(feed_names, feed_vals))
            env.update(zip(updated, upd_vals))
            env.update(zip(frozen, frz_vals))
            Executor._run_ops(env, comp_ops, amp_cast)
            return [env[n] for n in need]

        def vm_comp(feed_vals, upd_vals, frz_vals):
            """Reshape batch feeds device-major and vmap the
            forward+backward over the device axis."""
            batched, axes = gc.device_major(feed_vals, ndev, mesh,
                                            batch_flags=vmap_feed)
            outs = jax.vmap(
                lambda fv: comp_shard(fv, upd_vals, frz_vals),
                in_axes=(axes,))(batched)
            return dict(zip(need, outs))

        def aggregate(name, val):
            """Per-shard (ndev, ...) value -> global value: batch-shaped
            vars concatenate back to the full batch (exact); batch-
            reduced floats average across shards (the loss under a
            mean-type loss; rank-local-BN-style stats), integers sum."""
            lshape = tuple(blk.var(name)._data.shape)
            if val.ndim >= 2 and \
                    (val.shape[1] * ndev,) + tuple(val.shape[2:]) == lshape:
                return jnp.reshape(
                    val, (val.shape[1] * ndev,) + tuple(val.shape[2:]))
            red = val.sum(0)
            if jnp.issubdtype(val.dtype, jnp.floating) and \
                    comm.gradient_scale == "mean":
                red = red / ndev
            return red

        def flatten_cross(pershard):
            return plan.flatten_local(
                {n: pershard[n].astype(jnp.float32) for n in cross})

        def run_update(env, reduced, state, pershard_persist,
                       pershard_fetches):
            """The once-per-exchange tail: install aggregated comp
            values + reduced global grads, replay the update segment,
            advance the exchange state."""
            globals_ = plan.unflatten(reduced, dtypes=grad_dtypes)
            env.update(pershard_persist)
            env.update(pershard_fetches)
            env.update(globals_)
            Executor._run_ops(env, update_ops, amp_cast)
            if comm.quantize:
                new_resid, step_ctr = state
                new_state = list(new_resid) + [step_ctr + 1]
            else:
                new_state = []
            return env, new_state

        if N == 1:
            def raw(feeds, upd_all, frz_vals):
                upd_vals = list(upd_all[:n_base])
                state = list(upd_all[n_base:])
                residuals = state[:-1] if comm.quantize else None
                salt = state[-1] if comm.quantize else None
                pershard = vm_comp(feeds, upd_vals, frz_vals)
                reduced, new_resid = gc.exchange_bucketed(
                    plan, flatten_cross(pershard), mesh,
                    residuals=residuals, salt=salt)
                env = dict(consts)
                env.update(zip(feed_names, feeds))
                env.update(zip(updated, upd_vals))
                env.update(zip(frozen, frz_vals))
                env, new_state = run_update(
                    env, reduced, (new_resid, salt),
                    {n: aggregate(n, pershard[n]) for n in comp_persist},
                    {n: aggregate(n, pershard[n]) for n in comp_fetches})
                return ([env[n] for n in fetch_names],
                        [env[n] for n in updated] + new_state)

            return raw, tuple(state_names), plan, False

        # -- accumulate_steps > 1: nested scan over (K/N, N) windows.
        # The inner scan accumulates LOCAL per-device bucket partials
        # (zero communication); the exchange + update segment run once
        # per window, so the all-reduce fires once per N microbatches.
        K, W = int(steps), int(steps) // N

        def raw(stacked_feeds, upd_all, frz_vals):
            resh = [jnp.reshape(f, (W, N) + tuple(f.shape[1:]))
                    for f in stacked_feeds]

            def outer(carry, feeds_w):
                base, state = carry
                residuals = state[:-1] if comm.quantize else None
                salt = state[-1] if comm.quantize else None

                def inner(ic, feeds_k):
                    accs, pvals = ic
                    upd_cur = list(base)
                    for idx, n in enumerate(updated):
                        if n in comp_persist:
                            upd_cur[idx] = pvals[comp_persist.index(n)]
                    pershard = vm_comp(list(feeds_k), upd_cur, frz_vals)
                    accs = [a + f for a, f in
                            zip(accs, flatten_cross(pershard))]
                    new_pvals = [aggregate(n, pershard[n])
                                 for n in comp_persist]
                    fvals = [aggregate(n, pershard[n])
                             for n in fetch_names]
                    return (accs, new_pvals), fvals

                accs0 = [jax.lax.with_sharding_constraint(
                    jnp.zeros((ndev, b.padded), jnp.float32),
                    NamedSharding(mesh, P("data", None)))
                    for b in plan.buckets]
                pvals0 = [base[list(updated).index(n)]
                          for n in comp_persist]
                (accs, pvalsN), fetch_ys = jax.lax.scan(
                    inner, (accs0, pvals0), list(feeds_w))
                reduced, new_resid = gc.exchange_bucketed(
                    plan, accs, mesh, residuals=residuals, salt=salt)
                env = dict(consts)
                # update-segment feeds (e.g. @lr) take the window's last
                # microbatch row — the executor broadcast them over K
                env.update(zip(feed_names, [f[-1] for f in feeds_w]))
                env.update(zip(updated, base))
                env.update(zip(frozen, frz_vals))
                env, new_state = run_update(
                    env, reduced, (new_resid, salt),
                    dict(zip(comp_persist, pvalsN)), {})
                return ([env[n] for n in updated], new_state), fetch_ys

            upd_vals = list(upd_all[:n_base])
            state0 = list(upd_all[n_base:])
            (base_f, state_f), ys = jax.lax.scan(
                outer, (upd_vals, state0), resh)
            fetches = [jnp.reshape(y, (K,) + tuple(y.shape[2:]))
                       for y in ys]
            return fetches, list(base_f) + list(state_f)

        return raw, tuple(state_names), plan, True

    @staticmethod
    def _data_mesh():
        """One-axis ('data',) mesh over every local device. The reference's
        ParallelExecutor replicates the graph per GPU and all-reduces grads
        over NCCL (python/paddle/fluid/parallel_executor.py:28); here the
        same program is compiled ONCE as SPMD over this mesh and XLA
        inserts the ICI collectives. Local devices only: the Executor
        feeds host-local numpy arrays (multi-host DP goes through
        dist/parallel.py, which builds process-spanning arrays)."""
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.local_devices()), ("data",))

    def _compile(self, program, feed, fetch_list, data_parallel=False,
                 allow_replicated_fallback=False, optimize_level=None,
                 steps=None, comm_options=None, plan=None):
        from ..analysis import normalize_fetch

        if optimize_level is None:
            optimize_level = self.optimize_level
        if plan is not None:
            # an auto-parallel plan IS a data-parallel layout (its data
            # axis may be the whole mesh); the plan decides shardings
            data_parallel = True
        if _chaos.ACTIVE:  # chaos points: transient / optimized-only failure
            _chaos.fire("transient_compile")
            _chaos.fire("opt_compile_fail", optimize_level=optimize_level)
        feed_names = tuple(sorted(feed))
        fetch_names, _ = normalize_fetch(fetch_list)
        # per-STEP shapes even on the fused path (run_steps hands the
        # first microbatch here): the key describes the step body, and
        # `steps` carries the fusion axis. Metadata-only reads: a feed
        # value that is already a (possibly sharded, still-computing)
        # jax array must not be gathered to host just to learn its shape
        shapes = tuple(self._feed_shape_dtype(feed[n]) for n in feed_names)
        # program._uid is monotonic and never recycled (unlike id(program),
        # which the allocator can hand to a NEW Program after the old one
        # is GC'd — a stale-cache hit that replays the wrong executable)
        key = CacheKey(
            program_uid=program._uid, program_version=program._version,
            feed_names=feed_names, feed_shapes=shapes,
            fetch_names=fetch_names, optimize_level=int(optimize_level),
            steps=None if steps is None else int(steps),
            data_parallel=bool(data_parallel),
            allow_replicated_fallback=bool(allow_replicated_fallback),
            comm=None if comm_options is None else comm_options.cache_axis(),
            plan=None if plan is None else plan.cache_axis())
        if key in self._cache:
            compiled = self._cache[key]
            # coherence: uid+version are in the key, so a hit is the right
            # program UNLESS someone mutated Block.ops without bump() —
            # the one desync the key cannot see
            assert compiled.op_count == len(program.global_block.ops), \
                "executor cache incoherent: Block.ops changed without " \
                "Program.bump()"
            self.last_diagnostics = compiled.diagnostics
            self._cache_hits += 1
            _M_CACHE_HITS.inc()
            return compiled

        self._cache_misses += 1
        _M_CACHE_MISSES.inc()
        t0 = time.perf_counter()
        with _trace.span("executor.compile", uid=program._uid,
                         version=program._version,
                         optimize_level=int(optimize_level),
                         data_parallel=bool(data_parallel),
                         steps=steps):
            compiled = self._build(program, feed_names, fetch_names, shapes,
                                   fetch_list, data_parallel,
                                   allow_replicated_fallback, optimize_level,
                                   steps=steps, comm_options=comm_options,
                                   plan=plan)
        # NOTE: jax.jit is lazy — this times trace-side work (analysis
        # passes + jit wrapper construction); XLA's own compile lands in
        # the first executor.run_ms sample for this key
        compile_ms = (time.perf_counter() - t0) * 1e3
        _M_COMPILE_MS.observe(compile_ms)
        if _journal.ACTIVE is not None:
            # provenance: "xla" = compiled in this process (the lazy-jit
            # default), "aot_disk" = hydrated from the AOT executable
            # cache (runtime.aot) — zero XLA compile paid here. `via`
            # carries the same value on every site's compile events
            # (predictor/serving pin `source` to their site tag), so
            # run_report's cold-start summary reads one field.
            from ..runtime import aot as _aot

            prov = _aot.provenance_fields(
                getattr(compiled, "aot_info", None))
            prov.setdefault("via", "xla")
            extra = {"steps_fused": int(steps)} if steps else {}
            _journal.ACTIVE.event(
                "compile", uid=program._uid, version=program._version,
                optimize_level=int(optimize_level), ms=compile_ms,
                source=prov["via"], **prov, **extra)
            # one sharding event per compiled entry: feed/persistable
            # placement + footprints (metadata only — obs.spmd reads the
            # structs captured above, no device or XLA work)
            from ..obs import spmd as _spmd

            _journal.ACTIVE.event("sharding",
                                  **_spmd.sharding_summary(compiled))
            # one memory event per compiled entry: the static peak-HBM
            # prediction now; the measured memory_analysis() side is
            # re-journaled when the entry's lazy analysis lands
            _journal.ACTIVE.record_memory(compiled)
            if plan is not None:
                # one plan event per auto-parallel compile: the layout
                # the planner chose and its predicted-vs-measured wire
                # bytes (measured filled by fleet.verify_plan)
                _journal.ACTIVE.record_plan(plan, uid=program._uid,
                                            version=program._version)
        self._cache[key] = compiled
        return compiled

    def _build(self, program, feed_names, fetch_names, shapes, fetch_list,
               data_parallel, allow_replicated_fallback, optimize_level,
               steps=None, comm_options=None, plan=None):
        from ..analysis import run_compile_passes

        if plan is not None and comm_options is not None and \
                not plan.is_pure_dp:
            raise ValueError(
                "comm_options (dist.gradcomm) composes only with a "
                "pure data-parallel plan: the explicit exchange vmaps "
                f"over a single 'data' axis, but the plan spans "
                f"{plan.axes}")

        scope = global_scope()
        blk = program.global_block
        persist_in = tuple(
            v.name for v in blk.vars.values()
            if v.persistable and scope.find_var(v.name) is not None
            and not v.name.startswith("@comm@"))
        # @comm@* exchange state (dist.gradcomm error-feedback residuals
        # + rounding counter) is managed below: it must never ride the
        # generic persistable lists (a second compile would list it as
        # frozen AND updated)

        # -- analysis: verify always, optimize behind optimize_level --------
        # (raises ProgramVerificationError with coded, op-anchored
        # diagnostics instead of letting jax.jit fail mid-trace)
        ops, report = run_compile_passes(
            program, fetch_list=fetch_list,
            feed_shapes=dict(zip(feed_names, shapes)),
            scope_names=set(persist_in), optimize_level=optimize_level)
        self.last_diagnostics = report

        written = set()
        for op in ops:
            written.update(op.output_names)
        # only buffers the program re-emits may be donated; donating a
        # frozen (read-only) persistable would delete it from the scope
        updated = tuple(n for n in persist_in if n in written)
        frozen = tuple(n for n in persist_in if n not in written)

        # -- Executor-side verifier checks (need the live Scope / the
        # installed plan, which the pure-Program passes never see):
        # PTA011 use-after-donate buffer aliasing, PTA012 feed/fetch
        # specs inconsistent with the plan (analysis.dataflow)
        from ..analysis import dataflow as _ana_dataflow

        _ana_dataflow.check_donation_races(report, scope, updated, frozen)
        if plan is not None:
            _ana_dataflow.check_plan_consistency(
                report, plan, feed_names, shapes, fetch_names, scope)
        report.raise_if_errors()

        comm_state = ()
        comm_handles_steps = False
        if comm_options is not None:
            if not data_parallel:
                raise ValueError(
                    "comm_options requires a data-parallel program "
                    "(CompiledProgram.with_data_parallel)")
            raw, comm_state, comm_plan, comm_handles_steps = self._comm_raw(
                program, ops, feed_names, fetch_names, shapes, updated,
                frozen, steps, comm_options, self._data_mesh(), scope, blk)
            updated = updated + comm_state
        else:
            raw = self._replay_fn(program, ops, feed_names, updated,
                                  frozen, fetch_names)
        if steps and not comm_handles_steps:
            # fused multi-step path: drive K microbatches through ONE
            # lax.scan — the step body lowers once, the persistables ride
            # as the (donated) carry, stacked feeds are the scan xs, and
            # per-step fetches come back stacked as ys. One compile and
            # one dispatch per K steps instead of K Python dispatches —
            # the ParallelExecutor-era per-op dispatch amortization,
            # rebuilt on XLA's loop fusion.
            raw_step, K = raw, int(steps)

            def raw(stacked_feeds, updated_arrs, frozen_arrs):
                def body(carry, feeds_k):
                    fetches, new_updated = raw_step(list(feeds_k), carry,
                                                    frozen_arrs)
                    return new_updated, fetches

                new_updated, ys = jax.lax.scan(
                    body, list(updated_arrs), list(stacked_feeds), length=K)
                return ys, new_updated

        if data_parallel and plan is not None and not plan.is_pure_dp:
            # fleet.auto_parallel: the plan owns the layout — a multi-
            # axis mesh with per-variable PartitionSpecs (batch feeds
            # over the data axes, TP-paired weights over the model axis)
            # instead of the one-axis shard-the-batch default below.
            # GSPMD still inserts every collective; the plan just sets
            # the shardings it partitions around.
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = plan.build_mesh()
            rep = NamedSharding(mesh, P())

            def feed_sharding(name, shape):
                spec = plan.feed_spec_for(name, shape)
                if not spec:
                    return rep
                # fused entries carry a leading K scan axis every device
                # walks identically — the plan's specs shift right
                return NamedSharding(
                    mesh, P(*(((None,) + tuple(spec)) if steps
                              else spec)))

            feed_sh = [feed_sharding(n, s)
                       for n, (s, _) in zip(feed_names, shapes)]

            def persist_sharding(name):
                a = scope.find_var(name)
                shape = tuple(a.shape) if a is not None else None
                spec = plan.spec_for(name, shape)
                return NamedSharding(mesh, P(*spec)) if spec else rep

            upd_sh = [persist_sharding(n) for n in updated]
            frz_sh = [persist_sharding(n) for n in frozen]
            in_sh = (feed_sh, upd_sh, frz_sh)
            out_sh = ([rep] * len(fetch_names), upd_sh)
            jit_fn = jax.jit(raw, donate_argnums=(1,), in_shardings=in_sh,
                             out_shardings=out_sh)
        elif data_parallel:
            # Shard the feed batch axis over the data mesh; persistables
            # stay replicated. XLA partitions the one program and inserts
            # the grad all-reduce itself (GSPMD) — the TPU analog of the
            # reference's per-device graph replication + NCCL all_reduce.
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self._data_mesh()
            ndev = int(np.prod(mesh.devices.shape))
            rep = NamedSharding(mesh, P())

            def feed_sharding(shape):
                # `shape` is always the per-STEP shape; on the fused path
                # the actual jit argument carries a leading scan axis of
                # K microbatches, which must stay unsharded (every device
                # walks the same K steps) — the batch axis moves to dim 1
                if len(shape) >= 1 and shape[0] > 0 and shape[0] % ndev == 0:
                    return NamedSharding(
                        mesh, P(None, "data") if steps else P("data"))
                return rep  # non-batched / indivisible feeds replicate

            feed_sh = [feed_sharding(s) for s, _ in shapes]
            if shapes and not any(sh is not rep for sh in feed_sh):
                # NOTHING sharded: the "data-parallel" step would run
                # fully replicated — reference ParallelExecutor errors on
                # unsplittable batches (parallel_executor.py:28), so
                # refuse unless the user opted into the fallback. (An
                # indivisible AUXILIARY feed next to properly-sharded
                # batch feeds replicates quietly — that is correct, not
                # a degraded run.)
                dims = {n: s for (s, _), n in zip(shapes, feed_names)}
                if not allow_replicated_fallback:
                    raise ValueError(
                        f"data-parallel run but no feed's leading dim "
                        f"divides the {ndev} devices of the data mesh "
                        f"(feed shapes: {dims}): the step would execute "
                        "fully replicated with 0% DP speedup. Pad or "
                        "rebatch the feed, or opt in with "
                        "ExecutionStrategy.allow_replicated_fallback"
                        "=True")
                import warnings

                warnings.warn(
                    f"data-parallel feeds {dims} have no leading dim "
                    f"divisible by {ndev} devices: running fully "
                    "replicated (no DP speedup)", RuntimeWarning)

            def persist_sharding(name):
                # comm-exchange residuals are PER-DEVICE state: row d is
                # device d's error feedback — replicating them would
                # both waste HBM and gather what is semantically local
                from ..dist.gradcomm import EF_PREFIX

                if name.startswith(EF_PREFIX):
                    return NamedSharding(mesh, P("data", None))
                return rep

            upd_sh = [persist_sharding(n) for n in updated]
            in_sh = (feed_sh, upd_sh, [rep] * len(frozen))
            out_sh = ([rep] * len(fetch_names), upd_sh)
            jit_fn = jax.jit(raw, donate_argnums=(1,), in_shardings=in_sh,
                             out_shardings=out_sh)
        else:
            jit_fn = jax.jit(raw, donate_argnums=(1,))
        compiled = _Compiled(jit_fn, feed_names, updated + frozen, updated,
                             fetch_names)
        compiled.feed_shardings = in_sh[0] if data_parallel else None
        # persistable in_shardings, kept so the run path can re-place a
        # scope array a DIFFERENT entry committed to another mesh (two
        # plans over one program, or plan vs plain-DP): pjit refuses to
        # silently reshard committed args across meshes
        compiled.persist_shardings = (in_sh[1], in_sh[2]) \
            if data_parallel else None
        if data_parallel:
            # mesh identity for collective attribution + sharding
            # reports (obs.spmd): axis sizes and the device-id layout
            # the HLO replica groups refer to
            compiled.mesh_axes = dict(mesh.shape)
            compiled.mesh_device_ids = np.vectorize(
                lambda d: int(d.id))(mesh.devices)
        else:
            compiled.mesh_axes = None
            compiled.mesh_device_ids = None
        compiled.updated = updated
        compiled.frozen = frozen
        compiled.program_uid = program._uid
        compiled.program_version = program._version
        compiled.op_count = len(blk.ops)  # pre-optimization: mirrors _version
        compiled.diagnostics = report
        compiled.optimize_level = int(optimize_level)
        compiled.steps = None if steps is None else int(steps)
        compiled.comm_options = comm_options
        compiled.comm_plan = comm_plan if comm_options is not None else None
        compiled.plan = plan  # fleet.auto_parallel ShardingPlan (or None)
        # shape/dtype-only arg structs (no device data): what the lazy
        # per-entry memory/FLOP attribution (obs.mfu.entry_analysis) and
        # the journal's MFU accounting re-lower against on demand. Fused
        # entries record the STACKED feed shapes — the shapes the
        # executable actually takes — so a re-lower reproduces the scan.
        def _struct(name):
            a = scope.find_var(name)  # .shape/.dtype are metadata reads:
            return jax.ShapeDtypeStruct(  # no host transfer of the array
                tuple(a.shape), np.dtype(a.dtype))

        def _feed_struct(s, dt):
            s = (int(steps),) + tuple(s) if steps else tuple(s)
            return jax.ShapeDtypeStruct(s, np.dtype(dt))

        compiled.arg_structs = (
            [_feed_struct(s, dt) for s, dt in shapes],
            [_struct(n) for n in updated],
            [_struct(n) for n in frozen])
        # examples/step hint for throughput accounting: the largest
        # leading feed dim (the batch axis in every workload here)
        lead = [s[0] for s, _ in shapes if len(s) >= 1 and s[0] > 0]
        compiled.examples_hint = max(lead) if lead else None
        # static peak-HBM prediction for this entry (analysis.memory
        # liveness walk): journaled as a `memory` event and validated
        # against the executable's memory_analysis() once the lazy
        # entry analysis lands (obs.journal.record_memory)
        from ..analysis import memory as _ana_memory

        try:
            est = _ana_memory.estimate_entry(
                program, ops=ops, fetch_list=fetch_list,
                feed_shapes=dict(zip(feed_names, shapes)),
                scope_names=set(persist_in), steps=steps, plan=plan,
                data_devices=(len(jax.local_devices())
                              if data_parallel and plan is None else 1))
            compiled.memory_estimate = est
            compiled.predicted_memory = est.as_event()
        except Exception:  # an estimate failure must never cost a run
            compiled.memory_estimate = None
            compiled.predicted_memory = None
        # -- AOT executable cache (runtime.aot): with a cache active the
        # entry compiles EAGERLY — hydrated from disk when the content
        # digest (fingerprint + lowered StableHLO) matches, else
        # lowered.compile() + published — and compiled.fn becomes the
        # jax.stages.Compiled (same calling convention, donation and
        # shardings baked in, outputs bitwise what the lazy jit would
        # produce). No cache -> lazy jit, exactly as before.
        compiled.aot_info = None
        from ..runtime import aot as _aot

        cache = _aot.active_cache()
        if cache is not None:
            label = f"uid{program._uid}v{program._version}" + \
                (f"/steps{steps}" if steps else "")
            exe, info = _aot.load_or_compile(
                jit_fn, compiled.arg_structs, kind="executor",
                cache=cache, label=label)
            if exe is not None:
                compiled.fn = exe
                compiled.aot_info = info
        return compiled

    def cache_stats(self, per_entry=False):
        """Hit/miss/size of this executor's jit cache (the process-wide
        view lives in ``obs.metrics`` under ``executor.jit_cache.*``).
        Read-only: the cache-key layout is pinned by tests — never use
        this to re-key or evict.

        ``per_entry=True`` adds an ``entries`` list attributing cache
        growth: program uid/version/optimize_level plus bytes, FLOPs,
        and the ``collectives`` CollectiveProfile (per-kind counts/byte
        volumes, mesh-axis attribution — ``obs.spmd``) from the
        compiled executable — lazily computed on first request (one
        re-lower+compile per entry, cached), ``None`` where the backend
        doesn't report."""
        out = {"hits": self._cache_hits, "misses": self._cache_misses,
               "size": len(self._cache)}
        if per_entry:
            from ..obs.mfu import entry_analysis

            # dispatches only rides the opt-in shape: the default dict
            # {hits,misses,size} is pinned by tests
            out["dispatches"] = self._dispatches
            entries = []
            for compiled in self._cache.values():
                a = entry_analysis(compiled)
                mem = a["memory"]
                entries.append({
                    "program_uid": compiled.program_uid,
                    "program_version": compiled.program_version,
                    "optimize_level": getattr(compiled, "optimize_level",
                                              None),
                    "feed_names": list(compiled.feed_names),
                    "memory_bytes": (sum(v for k, v in mem.items()
                                         if k != "generated_code_size")
                                     if mem else None),
                    "memory": mem,
                    "flops": (a["cost"] or {}).get("flops"),
                    "collectives": a.get("collectives"),
                    "mesh": getattr(compiled, "mesh_axes", None),
                    "steps_fused": getattr(compiled, "steps", None),
                })
            out["entries"] = entries
        return out

    @staticmethod
    def _feed_shape_dtype(v):
        """(shape, dtype-str) of one feed value WITHOUT materializing
        it: jax arrays / Tensors / numpy answer from metadata (no
        device->host gather); only raw Python containers pay an
        np.asarray."""
        v = getattr(v, "_data", v)
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return tuple(v.shape), str(np.dtype(v.dtype))
        a = np.asarray(v)
        return a.shape, str(a.dtype)

    @staticmethod
    def _align_persistables(compiled, updated, frozen):
        """Re-place scope persistables whose COMMITTED sharding no
        longer matches this entry's in_shardings (the array was last
        touched by an entry over a different mesh — e.g. two
        auto-parallel plans over one program). pjit would reject the
        mismatch instead of resharding; an explicit device_put is the
        sanctioned cross-mesh move. Metadata-only when nothing moved:
        one sharding equality check per persistable."""
        shs = getattr(compiled, "persist_shardings", None)
        if shs is None:
            return updated, frozen

        def fix(vals, shardings):
            out = []
            for v, sh in zip(vals, shardings):
                if isinstance(v, jax.Array) and \
                        getattr(v, "committed", False) and \
                        v.sharding != sh:
                    v = jax.device_put(v, sh)
                out.append(v)
            return out

        return fix(updated, shs[0]), fix(frozen, shs[1])

    @staticmethod
    def _as_device(v):
        """Feed value -> jax array via the canonical
        ``core.tensor.as_device_array`` (already-device arrays pass
        through untouched — see its docstring)."""
        from ..core.tensor import as_device_array

        return as_device_array(v)

    @staticmethod
    def _unwrap_program(program):
        """CompiledProgram / transpiled-DP normalization shared by run
        and run_steps: returns (program, data_parallel,
        allow_replicated_fallback, comm_options, plan)."""
        from .compiler import CompiledProgram

        if program is None:
            program = default_main_program()
        data_parallel = False
        allow_replicated_fallback = False
        comm_options = None
        plan = None
        if isinstance(program, CompiledProgram):
            data_parallel = program._data_parallel
            allow_replicated_fallback = getattr(
                program._exec_strategy, "allow_replicated_fallback", False)
            comm_options = getattr(program._build_strategy, "comm_options",
                                   None)
            # fleet.auto_parallel attaches its ShardingPlan here; the
            # plan then rides _compile as a genuine CacheKey axis
            plan = getattr(program, "_plan", None)
            program = program._program
        if getattr(program, "_transpiled_dp", False):
            # fluid.transpiler.collective.GradAllReduce marked this
            # program: run it data-parallel (same SPMD path as
            # CompiledProgram.with_data_parallel)
            data_parallel = True
        return program, data_parallel, allow_replicated_fallback, \
            comm_options, plan

    @staticmethod
    def _materialize_fetches(fetches, return_numpy, fetch_async):
        """The step's host-sync policy, in one place. ``return_numpy``
        blocks on every fetch (np.asarray is the sync point);
        ``fetch_async`` hands back the raw jax arrays — the device may
        still be computing, and the caller syncs when (if) it reads
        them; the lazy-Tensor default in between wraps without forcing
        numpy."""
        tf = time.perf_counter()
        if fetch_async:  # no wrapper, no sync: overlap-friendly fetches
            out = list(fetches)
        elif return_numpy:  # np.asarray is the step's host sync point:
            out = [np.asarray(f) for f in fetches]  # fetch latency
        else:  # lazy Tensors: fetch_ms records only wrapper cost
            out = [Tensor(f, _internal=True) for f in fetches]
        _M_FETCH_MS.observe((time.perf_counter() - tf) * 1e3)
        return out

    # -- public API ---------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, feed_var_name=None,
            fetch_var_name=None, scope=None, return_numpy=True,
            use_program_cache=True, optimize_level=None, fetch_async=False):
        """Run ``program`` (ref: executor.py Executor.run). New vs the
        reference: ``optimize_level`` selects the ``paddle_tpu.analysis``
        pass pipeline applied before compilation — 0 verify-only,
        1 (default) identity-forwarding + dead-op elimination,
        2 additionally CSE. The verifier always runs; a malformed Program
        raises ``analysis.ProgramVerificationError`` with coded
        diagnostics. ``None`` inherits the Executor-level default
        (``Executor(optimize_level=...)`` / env ``PADDLE_TPU_OPT_LEVEL``).

        ``fetch_async=True`` returns the raw jax arrays with NO host
        sync: the dispatch is asynchronous, so the Python loop can feed
        the next batch while the device still computes this one. The
        caller pays the sync when it first reads a value (or via
        ``jax.block_until_ready``). Overrides ``return_numpy``.
        """
        program, data_parallel, allow_replicated_fallback, comm_options, \
            plan = self._unwrap_program(program)
        feed = feed or {}
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        if not program.global_block.ops:  # startup program: params already
            return []  # materialized eagerly at build time

        # schedulers: refresh host-side lr into the feed each run
        if program._lr_getter is not None:
            feed = dict(feed)
            feed["@lr"] = np.asarray(program._lr_getter(), np.float32)

        t0 = time.perf_counter()
        with _trace.span("executor.run", uid=program._uid,
                         n_fetch=len(fetch_list)):
            compiled = self._compile(
                program, feed, fetch_list, data_parallel=data_parallel,
                allow_replicated_fallback=allow_replicated_fallback,
                optimize_level=optimize_level, comm_options=comm_options,
                plan=plan)
            if _chaos.ACTIVE:  # disabled => one empty-dict test, no host sync
                _chaos.fire("transient_execute")
                feed = _chaos.fire("nan_feed", feed)
            feeds = [self._as_device(feed[n]) for n in compiled.feed_names]
            updated = [scope.find_var(n) for n in compiled.updated]
            frozen = [scope.find_var(n) for n in compiled.frozen]
            updated, frozen = self._align_persistables(compiled, updated,
                                                       frozen)
            self._dispatches += 1
            _M_DISPATCHES.inc()
            fetches, new_persist = compiled.fn(feeds, updated, frozen)
            for name, arr in zip(compiled.persist_out, new_persist):
                scope.set(name, arr)
            out = self._materialize_fetches(fetches, return_numpy,
                                            fetch_async)
        run_ms = (time.perf_counter() - t0) * 1e3
        _M_RUN_MS.observe(run_ms)
        if _journal.ACTIVE is not None:  # flight recorder: one None check
            # synced=False keeps the flight recorder off the device: a
            # lazy/async fetch must not pay a hidden per-step host sync
            # just to log a scalar
            _journal.ACTIVE.record_executor_run(
                compiled, out, run_ms,
                synced=bool(return_numpy) and not fetch_async)
        return out

    def run_steps(self, program=None, feeds=None, fetch_list=None,
                  steps=None, scope=None, return_numpy=True,
                  fetch_async=False, optimize_level=None):
        """Run K microbatches through ONE fused ``lax.scan`` executable.

        ``feeds`` is either a sequence of K per-step feed dicts (uniform
        shapes/dtypes) or a single dict of pre-stacked arrays with a
        leading axis of length ``steps``. The step body is lowered once,
        persistable buffers ride the scan as a DONATED carry (parameter
        updates stay in HBM across all K steps), and each fetch comes
        back stacked with a leading K axis — element ``[k]`` is what the
        k-th sequential ``run()`` would have fetched, to the dtype's rounding
        (to the bit where XLA fuses window and lone step alike: a loss, yes).

        vs K ``run()`` calls: one compile + one dispatch per window
        instead of K Python dispatches, K feed transfers issued as one
        stacked transfer, and zero intermediate host syncs. Host-side
        per-step work (LR scheduler reads, chaos hooks) necessarily
        happens once per WINDOW, not once per step: the learning rate is
        sampled once and applied to all K microbatches.

        Returns a list parallel to ``fetch_list`` of stacked values
        (numpy by default; lazy/async under ``return_numpy=False`` /
        ``fetch_async=True`` as in ``run``).
        """
        program, data_parallel, allow_replicated_fallback, comm_options, \
            plan = self._unwrap_program(program)
        fetch_list = fetch_list or []
        scope = scope or global_scope()

        # normalize to {name: stacked (K, ...) array}. Device arrays
        # stay device-side (jnp.stack), host values stack in numpy —
        # prefetched batches must not be gathered back to host here
        def _stackable(v):
            # host values stay numpy (np.stack below); device values
            # keep the canonical pass-through (same invariant as
            # core.tensor.as_device_array, minus the host->device move,
            # which is deferred to the single stacked transfer)
            v = getattr(v, "_data", v)
            return v if isinstance(v, jax.Array) else np.asarray(v)

        if isinstance(feeds, dict):
            if not steps:
                raise ValueError(
                    "run_steps with a pre-stacked feed dict needs an "
                    "explicit steps=K (the leading axis length)")
            K = int(steps)
            stacked = {n: _stackable(v) for n, v in feeds.items()}
            for n, v in stacked.items():
                if v.ndim < 1 or v.shape[0] != K:
                    raise ValueError(
                        f"pre-stacked feed {n!r} has shape {v.shape}; "
                        f"expected a leading microbatch axis of {K}")
        else:
            feeds = list(feeds or ())
            if not feeds:
                raise ValueError("run_steps needs at least one feed dict")
            K = int(steps) if steps else len(feeds)
            if K != len(feeds):
                raise ValueError(
                    f"steps={K} but {len(feeds)} feed dicts were given")
            names = sorted(feeds[0])
            for f in feeds[1:]:
                if sorted(f) != names:
                    raise ValueError(
                        "every microbatch must feed the same variables; "
                        f"got {sorted(f)} vs {names}")

            def _stack(vals):
                vals = [_stackable(v) for v in vals]
                if any(isinstance(v, jax.Array) for v in vals):
                    return jnp.stack([jnp.asarray(v) for v in vals])
                return np.stack(vals)

            stacked = {n: _stack([f[n] for f in feeds]) for n in names}
        if K <= 0:
            raise ValueError(f"steps must be >= 1, got {K}")

        if not program.global_block.ops:
            return []

        # LR schedulers are host-side state: fused windows sample once
        # per dispatch (documented above), exactly like the compiled
        # multi-step loops the scheduler API was designed around
        if program._lr_getter is not None:
            lr = np.asarray(program._lr_getter(), np.float32)
            stacked = dict(stacked)
            stacked["@lr"] = np.broadcast_to(lr, (K,) + lr.shape).copy()

        # shape/dtype probes for the cache key — structs, not slices, so
        # no device work happens before the dispatch
        per_step = {n: jax.ShapeDtypeStruct(tuple(v.shape[1:]),
                                            np.dtype(v.dtype))
                    for n, v in stacked.items()}
        t0 = time.perf_counter()
        with _trace.span("executor.run_steps", uid=program._uid,
                         steps=K, n_fetch=len(fetch_list)):
            compiled = self._compile(
                program, per_step, fetch_list, data_parallel=data_parallel,
                allow_replicated_fallback=allow_replicated_fallback,
                optimize_level=optimize_level, steps=K,
                comm_options=comm_options, plan=plan)
            if _chaos.ACTIVE:  # window-granularity chaos (one fused step)
                _chaos.fire("transient_execute")
                stacked = _chaos.fire("nan_feed", stacked)
            feed_arrs = [self._as_device(stacked[n])
                         for n in compiled.feed_names]
            updated = [scope.find_var(n) for n in compiled.updated]
            frozen = [scope.find_var(n) for n in compiled.frozen]
            updated, frozen = self._align_persistables(compiled, updated,
                                                       frozen)
            self._dispatches += 1
            _M_DISPATCHES.inc()
            fetches, new_persist = compiled.fn(feed_arrs, updated, frozen)
            for name, arr in zip(compiled.persist_out, new_persist):
                scope.set(name, arr)
            out = self._materialize_fetches(fetches, return_numpy,
                                            fetch_async)
        run_ms = (time.perf_counter() - t0) * 1e3
        _M_RUN_MS.observe(run_ms)
        if _journal.ACTIVE is not None:
            _journal.ACTIVE.record_fused_run(
                compiled, out, run_ms, steps=K,
                synced=bool(return_numpy) and not fetch_async)
        return out

    # -- dataset-driven loops (ref: executor.py:1436 train_from_dataset /
    # :1369 infer_from_dataset). The reference hands the dataset to the
    # C++ device-worker thread pool; here the dataset yields batches of
    # the program's exact feed shapes and ONE compiled executable
    # consumes them (thread/debug accepted for source compat).
    def _run_from_dataset(self, program, dataset, scope, fetch_list,
                          fetch_info, print_period, fetch_handler,
                          steps_per_dispatch=None):
        if dataset is None:
            raise ValueError("dataset is required (build one with "
                             "fluid.DatasetFactory().create_dataset())")
        fetch_list = list(fetch_list or [])
        if fetch_info is not None and len(fetch_info) != len(fetch_list):
            raise ValueError(
                f"fetch_info has {len(fetch_info)} entries for "
                f"{len(fetch_list)} fetch_list variables (reference "
                "asserts equal lengths)")
        names = list(fetch_info) if fetch_info else [
            getattr(v, "name", str(v)) for v in fetch_list]
        K = int(steps_per_dispatch or 0)
        if K > 1:
            return self._run_from_dataset_fused(
                program, dataset, scope, fetch_list, names, K,
                print_period, fetch_handler)
        last = None
        for step, feed in enumerate(dataset.iter_batches()):
            last = self.run(program, feed=feed, fetch_list=fetch_list,
                            scope=scope)
            if fetch_list and print_period and \
                    (step + 1) % print_period == 0:
                msg = ", ".join(f"{n}={np.asarray(v).ravel()[:4]}"
                                for n, v in zip(names, last))
                print(f"[step {step + 1}] {msg}")
            if fetch_handler is not None and last is not None:
                fetch_handler.handler(dict(zip(names, last)))
        self._warn_dropped(dataset)
        return last

    @staticmethod
    def _warn_dropped(dataset):
        dropped = getattr(dataset, "last_dropped", 0)
        if dropped:
            import warnings

            warnings.warn(
                f"train/infer_from_dataset dropped the final partial "
                f"batch ({dropped} samples): static programs bake "
                f"concrete feed shapes. Pad the data to a multiple of "
                f"batch_size={dataset.batch_size} to consume every "
                "sample", RuntimeWarning)

    def _run_from_dataset_fused(self, program, dataset, scope, fetch_list,
                                names, K, print_period, fetch_handler):
        """``steps_per_dispatch=K``: drive fused ``run_steps`` windows
        straight from the data pipeline — the reachable-from-the-loader
        form of the fused path (no hand-stacked feeds). The FIRST window
        runs from host batches and compiles the fused entry; every later
        batch then streams through a ``DevicePrefetcher`` seeded with
        that entry's committed feed shardings
        (``executor_feed_shardings``), so host->device transfers overlap
        the previous window's compute and DP batches land pre-sharded. A
        tail of fewer than K batches falls back to per-step ``run()``
        (one extra compile, every sample consumed). ``fetch_handler``
        and the ``print_period`` log fire once per WINDOW on the stacked
        fetches (last microbatch shown), matching run_steps' fetch
        shape; returns the last window's stacked fetches."""
        import itertools

        from ..io_.dataloader import (DevicePrefetcher,
                                      executor_feed_shardings)

        prog, _, _, comm_options, _plan = self._unwrap_program(program)
        accum = int(getattr(comm_options, "accumulate_steps", 1) or 1)
        it = iter(dataset.iter_batches())
        last = None
        step = 0

        def run_window(window):
            nonlocal last, step
            last = self.run_steps(program, feeds=window,
                                  fetch_list=fetch_list, scope=scope)
            step += len(window)
            if fetch_list and print_period and \
                    step // print_period > (step - len(window)) \
                    // print_period:
                msg = ", ".join(
                    f"{n}={np.asarray(v)[-1].ravel()[:4]}"
                    for n, v in zip(names, last))
                print(f"[step {step}] {msg}")
            if fetch_handler is not None and last is not None:
                fetch_handler.handler(dict(zip(names, last)))

        def run_tail(feeds):
            nonlocal last, step
            if accum > 1:
                # the per-step run() rejects accumulation by design, so
                # a ragged tail runs as one SMALLER fused window (extra
                # compile) covering the whole accumulation multiples;
                # the remainder is dropped with a warning — exchanging
                # a partial window would silently change the effective
                # batch
                usable = len(feeds) - len(feeds) % accum
                if usable:
                    last = self.run_steps(program, feeds=feeds[:usable],
                                          fetch_list=fetch_list,
                                          scope=scope)
                    step += usable
                if len(feeds) - usable:
                    import warnings

                    warnings.warn(
                        f"train_from_dataset dropped {len(feeds) - usable}"
                        f" tail batch(es): accumulate_steps={accum} "
                        "exchanges whole N-microbatch windows only",
                        RuntimeWarning)
                return
            for feed in feeds:
                last = self.run(program, feed=feed,
                                fetch_list=fetch_list, scope=scope)
                step += 1

        first = list(itertools.islice(it, K))
        if len(first) < K:
            run_tail(first)
            self._warn_dropped(dataset)
            return last
        run_window(first)
        entry = None
        for key, compiled in self._cache.items():
            if key.program_uid == prog._uid and key.steps == K:
                entry = compiled  # newest matching fused entry wins
        pf = DevicePrefetcher(
            it, shardings=(executor_feed_shardings(entry)
                           if entry is not None else None),
            depth=K + 1)
        try:
            while True:
                window = list(itertools.islice(pf, K))
                if len(window) < K:
                    run_tail(window)
                    break
                run_window(window)
        finally:
            pf.shutdown()
        self._warn_dropped(dataset)
        return last

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None, steps_per_dispatch=None):
        """Run ``dataset`` through ``program`` batch by batch
        (ref executor.py:1436); a ragged final batch is dropped WITH a
        RuntimeWarning (static feed shapes are concrete). Returns the
        last fetch values (the reference returns None; returning the
        fetches is strictly more useful and costs nothing).

        ``steps_per_dispatch=K`` (no reference analog) switches the loop
        onto the fused multi-step path: K dataset batches per compiled
        ``lax.scan`` dispatch (``run_steps``), with batches prefetched
        to the device — pre-sharded for DP programs — while the previous
        window computes. With a comm-efficient DP program
        (``with_data_parallel(comm_options=...)``), an
        ``accumulate_steps=N`` exchange fires once per N microbatches
        INSIDE these windows (K must be a multiple of N)."""
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period,
                                      fetch_handler,
                                      steps_per_dispatch=steps_per_dispatch)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """ref executor.py:1369 — identical loop; the program simply has
        no optimizer ops."""
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period,
                                      fetch_handler)


class FetchHandler:
    """ref: executor.py:429 — user callback fed periodic var snapshots
    during train_from_dataset (and by FetchHandlerMonitor's polling
    thread). Subclass and override ``handler``."""

    def __init__(self, var_dict=None, period_secs=60):
        assert var_dict is not None
        self.var_dict = var_dict
        self.period_secs = period_secs

    def handler(self, res_dict):
        import sys

        for key, val in res_dict.items():
            if isinstance(val, np.ndarray):
                sys.stdout.write(f"{key}[0]: {val.flat[0]} ")
        sys.stdout.write("\n")

    @staticmethod
    def help():
        print("Subclass FetchHandler({'name': var}) and override "
              "handler(res_dict) to consume periodic var snapshots.")


def _amp_cast_fn(amp_cfg):
    """List-driven dtype policy for program interpretation — the
    one-executable analog of the reference's rewrite_program cast-op
    insertion (fluid/contrib/mixed_precision/fp16_utils.py): white-list
    op inputs go to the half dtype, black-list inputs back to f32, and
    XLA fuses the casts into the ops. Grad ops (``<type>@grad``) follow
    their forward op's list entry, which keeps the vjp's internal
    forward identical to the casted forward (CSE'd by XLA)."""
    if not amp_cfg:
        return None
    wl = amp_cfg["lists"].white_list
    bl = amp_cfg["lists"].black_list
    half = jnp.bfloat16 if amp_cfg["dtype"] == "bfloat16" else jnp.float16

    def amp_cast(op_type, args):
        base = op_type[:-5] if op_type.endswith("@grad") else op_type
        if base in wl:
            dt = half
        elif base in bl:
            dt = jnp.float32
        else:
            return args
        return [a.astype(dt)
                if a is not None and hasattr(a, "dtype")
                and jnp.issubdtype(a.dtype, jnp.floating) else a
                for a in args]

    return amp_cast


def append_amp_backward(amp_decorator, loss, parameter_list=None):
    """AMP backward phase (ref: mixed_precision/decorator.py backward +
    amp_nn.py check_finite_and_unscale): create the persistable scaling
    state, scale the loss, append grad ops, then one op that both
    checks every grad for inf/nan and unscales to f32 master grads.
    Returns (params_grads_on_unscaled, found_inf_var_name)."""
    from .backward import append_backward
    from .program import Operator, default_main_program

    program = default_main_program()
    blk = program.global_block
    scope = global_scope()
    program._amp_cfg = {"dtype": amp_decorator._dtype,
                        "lists": amp_decorator._amp_lists}

    if not blk.has_var("@amp@scale"):
        blk.create_var(name="@amp@scale", shape=(), dtype="float32",
                       persistable=True)
        blk.create_var(name="@amp@good", shape=(), dtype="int32",
                       persistable=True)
        blk.create_var(name="@amp@bad", shape=(), dtype="int32",
                       persistable=True)
        scope.set("@amp@scale",
                  jnp.float32(amp_decorator._init_loss_scaling))
        scope.set("@amp@good", jnp.int32(0))
        scope.set("@amp@bad", jnp.int32(0))

    sname = loss.name + "@SCALED"
    sv = blk.create_var(name=sname, shape=loss.shape,
                        dtype=loss._data.dtype, stop_gradient=False)
    blk.append_op(Operator(
        "amp_scale_loss", lambda l, s: l * s.astype(l.dtype),
        [loss.name, "@amp@scale"], [sname], {}))
    amp_decorator._scaled_loss = sv

    params_grads = append_backward(sv, parameter_list=parameter_list)

    gnames = [g.name for _, g in params_grads]
    fi = "@amp@found_inf"
    if not blk.has_var(fi):
        blk.create_var(name=fi, shape=(), dtype="bool")
    out_names = [n + "@UNSCALED" for n in gnames]
    for (_, g), on in zip(params_grads, out_names):
        blk.create_var(name=on, shape=g.shape, dtype="float32")
    blk.append_op(Operator(
        "amp_check_finite_and_unscale",
        amp_decorator.check_and_unscale_rule,
        ["@amp@scale"] + gnames, [fi] + out_names, {}))
    program.bump()
    return ([(p, blk.var(on)) for (p, _), on in
             zip(params_grads, out_names)], fi)


def append_update_ops(optimizer, params_grads, amp_decorator=None,
                      found_inf_name=None):
    """Append clip + per-param optimizer-update ops (the update phase of
    the reference's Optimizer.minimize / apply_gradients). With an AMP
    decorator, every update is guarded on the found-inf flag and the
    dynamic loss-scaling state is advanced in the same executable."""
    from .program import default_main_program

    program = default_main_program()
    blk = program.global_block
    scope = global_scope()

    if optimizer._grad_clip is not None:
        clip = optimizer._grad_clip
        grads = [g for _, g in params_grads]
        gnames = [g.name for g in grads]

        def clip_fn(*gs):
            pairs = clip([(p, g) for (p, _), g in zip(params_grads, gs)])
            return tuple(g for _, g in pairs)

        out_names = [n + "@CLIPPED" for n in gnames]
        from .program import Operator

        for (p, g), on in zip(params_grads, out_names):
            blk.create_var(name=on, shape=g.shape, dtype=g._data.dtype)
        blk.append_op(Operator("grad_clip", clip_fn, gnames, out_names, {}))
        params_grads = [(p, blk.var(on)) for (p, _), on in
                        zip(params_grads, out_names)]

    # lr enters as a fed scalar so schedulers never retrigger compilation
    if not blk.has_var("@lr"):
        blk.create_var(name="@lr", shape=(), dtype="float32", is_data=True)
    program._lr_getter = optimizer.get_lr

    from .program import Operator

    for p, g in params_grads:
        reg = getattr(p, "regularizer", None) or optimizer._regularization
        state = optimizer._init_state(
            jax.ShapeDtypeStruct(tuple(p._data.shape), p._data.dtype))
        skeys = sorted(state)
        sname = {k: f"{p.name}@OPT@{k}" for k in skeys}
        for k in skeys:
            blk.create_var(name=sname[k], shape=state[k].shape,
                           dtype=state[k].dtype, persistable=True)
            scope.set(sname[k], jnp.asarray(state[k]))

        def upd_fn(pa, ga, lr, *rest, _opt=optimizer, _reg=reg, _skeys=skeys,
                   _pvar=p, _amp=amp_decorator is not None):
            from ..optim.optimizer import AdamW

            if _amp:
                found_inf, svals = rest[0], rest[1:]
            else:
                found_inf, svals = None, rest
            if _reg is not None and not isinstance(_opt, AdamW):
                ga = _reg(pa, ga)
            s = dict(zip(_skeys, svals))
            _opt._current_param = _pvar  # AdamW decay exclusion / lr_ratio
            new_p, new_s = _opt._update(pa, ga.astype(pa.dtype), s, lr)
            if found_inf is not None:
                # inf/nan step: freeze param AND slot state (ref:
                # update_loss_scaling's skip semantics)
                new_p = jnp.where(found_inf, pa, new_p)
                new_s = {k: jnp.where(found_inf, s[k], new_s[k])
                         for k in _skeys}
            return (new_p, *[new_s[k] for k in _skeys])

        amp_in = [found_inf_name] if amp_decorator is not None else []
        blk.append_op(Operator(
            "optimize_" + type(optimizer).__name__.lower(), upd_fn,
            [p.name, g.name, "@lr"] + amp_in + [sname[k] for k in skeys],
            [p.name] + [sname[k] for k in skeys], {}))

    if amp_decorator is not None and amp_decorator._use_dynamic:
        blk.append_op(Operator(
            "amp_update_loss_scaling", amp_decorator.update_scaling_rule,
            ["@amp@scale", "@amp@good", "@amp@bad", found_inf_name],
            ["@amp@scale", "@amp@good", "@amp@bad"], {}))
    program.bump()


def build_optimize_ops(optimizer, loss, parameter_list=None):
    """Append backward + optimizer-update ops to the current program
    (ref: Optimizer.minimize static path in fluid/optimizer.py)."""
    from .backward import append_backward

    params_grads = append_backward(loss, parameter_list=parameter_list)
    append_update_ops(optimizer, params_grads)
    return None, params_grads
