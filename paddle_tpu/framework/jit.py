"""Fused train/eval steps: the dygraph perf path.

TPU-native analog of the reference's CompiledProgram / ParallelExecutor
speedups for imperative code (and of paddle.jit.to_static,
python/paddle/fluid/dygraph/jit.py): a Python step function written against
eager Layers is traced ONCE into a pure jax function over the pytree of
(params, optimizer state, buffers, rng key, batch) and compiled with
``jax.jit`` — forward, backward, grad clip, and the optimizer update all
fuse into a single donated-buffer XLA executable. Per-step Python cost is
one dictionary of array handles; the reference pays per-op kernel launches.

Mechanism: Parameters/buffers are temporarily rebound to tracers while the
user's eager code runs under the trace (the same swap trick the fused RNN
runner uses), so arbitrary Layer code works unmodified, including
``loss.backward()`` — the eager tape walk is jax-traceable by design
(core/autograd.py).
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dispatch
from ..core import random as prandom
from ..core.tensor import Tensor, Parameter
from ..obs import metrics as _metrics
from ..obs.trace import span as _span, phase as _phase

__all__ = ["jit", "to_static", "TrainStep", "no_jit"]


# canonical Tensor-unwrap / device-array pass-through (core.tensor):
# batch items that are already on device must NOT round-trip host numpy
from ..core.tensor import as_device_array as _as_array  # noqa: E402


@contextlib.contextmanager
def _rebind(tensors, arrays):
    old = [t._data for t in tensors]
    for t, a in zip(tensors, arrays):
        t._data = a
    try:
        yield
    finally:
        for t, o in zip(tensors, old):
            t._data = o


def _collect_state(models):
    """All Parameters and Buffers reachable from the given layers."""
    params, buffers = [], []
    seen = set()
    for m in models:
        for _, p in m.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                params.append(p)
        for _, b in m.named_buffers():
            if id(b) not in seen and b is not None:
                seen.add(id(b))
                buffers.append(b)
    return params, buffers


class TrainStep:
    """One fused (forward + backward + clip + update) step.

    >>> step = TrainStep(model, optimizer, loss_fn)
    >>> loss = step(x, y)            # compiled on first call per shape

    ``loss_fn(model, *batch)`` must return a scalar loss Tensor. Extra
    models (e.g. a frozen teacher) can be passed via ``models=[...]``.

    The compiled step names its phases for a device profile
    (``jax.named_scope``, so HLO metadata only): ``forward`` round
    ``loss_fn``, ``backward`` round ``loss.backward()``, ``optimizer``
    round unscale / finite check / clip / update, ``grad_exchange`` round
    the comm-efficient exchange; on the host each call is the span
    ``trainstep.call`` with children ``feed``, ``execute``, ``rebind``
    (``obs.trace.span``: nothing unless tracing is on). The first call of
    a batch signature is the phase record ``trainstep.first_execute``
    (always written: the executable's load onto the device and the first
    enqueue, after ``runtime.aot``'s ``aot.*`` records of its compile).

    A model's own gauges (``publish_gauges()``: an expert layer's load, a
    loss's terms) are computed when the registry is read
    (``obs.snapshot()``), never after a dispatch: reading them waits for
    the step in flight.
    """

    def __init__(self, model, optimizer, loss_fn, models=None, donate=True,
                 scaler=None, check_nan=False):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.scaler = scaler  # amp.StaticLossScaler / DynamicLossScaler
        self.check_nan = check_nan  # on-device finite check, host raise
        self._models = list(models) if models is not None else [model]
        if model not in self._models:
            self._models.insert(0, model)
        self._params, self._buffers = _collect_state(self._models)
        self._trainable = [p for p in self._params
                           if isinstance(p, Parameter) and p.trainable]
        self._donate = donate
        self._compiled = {}
        self._arg_structs = {}   # sig -> shape/dtype/sharding structs
        self._profiles = {}      # sig -> cached CollectiveProfile
        self.last_found_inf = None  # device bool after each call
        self._scaler_state = scaler.state() if scaler is not None else {}
        # comm-efficient gradient exchange (dist.gradcomm), configured
        # by DistributedTrainStep: a BucketPlan + its mesh, plus the
        # reserved optimizer-state keys carrying error-feedback state
        self._comm = None
        self._comm_mesh = None
        self._comm_state_keys = ()
        # materialize optimizer slots eagerly so they join the carried state
        for p in self._trainable:
            optimizer._state_for(p)
        for m in self._models:
            publish = getattr(m, "publish_gauges", None)
            if publish is not None:
                _metrics.REGISTRY.add_collector(publish)

    # -- the pure function --------------------------------------------------
    def _make_tape(self):
        """The forward+backward closure: ``(param_arrs, buf_arrs, key,
        batch, scale) -> (loss_val, grads dict, new_buf_arrs)``. Shared
        by the plain pure step (full batch) and the comm-efficient step
        (vmapped over the device-major batch axis)."""
        buffers = self._buffers
        trainable = self._trainable

        def tape(param_arrs, buf_arrs, key, batch, scale):
            # only TRAINABLE params are threaded as jit arguments; frozen
            # params stay bound to their concrete arrays and become XLA
            # constants in the compiled step
            with _rebind(trainable, list(param_arrs)), \
                    _rebind(buffers, list(buf_arrs)), \
                    prandom.key_context(key), \
                    dispatch.fresh_tape():
                ts = [Tensor(a, _internal=True) for a in batch]
                with jax.named_scope("forward"):
                    loss = self.loss_fn(self.model, *ts)
                for p in self._params:
                    # ALL collected params, not just trainable: a frozen
                    # teacher's stale .grad (possibly a tracer from its
                    # own earlier TrainStep trace) must not be
                    # accumulated into by this backward
                    p.grad = None
                with jax.named_scope("backward"):
                    if scale is not None:
                        (loss * Tensor(scale, _internal=True)).backward()
                    else:
                        loss.backward()
                grads = {p.name: (p.grad._data if p.grad is not None
                                  else None)
                         for p in trainable}
                new_bufs = [b._data for b in buffers]
                loss_val = loss._data
            return loss_val, grads, new_bufs

        return tape

    def _comm_local(self, tape):
        """Comm-efficient forward+backward: reshape batch items
        device-major, vmap the tape over the device axis (zero
        collectives), and return per-device local grads as bucket
        flats: ``(param_arrs, buf_arrs, key, batch, scale) ->
        (loss_val, flats, new_bufs)``. Buffers and the loss aggregate
        across shards (mean — rank-local BN semantics); gradients stay
        local for the explicit exchange."""
        plan, mesh = self._comm, self._comm_mesh
        ndev = plan.ndev
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..dist.gradcomm import device_major

        def local(param_arrs, buf_arrs, key, batch, scale):
            batched, axes = device_major(batch, ndev, mesh)
            if not any(ax == 0 for ax in axes):
                shapes = [tuple(a.shape) for a in batch]
                raise ValueError(
                    "comm-efficient gradient exchange needs a batch arg "
                    f"whose leading dim divides the {ndev}-device data "
                    f"mesh (batch shapes: {shapes}); a fully replicated "
                    "step would run the whole batch on every device")
            # per-shard subkeys: shards must draw INDEPENDENT noise
            # (dropout etc.), not ndev copies of one mask
            keys = jax.lax.with_sharding_constraint(
                jax.random.split(key, ndev),
                NamedSharding(mesh, P("data", None)))
            losses, grads_sh, bufs_sh = jax.vmap(
                lambda b, k: tape(param_arrs, buf_arrs, k, list(b), scale),
                in_axes=(axes, 0))(batched, keys)
            denom = ndev if plan.options.gradient_scale == "mean" else 1
            loss_val = losses.sum(0) / denom
            locals_ = {}
            unreached = set()
            for p in self._trainable:
                g = grads_sh.get(p.name)
                if g is None:
                    # unreached param: exchange zeros to keep the bucket
                    # layout static, but record it (trace-time constant)
                    # so the update is SKIPPED like the non-comm path
                    unreached.add(p.name)
                    g = jnp.zeros((ndev,) + tuple(p._data.shape),
                                  jnp.float32)
                locals_[p.name] = g.astype(jnp.float32)
            self._comm_unreached = unreached
            flats = plan.flatten_local(locals_)
            new_bufs = [
                (b.sum(0) / ndev).astype(old.dtype)
                if jnp.issubdtype(old.dtype, jnp.floating)
                else b[0]
                for b, old in zip(bufs_sh, buf_arrs)]
            return loss_val, flats, new_bufs

        return local

    @jax.named_scope("grad_exchange")
    def _comm_exchange(self, flats, opt_state, denom=None):
        """Run the bucketed (possibly quantized) exchange over local
        bucket flats, pulling/advancing the error-feedback state from
        the reserved optimizer-state keys. Returns
        ``(grads dict, comm_updates dict)``."""
        from ..dist import gradcomm as gc

        comm = self._comm
        residuals = salt = None
        if comm.options.quantize:
            residuals = [opt_state[gc.EF_PREFIX + str(i)]["residual"]
                         for i in range(comm.n_buckets)]
            salt = opt_state[gc.STEP_VAR]["count"]
        reduced, new_resid = gc.exchange_bucketed(
            comm, flats, self._comm_mesh, residuals=residuals, salt=salt,
            denom=denom)
        grads = comm.unflatten(
            reduced,
            dtypes={p.name: p._data.dtype for p in self._trainable})
        for n in getattr(self, "_comm_unreached", ()):
            # params the backward never reached exchanged zeros (static
            # bucket layout) but must SKIP the update, exactly like the
            # non-comm path — a zero grad would still decay Adam moments
            grads[n] = None
        comm_updates = {}
        if comm.options.quantize:
            for i, r in enumerate(new_resid):
                comm_updates[gc.EF_PREFIX + str(i)] = {"residual": r}
            comm_updates[gc.STEP_VAR] = {"count": salt + 1}
        return grads, comm_updates

    def _make_pure(self):
        opt = self.optimizer
        buffers = self._buffers
        trainable = self._trainable
        t_names = [p.name for p in trainable]
        scaler = self.scaler
        tape = self._make_tape()
        comm = self._comm
        local = self._comm_local(tape) if comm is not None else None
        apply = self._make_apply()

        def pure(param_arrs, buf_arrs, opt_state, lr, key, batch,
                 scaler_state):
            scale = scaler_state["scale"] if scaler is not None else None
            comm_updates = {}
            if comm is None:
                loss_val, grads, new_bufs = tape(param_arrs, buf_arrs,
                                                 key, batch, scale)
            else:
                loss_val, flats, new_bufs = local(param_arrs, buf_arrs,
                                                  key, batch, scale)
                grads, comm_updates = self._comm_exchange(flats, opt_state)
            return apply(grads, loss_val, new_bufs, param_arrs, buf_arrs,
                         opt_state, lr, scaler_state, comm_updates)

        return pure

    def _make_apply(self):
        """The post-backward half of the step — unscale/finite-check,
        clip, optimizer update, scaler advance — as a closure over
        *global* gradients, shared by the plain pure step and the
        comm-efficient exchange paths."""
        opt = self.optimizer
        trainable = self._trainable
        t_names = [p.name for p in trainable]
        scaler = self.scaler

        @jax.named_scope("optimizer")
        def apply(grads, loss_val, new_bufs, param_arrs, buf_arrs,
                  opt_state, lr, scaler_state, comm_updates):
            found_inf = jnp.bool_(False)
            if scaler is not None:
                # unscale + single fused finite-check over every grad
                inv = 1.0 / scaler_state["scale"]
                flags = []
                for n in t_names:
                    if grads[n] is not None:
                        g = grads[n].astype(jnp.float32) * inv
                        grads[n] = g
                        flags.append(jnp.any(~jnp.isfinite(g)))
                if flags:
                    found_inf = jnp.stack(flags).any()
            elif self.check_nan:
                flags = [jnp.any(~jnp.isfinite(loss_val))]
                for n in t_names:
                    if grads[n] is not None:
                        flags.append(jnp.any(~jnp.isfinite(grads[n])))
                found_inf = jnp.stack(flags).any()

            pgs = [(p, grads[p.name]) for p in trainable
                   if grads[p.name] is not None]
            if opt._grad_clip is not None:
                pgs = opt._grad_clip(pgs)
            new_params = dict(zip(t_names, param_arrs))
            new_state = dict(opt_state)
            for p, g in pgs:
                reg = p.regularizer if p.regularizer is not None \
                    else opt._regularization
                from ..optim.optimizer import AdamW

                s = opt_state[p.name]
                master = s.get("master")  # multi_precision fp32 copy
                pw = master if master is not None else new_params[p.name]
                if reg is not None and not isinstance(opt, AdamW):
                    g = reg(pw, g)
                plr = lr * p.optimize_attr.get("learning_rate", 1.0)
                opt._current_param = p
                np_, ns_ = opt._update(pw, g.astype(pw.dtype), s, plr)
                if master is not None:
                    ns_ = {**ns_, "master": np_}
                    np_ = np_.astype(new_params[p.name].dtype)
                if scaler is not None:
                    # inf/nan step: keep params and optimizer state frozen
                    old_p, old_s = new_params[p.name], s
                    np_ = jnp.where(found_inf, old_p, np_)
                    ns_ = {k: jnp.where(found_inf, old_s[k], v)
                           if k in old_s else v for k, v in ns_.items()}
                new_params[p.name] = np_
                new_state[p.name] = ns_
            if scaler is not None:
                # skipped step: buffer updates (e.g. BN running stats) from
                # the overflowed forward must not be committed either
                new_bufs = [jnp.where(found_inf, old, new)
                            for old, new in zip(buf_arrs, new_bufs)]
            new_scaler_state = scaler.update_state(scaler_state, found_inf) \
                if scaler is not None else scaler_state
            out_state = {n: new_state[n] for n in t_names}
            out_state.update(comm_updates)  # EF residuals + salt counter
            return loss_val, [new_params[n] for n in t_names], new_bufs, \
                out_state, new_scaler_state, found_inf

        return apply

    def _maybe_aot(self, sig, call_args, kind):
        """AOT executable cache (``runtime.aot``): with a cache active,
        the first call per compiled signature hydrates the fused step
        from disk (or compiles eagerly and publishes) instead of
        letting ``jax.jit`` compile lazily — a warm replica pays
        deserialize time, not XLA compile time. The cache entry
        replaces the lazy wrapper in ``self._compiled`` (same calling
        convention, donation baked in, outputs bitwise identical); with
        no cache the lazy jit stays. A step the compiler refuses raises
        here exactly as it would on first dispatch."""
        fn = self._compiled[sig]
        if not hasattr(fn, "lower"):
            return fn  # already hydrated for this signature
        from ..runtime import aot as _aot

        cache = _aot.active_cache()
        if cache is None:
            return fn
        import time

        t0 = time.perf_counter()
        exe, info = _aot.load_or_compile(
            fn, call_args, kind=kind, cache=cache,
            label=type(self.model).__name__)
        if exe is None:
            return fn
        self._compiled[sig] = exe
        from ..obs import journal as _journal

        if _journal.ACTIVE is not None:
            prov = _aot.provenance_fields(info)
            _journal.ACTIVE.event(
                "compile", source=prov.get("via", "xla"),
                site="trainstep",
                ms=(time.perf_counter() - t0) * 1e3, **prov)
        return exe

    def _capture_arg_structs(self, sig, args):
        """Once per compiled shape (NOT per step): shape/dtype/sharding
        structs of the call args, so obs.spmd can later re-lower the
        exact executable for its CollectiveProfile without holding
        the (donated) arrays alive. Only COMMITTED shardings are
        kept (a mesh-placed param next to an uncommitted lr scalar
        must not read as a device conflict); uncommitted args
        replicate over the committed arrays' mesh."""
        mesh = None
        for a in jax.tree_util.tree_leaves(args):
            sh = getattr(a, "sharding", None)
            if getattr(a, "committed", False) and \
                    getattr(sh, "mesh", None) is not None:
                mesh = sh.mesh
                break
        rep = None if mesh is None else \
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())

        def _struct(a):
            try:
                sh = a.sharding if getattr(a, "committed", False) \
                    else rep
                if sh is None:
                    return jax.ShapeDtypeStruct(a.shape, a.dtype)
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=sh)
            except (AttributeError, TypeError):
                return jax.ShapeDtypeStruct(np.shape(a),
                                            np.asarray(a).dtype)

        self._arg_structs[sig] = jax.tree_util.tree_map(_struct, args)

    def _first_call(self, sig, args):
        """The first call of a batch signature: its arg structs, its
        compile (``_maybe_aot``: the ``aot.*`` phase records) and its
        first execute, which loads the executable onto the device (and,
        with no cache active, is the lazy jit's whole compile): the phase
        record ``trainstep.first_execute``. Every later call goes through
        the gated span ``trainstep.execute``."""
        self._capture_arg_structs(sig, args)
        fn = self._maybe_aot(sig, args, "trainstep")
        with _phase("trainstep.first_execute", sig=str(sig)):
            return fn(*args)

    def __call__(self, *batch):
        # host spans (obs.trace: a no-op unless tracing is on); step_num
        # makes this one the profiler's step marker
        with _span("trainstep.call", step_num=self.optimizer._global_step):
            return self._call(batch)

    def _call(self, batch):
        if self._comm is not None and \
                self._comm.options.accumulate_steps > 1:
            raise ValueError(
                "accumulate_steps > 1 exchanges gradients once per N "
                "microbatches and therefore needs the fused path: call "
                "run_fused(batches, steps=K) with K a multiple of N")
        opt = self.optimizer
        with _span("trainstep.feed"):
            arrays = [_as_array(b) for b in batch]
            sig = tuple((a.shape, str(a.dtype)) for a in arrays)
            if sig not in self._compiled:
                pure = self._make_pure()
                donate = (0, 1, 2) if self._donate else ()
                self._compiled[sig] = jax.jit(pure, donate_argnums=donate)
            opt_state = {p.name: opt._accumulators[p.name]
                         for p in self._trainable}
            for k in self._comm_state_keys:
                opt_state[k] = opt._accumulators[k]
            param_arrs = [p._data for p in self._trainable]
            buf_arrs = [b._data for b in self._buffers]
            lr = jnp.float32(opt.get_lr())
            key = prandom.next_key()
        if sig not in self._arg_structs:
            loss, new_params, new_bufs, new_state, new_scaler, found_bad = \
                self._first_call(
                    sig, (param_arrs, buf_arrs, opt_state, lr, key, arrays,
                          self._scaler_state))
        else:
            fn = self._maybe_aot(
                sig, (param_arrs, buf_arrs, opt_state, lr, key, arrays,
                      self._scaler_state), "trainstep")
            with _span("trainstep.execute"):
                loss, new_params, new_bufs, new_state, new_scaler, \
                    found_bad = fn(param_arrs, buf_arrs, opt_state, lr, key,
                                   arrays, self._scaler_state)
        with _span("trainstep.rebind"):
            for p, a in zip(self._trainable, new_params):
                p._data = a
            for b, a in zip(self._buffers, new_bufs):
                b._data = a
            for n, s in new_state.items():
                opt._accumulators[n] = s
            self._scaler_state = new_scaler
        opt._global_step += 1
        # the raw device flag (no sync): resilience.GuardedStep and tests
        # read it to count in-graph scaler skips without a host round-trip
        self.last_found_inf = found_bad
        if self.check_nan and self.scaler is None and bool(found_bad):
            from ..utils.nan_guard import NanInfError, nonfinite_summary

            # only the loss is still on hand (grads died with the trace);
            # attach its summary when IT is the nonfinite value, and an
            # empty one when the overflow was grad-only — a zero-count
            # summary would be an actively misleading postmortem
            s = nonfinite_summary(loss)
            raise NanInfError(
                f"NaN/Inf in loss or gradients at step {opt._global_step} "
                f"(loss={float(np.asarray(loss))})",
                summary=s if s["num_nan"] or s["num_inf"] else None)
        return Tensor(loss, _internal=True)

    def _make_fused_accum(self, K, N):
        """Fused window with gradient accumulation (comm-efficient path
        only): a nested scan over (K/N windows, N microbatches). The
        inner scan runs the vmapped tape and ADDS the per-device local
        bucket flats — zero communication; the exchange + optimizer
        update run once per window, so the all-reduce fires once per N
        microbatches. Buffers (BN stats) evolve per microbatch through
        the inner carry; the scaler's found-inf freeze applies to the
        whole window (its skip decision is made on the accumulated
        gradient)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        plan, mesh = self._comm, self._comm_mesh
        scaler = self.scaler
        tape = self._make_tape()
        local = self._comm_local(tape)
        apply = self._make_apply()
        W = K // N
        sh_acc = NamedSharding(mesh, P("data", None))

        def fused(param_arrs, buf_arrs, opt_state, lrs, keys,
                  stacked_batch, scaler_state):
            def resh(x):
                return jnp.reshape(x, (W, N) + tuple(x.shape[1:]))

            def outer(carry, xs):
                params, bufs, state, sstate = carry
                lr_w, key_w, batch_w = xs
                scale = sstate["scale"] if scaler is not None else None

                def inner(ic, xk):
                    accs, ibufs = ic
                    key_k, batch_k = xk
                    loss_k, flats, nb = local(params, ibufs, key_k,
                                              list(batch_k), scale)
                    return ([a + f for a, f in zip(accs, flats)], nb), \
                        loss_k

                accs0 = [jax.lax.with_sharding_constraint(
                    jnp.zeros((plan.ndev, b.padded), jnp.float32), sh_acc)
                    for b in plan.buckets]
                (accs, nbufs), losses_w = jax.lax.scan(
                    inner, (accs0, list(bufs)), (key_w, list(batch_w)))
                # denom defaults to ndev * N: the exchanged gradient is
                # the mean over the whole N x B effective batch
                grads, comm_updates = self._comm_exchange(accs, state)
                _, np_, nb_, ns_, nss_, finf = apply(
                    grads, losses_w[-1], nbufs, params, bufs, state,
                    lr_w[-1], sstate, comm_updates)
                return (np_, nb_, ns_, nss_), (losses_w, finf)

            (np_, nb_, ns_, nss_), (losses, finfs) = jax.lax.scan(
                outer,
                (list(param_arrs), list(buf_arrs), dict(opt_state),
                 scaler_state),
                (resh(lrs), resh(keys), [resh(b) for b in stacked_batch]))
            # (W, N) microbatch losses -> the (K,) trajectory; the
            # per-window found-inf flag covers each of its N microbatches
            return (jnp.reshape(losses, (K,)), np_, nb_, ns_, nss_,
                    jnp.repeat(finfs, N))

        return fused

    def run_fused(self, batches, steps=None):
        """Run K microbatches through ONE fused ``lax.scan`` executable.

        ``batches`` is a sequence of K per-step batch tuples (uniform
        shapes/dtypes — the same tuples K ``step(*batch)`` calls would
        take), or a single pre-stacked tuple of arrays with a leading K
        axis (then ``steps=K`` is required). The whole training state —
        params, buffers, optimizer slots, scaler state — rides the scan
        as a DONATED carry; per-step PRNG keys are pre-drawn from the
        host RNG stream (the same draws K sequential calls would make),
        so the K-step loss trajectory matches K sequential
        ``step(*batch)`` calls step for step — same ops, same keys, same
        LR; XLA may fuse the scan body marginally differently than the
        standalone step (last-ulp float drift after a few steps), so
        equality is to float tolerance here. (The static
        ``Executor.run_steps`` path IS pinned bitwise.) Cost: one
        compile + one dispatch per window instead of K.

        Host-side per-step work necessarily happens at WINDOW
        granularity: the learning rate is sampled once for all K
        microbatches, ``optimizer._global_step`` advances by K at the
        end (it counts MICROBATCHES, matching the per-call path and the
        journal's ``steps``, even when ``accumulate_steps=N`` means only
        K/N optimizer updates ran — LR schedulers here key on their own
        explicit ``scheduler.step()`` calls, not this counter), and with
        ``check_nan`` a nonfinite ANY microbatch raises after the
        window. ``last_found_inf`` becomes the any-step flag;
        ``last_found_inf_per_step`` keeps the per-step (K,) vector.

        Returns the (K,) per-microbatch loss trajectory as a Tensor.
        """
        if steps is None:
            try:
                steps = len(batches)
            except TypeError:
                raise ValueError(
                    "run_fused needs steps=K when batches is not a "
                    "sized sequence of per-step batch tuples")
        K = int(steps)
        if K <= 0:
            raise ValueError(f"steps must be >= 1, got {K}")

        seq = list(batches)
        if seq and isinstance(seq[0], (list, tuple)):
            # K per-step batch tuples (the same tuples __call__ takes;
            # a single-input loss still passes [(x0,), (x1,), ...])
            if len(seq) != K:
                raise ValueError(
                    f"steps={K} but {len(seq)} microbatches were given")
            rows = [tuple(_as_array(b) for b in row) for row in seq]
            sig0 = tuple((a.shape, str(a.dtype)) for a in rows[0])
            for i, row in enumerate(rows[1:], 1):
                if tuple((a.shape, str(a.dtype)) for a in row) != sig0:
                    raise ValueError(
                        f"microbatch {i} signature "
                        f"{[(a.shape, str(a.dtype)) for a in row]} != "
                        f"microbatch 0 {list(sig0)}: fused steps need "
                        "uniform shapes")
            stacked = [jnp.stack([row[i] for row in rows])
                       for i in range(len(rows[0]))]
        else:  # pre-stacked tuple of (K, ...) arrays
            stacked = [_as_array(b) for b in seq]
            for a in stacked:
                if a.ndim < 1 or a.shape[0] != K:
                    raise ValueError(
                        f"pre-stacked batch array has shape {a.shape}; "
                        f"expected a leading microbatch axis of {K}")
            sig0 = tuple((a.shape[1:], str(a.dtype)) for a in stacked)
        N = (self._comm.options.accumulate_steps
             if self._comm is not None else 1)
        if N > 1 and K % N:
            raise ValueError(
                f"accumulate_steps={N} must divide the fused window "
                f"(steps={K}): partial accumulation windows would "
                "silently change the effective batch")
        fsig = ("fused", K) + sig0
        if fsig not in self._compiled:
            if N == 1:
                pure = self._make_pure()

                def fused(param_arrs, buf_arrs, opt_state, lrs, keys,
                          stacked_batch, scaler_state):
                    def body(carry, xs):
                        params, bufs, state, sstate = carry
                        lr, key, batch = xs
                        loss, np_, nb_, ns_, nss_, finf = pure(
                            params, bufs, state, lr, key, list(batch),
                            sstate)
                        return (np_, nb_, ns_, nss_), (loss, finf)

                    (np_, nb_, ns_, nss_), (losses, finfs) = jax.lax.scan(
                        body,
                        (list(param_arrs), list(buf_arrs), dict(opt_state),
                         scaler_state),
                        (lrs, keys, list(stacked_batch)), length=K)
                    return losses, np_, nb_, ns_, nss_, finfs
            else:
                fused = self._make_fused_accum(K, N)

            donate = (0, 1, 2) if self._donate else ()
            self._compiled[fsig] = jax.jit(fused, donate_argnums=donate)
        fn = self._compiled[fsig]
        opt = self.optimizer
        opt_state = {p.name: opt._accumulators[p.name]
                     for p in self._trainable}
        for k in self._comm_state_keys:
            opt_state[k] = opt._accumulators[k]
        param_arrs = [p._data for p in self._trainable]
        buf_arrs = [b._data for b in self._buffers]
        # one LR sample per window; per-step keys are PRE-DRAWN from the
        # host stream — bitwise the draws K sequential calls would make
        lrs = jnp.full((K,), jnp.float32(opt.get_lr()))
        keys = jnp.stack([prandom.next_key() for _ in range(K)])
        if fsig not in self._arg_structs:
            self._capture_arg_structs(
                fsig, (param_arrs, buf_arrs, opt_state, lrs, keys,
                       stacked, self._scaler_state))
        fn = self._maybe_aot(
            fsig, (param_arrs, buf_arrs, opt_state, lrs, keys, stacked,
                   self._scaler_state), "trainstep_fused")
        losses, new_params, new_bufs, new_state, new_scaler, finfs = fn(
            param_arrs, buf_arrs, opt_state, lrs, keys, stacked,
            self._scaler_state)
        for p, a in zip(self._trainable, new_params):
            p._data = a
        for b, a in zip(self._buffers, new_bufs):
            b._data = a
        for n, s in new_state.items():
            opt._accumulators[n] = s
        self._scaler_state = new_scaler
        opt._global_step += K
        # raw device flags, no sync (same contract as __call__)
        self.last_found_inf = jnp.any(finfs)
        self.last_found_inf_per_step = finfs
        if self.check_nan and self.scaler is None and \
                bool(np.asarray(self.last_found_inf)):
            from ..utils.nan_guard import NanInfError

            bad = np.flatnonzero(np.asarray(finfs))
            raise NanInfError(
                f"NaN/Inf in loss or gradients in fused window ending at "
                f"step {opt._global_step} (microbatch index(es) "
                f"{bad.tolist()} of {K})")
        return Tensor(losses, _internal=True)

    def compiled(self):
        """The ``jax.stages.Compiled`` of the most recently compiled step
        shape — its HLO text, ``memory_analysis()`` and cost are what the
        device will run. With an AOT cache active this is the hydrated
        executable itself; otherwise the lazy jit is lowered against the
        arg structs captured at its first call and compiled (BLOCKING:
        reporting code only, never the training loop). None before the
        first step."""
        if not self._arg_structs:
            return None
        sig = next(reversed(self._arg_structs))
        fn = self._compiled[sig]
        if hasattr(fn, "lower"):
            fn = fn.lower(*self._arg_structs[sig]).compile()
        return fn

    def collective_profile(self, mesh=None):
        """CollectiveProfile of the most recently compiled step shape
        (``obs.spmd``): per-kind collective op counts and byte volumes
        parsed from the executable's HLO, attributed to ``mesh``'s axes
        when given (``DistributedTrainStep`` passes its own mesh).
        BLOCKING (see :meth:`compiled`). None before the first step;
        cached per (compiled shape, mesh)."""
        if not self._arg_structs:
            return None
        sig = next(reversed(self._arg_structs))
        key = (sig, None if mesh is None else tuple(mesh.shape.items()))
        if key not in self._profiles:
            from ..obs import spmd as _spmd

            self._profiles[key] = _spmd.collective_profile(
                self.compiled().as_text(), mesh=mesh)
        return self._profiles[key]


class StaticFunction:
    """jit-compiled forward wrapper (ref: dygraph/jit.py StaticFunction)."""

    def __init__(self, fn, model=None, train=False):
        self._fn = fn
        self.__wrapped__ = fn  # functools convention: inspect/unwrap
        self._model = model
        self._train = train
        self._compiled = {}
        if model is not None:
            self._params, self._buffers = _collect_state([model])
        else:
            self._params, self._buffers = [], []

    def __call__(self, *args):
        arrays = [a._data if isinstance(a, Tensor)
                  else jnp.asarray(np.asarray(a)) for a in args]
        sig = tuple((a.shape, str(a.dtype)) for a in arrays)
        if sig not in self._compiled:
            params, buffers = self._params, self._buffers

            def pure(param_arrs, buf_arrs, key, xs):
                with _rebind(params, list(param_arrs)), \
                        _rebind(buffers, list(buf_arrs)), \
                        prandom.key_context(key), \
                        dispatch.no_grad(), dispatch.fresh_tape():
                    ts = [Tensor(a, _internal=True) for a in xs]
                    out = self._fn(*ts) if self._model is None \
                        else self._fn(self._model, *ts)
                    return jax.tree_util.tree_map(
                        lambda t: t._data if isinstance(t, Tensor) else t, out,
                        is_leaf=lambda t: isinstance(t, Tensor))

            self._compiled[sig] = jax.jit(pure)
        param_arrs = [p._data for p in self._params]
        buf_arrs = [b._data for b in self._buffers]
        out = self._compiled[sig](param_arrs, buf_arrs, prandom.next_key(),
                                  arrays)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, _internal=True) if isinstance(a, jax.Array) else a,
            out)


def to_static(layer_or_fn=None, input_spec=None, **kwargs):
    """ref: paddle.jit.to_static. Wraps a Layer (its forward) or a function
    into a shape-cached jax.jit callable."""
    from ..nn.layer import Layer

    def wrap(obj):
        if isinstance(obj, Layer):
            sf = StaticFunction(lambda m, *xs: m(*xs), model=obj)
            obj._static_forward = sf
            return sf
        return StaticFunction(obj)

    if layer_or_fn is None:
        return wrap
    return wrap(layer_or_fn)


def jit(fn=None, **kwargs):
    """Decorator alias: ``@paddle_tpu.jit`` compiles an eager function."""
    return to_static(fn, **kwargs)


_no_jit = contextlib.nullcontext
no_jit = _no_jit
