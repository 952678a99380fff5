"""Central op dispatch.

This replaces the reference's op-dispatch machinery
(``paddle/fluid/framework/operator.cc`` OperatorWithKernel::Run and
``paddle/fluid/imperative/tracer.cc``): every framework op is a *pure jax
function*. In eager (dygraph) mode we execute it immediately, recording a
vjp closure on the autograd tape when gradients are required. In static mode
a Program builder intercepts the call and records a symbolic op instead; the
Executor later re-plays the recorded graph under ``jax.jit`` so the whole
program compiles to ONE fused XLA executable (the TPU-correct analog of the
reference's op-by-op kernel launches).
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

__all__ = [
    "apply",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "register_tracer",
    "current_tracer",
    "program_scope",
]

_tls = threading.local()


def _state():
    if not hasattr(_tls, "grad_enabled"):
        _tls.grad_enabled = True
        _tls.tracer_stack = []  # static-graph program builders
        _tls.tape_stack = []  # autograd tapes (innermost last)
        _tls.scopes = ()  # program scopes open round the ops (program_scope)
    return _tls


def is_grad_enabled() -> bool:
    return _state().grad_enabled


@contextlib.contextmanager
def no_grad():
    st = _state()
    prev, st.grad_enabled = st.grad_enabled, False
    try:
        yield
    finally:
        st.grad_enabled = prev


@contextlib.contextmanager
def enable_grad():
    st = _state()
    prev, st.grad_enabled = st.grad_enabled, True
    try:
        yield
    finally:
        st.grad_enabled = prev


# ---------------------------------------------------------------------------
# Static-graph tracer hook
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def register_tracer(tracer):
    """Push a static-graph tracer; ops are recorded instead of executed."""
    st = _state()
    st.tracer_stack.append(tracer)
    try:
        yield tracer
    finally:
        st.tracer_stack.pop()


def current_tracer():
    st = _state()
    return st.tracer_stack[-1] if st.tracer_stack else None


# ---------------------------------------------------------------------------
# Autograd tape
# ---------------------------------------------------------------------------


class TapeNode:
    __slots__ = ("inputs", "outputs", "vjp_fn", "name")

    def __init__(self, name, inputs, outputs, vjp_fn):
        self.name = name
        self.inputs = inputs  # list[Tensor]
        self.outputs = outputs  # list[Tensor]
        self.vjp_fn = vjp_fn


class Tape:
    def __init__(self):
        self.nodes: list[TapeNode] = []

    def record(self, node):
        self.nodes.append(node)

    def clear(self):
        self.nodes.clear()


def default_tape() -> Tape:
    st = _state()
    if not st.tape_stack:
        st.tape_stack.append(Tape())
    return st.tape_stack[-1]


@contextlib.contextmanager
def fresh_tape():
    """Scoped tape, used by paddle_tpu.grad() for double-backward isolation."""
    st = _state()
    t = Tape()
    st.tape_stack.append(t)
    try:
        yield t
    finally:
        st.tape_stack.pop()


# ---------------------------------------------------------------------------
# apply(): the single entry point every op goes through
# ---------------------------------------------------------------------------


def _is_tensor(x):
    from .tensor import Tensor

    return isinstance(x, Tensor)


def _unwrap(x):
    return x._data if _is_tensor(x) else x


def _wrap(arr, stop_gradient=True):
    from .tensor import Tensor

    return Tensor(arr, stop_gradient=stop_gradient, _internal=True)


def _all_float(out):
    outs = out if isinstance(out, tuple) else (out,)
    return all(jnp.issubdtype(o.dtype, jnp.inexact) for o in outs)


_amp_state = _cast_op_inputs = _nan_guard = None

# Push-style chaos hook (resilience.inject 'nan_op' corruption): None when
# no injector is active, so the disabled hot path pays one None check.
_chaos_op_hook = None

# Push-style telemetry hook (obs.enable_op_sampling): eager op counting
# is off by default and the disabled hot path pays the same one None
# check — the dispatcher cannot afford a registry probe per op.
_op_metrics_hook = None


def set_chaos_op_hook(fn):
    global _chaos_op_hook
    _chaos_op_hook = fn


def set_op_metrics_hook(fn):
    global _op_metrics_hook
    _op_metrics_hook = fn


def _lazy_hooks():
    """Bind the AMP / nan-guard hooks once (module-level import would be a
    cycle: amp.grad_scaler -> core.tensor -> core.dispatch)."""
    global _amp_state, _cast_op_inputs, _nan_guard
    if _amp_state is None:
        from ..amp.autocast import amp_state, cast_op_inputs
        from ..utils import nan_guard

        _amp_state, _cast_op_inputs, _nan_guard = \
            amp_state, cast_op_inputs, nan_guard


@contextlib.contextmanager
def program_scope(name):
    """Name a part of the model for a device profile: every op applied
    inside runs, under a trace, within ``jax.named_scope(name)`` as well as
    within its own name, and like that one the scope sits inside the
    differentiated function, so forward and backward instructions alike
    carry it (``forward/jvp(mtp)/rms_norm/mul``,
    ``backward/transpose(jvp(mtp))/rms_norm/reduce_sum``). HLO
    metadata only; eager dispatch enters no scope."""
    st = _state()
    st.scopes += (name,)
    try:
        yield
    finally:
        st.scopes = st.scopes[:-1]


def _named(name, fn):
    """``fn`` run under ``jax.named_scope(name)``, inside the program scopes
    open at the call (``program_scope``). The scope sits INSIDE the
    function ``jax.vjp`` differentiates, so the op's name lands in the HLO
    ``op_name`` of its forward ops (``.../jvp(sdpa)/...``) and, carried by
    the transposition, of its backward ops (``.../transpose(jvp(sdpa))/...``)
    with no second scope round the tape walk's ``vjp_fn``."""
    st = _state()
    names = st.scopes + (name,)

    def scoped(*xs, **attrs):
        # the ops ``fn`` itself applies (a recomputed block's) are traced
        # inside these scopes already: they open none of them again
        open_now, st.scopes = st.scopes, ()
        try:
            with contextlib.ExitStack() as stack:
                for scope in names:
                    stack.enter_context(jax.named_scope(scope))
                return fn(*xs, **attrs)
        finally:
            st.scopes = open_now

    return scoped


def apply(name, fn, *args, **attrs):
    """Run op ``name`` implemented by pure function ``fn``.

    ``args`` are tensor-like (differentiable) inputs; ``attrs`` are static
    python attributes baked into the computation (ref: OpDesc attrs).
    ``fn(*arrays, **attrs)`` must be jax-traceable and return one array or a
    tuple of arrays.

    Under a trace (``TrainStep``, ``to_static``: some input is a tracer) the
    op runs inside ``jax.named_scope(name)``, so a device profile names the
    compiled step's instructions by program op. Names are HLO metadata: they
    cost the compiled step nothing. Eager dispatch enters no scope.
    """
    tracer = current_tracer()
    if tracer is not None:
        return tracer.trace_op(name, fn, args, attrs)

    if _op_metrics_hook is not None:  # eager executions only: a recorded
        _op_metrics_hook(name)        # static op is not a dispatch

    arrays = [_unwrap(a) for a in args]
    need_grad = is_grad_enabled() and any(
        _is_tensor(a) and not a.stop_gradient for a in args
    )
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        fn = _named(name, fn)

    # AMP: cast inputs per the active auto_cast policy INSIDE the
    # differentiated function, so grads flow back in the original dtype and
    # XLA fuses the casts into the op (paddle_tpu.amp.auto_cast). The
    # helpers are imported once (cycle-safe) and the no-AMP hot path avoids
    # any extra closure.
    _lazy_hooks()
    if _amp_state() is not None:
        op_fn = lambda *xs: fn(*_cast_op_inputs(name, xs), **attrs)  # noqa: E731
        if need_grad:
            out, vjp_fn = jax.vjp(op_fn, *arrays)
        else:
            out = op_fn(*arrays)
    elif need_grad:
        out, vjp_fn = jax.vjp(lambda *xs: fn(*xs, **attrs), *arrays)
    else:
        out = fn(*arrays, **attrs)
    if need_grad and not _all_float(out):
        # Non-differentiable outputs (argmax, comparisons...): keep the
        # values, drop the tape record.
        need_grad = False

    multi = isinstance(out, tuple)
    outs = out if multi else (out,)

    if _chaos_op_hook is not None and not isinstance(
            outs[0], jax.core.Tracer):
        # chaos corruption BEFORE the nan-guard check, so detection sees
        # the injected fault; never under a trace (a corrupted tracer
        # would bake NaN into the compiled function permanently)
        outs = _chaos_op_hook(name, outs)

    if _nan_guard.check_nan_enabled() and not isinstance(
            outs[0], jax.core.Tracer):
        _nan_guard.check_op_outputs(name, outs)

    out_tensors = tuple(_wrap(o, stop_gradient=not need_grad) for o in outs)

    if need_grad:
        in_tensors = [a if _is_tensor(a) else None for a in args]
        default_tape().record(
            TapeNode(name, in_tensors, list(out_tensors), vjp_fn)
        )
    return out_tensors if multi else out_tensors[0]
