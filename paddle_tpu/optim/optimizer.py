"""Optimizers.

Ref: python/paddle/fluid/optimizer.py (SGD..Lamb + EMA/LookAhead wrappers)
and paddle/fluid/operators/optimizers/*.

Design: each rule is a pure function ``_update(p, g, state, lr) ->
(new_p, new_state)`` over jax arrays. Eager ``step()`` walks Parameters and
rebinds; the jitted train-step path (framework/jit.py) calls
``apply_gradients`` on whole pytrees so the optimizer update fuses into the
step executable together with forward+backward — one XLA program, donated
buffers, no per-op launches (the reference launches one CUDA kernel per
param per step).

``multi_precision`` keeps float32 master weights for bf16/fp16 params
(ref: mixed_precision master-weight behavior).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dispatch
from ..core.tensor import Tensor
from .lr import LRScheduler
from .regularizer import L1Decay, L2Decay, WeightDecayRegularizer

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adagrad", "Adadelta", "RMSProp", "Adam",
    "AdamW", "Adamax", "Lamb", "Ftrl", "ExponentialMovingAverage",
    "LookAhead", "DecayedAdagrad", "Dpsgd", "LarsMomentum", "DGCMomentum",
    "ModelAverage", "RecomputeOptimizer", "PipelineOptimizer",
]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        self._parameter_list = list(parameters) if parameters is not None else None
        self._learning_rate = learning_rate
        if isinstance(weight_decay, (int, float)):
            weight_decay = L2Decay(weight_decay)
        self._regularization = weight_decay
        self._explicit_grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: dict[str, dict] = {}
        self._global_step = 0

    @property
    def _grad_clip(self):
        """Explicit clip wins; otherwise the process-wide default from
        fluid's set_gradient_clip(), resolved at USE time (the reference
        resolves it in minimize, so clips registered after optimizer
        construction must still apply)."""
        explicit = getattr(self, "_explicit_grad_clip", None)  # wrapper
        if explicit is not None:  # subclasses may skip Optimizer.__init__
            return explicit
        from .clip import get_gradient_clip

        return get_gradient_clip()

    @_grad_clip.setter
    def _grad_clip(self, value):
        self._explicit_grad_clip = value

    # -- lr -----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    @property
    def _param_groups(self):
        if self._parameter_list is None:
            raise ValueError("optimizer constructed without parameters")
        return self._parameter_list

    # -- state --------------------------------------------------------------
    def _state_for(self, p):
        key = p.name
        if key not in self._accumulators:
            # with an f32 master copy the update runs in f32, so the slots
            # are made in f32 too: a slot that changed dtype after the
            # first step would recompile the fused step (and an
            # AOT-compiled one refuses the call)
            master = p._data.astype(jnp.float32) if self._multi_precision \
                and p._data.dtype in (jnp.bfloat16, jnp.float16) else None
            s = self._init_state(master if master is not None else p._data)
            if master is not None:
                s["master"] = master
            self._accumulators[key] = s
        return self._accumulators[key]

    def _init_state(self, p):
        return {}

    def _update(self, p, g, s, lr):
        raise NotImplementedError

    # -- the eager step -----------------------------------------------------
    def step(self):
        with dispatch.no_grad():
            pgs = [(p, p.grad._data if isinstance(p.grad, Tensor) else p.grad)
                   for p in self._param_groups
                   if p.trainable and p.grad is not None]
            if self._grad_clip is not None:
                pgs = self._grad_clip(pgs)
            base_lr = self.get_lr()
            for p, g in pgs:
                self._current_param = p
                reg = p.regularizer if p.regularizer is not None else self._regularization
                s = self._state_for(p)
                master = s.get("master")
                pw = master if master is not None else p._data
                g = g.astype(pw.dtype)
                if reg is not None and not isinstance(self, AdamW):
                    g = reg(pw, g)
                lr = base_lr * p.optimize_attr.get("learning_rate", 1.0)
                new_p, new_s = self._update(pw, g, s, lr)
                if master is not None:
                    new_s["master"] = new_p
                    p._replace(new_p.astype(p._data.dtype))
                else:
                    p._replace(new_p)
                self._accumulators[p.name] = new_s
        self._global_step += 1

    def clear_grad(self):
        for p in self._param_groups:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        tracer = dispatch.current_tracer()
        if tracer is not None:  # static-graph mode: delegate to the program
            from ..static_ import build_optimize_ops

            return build_optimize_ops(self, loss, parameters)
        if loss.stop_gradient:
            raise ValueError("loss has stop_gradient=True; nothing to minimize")
        loss.backward()
        self.step()
        return None, None

    # -- functional path (used inside jit) ----------------------------------
    def apply_gradients_tree(self, params, grads, states, lr=None):
        """Pure pytree update: (params, states) -> (new_params, new_states).

        params/grads: dict name->array; states: dict name->state-dict.
        Safe to call inside jax.jit — nothing here touches Python state.
        """
        lr = self.get_lr() if lr is None else lr
        new_p, new_s = {}, {}
        for name, p in params.items():
            g = grads.get(name)
            if g is None:
                new_p[name], new_s[name] = p, states.get(name, {})
                continue
            s = states.get(name) or self._init_state(p)
            np_, ns_ = self._update(p, g.astype(p.dtype), s, lr)
            new_p[name], new_s[name] = np_, ns_
        return new_p, new_s

    # -- serialization ------------------------------------------------------
    def state_dict(self):
        out = {}
        for pname, s in self._accumulators.items():
            for k, v in s.items():
                out[f"{pname}.{k}"] = np.asarray(v)
        out["@global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            out["@lr"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        for k, v in state.items():
            if k == "@global_step":
                self._global_step = int(v)
            elif k == "@lr":
                if isinstance(self._learning_rate, LRScheduler):
                    self._learning_rate.set_state_dict(v)
            else:
                pname, slot = k.rsplit(".", 1)
                self._accumulators.setdefault(pname, {})[slot] = jnp.asarray(v)

    set_dict = set_state_dict


class SGD(Optimizer):
    def _update(self, p, g, s, lr):
        return p - lr * g, s


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def _update(self, p, g, s, lr):
        v = self._momentum * s["velocity"] + g
        if self._use_nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {**s, "velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6,
                 initial_accumulator_value=0.0, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, p):
        return {"moment": jnp.full_like(p, self._init_acc)}

    def _update(self, p, g, s, lr):
        m = s["moment"] + g * g
        return p - lr * g / (jnp.sqrt(m) + self._epsilon), {**s, "moment": m}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._rho = rho

    def _init_state(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p),
                "avg_squared_update": jnp.zeros_like(p)}

    def _update(self, p, g, s, lr):
        asg = self._rho * s["avg_squared_grad"] + (1 - self._rho) * g * g
        delta = jnp.sqrt((s["avg_squared_update"] + self._epsilon) /
                         (asg + self._epsilon)) * g
        asu = self._rho * s["avg_squared_update"] + (1 - self._rho) * delta * delta
        return p - lr * delta, {**s, "avg_squared_grad": asg,
                                "avg_squared_update": asu}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_state(self, p):
        s = {"mean_square": jnp.zeros_like(p), "momentum": jnp.zeros_like(p)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p)
        return s

    def _update(self, p, g, s, lr):
        ms = self._rho * s["mean_square"] + (1 - self._rho) * g * g
        ns = {**s, "mean_square": ms}
        if self._centered:
            mg = self._rho * s["mean_grad"] + (1 - self._rho) * g
            ns["mean_grad"] = mg
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * s["momentum"] + lr * g / denom
        ns["momentum"] = mom
        return p - mom, ns


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, p):
        f32 = jnp.float32
        return {"moment1": jnp.zeros(p.shape, f32),
                "moment2": jnp.zeros(p.shape, f32),
                "beta1_pow": jnp.ones((), f32),
                "beta2_pow": jnp.ones((), f32)}

    def _update(self, p, g, s, lr):
        gf = g.astype(jnp.float32)
        b1p = s["beta1_pow"] * self._beta1
        b2p = s["beta2_pow"] * self._beta2
        m = self._beta1 * s["moment1"] + (1 - self._beta1) * gf
        v = self._beta2 * s["moment2"] + (1 - self._beta2) * gf * gf
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        step = lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        new_p = (p.astype(jnp.float32) - step).astype(p.dtype)
        return new_p, {**s, "moment1": m, "moment2": v,
                       "beta1_pow": b1p, "beta2_pow": b2p}


class AdamW(Adam):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name)
        self._coeff = float(weight_decay) if isinstance(weight_decay, (int, float)) \
            else weight_decay.coeff
        self._apply_decay_fn = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _update(self, p, g, s, lr):
        # decoupled decay (ref: AdamW paper / paddle adamw_op);
        # apply_decay_param_fun excludes e.g. biases/LayerNorm by name, and
        # lr_ratio scales the per-param lr (layer-wise decay recipes)
        cur = getattr(self, "_current_param", None)
        if self._lr_ratio is not None and cur is not None:
            lr = lr * float(self._lr_ratio(cur))
        new_p, ns = super()._update(p, g, s, lr)
        if self._apply_decay_fn is not None and cur is not None and \
                not self._apply_decay_fn(cur.name):
            return new_p, ns
        decay = lr * self._coeff
        new_p = (new_p.astype(jnp.float32) -
                 decay * p.astype(jnp.float32)).astype(p.dtype)
        return new_p, ns


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, p):
        return {"moment": jnp.zeros_like(p, jnp.float32),
                "inf_norm": jnp.zeros_like(p, jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32)}

    def _update(self, p, g, s, lr):
        gf = g.astype(jnp.float32)
        b1p = s["beta1_pow"] * self._beta1
        m = self._beta1 * s["moment"] + (1 - self._beta1) * gf
        u = jnp.maximum(self._beta2 * s["inf_norm"], jnp.abs(gf))
        step = (lr / (1 - b1p)) * m / (u + self._epsilon)
        return (p.astype(jnp.float32) - step).astype(p.dtype), \
            {**s, "moment": m, "inf_norm": u, "beta1_pow": b1p}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, p):
        return {"moment1": jnp.zeros_like(p, jnp.float32),
                "moment2": jnp.zeros_like(p, jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _update(self, p, g, s, lr):
        gf = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        b1p = s["beta1_pow"] * self._beta1
        b2p = s["beta2_pow"] * self._beta2
        m = self._beta1 * s["moment1"] + (1 - self._beta1) * gf
        v = self._beta2 * s["moment2"] + (1 - self._beta2) * gf * gf
        mhat = m / (1 - b1p)
        vhat = v / (1 - b2p)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + self._lamb_wd * pf
        w_norm = jnp.sqrt(jnp.sum(pf * pf))
        r_norm = jnp.sqrt(jnp.sum(r * r))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (pf - lr * trust * r).astype(p.dtype), \
            {**s, "moment1": m, "moment2": v, "beta1_pow": b1p,
             "beta2_pow": b2p}


class Ftrl(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _init_state(self, p):
        return {"squared": jnp.zeros_like(p, jnp.float32),
                "linear": jnp.zeros_like(p, jnp.float32)}

    def _update(self, p, g, s, lr):
        gf = g.astype(jnp.float32)
        pf = p.astype(jnp.float32)
        n, z = s["squared"], s["linear"]
        new_n = n + gf * gf
        sigma = (new_n ** -self._lr_power - n ** -self._lr_power) / lr
        new_z = z + gf - sigma * pf
        denom = new_n ** -self._lr_power / lr + 2 * self._l2
        new_p = jnp.where(
            jnp.abs(new_z) > self._l1,
            (jnp.sign(new_z) * self._l1 - new_z) / denom, 0.0)
        return new_p.astype(p.dtype), {**s, "squared": new_n, "linear": new_z}


class ExponentialMovingAverage:
    """ref: fluid/optimizer.py ExponentialMovingAverage (dygraph semantics)."""

    def __init__(self, model_or_params, decay=0.999, thres_steps=None):
        from ..nn.layer import Layer

        if isinstance(model_or_params, Layer):
            self._params = model_or_params.parameters()
        else:
            self._params = list(model_or_params)
        self._decay = decay
        self._thres_steps = thres_steps
        self._shadow = {p.name: jnp.asarray(p._data) for p in self._params}
        self._backup = {}
        self._step = 0

    def update(self):
        self._step += 1
        if self._thres_steps is not None:
            # warm-up ramp only when requested (ref: EMA thres_steps)
            d = min(self._decay, (1 + self._step) / (10 + self._step))
        else:
            d = self._decay
        for p in self._params:
            self._shadow[p.name] = d * self._shadow[p.name] + \
                (1 - d) * p._data.astype(self._shadow[p.name].dtype)

    def apply(self):
        self._backup = {p.name: p._data for p in self._params}
        for p in self._params:
            p._replace(self._shadow[p.name].astype(p._data.dtype))

    def restore(self):
        for p in self._params:
            p._replace(self._backup[p.name])
        self._backup = {}


class LookAhead(Optimizer):
    """ref: fluid LookaheadOptimizer: k fast steps, then slow-weights pull."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5, name=None):
        self.inner = inner_optimizer
        self.alpha = alpha
        self.k = k
        self._slow = None
        self._steps = 0

    @property
    def _param_groups(self):
        return self.inner._param_groups

    def get_lr(self):
        return self.inner.get_lr()

    def step(self):
        if self._slow is None:
            self._slow = {p.name: jnp.asarray(p._data)
                          for p in self.inner._param_groups}
        self.inner.step()
        self._steps += 1
        if self._steps % self.k == 0:
            for p in self.inner._param_groups:
                slow = self._slow[p.name] + self.alpha * (
                    p._data.astype(jnp.float32) - self._slow[p.name])
                self._slow[p.name] = slow
                p._replace(slow.astype(p._data.dtype))

    def clear_grad(self):
        self.inner.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, **kw):
        loss.backward()
        self.step()
        return None, None

    def state_dict(self):
        return self.inner.state_dict()

    def set_state_dict(self, state):
        self.inner.set_state_dict(state)


class DecayedAdagrad(Optimizer):
    """ref: fluid/optimizer.py DecayedAdagradOptimizer:
    moment = decay * moment + (1 - decay) * g^2."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, name)
        self._decay = decay
        self._epsilon = epsilon

    def _init_state(self, p):
        return {"moment": jnp.zeros_like(p)}

    def _update(self, p, g, s, lr):
        m = self._decay * s["moment"] + (1 - self._decay) * g * g
        return p - lr * g / (jnp.sqrt(m) + self._epsilon), \
            {**s, "moment": m}


class Dpsgd(Optimizer):
    """ref: fluid/optimizer.py DpsgdOptimizer (differentially-private
    SGD): per-update gradient clip to ``clip`` then Gaussian noise with
    scale ``sigma * clip`` scaled by 1/batch_size."""

    def __init__(self, learning_rate=0.001, clip=0.9, batch_size=0.999,
                 sigma=1.0, parameters=None, seed=0, name=None):
        super().__init__(learning_rate, parameters, None, None, name)
        self._clip = clip
        self._batch = batch_size
        self._sigma = sigma

    def _update(self, p, g, s, lr):
        from ..core import random as prandom

        if isinstance(g, jax.core.Tracer) and prandom._STATE.get("ctx") \
                is None:
            # Without a threaded key the noise would bake into the
            # compiled update as a constant — identical (cancellable)
            # noise every step, voiding the DP guarantee.
            raise RuntimeError(
                "Dpsgd under jit needs a threaded PRNG key: drive it "
                "through paddle_tpu.TrainStep / paddle_tpu.jit (which "
                "thread one per step), not a bare jax.jit")
        norm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
        g = g * jnp.minimum(1.0, self._clip / jnp.maximum(norm, 1e-12)) \
            .astype(g.dtype)
        noise = jax.random.normal(prandom.next_key(), g.shape,
                                  jnp.float32) * (self._sigma * self._clip)
        g = g + (noise / self._batch).astype(g.dtype)
        return p - lr * g, s


class LarsMomentum(Optimizer):
    """ref: fluid/optimizer.py LarsMomentumOptimizer: layerwise adaptive
    rate scaling — local_lr = lr * coeff * ||w|| / (||g|| + decay*||w||)."""

    def __init__(self, learning_rate, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._momentum = momentum
        self._coeff = lars_coeff
        self._decay = lars_weight_decay

    def _init_state(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def _update(self, p, g, s, lr):
        pf = p.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        wn = jnp.sqrt(jnp.sum(pf * pf))
        gn = jnp.sqrt(jnp.sum(gf * gf))
        local = lr * self._coeff * wn / jnp.maximum(
            gn + self._decay * wn, 1e-12)
        v = self._momentum * s["velocity"] + \
            (local * (gf + self._decay * pf)).astype(p.dtype)
        return p - v, {**s, "velocity": v}


class DGCMomentum(Momentum):
    """ref: fluid DGCMomentumOptimizer (deep gradient compression). The
    compression half is a network-transport optimization for NCCL rings;
    over ICI the gradients ride XLA all-reduce, so the TPU-native
    equivalent is plain Momentum (sparsification would only add host
    work). Kept for recipe compatibility."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, momentum, parameters=parameters,
                         use_nesterov=use_nesterov,
                         weight_decay=weight_decay, grad_clip=grad_clip,
                         name=name)


class ModelAverage:
    """ref: fluid/optimizer.py ModelAverage: accumulate parameter sums
    during training; apply() swaps in the running average over the last
    [min_average_window, max_average_window] updates."""

    def __init__(self, average_window_rate, model_or_params=None,
                 min_average_window=10000, max_average_window=10000,
                 parameters=None, name=None):
        from ..nn.layer import Layer

        src = model_or_params if model_or_params is not None else parameters
        if isinstance(src, Layer):
            self._params = src.parameters()
        else:
            self._params = list(src or [])
        self.rate = average_window_rate
        self.min_w = min_average_window
        self.max_w = max_average_window
        self._sum = {p.name: jnp.zeros_like(p._data, jnp.float32)
                     for p in self._params}
        self._count = 0
        self._backup = {}

    def step(self):
        self._count += 1
        # restart the window past max_average_window, but never while the
        # window is still shorter than min_average_window
        restart = self._count > self.max_w and self._count > self.min_w
        for p in self._params:
            if restart:
                self._sum[p.name] = p._data.astype(jnp.float32)
            else:
                self._sum[p.name] = self._sum[p.name] + \
                    p._data.astype(jnp.float32)
        if restart:
            self._count = 1

    def apply(self, executor=None, need_restore=True):
        if self._count == 0:
            raise RuntimeError(
                "ModelAverage.apply() before any step(): no accumulated "
                "window to average (parameters would be zeroed)")
        self._backup = {p.name: p._data for p in self._params}
        for p in self._params:
            p._replace((self._sum[p.name] / self._count)
                       .astype(p._data.dtype))

    def restore(self, executor=None):
        for p in self._params:
            p._replace(self._backup[p.name])
        self._backup = {}


class RecomputeOptimizer:
    """ref: fluid RecomputeOptimizer: wraps an optimizer so the listed
    checkpoint activations are rematerialized in backward. TPU-native:
    recompute is a property of the forward function (jax.checkpoint via
    framework/recompute.py), so this wrapper stores the segment spec and
    otherwise delegates."""

    def __init__(self, optimizer):
        self.inner = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = checkpoints

    def backward(self, loss, **kw):
        loss.backward()
        return []

    def apply_gradients(self, params_grads=None):
        self.inner.step()

    def minimize(self, loss, **kw):
        loss.backward()
        self.inner.step()
        return None, None

    def __getattr__(self, item):
        return getattr(self.inner, item)


class PipelineOptimizer:
    """ref: fluid PipelineOptimizer: stage-parallel training. The
    TPU-native pipeline is ``dist/pipeline.py`` (GPipe over ppermute);
    this wrapper keeps the fluid recipe shape and delegates stepping."""

    def __init__(self, optimizer, num_microbatches=1, start_cpu_core_id=0):
        self.inner = optimizer
        self.num_microbatches = num_microbatches

    def minimize(self, loss, **kw):
        loss.backward()
        self.inner.step()
        return None, None

    def __getattr__(self, item):
        return getattr(self.inner, item)
