"""Layer: the module base class.

TPU-native analog of ``python/paddle/fluid/dygraph/layers.py`` (class Layer).
A Layer owns Parameters (leaf jax arrays), Buffers (non-trainable state like
BN running stats) and sub-layers, with the reference's state_dict /
named_parameters / hook API. Layers are pure-functional at the jax level:
parameters live outside jit; `paddle_tpu.jit`/`Model` extract the pytree of
params and close the functional train step over it.
"""
from __future__ import annotations

import collections

import numpy as np

from ..core.tensor import Tensor, Parameter
from ..core.dtype import convert_dtype
from ..core import dispatch
from ..obs.trace import phase as _phase
from ..utils import unique_name
from . import initializer as I
from .param_attr import ParamAttr

__all__ = ["Layer", "Sequential", "LayerList", "ParameterList", "LayerDict"]


class HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks):
        self._hooks = hooks
        self._hook_id = HookRemoveHelper._next_id[0]
        HookRemoveHelper._next_id[0] += 1

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        if name_scope is None:
            name_scope = _camel_to_snake(type(self).__name__)
        self._full_name = unique_name.generate(name_scope)
        self._dtype = convert_dtype(dtype) if dtype is not None else None
        self.training = True
        self._parameters: dict[str, Parameter] = collections.OrderedDict()
        self._buffers: dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names: set[str] = set()
        self._sub_layers: dict[str, Layer] = collections.OrderedDict()
        self._forward_pre_hooks: dict[int, callable] = collections.OrderedDict()
        self._forward_post_hooks: dict[int, callable] = collections.OrderedDict()

    # -- identity -----------------------------------------------------------
    def full_name(self):
        return self._full_name

    # -- train/eval ---------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- parameter creation (ref: LayerObjectHelper / LayerHelperBase) ------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = convert_dtype(dtype) if dtype is not None else (self._dtype or convert_dtype("float32"))
        init = attr.initializer or default_initializer or I.global_initializer(is_bias)
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        name = attr.name or unique_name.generate(self._full_name + ("_b" if is_bias else "_w"))
        # once a parameter: the host's time in its initializer, the programs
        # it compiles as children (obs.trace phase records, always written)
        with _phase("startup.param_init", name=name,
                    bytes=int(np.prod(shape)) * np.dtype(dtype).itemsize):
            data = init(shape, dtype)
        tracer = dispatch.current_tracer()
        if tracer is not None:
            # static mode: create a persistable parameter Variable; the
            # initializer ran eagerly (shapes are known at build time), so
            # the value goes straight into the global scope — the startup
            # program is a no-op (ref: startup initializer ops).
            from ..static_.program import global_scope

            blk = tracer.program.global_block
            v = blk.create_var(name=name, shape=shape, dtype=dtype,
                               persistable=True, stop_gradient=not attr.trainable)
            v.is_parameter = True
            v.trainable = attr.trainable
            v.optimize_attr = {"learning_rate": attr.learning_rate}
            v.regularizer = attr.regularizer
            v.need_clip = attr.need_clip
            global_scope().set(name, data)
            self._parameters[name.replace(".", "_")] = v  # traversal support
            return v
        p = Parameter(data, name=name, trainable=attr.trainable)
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def create_tensor(self, name=None, dtype=None, default_initializer=None):
        dtype = convert_dtype(dtype) if dtype is not None else (self._dtype or convert_dtype("float32"))
        init = default_initializer or I.Constant(0.0)
        t = Tensor(init([], dtype), _internal=True)
        t.name = name or unique_name.generate(self._full_name + "_t")
        return t

    # -- registration -------------------------------------------------------
    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError(f"{name} is not a Parameter")
        self.__dict__.setdefault("_parameters", collections.OrderedDict())
        object.__getattribute__(self, "_parameters")[name] = parameter
        self.__dict__.pop(name, None)
        return parameter

    def add_sublayer(self, name, sublayer):
        if sublayer is not None and not isinstance(sublayer, Layer):
            raise TypeError(f"{name} is not a Layer")
        object.__getattribute__(self, "_sub_layers")[str(name)] = sublayer
        self.__dict__.pop(str(name), None)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)
        self.__dict__.pop(name, None)
        return tensor

    # -- attribute routing --------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            if buffers is not None:
                buffers.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params[name] = None
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name}")
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                buffers[name].set_value(value)
        elif layers is not None and name in layers and value is None:
            layers[name] = None
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._buffers) + list(self._sub_layers)

    # -- traversal ----------------------------------------------------------
    def children(self):
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, l in self.named_children():
            if id(l) in layers_set:
                continue
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True,
                                         layers_set=layers_set)

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        layers = self.named_sublayers(prefix=prefix, include_self=True) if \
            include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (lp + ("." if lp else "") + name), p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        layers = self.named_sublayers(prefix=prefix, include_self=True) if \
            include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (lp + ("." if lp else "") + name), b

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._hook_id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._hook_id] = hook
        return helper

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        if destination is None:
            destination = collections.OrderedDict()
        prefix = structured_name_prefix
        if prefix and not prefix.endswith("."):
            prefix += "."
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            destination[prefix + name] = p
        # non-persistable buffers are excluded; collect their UNPREFIXED
        # names first so an external prefix can't defeat the lookup
        skip = set()
        for lp, layer in self.named_sublayers(include_self=True):
            for bname in layer._non_persistable_buffer_names:
                skip.add(lp + ("." if lp else "") + bname)
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            if name in skip:
                continue
            destination[prefix + name] = b
        return destination

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = self.state_dict()
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            v = value._data if isinstance(value, Tensor) else np.asarray(value)
            if tuple(np.shape(v)) != tuple(target.shape):
                raise ValueError(
                    f"shape mismatch for {name}: loaded {np.shape(v)} vs "
                    f"expected {tuple(target.shape)}")
            target.set_value(v)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device -----------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._to_dtype(convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._to_dtype(convert_dtype(dtype))
        return self

    def _to_dtype(self, dtype):
        import jax.numpy as jnp

        for layer in self.sublayers(include_self=True):
            layer._dtype = dtype
            for p in layer._parameters.values():
                if p is not None and jnp.issubdtype(p.dtype, jnp.floating):
                    p._replace(p._data.astype(dtype))
            for b in layer._buffers.values():
                if b is not None and jnp.issubdtype(b.dtype, jnp.floating):
                    b._replace(b._data.astype(dtype))

    def float(self):
        return self.astype("float32")

    def half(self):
        return self.astype("float16")

    def bfloat16(self):
        return self.astype("bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self.named_children():
            mod_str = repr(l)
            mod_str = _addindent(mod_str, 2)
            lines.append(f"({name}): {mod_str}")
        main = type(self).__name__ + "("
        if extra:
            main += extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"


def _camel_to_snake(name):
    out = []
    for i, c in enumerate(name):
        if c.isupper() and i > 0:
            out.append("_")
        out.append(c.lower())
    return "".join(out)


def _addindent(s, n):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    return lines[0] + "\n" + "\n".join(" " * n + l for l in lines[1:])


class Sequential(Layer):
    """ref: dygraph/container.py Sequential."""

    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                len(layers[0]) and isinstance(layers[0][0], (list, tuple)):
            for name, layer in layers[0]:
                self.add_sublayer(name, layer)
        elif len(layers) and isinstance(layers[0], tuple) and len(layers[0]) == 2 \
                and isinstance(layers[0][0], str):
            for name, layer in layers:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers)
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return self._sub_layers[str(self._abs_idx(idx))]

    def __setitem__(self, idx, layer):
        self.add_sublayer(str(self._abs_idx(idx)), layer)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def _abs_idx(self, idx):
        return idx + len(self) if idx < 0 else idx

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self.add_sublayer(str(i), l)

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx + len(self) if idx < 0 else idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self)), parameter)
        return self


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        l = self._sub_layers.pop(key)
        return l

    def keys(self):
        return self._sub_layers.keys()

    def items(self):
        return self._sub_layers.items()

    def values(self):
        return self._sub_layers.values()

    def update(self, sublayers):
        items = sublayers.items() if isinstance(sublayers, (dict, LayerDict)) else sublayers
        for key, layer in items:
            self.add_sublayer(key, layer)
        return self
