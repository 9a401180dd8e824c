"""nn.Layer — the module base class.

Parity target: the reference Layer (python/paddle/nn/layer/layers.py):
parameter/buffer/sublayer registration via __setattr__, hooks, state_dict,
train/eval, apply/to.  TPU-native difference: a Layer is ALSO a functional
model — `paddle_tpu.core.functional.functional_call(layer, params, x)` runs
it as a pure function for jit/grad/pjit, with no source rewriting.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from paddle_tpu.core import dtypes as _dtypes
from paddle_tpu.core.tensor import Parameter, Tensor

__all__ = ["Layer", "LazyGuard"]


class LazyGuard:
    """Construct a model without device arrays of its own (the reference
    framework's ``LazyGuard`` role): under the guard ``create_parameter``
    records a parameter's shape and type (``_data`` is a
    ``jax.ShapeDtypeStruct``) and runs no initialiser; the first
    ``_set_data`` materialises it.  For a model whose weights are given
    after construction (a checkpoint, a seeded set) the peak is then the
    weights and one leaf, not the weights twice.  A parameter that is
    read before it is given raises from whatever touches the struct."""

    _depth = 0

    def __enter__(self):
        LazyGuard._depth += 1
        return self

    def __exit__(self, *exc):
        LazyGuard._depth -= 1
        return False


class _HookHandle:
    _next_id = 0

    def __init__(self, registry):
        self._registry = registry
        self._id = _HookHandle._next_id
        _HookHandle._next_id += 1

    def remove(self):
        self._registry.pop(self._id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtype
        self._name_scope = name_scope or type(self).__name__.lower()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()

    # -- registration --------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning "
                                   "parameters")
            self.__dict__.pop(name, None)
            self._buffers.pop(name, None)
            self._sub_layers.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            subs = self.__dict__.get("_sub_layers")
            if subs is None:
                raise RuntimeError("call super().__init__() before assigning "
                                   "sublayers")
            self.__dict__.pop(name, None)
            if params is not None:
                params.pop(name, None)
            self._buffers.pop(name, None)
            subs[name] = value
        else:
            if params is not None and name in params:
                del params[name]
            if self.__dict__.get("_sub_layers") is not None and \
                    name in self._sub_layers:
                del self._sub_layers[name]
            if self.__dict__.get("_buffers") is not None and \
                    name in self._buffers:
                if isinstance(value, Tensor):
                    self._buffers[name] = value
                    return
                del self._buffers[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in (self._parameters, self._buffers, self._sub_layers):
            if name in store:
                del store[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is not None and not isinstance(parameter, Parameter):
            parameter = Parameter(parameter)
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None,
                         is_bias=False, default_initializer=None):
        """Reference: Layer.create_parameter (layers.py).  Initializer
        resolution mirrors paddle: explicit initializer > attr > Xavier
        for weights / zeros for bias."""
        from paddle_tpu.nn import initializer as I
        dtype = dtype or self._dtype
        init = default_initializer
        if init is None and attr is not None:
            init = getattr(attr, "initializer", None)
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        if LazyGuard._depth:
            import jax
            p = Parameter(jax.ShapeDtypeStruct(
                tuple(int(n) for n in shape), _dtypes.to_jax(dtype)))
        else:
            p = Parameter(init(shape, dtype))
        if attr is not None and getattr(attr, "learning_rate", None) is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        if attr is not None and getattr(attr, "trainable", True) is False:
            p.stop_gradient = True
            p.trainable = False
        return p

    # -- traversal -----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        for name, p in self._parameters.items():
            if p is not None:
                yield (prefix + name if not prefix else f"{prefix}.{name}"), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_parameters(sub_prefix, True)

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix, True)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix, False)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def apply(self, fn: Callable[["Layer"], None]):
        for layer in self.children():
            if layer is not None:
                layer.apply(fn)
        fn(self)
        return self

    # -- state ---------------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True, keep_vars=True
                   ) -> Dict[str, Tensor]:
        out = {} if destination is None else destination
        p = structured_name_prefix
        for name, param in self._parameters.items():
            if param is not None:
                out[p + name] = param if keep_vars else param.detach()
        for name, buf in self._buffers.items():
            if buf is not None and name not in self._non_persistable_buffer_names:
                out[p + name] = buf if keep_vars else buf.detach()
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is not None:
                    layer.state_dict(out, True, p + lname + ".", use_hook,
                                     keep_vars)
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict(keep_vars=True)
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                value = state_dict[name]
                arr = value._data if isinstance(value, Tensor) else \
                    np.asarray(value)
                if tuple(np.shape(arr)) != tuple(t._data.shape):
                    raise ValueError(
                        f"shape mismatch for '{name}': checkpoint "
                        f"{np.shape(arr)} vs layer {tuple(t._data.shape)}")
                t.set_value(arr)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # -- mode / dtype --------------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_all(dtype)
        return self

    def astype(self, dtype):
        self._cast_all(dtype)
        return self

    def float(self):
        return self.astype("float32")

    def bfloat16(self):
        return self.astype("bfloat16")

    def half(self):
        return self.astype("float16")

    def _cast_all(self, dtype):
        jdt = _dtypes.to_jax(dtype)
        import jax
        import jax.numpy as jnp
        for t in list(self.parameters()) + list(self.buffers()):
            if not jnp.issubdtype(t._data.dtype, jnp.floating):
                continue
            if isinstance(t._data, jax.ShapeDtypeStruct):   # LazyGuard
                t._data = jax.ShapeDtypeStruct(t._data.shape, jdt)
            else:
                t._set_data(t._data.astype(jdt))
        for layer in self.sublayers(include_self=True):
            layer._dtype = _dtypes.from_jax(jdt)

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle._id] = hook
        return handle

    # -- call ----------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, args)
            if result is not None:
                args = result if isinstance(result, tuple) else (result,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, args, out)
            if result is not None:
                out = result
        return out

    # -- misc ----------------------------------------------------------------
    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            sub = repr(layer).split("\n")
            sub = [sub[0]] + ["  " + s for s in sub[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._name_scope
