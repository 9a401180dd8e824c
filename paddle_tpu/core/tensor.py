"""Eager Tensor facade over jax.Array with a tape-based autograd engine.

Design (TPU-native rethink of the reference's eager mode):
  * The reference (Paddle) implements eager autograd as generated C++ GradNode
    classes per op (/root/reference/paddle/fluid/eager/, grad_node_info.h:168,
    backward.cc:104).  Re-deriving per-op VJPs by hand would duplicate what JAX
    already provides, so here every differentiable eager op call is routed
    through ``jax.vjp`` once and the returned pullback is recorded on a tape
    (`GradNode`).  ``Tensor.backward()`` then walks the tape exactly like the
    reference's ``RunBackward`` queue.
  * Inside ``jax.jit`` traces there are no Tensors at all: the same op
    implementations run directly on traced jax values (see
    paddle_tpu/core/dispatch.py), so the compiled path pays zero overhead for
    the eager machinery.  This is the dygraph/static duality of the reference
    collapsed onto one code path.

Semantics parity notes:
  * ``stop_gradient`` defaults to True for ad-hoc tensors (matching
    paddle.to_tensor) and False for ``Parameter``.
  * ``.grad`` accumulates across ``backward()`` calls until ``clear_grad()``.
  * In-place mutation of a tensor that another node saved for backward uses the
    *saved* (old) value: jax arrays are immutable, so the tape closure holds
    the pre-mutation value.  The reference aborts in this case via
    inplace_version checks (eager/tensor_wrapper.h); we track versions and
    raise on backward when detected.
"""

from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtypes as _dtypes

__all__ = [
    "Tensor",
    "Parameter",
    "GradNode",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
]


class _AutogradState(threading.local):
    def __init__(self):
        self.grad_enabled = True


_STATE = _AutogradState()


def is_grad_enabled() -> bool:
    return _STATE.grad_enabled


def set_grad_enabled(mode: bool):
    _STATE.grad_enabled = bool(mode)


@contextlib.contextmanager
def no_grad():
    prev = _STATE.grad_enabled
    _STATE.grad_enabled = False
    try:
        yield
    finally:
        _STATE.grad_enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = _STATE.grad_enabled
    _STATE.grad_enabled = True
    try:
        yield
    finally:
        _STATE.grad_enabled = prev


class GradNode:
    """One tape entry: the pullback of a single eager op call.

    Mirrors the role of the reference's GradNodeBase
    (paddle/fluid/eager/grad_node_info.h:168) but the gradient function is the
    jax.vjp pullback instead of a hand-written grad kernel.
    """

    __slots__ = (
        "vjp_fn",
        "inputs",
        "input_versions",
        "out_avals",
        "out_treedef",
        "n_outputs",
        "name",
        "create_graph_apply",
        "__weakref__",
    )

    def __init__(self, vjp_fn, inputs, out_avals, out_treedef, name=""):
        self.vjp_fn = vjp_fn
        self.inputs = inputs  # list[Tensor] — the differentiable inputs
        self.input_versions = [t._version for t in inputs]
        self.out_avals = out_avals  # list[(shape, dtype)] flat over outputs
        self.out_treedef = out_treedef
        self.n_outputs = len(out_avals)
        self.name = name
        # Optional taped double-backward: list[Tensor|None] -> list[Tensor|None].
        # Set by the dispatcher (re-entrant jax.vjp over the op closure) and by
        # PyLayer (user backward under enable_grad); used by
        # grad(create_graph=True) so grads themselves carry grad history.
        self.create_graph_apply = None

    def apply(self, cotangents):
        """cotangents: flat list aligned with out_avals (None → zeros)."""
        if self.vjp_fn is None:
            raise RuntimeError(
                f"Trying to run backward through '{self.name}' a second time, "
                "but the saved intermediate results have already been freed. "
                "Specify retain_graph=True on the first backward() if you "
                "need to backward through the graph again.")
        filled = [
            c if c is not None else jnp.zeros(shape, dtype)
            for c, (shape, dtype) in zip(cotangents, self.out_avals)
        ]
        cot_tree = jax.tree.unflatten(self.out_treedef, filled)
        for t, v in zip(self.inputs, self.input_versions):
            if t._version != v:
                raise RuntimeError(
                    f"Tensor saved for backward of '{self.name}' was modified "
                    f"in-place (version {v} -> {t._version}). Clone it before "
                    "mutating, or avoid in-place ops on tensors needed for grad."
                )
        return self.vjp_fn(cot_tree)

    def __repr__(self):
        return f"<GradNode {self.name} n_in={len(self.inputs)} n_out={self.n_outputs}>"


def _as_jax_array(data, dtype=None):
    if isinstance(data, Tensor):
        arr = data._data
        if dtype is not None:
            arr = arr.astype(_dtypes.to_jax(dtype))
        return arr
    if isinstance(data, (jnp.ndarray, jax.Array)):
        return data if dtype is None else data.astype(_dtypes.to_jax(dtype))
    if isinstance(data, jax.ShapeDtypeStruct):
        return data     # nn.LazyGuard: a shape and a type, no array yet
    if isinstance(data, np.ndarray):
        if dtype is None and data.dtype == np.float64:
            data = data.astype(np.float32)
        return jnp.asarray(data, dtype=None if dtype is None else _dtypes.to_jax(dtype))
    if isinstance(data, (bool, int, float, complex, list, tuple, np.generic)):
        arr = np.asarray(data)
        if dtype is None and arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        return jnp.asarray(arr, dtype=None if dtype is None else _dtypes.to_jax(dtype))
    raise TypeError(f"Cannot convert {type(data)} to Tensor")


class Tensor:
    """Paddle-flavoured eager tensor wrapping an immutable jax.Array."""

    __slots__ = ("_data", "stop_gradient", "_grad", "_grad_node", "_out_index",
                 "_version", "name", "persistable", "_retain_grads",
                 "partition_spec", "__weakref__")

    # let Tensor win in  np_array op tensor  reflected dispatch
    __array_priority__ = 100

    def __init__(self, data, dtype=None, stop_gradient=True, name=None):
        self._data = _as_jax_array(data, dtype)
        self.stop_gradient = stop_gradient
        self._grad: Optional[Tensor] = None
        self._grad_node: Optional[GradNode] = None
        self._out_index: int = 0
        self._version = 0
        self.name = name
        self.persistable = False
        self._retain_grads = False

    # -- construction helpers ------------------------------------------------
    @staticmethod
    def _wrap(arr, stop_gradient=True, node=None, out_index=0):
        t = Tensor.__new__(Tensor)
        t._data = arr
        t.stop_gradient = stop_gradient
        t._grad = None
        t._grad_node = node
        t._out_index = out_index
        t._version = 0
        t.name = None
        t.persistable = False
        t._retain_grads = False
        return t

    # -- basic properties ----------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return _dtypes.from_jax(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    @property
    def place(self):
        devs = getattr(self._data, "devices", None)
        if devs is None:
            return "unknown"
        return str(next(iter(self._data.devices())))

    @property
    def is_leaf(self):
        return self._grad_node is None

    def numel(self):
        return int(self._data.size)

    def numpy(self):
        return np.asarray(self._data)

    def item(self, *args):
        return self._data.item(*args)

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __len__(self):
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}{grad_info},\n"
                f"       {np.asarray(self._data)!r})")

    def __bool__(self):
        return bool(self._data)

    def __int__(self):
        return int(self._data)

    def __float__(self):
        return float(self._data)

    def __index__(self):
        return int(self._data)

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a if dtype is None else a.astype(dtype)

    def __jax_array__(self):
        # lets raw jnp ops consume Tensors transparently (no grad tracking!)
        return self._data

    # -- grad machinery ------------------------------------------------------
    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self):  # paddle alias
        self._grad = None

    def retain_grads(self):
        self._retain_grads = True

    def backward(self, grad_tensor=None, retain_graph=False):
        from paddle_tpu.autograd.backward_engine import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def detach(self):
        return Tensor._wrap(self._data, stop_gradient=True)

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from paddle_tpu.core.dispatch import dispatch
        return dispatch(lambda x: x + jnp.zeros((), x.dtype), self, op_name="clone")

    # -- dtype / device ------------------------------------------------------
    def astype(self, dtype):
        from paddle_tpu.core.dispatch import dispatch
        jdt = _dtypes.to_jax(dtype)
        return dispatch(lambda x: x.astype(jdt), self, op_name="cast")

    def cast(self, dtype):
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        # device moves are no-ops (single logical device per process); dtype honoured
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in _dtypes.ALL_DTYPE_NAMES:
                return self.astype(a)
            if hasattr(a, "dtype") or str(a) in _dtypes.ALL_DTYPE_NAMES:
                try:
                    return self.astype(a)
                except Exception:
                    pass
        return self

    def cpu(self):
        return Tensor._wrap(self._data, stop_gradient=self.stop_gradient)

    def cuda(self, *a, **k):
        return self

    def pin_memory(self):
        return self

    # -- in-place ------------------------------------------------------------
    def _set_data(self, arr):
        """Raw in-place value replacement (version-bumping)."""
        self._data = arr
        self._version += 1

    def set_value(self, value):
        arr = _as_jax_array(value)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        self._set_data(arr.astype(self._data.dtype))

    def copy_(self, other, *a):
        self.set_value(other)
        return self

    def fill_(self, value):
        self._set_data(jnp.full_like(self._data, value))
        return self

    def zero_(self):
        self._set_data(jnp.zeros_like(self._data))
        return self

    def scale_(self, scale=1.0, bias=0.0):
        self._set_data(self._data * scale + bias)
        return self

    def add_(self, y):
        self._set_data(self._data + _as_jax_array(y).astype(self._data.dtype))
        return self

    def subtract_(self, y):
        self._set_data(self._data - _as_jax_array(y).astype(self._data.dtype))
        return self

    def multiply_(self, y):
        self._set_data(self._data * _as_jax_array(y).astype(self._data.dtype))
        return self

    def clip_(self, min=None, max=None):
        self._set_data(jnp.clip(self._data, min, max))
        return self

    # -- indexing ------------------------------------------------------------
    def _normalize_index(self, idx):
        def conv(i):
            if isinstance(i, Tensor):
                return i._data
            return i
        if isinstance(idx, tuple):
            return tuple(conv(i) for i in idx)
        return conv(idx)

    def __getitem__(self, idx):
        from paddle_tpu.core.dispatch import dispatch
        nidx = self._normalize_index(idx)
        return dispatch(lambda x: x[nidx], self, op_name="getitem")

    def __setitem__(self, idx, value):
        nidx = self._normalize_index(idx)
        val = _as_jax_array(value)
        self._set_data(self._data.at[nidx].set(val.astype(self._data.dtype)))

    # NOTE: arithmetic dunders are attached in paddle_tpu/core/tensor_methods.py
    # (generated from the op table) to keep this file focused on the engine.


class Parameter(Tensor):
    """A trainable Tensor (stop_gradient=False by default), as registered by
    nn.Layer — parity with paddle's EagerParamBase."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip", "is_distributed")

    def __init__(self, data, dtype=None, trainable=True, name=None):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False
        self.persistable = True

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
