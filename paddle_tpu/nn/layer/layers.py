"""Layer — the module base class.

Analogue of ``paddle.nn.Layer`` (reference:
``python/paddle/nn/layer/layers.py:340``): named parameters/buffers/sublayers,
forward pre/post hooks, state_dict/set_state_dict, train/eval mode, ``to``
dtype conversion, ``apply``.  Parameters are eager Tensors; the jit path lifts
them functionally (see paddle_tpu.jit), so one Layer definition serves both
eager UX and compiled SPMD execution — the TPU-native replacement for the
reference's dygraph/static dual stack.
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...core.dtypes import convert_dtype, default_float_dtype
from ...core.tensor import Tensor

# global registry used by jit param discovery & distributed init
_ALL_PARAMETERS: "weakref.WeakSet[Parameter]" = weakref.WeakSet()


class Parameter(Tensor):
    """Trainable parameter (analogue of paddle's Parameter/EagerParamBase)."""

    def __init__(self, value, trainable: bool = True, name: Optional[str] = None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self._is_param = True
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False
        _ALL_PARAMETERS.add(self)

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v


class ParamAttr:
    """Analogue of paddle.ParamAttr."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if attr is False:
            return False
        # an Initializer instance
        return ParamAttr(initializer=attr)


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        self._dtype = convert_dtype(dtype) if dtype else default_float_dtype()
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._buffers: "OrderedDict[str, Tensor]" = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._hook_id = 0
        self._name = name_scope or self.__class__.__name__.lower()

    # ---- attribute routing ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                buffers[name] = Tensor(jnp.asarray(value))
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # ---- construction helpers ----
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from ..initializer import Constant, XavierUniform
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = convert_dtype(dtype) or self._dtype
        init = attr.initializer or default_initializer or (
            Constant(0.0) if is_bias else XavierUniform())
        from ..lazy import lazy_init_scope, placeholder
        shape = tuple(int(s) for s in shape)
        deferred = placeholder(shape, dtype)
        if deferred is None:
            with lazy_init_scope():
                value = init(shape, dtype)
        else:
            value = jnp.zeros((), dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        if deferred is not None:
            p._value = deferred
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    # ---- iteration ----
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else prefix + "." + name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + "." + lname if prefix else lname
                for item in layer.named_parameters(sub_prefix, True):
                    yield item

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters("", include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (prefix + name if not prefix else prefix + "." + name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = prefix + "." + lname if prefix else lname
                for item in layer.named_buffers(sub_prefix, True):
                    yield item

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers("", include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + "." + name if prefix else name
            yield sub_prefix, layer
            for item in layer.named_sublayers(sub_prefix, False):
                yield item

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return [l for l in self._sub_layers.values() if l is not None]

    def named_children(self):
        return [(n, l) for n, l in self._sub_layers.items() if l is not None]

    def apply(self, fn):
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    # ---- modes ----
    def train(self):
        self.training = True
        for layer in self.children():
            layer.train()
        return self

    def eval(self):
        self.training = False
        for layer in self.children():
            layer.eval()
        return self

    # ---- hooks ----
    class _HookHandle:
        def __init__(self, store, hid):
            self._store = store
            self._hid = hid

        def remove(self):
            self._store.pop(self._hid, None)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return Layer._HookHandle(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return Layer._HookHandle(self._forward_post_hooks, self._hook_id)

    # ---- call ----
    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            rep = [rep[0]] + ["  " + r for r in rep[1:]]
            lines.append(f"  ({name}): " + "\n".join(rep))
        main = f"{type(self).__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    # ---- state dict ----
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        out = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(structured_name_prefix.rstrip("."),
                                             include_sublayers):
            out[name] = p
        prefix = structured_name_prefix.rstrip(".")
        for name, b in self.named_buffers(prefix, include_sublayers):
            short = name.rsplit(".", 1)[-1]
            # find owning layer to check persistability
            out[name] = b
        # drop non-persistable buffers
        for lname, layer in list(self.named_sublayers("", include_self=True)):
            for bname in layer._non_persistable_buffer_names:
                full = (lname + "." + bname) if lname else bname
                out.pop(full, None)
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, tensor in own.items():
            if name in state_dict:
                src = state_dict[name]
                arr = src._value if isinstance(src, Tensor) else jnp.asarray(
                    np.asarray(src))
                if tuple(arr.shape) != tuple(tensor._value.shape):
                    raise ValueError(
                        f"shape mismatch for {name}: checkpoint "
                        f"{tuple(arr.shape)} vs model {tuple(tensor._value.shape)}")
                tensor.set_value(arr)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # ---- dtype/device movement ----
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = convert_dtype(dtype)
            self._dtype = d
            for t in list(self.parameters()) + list(self.buffers()):
                if jnp.issubdtype(t._value.dtype, jnp.floating):
                    t._value = t._value.astype(d)
                    t._node = None
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def full_name(self):
        return self._name

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()
