"""`nn.Layer` module system (ref: python/paddle/nn/layer/layers.py).

The reference Layer is an eager module over the C++ autograd; here Layer is a
*dual-mode* module:

- eager: `layer(x)` runs jnp ops immediately, parameters are `Parameter`
  tensors, the eager tape records for `loss.backward()`.
- functional (the perf path): `functional_call(layer, state, *args, rng=...)`
  temporarily swaps the layer's parameters/buffers for the entries of a state
  pytree and runs forward. Because jit traces once, this gives a *pure*
  function of (state, inputs, rng) that XLA compiles — the moral equivalent
  of the reference's @to_static program construction, without an AST pass.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework
from ..tensor import Tensor


class Parameter(Tensor):
    """Trainable tensor (ref: paddle.base.framework.EagerParamBase).

    `sharding_spec` carries an optional jax PartitionSpec placement
    (ref: the reference's DistAttr on a dist tensor) consumed by
    `paddle_tpu.distributed.shard_model`.
    """
    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip",
                 "sharding_spec")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.sharding_spec = None

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


jax.tree_util.register_pytree_node(
    Parameter,
    lambda p: ((p._value,), (p.trainable,)),
    lambda aux, c: Parameter(c[0], trainable=aux[0]),
)

_name_counters = {}


def _unique_name(prefix):
    n = _name_counters.get(prefix, 0)
    _name_counters[prefix] = n + 1
    return f"{prefix}_{n}"


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_forward_pre_hooks", OrderedDict())
        object.__setattr__(self, "_forward_post_hooks", OrderedDict())
        self.training = True
        self._dtype = framework.convert_dtype(dtype)
        self._name = _unique_name(name_scope or type(self).__name__.lower())

    # -- registration -------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                del params[name]
            else:
                params[name] = value
        elif layers is not None and name in layers:
            if value is None:
                del layers[name]
            else:
                layers[name] = value
        elif buffers is not None and name in buffers:
            if value is None:
                del buffers[name]
                object.__setattr__(self, name, None)
            else:
                buffers[name] = value if isinstance(value, Tensor) else Tensor(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor if (isinstance(tensor, Tensor) or tensor is None) \
            else Tensor(tensor)
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """ref: Layer.create_parameter — initializer from ParamAttr or the
        layer default (Xavier-uniform weights / zeros bias like the
        reference's defaults for most layers)."""
        from .initializer import Constant, XavierUniform, _resolve_attr
        dtype = framework.convert_dtype(dtype) or self._dtype
        init, name, trainable = _resolve_attr(attr, default_initializer,
                                              is_bias=is_bias)
        arr = init(tuple(int(s) for s in shape), dtype)
        return Parameter(arr, trainable=trainable, name=name)

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False) \
            -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            p = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=p, include_self=True)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return list(self._sub_layers.values())

    def named_children(self):
        return list(self._sub_layers.items())

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for lp, layer in self.named_sublayers(prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                full = f"{lp}.{name}" if lp else name
                if p.name is None:
                    # structured path doubles as the reference's param name
                    # (used by apply_decay_param_fun / optimizer state keys)
                    p.name = full
                yield full, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for lp, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{lp}.{name}" if lp else name), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers()]

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        out = destination if destination is not None else OrderedDict()
        for n, p in self.named_parameters(prefix=structured_name_prefix):
            out[n] = p
        for lp, layer in self.named_sublayers(
                prefix=structured_name_prefix, include_self=True):
            for name, b in layer._buffers.items():
                if b is None or name in layer._non_persistable_buffer_names:
                    continue
                out[f"{lp}.{name}" if lp else name] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            arr = v._value if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            tgt = own[k]
            if tuple(arr.shape) != tuple(tgt._value.shape):
                raise ValueError(
                    f"shape mismatch for {k}: {arr.shape} vs {tgt._value.shape}")
            tgt._value = arr.astype(tgt._value.dtype)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- mode / dtype -------------------------------------------------------
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
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = framework.convert_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p._value.dtype, jnp.floating):
                    p._value = p._value.astype(dt)
            for b in self.buffers():
                if jnp.issubdtype(b._value.dtype, jnp.floating):
                    b._value = b._value.astype(dt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        h = _HookRemoveHelper(self._forward_pre_hooks, hook)
        return h

    def register_forward_post_hook(self, hook):
        h = _HookRemoveHelper(self._forward_post_hooks, hook)
        return h

    # -- call ---------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            r = hook(self, args)
            if r is not None:
                args = r if isinstance(r, tuple) else (r,)
        # the class name, not _unique_name's counter: the scope reaches
        # the compiled program's op metadata and has to be the same in
        # every process (introspect.site_scopes reads it back)
        with jax.named_scope(type(self).__name__):
            out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            r = hook(self, args, out)
            if r is not None:
                out = r
        return out

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = "\n  ".join(sub_repr)
            lines.append(f"({name}): {sub_repr}")
        body = ",\n  ".join(([extra] if extra else []) + lines)
        if body:
            return f"{type(self).__name__}(\n  {body}\n)" if lines else \
                f"{type(self).__name__}({extra})"
        return f"{type(self).__name__}()"

    # -- functional state access (TPU perf path) ---------------------------
    def raw_state(self):
        """(params, buffers) as flat name->jax.Array dicts."""
        params = {n: p._value for n, p in self.named_parameters()}
        buffers = {}
        for lp, layer in self.named_sublayers(include_self=True):
            for name, b in layer._buffers.items():
                if b is None:
                    continue
                buffers[f"{lp}.{name}" if lp else name] = b._value
        return params, buffers

    def load_raw_state(self, params=None, buffers=None):
        """Write arrays back into the live Parameter/buffer tensors."""
        if params:
            for n, p in self.named_parameters():
                if n in params:
                    p._value = params[n]
        if buffers:
            idx = {}
            for lp, layer in self.named_sublayers(include_self=True):
                for name, b in layer._buffers.items():
                    if b is not None:
                        idx[f"{lp}.{name}" if lp else name] = b
            for n, v in buffers.items():
                if n in idx:
                    idx[n]._value = v


class _HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks, hook):
        self._hooks = hooks
        self._id = _HookRemoveHelper._next_id
        _HookRemoveHelper._next_id += 1
        hooks[self._id] = hook

    def remove(self):
        self._hooks.pop(self._id, None)


@contextlib.contextmanager
def _swapped_state(layer: Layer, params=None, buffers=None):
    saved = []
    try:
        if params:
            for n, p in layer.named_parameters():
                if n in params:
                    saved.append((p, p._value))
                    v = params[n]
                    p._value = v._value if isinstance(v, Tensor) else v
        buffer_objs = {}
        if buffers is not None:
            for lp, sub in layer.named_sublayers(include_self=True):
                for name, b in sub._buffers.items():
                    if b is None:
                        continue
                    full = f"{lp}.{name}" if lp else name
                    buffer_objs[full] = b
                    if full in buffers:
                        saved.append((b, b._value))
                        v = buffers[full]
                        b._value = v._value if isinstance(v, Tensor) else v
        yield buffer_objs
    finally:
        for t, old in saved:
            t._value = old


def functional_call(layer: Layer, params, buffers, *args, rng=None,
                    mutable=False, **kwargs):
    """Run `layer` as a pure function of (params, buffers, rng, *args).

    Returns (out, new_buffers) when mutable=True (e.g. BatchNorm running
    stats updated during the traced step) else just out.
    """
    with _swapped_state(layer, params, buffers) as buffer_objs:
        if rng is not None:
            with framework.rng_scope(rng):
                out = layer(*args, **kwargs)
        else:
            out = layer(*args, **kwargs)
        if mutable:
            new_buffers = {n: b._value for n, b in buffer_objs.items()}
            return out, new_buffers
    return out
