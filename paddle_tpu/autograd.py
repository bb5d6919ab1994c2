"""Eager autograd engine.

The reference implements reverse-mode autodiff with a C++ tape over PHI
kernels (ref: paddle/fluid/eager/, imperative::Tracer). TPU-native rebuild:
every differentiable op is dispatched through :func:`apply_op`, which — when
gradients are required — runs the op under ``jax.vjp`` and links the pullback
into a graph *owned by the output tensors* (entries hold inputs strongly and
outputs weakly, so the graph is freed by normal GC when outputs are dropped —
an eval loop without no_grad() cannot leak, matching the reference's
refcounted autograd graph). ``Tensor.backward()`` walks the reachable graph
in reverse topological order and accumulates cotangents into ``.grad``.

This graph exists for *API parity* with eager training loops
(``loss.backward(); opt.step()``). The performance path (``hapi.Model`` /
``Engine``) never uses it: there, the whole train step is a pure function
differentiated with ``jax.grad`` and compiled once with ``jax.jit``.
"""
from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Callable

import jax
import jax.numpy as jnp

_state = threading.local()

# jax 0.9.0 has no public spelling of "is any trace active"
from jax._src.core import trace_state_clean as _trace_state_clean


def in_jax_trace(arrs=()) -> bool:
    """True when executing under an active jax trace (jit/grad/vmap/...).

    Inside a trace the eager tape must NOT be built: the outer transform
    already owns differentiation, and a nested ``jax.vjp`` both bloats the
    jaxpr and breaks ``custom_vjp`` ops (Pallas kernels hit
    ``_pallas_call_jvp_rule`` asserts when a vjp is opened inside another
    vjp inside ``jax.grad``). `arrs` is accepted for the callers that
    pass their inputs; the global trace-state flag decides.
    """
    return not _trace_state_clean()


def is_grad_enabled() -> bool:
    return getattr(_state, "grad_enabled", True)


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


@contextlib.contextmanager
def no_grad():
    """ref: paddle.no_grad (decorator/context)."""
    prev = is_grad_enabled()
    set_grad_enabled(False)
    try:
        yield
    finally:
        set_grad_enabled(prev)


@contextlib.contextmanager
def enable_grad():
    prev = is_grad_enabled()
    set_grad_enabled(True)
    try:
        yield
    finally:
        set_grad_enabled(prev)


class GradNode:
    """One recorded op: pullback + its tensor inputs (strong) and outputs
    (weak). fwd_fn (the op over its diff inputs, non-diff args closed
    over) enables functional REPLAY of the subgraph — what
    grad(create_graph=True) differentiates, since re-deriving the
    gradients from the inputs is the only way the residual term of the
    second derivative survives (a vjp-of-the-stored-vjp would treat the
    residuals as constants and silently drop it)."""
    __slots__ = ("inputs", "out_refs", "vjp_fn", "fwd_fn", "n_outs",
                 "__weakref__")

    def __init__(self, inputs, outputs, vjp_fn, fwd_fn=None):
        self.inputs = inputs                       # list[Tensor]
        self.out_refs = [weakref.ref(o) for o in outputs]
        self.n_outs = len(outputs)
        self.vjp_fn = vjp_fn
        self.fwd_fn = fwd_fn


def _is_tensor(x) -> bool:
    from .tensor import Tensor
    return isinstance(x, Tensor)


def _float_like(arr) -> bool:
    return jnp.issubdtype(jnp.asarray(arr).dtype, jnp.inexact)


def apply_op(fn: Callable, *args, differentiable: bool = True, **kwargs):
    """Dispatch `fn` (a jnp-level function) over Tensor/array args.

    Tensors are unwrapped to jax arrays; if grad mode is on, any input has
    stop_gradient=False, and the op is differentiable, the call is run under
    jax.vjp and linked into the autograd graph. Returns Tensors mirroring
    fn's output structure.
    """
    from .tensor import Tensor

    flat, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=_is_tensor)
    t_idx = [i for i, x in enumerate(flat) if _is_tensor(x)]
    tensors = [flat[i] for i in t_idx]

    def run(arrs):
        buf = list(flat)
        for i, a in zip(t_idx, arrs):
            buf[i] = a
        a2, k2 = jax.tree_util.tree_unflatten(treedef, buf)
        return fn(*a2, **k2)

    needs_grad = (
        differentiable
        and is_grad_enabled()
        and any(not t.stop_gradient for t in tensors)
    )

    arrs = [t._value for t in tensors]
    if in_jax_trace(arrs):
        # Functional path (Engine/jit/grad/vmap): the outer transform owns
        # differentiation — dispatch directly, no tape. Grads flow through
        # the outer trace; building a nested vjp here is pure overhead and
        # crashes custom_vjp kernels (Pallas flash attention).
        out = run(arrs)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=not needs_grad), out)
    if not needs_grad:
        out = run(arrs)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True), out)

    diff_pos = [i for i, t in enumerate(tensors)
                if not t.stop_gradient and _float_like(t._value)]
    if not diff_pos:
        out = run(arrs)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True), out)

    def run_diff(*darrs):
        buf = list(arrs)
        for i, a in zip(diff_pos, darrs):
            buf[i] = a
        return run(buf)

    out_arrs, vjp_fn = jax.vjp(run_diff, *(arrs[i] for i in diff_pos))

    # replay closure for create_graph: closes over raw ARRAYS and the
    # treedef only — not the Tensor wrappers `run` pins via `flat` — so
    # taping an op does not extend wrapper lifetimes on the default path
    base_flat = [None if j in t_idx else x for j, x in enumerate(flat)]

    def fwd_replay(*darrs):
        buf = list(base_flat)
        for j, a in zip(t_idx, arrs):
            buf[j] = a
        for i, a in zip(diff_pos, darrs):
            buf[t_idx[i]] = a
        a2, k2 = jax.tree_util.tree_unflatten(treedef, buf)
        return fn(*a2, **k2)
    out_tensors = jax.tree_util.tree_map(
        lambda a: Tensor(a, stop_gradient=False), out_arrs)
    flat_outs = [t for t in jax.tree_util.tree_leaves(
        out_tensors, is_leaf=_is_tensor) if _is_tensor(t)]
    node = GradNode(inputs=[tensors[i] for i in diff_pos],
                    outputs=flat_outs, vjp_fn=vjp_fn, fwd_fn=fwd_replay)
    for t in flat_outs:
        t._grad_node = node
    return out_tensors


def _toposort(roots):
    """Nodes reachable from roots' grad nodes, consumers-before-producers
    (Kahn's algorithm on consumer->producer edges, so every node is
    processed only after ALL its consumers contributed cotangents —
    correct for diamond graphs like loss = a + f(a))."""
    nodes = {}
    stack = []
    for r in roots:
        node = getattr(r, "_grad_node", None)
        if node is not None and id(node) not in nodes:
            nodes[id(node)] = node
            stack.append(node)
    while stack:
        node = stack.pop()
        for t in node.inputs:
            child = getattr(t, "_grad_node", None)
            if child is not None and id(child) not in nodes:
                nodes[id(child)] = child
                stack.append(child)
    indeg = {nid: 0 for nid in nodes}
    for node in nodes.values():
        for t in node.inputs:
            child = getattr(t, "_grad_node", None)
            if child is not None and id(child) in nodes:
                indeg[id(child)] += 1
    order = []
    ready = [n for nid, n in nodes.items() if indeg[nid] == 0]
    while ready:
        node = ready.pop()
        order.append(node)
        for t in node.inputs:
            child = getattr(t, "_grad_node", None)
            if child is not None and id(child) in nodes:
                indeg[id(child)] -= 1
                if indeg[id(child)] == 0:
                    ready.append(child)
    return order


def _apply_grad_hooks(t, c):
    """Run a tensor's registered grad hooks on cotangent array `c`; a
    non-None Tensor/array return replaces it."""
    from .tensor import Tensor
    for hook in list(t._grad_hooks.values()):
        r = hook(Tensor(c, stop_gradient=True))
        if r is not None:
            c = r._value if _is_tensor(r) else jnp.asarray(r)
    return c


def backward(tensors, grad_tensors=None, retain_graph: bool = False):
    """ref: paddle.autograd.backward / Tensor.backward."""
    from .tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    cot = {}
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            g_arr = jnp.ones_like(t._value)
        else:
            g_arr = g._value if _is_tensor(g) else jnp.asarray(g)
        cot[id(t)] = cot.get(id(t), 0) + g_arr

    order = _toposort(tensors)
    hooked_leaves = {}
    hooks_done = set()
    for node in order:
        out_cots = []
        has_any = False
        for ref in node.out_refs:
            o = ref()
            # grad hooks fire on the ACCUMULATED gradient of a tensor: for
            # produced tensors that moment is here (topo order guarantees
            # every consumer already contributed to cot[id(o)])
            if (o is not None and o._grad_hooks and id(o) in cot
                    and id(o) not in hooks_done):
                hooks_done.add(id(o))
                cot[id(o)] = _apply_grad_hooks(o, cot[id(o)])
                if not o.stop_gradient and o._retain_grads:
                    prev = o._grad_value
                    o._grad_value = (cot[id(o)] if prev is None
                                     else prev + cot[id(o)])
            c = cot.get(id(o)) if o is not None else None
            if c is None:
                shape_src = o._value if o is not None else None
                c = jnp.zeros_like(shape_src) if shape_src is not None else None
                if c is None:
                    # output tensor was GC'd and nothing flowed into it
                    out_cots = None
                    break
            else:
                has_any = True
            out_cots.append(c)
        if not has_any or out_cots is None:
            continue
        seed = out_cots[0] if node.n_outs == 1 else tuple(out_cots)
        in_cots = node.vjp_fn(seed)
        for t, c in zip(node.inputs, in_cots):
            cot[id(t)] = cot.get(id(t), 0) + c
            is_leaf = getattr(t, "_grad_node", None) is None
            if t._grad_hooks:
                # defer the .grad write until the accumulated total is
                # final and the hooks have fired (producer time for
                # intermediates, post-loop for leaves)
                if is_leaf:
                    hooked_leaves[id(t)] = t
                continue
            if not t.stop_gradient and (is_leaf or t._retain_grads):
                prev = t._grad_value
                t._grad_value = c if prev is None else prev + c

    for t in hooked_leaves.values():
        total = _apply_grad_hooks(t, cot[id(t)])
        if not t.stop_gradient:
            prev = t._grad_value
            t._grad_value = total if prev is None else prev + total

    if not retain_graph:
        # sever links so the graph (and its vjp residuals) frees now
        for node in order:
            for ref in node.out_refs:
                o = ref()
                if o is not None:
                    o._grad_node = None
            node.vjp_fn = None
            node.fwd_fn = None
            node.inputs = []


def _grad_create_graph(outputs, inputs, grad_outputs, allow_unused):
    """grad(create_graph=True): functionally REPLAY the recorded
    subgraph from the inputs (and every requires-grad leaf, so a later
    backward through the returned grads reaches the parameters — the
    WGAN-GP pattern), take jax.vjp of the replay, and record the whole
    thing as ONE tape op. Differentiating the result re-runs jax's
    second-order machinery over the true function of the inputs, so the
    residual term of d2y/dx2 is exact (unlike differentiating the stored
    pullback, which would treat residuals as constants).

    Gradient hooks do not fire on this path (it never walks the tape
    node-by-node); use backward()/grad(create_graph=False) for hooks."""
    from .tensor import Tensor

    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    seeds = tuple(
        jnp.ones_like(o._value) if g is None
        else (g._value if _is_tensor(g) else jnp.asarray(g))
        for o, g in zip(outputs, grad_outputs))

    order = _toposort(outputs)
    if any(n.fwd_fn is None for n in order):
        raise RuntimeError(
            "create_graph=True needs the recorded forward fns; part of "
            "this graph was built by an op that did not store one")
    used_ids = {id(t) for node in order for t in node.inputs}
    for o in outputs:              # an output passed as input is "used"
        used_ids.add(id(o))
    unused = [t for t in inputs if id(t) not in used_ids]
    if unused and not allow_unused:
        raise ValueError(
            "some inputs are not reachable from outputs; pass "
            "allow_unused=True to get None gradients for them")
    # duplicates in `inputs` would fight over the id-keyed replay env;
    # differentiate once per unique tensor and fan the result back out
    uniq, uniq_ids = [], set()
    for t in inputs:
        if id(t) not in uniq_ids:
            uniq_ids.add(id(t))
            uniq.append(t)
    in_ids = {id(t) for t in uniq}
    leaves = []                    # requires-grad leaves beyond `inputs`
    seen = set(in_ids)
    for node in order:
        for t in node.inputs:
            if (getattr(t, "_grad_node", None) is None
                    and not t.stop_gradient and id(t) not in seen):
                seen.add(id(t))
                leaves.append(t)
    n_in = len(uniq)

    def gradfn(*all_arrs):
        in_arrs, leaf_arrs = all_arrs[:n_in], all_arrs[n_in:]

        def replay(*xs):
            env = {id(t): a for t, a in zip(uniq, xs)}
            env.update({id(t): a for t, a in zip(leaves, leaf_arrs)})
            for node in reversed(order):    # producers first
                vals = [env.get(id(t), t._value) for t in node.inputs]
                outs = jax.tree_util.tree_leaves(node.fwd_fn(*vals))
                for ref, o in zip(node.out_refs, outs):
                    ot = ref()
                    # never overwrite a SEEDED value: for a non-leaf
                    # input the producer also replays, and clobbering
                    # the tracer would sever the vjp dependence
                    if ot is not None and id(ot) not in in_ids:
                        env[id(ot)] = o
            return tuple(env.get(id(o), o._value) for o in outputs)

        _, vjp = jax.vjp(replay, *in_arrs)
        res = vjp(seeds)
        # a bare array for the single-input case: the tape seeds a
        # 1-output node with the raw cotangent, not a 1-tuple
        return res[0] if len(res) == 1 else res

    # create_graph means BUILD the graph — even under no_grad (the
    # reference semantics); without taping, the later backward through
    # the returned grads would be a silent no-op
    with enable_grad():
        grads = apply_op(gradfn, *uniq, *leaves)
    grads = list(grads) if isinstance(grads, (tuple, list)) else [grads]
    by_id = {id(t): g for t, g in zip(uniq, grads)}
    return [None if id(t) not in used_ids else by_id[id(t)]
            for t in inputs]


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, allow_unused=False):
    """ref: paddle.grad — gradients of outputs w.r.t. inputs via the eager
    graph. create_graph=True returns gradients that are themselves on
    the tape (functional replay — see _grad_create_graph), enabling
    double/triple grad and gradient penalties.
    """
    from .tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if create_graph:
        return _grad_create_graph(outputs, inputs, grad_outputs,
                                  allow_unused)
    keep = {id(t): t._grad_value for t in inputs}
    retain = [t._retain_grads for t in inputs]
    for t in inputs:
        t._grad_value = None
        t._retain_grads = True
    backward(outputs, grad_outputs,
             retain_graph=bool(retain_graph))
    res = []
    for t, r in zip(inputs, retain):
        g = t._grad_value
        if g is None and not allow_unused:
            raise ValueError(
                "paddle_tpu.grad: an input is not reachable from outputs; "
                "pass allow_unused=True to get None for it instead")
        res.append(Tensor(g, stop_gradient=True) if g is not None else None)
        t._grad_value = keep[id(t)]
        t._retain_grads = r
    return res


# ---------------------------------------------------------------------------
# PyLayer: user-defined forward/backward (ref: paddle.autograd.PyLayer,
# python/paddle/autograd/py_layer.py)
# ---------------------------------------------------------------------------
class PyLayerContext:
    """ref: paddle.autograd.PyLayerContext — carries state from forward to
    backward (`save_for_backward` / `saved_tensor`, plus arbitrary
    attributes)."""

    def __init__(self):
        self._saved = ()
        self.not_inplace_tensors = ()

    def save_for_backward(self, *tensors):
        self._saved = tuple(tensors)

    def saved_tensor(self):
        return self._saved


class PyLayerMeta(type):
    pass


class PyLayer(metaclass=PyLayerMeta):
    """ref: paddle.autograd.PyLayer — custom op with a user-defined
    backward.

    TPU-native dual dispatch:
    - eagerly, ``apply`` runs ``forward`` under no_grad and links one
      GradNode whose pullback calls ``backward`` (exact reference
      semantics: ops inside forward are NOT taped);
    - inside a jax trace (Engine/jit/grad), ``apply`` wraps the pair as a
      ``jax.custom_vjp`` so the compiled step uses the custom rule — the
      same mechanism the Pallas flash-attention kernel uses. Saved
      tensors ride the custom_vjp residuals, so nothing leaks across
      traces.
    """

    @staticmethod
    def forward(ctx, *args, **kwargs):
        raise NotImplementedError

    @staticmethod
    def backward(ctx, *grad_outputs):
        raise NotImplementedError

    @classmethod
    def apply(cls, *args, **kwargs):
        from .tensor import Tensor

        flat, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=_is_tensor)
        t_idx = [i for i, x in enumerate(flat) if _is_tensor(x)]
        tensors = [flat[i] for i in t_idx]
        arrs = [t._value for t in tensors]

        def rebuild(darrs, stop_gradient=True):
            buf = list(flat)
            for i, a in zip(t_idx, darrs):
                buf[i] = Tensor(a, stop_gradient=stop_gradient)
            a2, k2 = jax.tree_util.tree_unflatten(treedef, buf)
            return a2, k2

        if in_jax_trace(arrs):
            return cls._apply_traced(rebuild, arrs)

        ctx = PyLayerContext()
        a2, k2 = rebuild(arrs)
        with no_grad():
            out = cls.forward(ctx, *a2, **k2)

        needs_grad = (is_grad_enabled()
                      and any(not t.stop_gradient for t in tensors))
        if not needs_grad:
            return out

        # pass-through outputs cannot self-cycle the toposort: forward only
        # ever sees the REBUILT input wrappers (rebuild() above), never the
        # caller's tensors, so a returned input is already a distinct
        # object from the node's recorded inputs
        out_flat = [t for t in jax.tree_util.tree_leaves(
            out, is_leaf=_is_tensor) if _is_tensor(t)]

        for t in out_flat:
            t.stop_gradient = False
        diff_pos = [i for i, t in enumerate(tensors)
                    if not t.stop_gradient and _float_like(t._value)]
        n_outs = len(out_flat)

        def vjp_fn(seed):
            seeds = (seed,) if n_outs == 1 else tuple(seed)
            seed_ts = [Tensor(s, stop_gradient=True) for s in seeds]
            with no_grad():
                grads = cls.backward(ctx, *seed_ts)
            if not isinstance(grads, (tuple, list)):
                grads = (grads,)
            if len(grads) != len(tensors):
                raise ValueError(
                    f"{cls.__name__}.backward returned {len(grads)} grads "
                    f"for {len(tensors)} tensor inputs")
            out = []
            for i in diff_pos:
                g = grads[i]
                if g is None:
                    out.append(jnp.zeros_like(tensors[i]._value))
                else:
                    out.append(g._value if _is_tensor(g) else jnp.asarray(g))
            return tuple(out)

        node = GradNode(inputs=[tensors[i] for i in diff_pos],
                        outputs=out_flat, vjp_fn=vjp_fn)
        for t in out_flat:
            t._grad_node = node
        return out

    @classmethod
    def _apply_traced(cls, rebuild, arrs):
        from .tensor import Tensor

        n_in = len(arrs)
        ctx_cell = {}

        def prim(*darrs):
            ctx = PyLayerContext()
            ctx_cell["ctx"] = ctx
            a2, k2 = rebuild(darrs)
            out = cls.forward(ctx, *a2, **k2)
            return jax.tree_util.tree_map(
                lambda t: t._value if _is_tensor(t) else t, out,
                is_leaf=_is_tensor)

        f = jax.custom_vjp(prim)

        def fwd(*darrs):
            out = prim(*darrs)
            ctx = ctx_cell["ctx"]
            saved = tuple(t._value if _is_tensor(t) else t
                          for t in ctx._saved)
            return out, saved

        def bwd(saved, ct):
            ctx = ctx_cell["ctx"]
            ctx._saved = tuple(Tensor(s) if isinstance(s, jax.Array)
                               or hasattr(s, "dtype") else s for s in saved)
            cts = jax.tree_util.tree_leaves(ct)
            seed_ts = [Tensor(c, stop_gradient=True) for c in cts]
            grads = cls.backward(ctx, *seed_ts)
            if not isinstance(grads, (tuple, list)):
                grads = (grads,)
            if len(grads) != n_in:
                raise ValueError(
                    f"{cls.__name__}.backward returned {len(grads)} grads "
                    f"for {n_in} tensor inputs")
            out = []
            for i in range(n_in):
                g = grads[i]
                if g is None:
                    out.append(jnp.zeros_like(arrs[i]))
                else:
                    out.append((g._value if _is_tensor(g)
                                else jnp.asarray(g)).astype(arrs[i].dtype))
            return tuple(out)

        f.defvjp(fwd, bwd)
        out = f(*arrs)
        return jax.tree_util.tree_map(lambda a: Tensor(a, stop_gradient=False),
                                      out)


# ---------------------------------------------------------------------------
# functional autograd API (ref: python/paddle/autograd/functional.py +
# paddle.incubate.autograd, exposed as paddle_tpu.incubate.autograd too):
# jacobian / hessian / jvp / vjp built on jax's transforms — exact,
# composable, jit-compatible. Tensor<->array pytree plumbing reuses
# functional_transforms._unwrap/_wrap.
# ---------------------------------------------------------------------------
def _check_fn_flags(create_graph, where):
    if create_graph:
        raise NotImplementedError(
            f"{where}: create_graph=True is not supported on this API — "
            "compose jax transforms via paddle_tpu.functional_grad / "
            "paddle_tpu.value_and_grad for higher-order pipelines")


def _wrap_fn(func):
    """Lift a Tensor-level callable to a jnp-level one."""
    from .functional_transforms import _unwrap
    from .tensor import Tensor

    def jf(*arrs):
        ts = [Tensor(a, stop_gradient=False) for a in arrs]
        return _unwrap(func(*ts))
    return jf


def _input_arrays(xs):
    from .functional_transforms import _unwrap
    multi = isinstance(xs, (list, tuple))
    arrs = _unwrap(list(xs) if multi else [xs])
    return multi, arrs


def jacobian(func, xs, create_graph=False, allow_unused=False):
    """ref: paddle.autograd.jacobian — J[i, j] = d out_i / d x_j."""
    from .functional_transforms import _wrap
    _check_fn_flags(create_graph, "jacobian")
    multi, arrs = _input_arrays(xs)
    jf = _wrap_fn(func)
    jac = jax.jacrev(lambda *a: jf(*a), argnums=tuple(range(len(arrs))))(
        *arrs)
    out = _wrap(jac)
    if not multi:
        return out[0] if isinstance(out, tuple) else out
    return out


def hessian(func, xs, create_graph=False, allow_unused=False):
    """ref: paddle.autograd.hessian — for a SCALAR-output func."""
    from .functional_transforms import _wrap
    _check_fn_flags(create_graph, "hessian")
    multi, arrs = _input_arrays(xs)
    jf = _wrap_fn(func)

    def scalar(*a):
        return jnp.reshape(jf(*a), ())
    hes = jax.hessian(scalar, argnums=tuple(range(len(arrs))))(*arrs)
    out = _wrap(hes)
    if not multi:
        return out[0][0] if isinstance(out, tuple) else out
    return out


def jvp(func, xs, v=None, create_graph=False, allow_unused=False):
    """ref: paddle.incubate.autograd.jvp -> (outputs, jvp_result)."""
    from .functional_transforms import _unwrap, _wrap
    _check_fn_flags(create_graph, "jvp")
    multi, arrs = _input_arrays(xs)
    if v is None:
        tangents = tuple(jnp.ones_like(a) for a in arrs)
    else:
        tangents = tuple(_unwrap(list(v) if isinstance(v, (list, tuple))
                                 else [v]))
    jf = _wrap_fn(func)
    out, tangent_out = jax.jvp(lambda *a: jf(*a), tuple(arrs), tangents)
    return _wrap(out), _wrap(tangent_out)


def vjp(func, xs, v=None, create_graph=False, allow_unused=False):
    """ref: paddle.incubate.autograd.vjp -> (outputs, vjp_result)."""
    from .functional_transforms import _unwrap, _wrap
    _check_fn_flags(create_graph, "vjp")
    multi, arrs = _input_arrays(xs)
    jf = _wrap_fn(func)
    out, pullback = jax.vjp(lambda *a: jf(*a), *arrs)
    if v is None:
        cot = jax.tree_util.tree_map(jnp.ones_like, out)
    else:
        # cotangent must mirror the OUTPUT pytree structure exactly
        cot_arrays = _unwrap(v)
        out_flat, out_tree = jax.tree_util.tree_flatten(out)
        cot_flat = jax.tree_util.tree_leaves(cot_arrays)
        if len(cot_flat) != len(out_flat):
            raise ValueError(
                f"vjp: cotangent has {len(cot_flat)} leaves but the "
                f"output has {len(out_flat)}")
        cot = jax.tree_util.tree_unflatten(out_tree, cot_flat)
    grads = pullback(cot)
    outs_t = _wrap(out)
    grads_w = [_wrap(g) for g in grads]
    if not multi:
        return outs_t, grads_w[0]
    return outs_t, grads_w
