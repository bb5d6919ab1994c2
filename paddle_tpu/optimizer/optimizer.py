"""Optimizers (ref: python/paddle/optimizer/optimizer.py + per-opt files).

Each optimizer is a *functional core* — ``init_state(params)`` and
``update(params, grads, state, lr, step)`` over pytrees of jax arrays — plus
the reference's eager class API (``opt.step()`` over Parameter.grad). The
Engine/hapi path jits the functional core together with the model's grad
computation into one fused train step (the reference fuses the same way via
its fused_adam / multi_tensor kernels; XLA does the fusion for us).

multi_precision=True keeps fp32 master weights when params are bf16/fp16
(ref: paddle.amp O2 master weights).
"""
from __future__ import annotations

import sys
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..nn.clip import ClipGradBase
from ..tensor import Tensor
from .lr import LRScheduler


def _tree_zeros_like(params):
    return jax.tree_util.tree_map(lambda p: jnp.zeros_like(p), params)


def _sround_bf16(x32, key):
    """Unbiased stochastic rounding fp32 -> bf16: add uniform 16-bit noise
    below the bf16 mantissa cut, then truncate. E[result] == x32, so a
    bf16-stored Adam second moment still accumulates (1-b2)=1e-3 relative
    increments that nearest-rounding would silently drop (they sit below
    bf16's 2^-8 resolution). This is what makes half-width moments usable:
    it halves the optimizer's HBM state traffic (9.9 GB/step at
    gpt3-345M) without biasing the moment estimates.
    ref parity: paddle.optimizer.adamw multi_precision / master-weight
    path (python/paddle/optimizer/adamw.py) — same goal (reduced-precision
    state with fp32 math), TPU-native mechanism."""
    x32 = x32.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    noise = jax.random.bits(key, x32.shape, jnp.uint16).astype(jnp.uint32)
    rounded = jax.lax.bitcast_convert_type(
        ((bits + noise) >> 16).astype(jnp.uint16), jnp.bfloat16)
    # non-finite bit patterns must bypass the noise add: inf + payload
    # truncates to NaN, and uint32 wraparound on negative-NaN patterns
    # flips the sign bit — keep a diverged run's inf recoverable
    return jnp.where(jnp.isfinite(x32), rounded, x32.astype(jnp.bfloat16))


def _store_moment(x32, dtype, key):
    if dtype is None or x32.dtype == dtype:
        return x32
    if dtype == jnp.bfloat16:
        return _sround_bf16(x32, key)
    return x32.astype(dtype)


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None,
                 apply_decay_param_fun=None):
        self._lr = learning_rate
        self._parameter_list = self._normalize_params(parameters)
        if isinstance(weight_decay, (int, float)) or weight_decay is None:
            self._weight_decay = float(weight_decay or 0.0)
        else:  # L1Decay/L2Decay objects expose .coeff
            self._weight_decay = float(getattr(weight_decay, "_coeff",
                                               getattr(weight_decay, "coeff", 0.0)))
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._apply_decay_param_fun = apply_decay_param_fun
        self._step_count = 0
        self._accumulators: Dict = {}
        self._func_state = None
        self._seen_keys = set()
        self._pending_state_leaves = None

    @staticmethod
    def _normalize_params(parameters):
        if parameters is None:
            return None
        plist = list(parameters)
        if plist and isinstance(plist[0], dict):
            # param groups; flatten (per-group lr kept in optimize_attr)
            flat = []
            for group in plist:
                lr_mult = group.get("learning_rate", 1.0)
                wd = group.get("weight_decay", None)
                for p in group["params"]:
                    p.optimize_attr["learning_rate"] = lr_mult
                    if wd is not None:
                        p.optimize_attr["weight_decay"] = \
                            float(getattr(wd, "_coeff", wd))
                    flat.append(p)
            return flat
        return plist

    # -- lr ----------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def _lr_value(self):
        return self.get_lr()

    # -- functional core (override per optimizer) ---------------------------
    def init_state(self, params):
        return {}

    def update(self, params, grads, state, lr, step):
        raise NotImplementedError

    # -- decoupled/coupled weight decay helpers -----------------------------
    def _decay_mask(self, params):
        """True where weight decay applies (apply_decay_param_fun parity)."""
        fn = self._apply_decay_param_fun
        if fn is None:
            return jax.tree_util.tree_map(lambda _: True, params)
        if isinstance(params, dict):
            return {k: bool(fn(k)) for k in params}
        return jax.tree_util.tree_map(lambda _: True, params)

    # -- eager API ----------------------------------------------------------
    def _param_key(self, p, i):
        """Stable per-parameter key: the parameter's name when it has one
        (Layer.named_parameters assigns the structured path), else a key
        pinned to the object identity — so optimizer state survives steps
        where only a subset of params received grads."""
        if p.name:
            return p.name
        keys = self.__dict__.setdefault("_obj_keys", {})
        k = keys.get(id(p))
        if k is None:
            k = f"param_{i}_{len(keys)}"
            keys[id(p)] = k
        return k

    def step(self):
        params = [p for p in (self._parameter_list or []) if p.trainable]
        pg = [(p, p.grad) for p in params]
        if self._grad_clip is not None and isinstance(self._grad_clip, ClipGradBase):
            clip_in = {i: g._value for i, (p, g) in enumerate(pg) if g is not None
                       and p.need_clip}
            clipped = self._grad_clip.apply(clip_in)
            for i, (p, g) in enumerate(pg):
                if i in clipped:
                    pg[i] = (p, Tensor(clipped[i]))
        keys = [self._param_key(p, i) for i, (p, g) in enumerate(pg)]
        pdict = {k: p._value for k, (p, g) in zip(keys, pg) if g is not None}
        gdict = {k: g._value.astype(p._value.dtype)
                 for k, (p, g) in zip(keys, pg) if g is not None}
        if not pdict:
            self._step_count += 1
            return
        full = {k: p._value for k, (p, g) in zip(keys, pg)}
        if self._func_state is None:
            self._func_state = self.init_state(full)
            self._apply_pending_state()
            self._apply_group_sharded_placement(params)
        else:
            # init slots for params never seen before, keep existing moments
            new_keys = [k for k in full if k not in self._seen_keys]
            if new_keys:
                fresh = self.init_state({k: full[k] for k in new_keys})
                for sk, sub in fresh.items():
                    if isinstance(self._func_state.get(sk), dict):
                        self._func_state[sk].update(sub)
        self._seen_keys = set(full)
        # update() touches only grad-bearing keys this step
        state_view = {sk: ({k: sub[k] for k in pdict if k in sub}
                           if isinstance(sub, dict) else sub)
                      for sk, sub in self._func_state.items()}
        lr = self._lr_value()
        lr_mult = {k: p.optimize_attr.get("learning_rate", 1.0)
                   for k, (p, g) in zip(keys, pg) if k in pdict}
        new_p, new_state = self.update(
            pdict, gdict, state_view, lr, self._step_count + 1,
            lr_mult=lr_mult)
        for sk, sub in new_state.items():
            if isinstance(sub, dict) and isinstance(self._func_state.get(sk), dict):
                self._func_state[sk].update(sub)
            else:
                self._func_state[sk] = sub
        for k, (p, g) in zip(keys, pg):
            if k in new_p:
                p._value = new_p[k]
        self._step_count += 1
        self._mem_report(gdict)

    def _mem_report(self, gdict):
        """Level-set optimizer_state/grads bytes into the process's
        active memory ledger, if one is armed. Guarded on the module
        already being imported: a training loop with no ledger pays a
        dict lookup, not an import, and never creates mem_* series
        (the observability dormancy contract)."""
        mod = sys.modules.get("paddle_tpu.observability.memledger")
        if mod is None:
            return
        try:
            led = mod.active_ledger()
            if led is None:
                return
            led.set_level("optimizer_state",
                          mod.nbytes_of(self._func_state),
                          label=type(self).__name__)
            led.set_level("grads", mod.nbytes_of(gdict),
                          label=type(self).__name__)
        except Exception:  # noqa: BLE001 — accounting must never
            pass           # take a training step down

    def _apply_group_sharded_placement(self, params=None):
        """GroupSharded/ZeRO in the eager loop (ref: the reference's primary
        group_sharded_parallel usage is loss.backward(); opt.step()): place
        optimizer state — and at stage 3 the live parameters — on their
        dp-sharded layout the first time state is materialised."""
        gs = getattr(self, "_group_sharded", None)
        if gs is None or self._func_state is None:
            return
        from ..distributed.fleet.sharding import shard_tree
        self._func_state = shard_tree(self._func_state, gs.mesh, gs.axis)
        if gs.shard_params and params:
            for p in params:
                p._value = shard_tree([p._value], gs.mesh, gs.axis)[0]

    def _apply_pending_state(self):
        pending = getattr(self, "_pending_state_leaves", None)
        if pending is None or self._func_state is None:
            return
        import jax as _jax
        leaves, treedef = _jax.tree_util.tree_flatten(self._func_state)
        if len(pending) == len(leaves):
            self._func_state = _jax.tree_util.tree_unflatten(treedef, pending)
        self._pending_state_leaves = None

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list or []:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()

    # -- state dict (checkpoint/resume) -------------------------------------
    def state_dict(self):
        flat = {}
        if self._func_state is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._func_state)
            flat["__leaves__"] = [Tensor(l) if isinstance(l, jax.Array) else l
                                  for l in leaves]
        flat["__step__"] = self._step_count
        if isinstance(self._lr, LRScheduler):
            flat["LR_Scheduler"] = self._lr.state_dict()
        return flat

    def set_state_dict(self, state):
        self._step_count = int(state.get("__step__", 0))
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])
        if "__leaves__" in state:
            new_leaves = [l._value if isinstance(l, Tensor) else l
                          for l in state["__leaves__"]]
            if self._func_state is not None:
                leaves, treedef = jax.tree_util.tree_flatten(self._func_state)
                if len(new_leaves) == len(leaves):
                    self._func_state = jax.tree_util.tree_unflatten(
                        treedef, new_leaves)
                    return
            # state not built yet (no step taken): stash and apply on the
            # first init_state (both eager step() and Engine honor this)
            self._pending_state_leaves = new_leaves

    # -- helpers shared by subclasses ---------------------------------------
    def _wd_for(self, key, default):
        return default

    def _effective_lr(self, lr, lr_mult, key):
        if lr_mult is None:
            return lr
        return lr * lr_mult.get(key, 1.0)


def _bias_correction(beta, step):
    """1 - beta**step as an f32 scalar.

    framework.py turns jax_enable_x64 on, and there a Python float
    raised to the traced int32 step is an f64 scalar; dividing a
    parameter-sized f32 tensor by it promotes the WHOLE update to f64,
    which a TPU emulates in software. The scalar itself is still
    computed in double (a handful of scalar ops) and rounded once."""
    return jnp.asarray(1.0 - beta ** step, jnp.float32)


class SGD(Optimizer):
    """ref: paddle.optimizer.SGD — vanilla + optional (coupled) L2 decay."""

    def update(self, params, grads, state, lr, step, lr_mult=None):
        wd = self._weight_decay

        def upd(k):
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            return p - self._effective_lr(lr, lr_mult, k) * g
        return {k: upd(k) for k in params}, state


class Momentum(Optimizer):
    """ref: paddle.optimizer.Momentum (heavy-ball, optional Nesterov)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_state(self, params):
        return {"velocity": _tree_zeros_like(params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        mu = self._momentum
        wd = self._weight_decay
        new_v, new_p = {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            v = mu * state["velocity"][k] + g
            elr = self._effective_lr(lr, lr_mult, k)
            if self._nesterov:
                new_p[k] = p - elr * (g + mu * v)
            else:
                new_p[k] = p - elr * v
            new_v[k] = v
        return new_p, {"velocity": new_v}


class Adam(Optimizer):
    """ref: paddle.optimizer.Adam (bias-corrected, coupled L2 decay)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, apply_decay_param_fun=None, amsgrad=False,
                 moment_dtype=None, fused_kernel=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name, apply_decay_param_fun)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad
        self._decoupled = False
        # one-HBM-pass Pallas update for large fp32 leaves (ref: the
        # CUDA fused adamw_kernel) — r4 step anatomy measured the jnp
        # chain at ~2x its bandwidth floor. Opt-in, not measured on
        # the chip; ineligible leaves (small, amsgrad,
        # master weights, bf16 moments) keep the jnp path.
        self._fused_kernel = bool(fused_kernel)
        # reduced-precision moment storage (bf16 halves optimizer HBM
        # traffic; math stays fp32, stores use stochastic rounding)
        self._moment_dtype = jnp.dtype(moment_dtype) if moment_dtype else None
        if self._moment_dtype not in (None, jnp.dtype(jnp.bfloat16),
                                      jnp.dtype(jnp.float32)):
            raise ValueError(
                f"moment_dtype={moment_dtype}: only bfloat16 (stochastic "
                "rounding) or float32 are supported")

    def init_state(self, params):
        mdt = self._moment_dtype

        def zeros(p):
            return jnp.zeros(p.shape, mdt or p.dtype)
        st = {"m": jax.tree_util.tree_map(zeros, params),
              "v": jax.tree_util.tree_map(zeros, params)}
        if self._amsgrad:
            # fp32 regardless of moment_dtype: see the vhat note in update()
            st["vhat"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if self._multi_precision:
            st["master"] = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), params)
        return st

    def update(self, params, grads, state, lr, step, lr_mult=None):
        import zlib
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = self._weight_decay
        decay_fn = self._apply_decay_param_fun
        mdt = self._moment_dtype
        bc1 = _bias_correction(b1, step)
        bc2 = _bias_correction(b2, step)
        skey = None
        if mdt == jnp.bfloat16:
            # per-step, per-parameter keys derived inside the trace: no
            # threading through the Engine signature, identical eager/jit
            skey = jax.random.fold_in(jax.random.PRNGKey(0xAD04), step)
        new_m, new_v, new_p = {}, {}, {}
        new_vhat = {}
        new_master = {}
        use_fused = self._fused_kernel and not self._amsgrad \
            and not self._multi_precision
        if use_fused:
            from ..ops.pallas.fused_adamw import (fused_adamw_supported,
                                                  fused_adamw_update)
        for k in params:
            if use_fused and fused_adamw_supported(
                    params[k], state["m"][k], state["v"][k]):
                apply_wd = wd and (decay_fn is None or decay_fn(k))
                elr = self._effective_lr(lr, lr_mult, k)
                new_p[k], new_m[k], new_v[k] = fused_adamw_update(
                    params[k], state["m"][k], state["v"][k], grads[k],
                    elr, bc1, bc2, beta1=b1, beta2=b2, eps=eps,
                    weight_decay=(wd if apply_wd else 0.0),
                    decoupled=self._decoupled)
                continue
            g = grads[k].astype(jnp.float32)
            p32 = state["master"][k] if self._multi_precision else \
                params[k].astype(jnp.float32)
            apply_wd = wd and (decay_fn is None or decay_fn(k))
            if apply_wd and not self._decoupled:
                g = g + wd * p32
            m = b1 * state["m"][k].astype(jnp.float32) + (1 - b1) * g
            v = b2 * state["v"][k].astype(jnp.float32) + \
                (1 - b2) * jnp.square(g)
            m_hat = m / bc1
            if self._amsgrad:
                vh = jnp.maximum(state["vhat"][k].astype(jnp.float32), v)
                denom = jnp.sqrt(vh / bc2) + eps
            else:
                vh = None
                denom = jnp.sqrt(v / bc2) + eps
            elr = self._effective_lr(lr, lr_mult, k)
            stepv = elr * m_hat / denom
            if apply_wd and self._decoupled:
                stepv = stepv + elr * wd * p32
            p_new32 = p32 - stepv
            if skey is not None:
                kk = jax.random.fold_in(
                    skey, zlib.crc32(k.encode()) & 0x7FFFFFFF)
                k_m, k_v = jax.random.split(kk)
                new_m[k] = _store_moment(m, mdt, k_m)
                new_v[k] = _store_moment(v, mdt, k_v)
            else:
                new_m[k], new_v[k] = m, v
            if vh is not None:
                # vhat stays fp32 even under moment_dtype: AMSGrad's
                # monotone-max invariant turns unbiased rounding noise
                # into an upward ratchet (max acts as a reflecting
                # barrier), silently shrinking the effective lr
                new_vhat[k] = vh
            if self._multi_precision:
                new_master[k] = p_new32
                new_p[k] = p_new32.astype(params[k].dtype)
            else:
                new_p[k] = p_new32.astype(params[k].dtype)
        st = {"m": new_m, "v": new_v}
        if self._amsgrad:
            st["vhat"] = new_vhat
        if self._multi_precision:
            st["master"] = new_master
        return new_p, st


class AdamW(Adam):
    """ref: paddle.optimizer.AdamW — decoupled weight decay."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 amsgrad=False, moment_dtype=None, fused_kernel=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name, apply_decay_param_fun, amsgrad,
                         moment_dtype=moment_dtype,
                         fused_kernel=fused_kernel)
        self._decoupled = True


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def init_state(self, params):
        return {"m": _tree_zeros_like(params), "u": _tree_zeros_like(params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = self._weight_decay
        new = ({}, {}, {})
        for k in params:
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            m = b1 * state["m"][k] + (1 - b1) * g
            u = jnp.maximum(b2 * state["u"][k], jnp.abs(g))
            elr = self._effective_lr(lr, lr_mult, k) / _bias_correction(b1, step)
            new[0][k] = p - elr * m / (u + eps)
            new[1][k] = m
            new[2][k] = u
        return new[0], {"m": new[1], "u": new[2]}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def init_state(self, params):
        return {"moment": jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, self._init_acc), params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        wd = self._weight_decay
        new_m, new_p = {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            m = state["moment"][k] + jnp.square(g)
            new_p[k] = p - self._effective_lr(lr, lr_mult, k) * g / \
                (jnp.sqrt(m) + self._epsilon)
            new_m[k] = m
        return new_p, {"moment": new_m}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._epsilon, self._rho = epsilon, rho

    def init_state(self, params):
        return {"avg_sq_grad": _tree_zeros_like(params),
                "avg_sq_update": _tree_zeros_like(params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        rho, eps = self._rho, self._epsilon
        wd = self._weight_decay
        n1, n2, np_ = {}, {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            asg = rho * state["avg_sq_grad"][k] + (1 - rho) * jnp.square(g)
            upd = g * jnp.sqrt(state["avg_sq_update"][k] + eps) / jnp.sqrt(asg + eps)
            asu = rho * state["avg_sq_update"][k] + (1 - rho) * jnp.square(upd)
            np_[k] = p - self._effective_lr(lr, lr_mult, k) * upd
            n1[k], n2[k] = asg, asu
        return np_, {"avg_sq_grad": n1, "avg_sq_update": n2}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name=name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def init_state(self, params):
        st = {"mean_sq": _tree_zeros_like(params),
              "velocity": _tree_zeros_like(params)}
        if self._centered:
            st["mean_g"] = _tree_zeros_like(params)
        return st

    def update(self, params, grads, state, lr, step, lr_mult=None):
        rho, eps, mu = self._rho, self._epsilon, self._momentum
        wd = self._weight_decay
        new_ms, new_v, new_mg, new_p = {}, {}, {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if wd:
                g = g + wd * p
            ms = rho * state["mean_sq"][k] + (1 - rho) * jnp.square(g)
            if self._centered:
                mg = rho * state["mean_g"][k] + (1 - rho) * g
                denom = jnp.sqrt(ms - jnp.square(mg) + eps)
                new_mg[k] = mg
            else:
                denom = jnp.sqrt(ms + eps)
            v = mu * state["velocity"][k] + \
                self._effective_lr(lr, lr_mult, k) * g / denom
            new_p[k] = p - v
            new_ms[k], new_v[k] = ms, v
        st = {"mean_sq": new_ms, "velocity": new_v}
        if self._centered:
            st["mean_g"] = new_mg
        return new_p, st


class Lamb(Optimizer):
    """ref: paddle.optimizer.Lamb — layerwise-adaptive Adam for large batch."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, multi_precision, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def init_state(self, params):
        return {"m": _tree_zeros_like(params), "v": _tree_zeros_like(params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = self._weight_decay
        new_m, new_v, new_p = {}, {}, {}
        for k in params:
            g = grads[k].astype(jnp.float32)
            p = params[k].astype(jnp.float32)
            m = b1 * state["m"][k] + (1 - b1) * g
            v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
            m_hat = m / _bias_correction(b1, step)
            v_hat = v / _bias_correction(b2, step)
            r = m_hat / (jnp.sqrt(v_hat) + eps)
            use_wd = wd and (self._exclude_fn is None or not self._exclude_fn(k))
            if use_wd:
                r = r + wd * p
            w_norm = jnp.linalg.norm(p)
            r_norm = jnp.linalg.norm(r)
            trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
            new_p[k] = (p - self._effective_lr(lr, lr_mult, k) * trust * r
                        ).astype(params[k].dtype)
            new_m[k], new_v[k] = m, v
        return new_p, {"m": new_m, "v": new_v}


class NAdam(Adam):
    def update(self, params, grads, state, lr, step, lr_mult=None):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        new_m, new_v, new_p = {}, {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if self._weight_decay:
                g = g + self._weight_decay * p
            m = b1 * state["m"][k] + (1 - b1) * g
            v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
            m_hat = m / _bias_correction(b1, step + 1)
            v_hat = v / _bias_correction(b2, step)
            m_bar = b1 * m_hat + (1 - b1) * g / _bias_correction(b1, step)
            new_p[k] = p - self._effective_lr(lr, lr_mult, k) * m_bar / \
                (jnp.sqrt(v_hat) + eps)
            new_m[k], new_v[k] = m, v
        return new_p, {"m": new_m, "v": new_v}


class RAdam(Adam):
    def update(self, params, grads, state, lr, step, lr_mult=None):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        rho_inf = 2.0 / (1 - b2) - 1
        new_m, new_v, new_p = {}, {}, {}
        for k in params:
            g = grads[k]
            p = params[k]
            if self._weight_decay:
                g = g + self._weight_decay * p
            m = b1 * state["m"][k] + (1 - b1) * g
            v = b2 * state["v"][k] + (1 - b2) * jnp.square(g)
            m_hat = m / _bias_correction(b1, step)
            rho_t = rho_inf - 2 * step * (b2 ** step) / (1 - b2 ** step)
            elr = self._effective_lr(lr, lr_mult, k)
            v_hat = jnp.sqrt(v / _bias_correction(b2, step))
            r_num = (rho_t - 4) * (rho_t - 2) * rho_inf
            r_den = (rho_inf - 4) * (rho_inf - 2) * rho_t
            # scalar math in double, rounded before it meets a tensor
            r = jnp.asarray(jnp.sqrt(jnp.maximum(r_num / r_den, 0.0)),
                            jnp.float32)
            rect = p - elr * r * m_hat / (v_hat + eps)
            plain = p - elr * m_hat
            new_p[k] = jnp.where(rho_t > 5.0, rect, plain)
            new_m[k], new_v[k] = m, v
        return new_p, {"m": new_m, "v": new_v}


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name=name)
        self._lr_range = learning_rate_range
        self._etas = etas

    def init_state(self, params):
        return {"prev_grad": _tree_zeros_like(params),
                "step_size": jax.tree_util.tree_map(
                    lambda p: jnp.full_like(p, float(self.get_lr())), params)}

    def update(self, params, grads, state, lr, step, lr_mult=None):
        eta_m, eta_p = self._etas
        lo, hi = self._lr_range
        new_pg, new_ss, new_p = {}, {}, {}
        for k in params:
            g = grads[k]
            sign = jnp.sign(g * state["prev_grad"][k])
            ss = jnp.clip(jnp.where(sign > 0, state["step_size"][k] * eta_p,
                                    jnp.where(sign < 0,
                                              state["step_size"][k] * eta_m,
                                              state["step_size"][k])), lo, hi)
            g_eff = jnp.where(sign < 0, 0.0, g)
            new_p[k] = params[k] - jnp.sign(g_eff) * ss
            new_pg[k] = g_eff
            new_ss[k] = ss
        return new_p, {"prev_grad": new_pg, "step_size": new_ss}
