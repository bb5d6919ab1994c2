"""Engine: compiles (model, loss, optimizer) into ONE jitted train step.

ref: the reference's Model.fit dispatches per-op through the dygraph tracer
(or builds a static Program under @to_static). TPU-native: the entire
step — forward, loss, backward, grad clip, optimizer update, running-stat
updates — is a single pure function of (params, buffers, opt_state, lr,
rng, batch), compiled once by XLA with buffer donation so parameter update
is in-place in HBM. Data parallelism: pass a Mesh and the batch is sharded
over 'dp' while params follow their annotated shardings (GSPMD inserts the
grad psum — the moral equivalent of fleet's allreduce hooks).
"""
from __future__ import annotations

import weakref
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.layer import Layer, functional_call
from ..observability.trace import RecompileTracer
from ..optimizer.lr import LRScheduler
from ..tensor import Tensor


def _unwrap(x):
    return jax.tree_util.tree_map(
        lambda t: t._value if isinstance(t, Tensor) else (
            jnp.asarray(t) if isinstance(t, np.ndarray) else t), x,
        is_leaf=lambda t: isinstance(t, Tensor))


def _global_grad_norm(grads):
    """Global L2 norm over every gradient leaf, fp32. Computed INSIDE
    the compiled step (the reductions fuse into the backward pass's
    epilogue — no extra dispatch); surfaced as Engine.last_grad_norm
    for the telemetry layer, which syncs it lazily."""
    leaves = [g for g in jax.tree_util.tree_leaves(grads)
              if hasattr(g, "dtype")]
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def _clip_and_update(opt, clip, collect_gnorm, live, grads, opt_state, lr,
                     opt_step_i):
    """The tail every train-step variant shares, each part under the
    scope a device trace attributes it by (`grad_norm`, `grad_clip`,
    `optimizer`). Returns (new params, new optimizer state, grad norm)."""
    if collect_gnorm:
        with jax.named_scope("grad_norm"):
            gnorm = _global_grad_norm(grads)
    else:
        gnorm = jnp.float32(0.0)
    if clip is not None:
        with jax.named_scope("grad_clip"):
            grads = clip.apply(grads)
    with jax.named_scope("optimizer"):
        new_live, new_opt = opt.update(live, grads, opt_state, lr,
                                       opt_step_i)
    return new_live, new_opt, gnorm


class Engine:
    def __init__(self, network: Layer, loss=None, optimizer=None,
                 metrics=None, amp_dtype=None, mesh=None,
                 donate_params=True, guard=None):
        self.network = network
        self.loss = loss
        self.optimizer = optimizer
        if optimizer is not None:
            import weakref
            optimizer._engine_ref = weakref.ref(self)
        self.metrics = metrics or []
        self.amp_dtype = amp_dtype
        self.mesh = mesh
        if mesh is not None:
            # model code reads the process-wide mesh (mpu.annotate's
            # sharding constraints, the per-shard flash kernel): an
            # Engine given a mesh makes it that mesh
            from ..distributed.mesh import set_mesh
            set_mesh(mesh)
        self.donate = donate_params
        # resilience.TrainGuard: when set, train_batch compiles the
        # guarded step variant (fused all-finite check, masked update,
        # optional in-step GradScaler state) — see _build_guarded_fn.
        # A property: assigning engine.guard (attach OR detach) drops
        # the compiled step, whose signature depends on guard presence
        self._guard = guard
        self._scaler_state = None
        self._params, self._buffers = network.raw_state()
        self._opt_state = None
        self._step = 0
        self._train_fn = None
        self._multi_fns = {}
        self._eval_fn = None
        self._pred_fn = None
        self._rng_key = jax.random.PRNGKey(0)
        # recompile accounting (docs/observability.md): every jitted
        # entry point below is wrapped by this tracer, so "the train
        # step retraced mid-run" is a queryable run fact, not a
        # mystery slowdown. The device-resident grad norm of the last
        # fused step rides here for telemetry (no sync until read).
        from ..observability.metrics import get_registry
        self.tracer = RecompileTracer(name="engine",
                                      registry=get_registry())
        # retire the tracer when this Engine is collected: repeated
        # Engine construction (sweeps, notebooks, pytest) must not grow
        # the process-wide live-tracer list; close() keeps the site
        # aggregates visible to report_all() via the bounded
        # closed-report ring
        weakref.finalize(self, self.tracer.close)
        # grad-norm telemetry is OPT-IN: the reduction is fused into
        # the step but is still a real all-gradients fp32 reduce XLA
        # cannot dead-code-eliminate (it is a program output) — a bare
        # Engine run stays measurement-neutral vs pre-telemetry
        # baselines. TelemetryCallback enables it at train begin,
        # before the step first builds.
        self.collect_grad_norm = False
        self.last_grad_norm = None
        self._train_fn_collects_gnorm = False
        # gradient accumulation (two extra jitted programs, built lazily)
        self._grad_fn = None
        self._apply_fn = None
        self._acc_grads = None
        self._micro_count = 0
        # optimizer updates, NOT microbatches: Adam's bias correction
        # must see the number of update() calls
        self._opt_step = 0

    # ------------------------------------------------------------------
    def sync_from_layer(self):
        self._params, self._buffers = self.network.raw_state()

    def sync_to_layer(self):
        self.network.load_raw_state(self._params, self._buffers)

    def _shard_batch(self, arrs, allow_ragged=False):
        if self.mesh is None or "dp" not in self.mesh.axis_names:
            return arrs
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(self.mesh, PartitionSpec("dp"))
        ndp = self.mesh.shape["dp"]

        def place(a):
            if not (hasattr(a, "ndim") and a.ndim >= 1):
                return a
            if a.shape[0] % ndp == 0:
                return jax.device_put(a, sh)
            if allow_ragged:
                # eval's last DataLoader batch (no drop_last): run it
                # replicated rather than raising mid-epoch
                return a
            raise ValueError(
                f"training batch dim {a.shape[0]} is not divisible by the "
                f"dp mesh axis ({ndp}): every train step would silently "
                "lose data parallelism. Use a divisible batch_size or "
                "drop_last=True.")
        return jax.tree_util.tree_map(place, arrs)

    # ------------------------------------------------------------------
    def _trainable_keys(self):
        # frozen (trainable=False) params are closed over as constants of
        # the step — they get no grads and no optimizer update (parity with
        # the eager Optimizer.step's p.trainable filter)
        return {n for n, p in self.network.named_parameters() if p.trainable}

    def _grad_shardings(self, trainable_keys):
        """GroupSharded/ZeRO stage 2+: constraints that make XLA lower
        the dp grad-sum to reduce-scatter (None when not sharding)."""
        gs = getattr(self.optimizer, "_group_sharded", None)
        if gs is None or not gs.shard_grads:
            return None
        from jax.sharding import NamedSharding
        from ..distributed.fleet.sharding import constraint_specs
        live_arrs = {k: v for k, v in self._params.items()
                     if k in trainable_keys}
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(gs.mesh, s),
            constraint_specs(live_arrs, gs.mesh, gs.axis))

    @staticmethod
    def _make_loss_fn(network, loss_layer, amp_dt, frozen, buffers,
                      inputs, labels, rng):
        """The forward+loss closure shared by the fused train step and
        the accumulation grad step (single source of truth for the AMP
        cast and buffer-dtype-restore logic). The network runs under
        its layers' own scopes (nn.Layer.__call__); what is no Layer
        gets an explicit one here: `amp_cast` and `loss`."""
        def loss_fn(p):
            run_p = {**frozen, **p}
            run_in = inputs
            if amp_dt is not None:
                with jax.named_scope("amp_cast"):
                    cast = jax.tree_util.tree_map(
                        lambda a: a.astype(amp_dt)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        (run_p, list(inputs)))
                run_p, run_in = cast
            outs, new_buf = functional_call(
                network, run_p, buffers, *run_in, rng=rng, mutable=True)
            if amp_dt is not None:
                # keep running stats at their original dtype so the step
                # signature is stable (no recompile) and stats stay fp32
                with jax.named_scope("amp_cast"):
                    new_buf = jax.tree_util.tree_map(
                        lambda n, o: n.astype(o.dtype)
                        if hasattr(n, "astype") else n, new_buf, buffers)
            outs_t = outs if isinstance(outs, (list, tuple)) else [outs]
            if loss_layer is not None:
                with jax.named_scope("loss"):
                    l = loss_layer(*outs_t, *labels)
            else:
                l = outs_t[0]
            l_arr = l._value if isinstance(l, Tensor) else l
            if isinstance(outs, dict) and outs.get("_loss_only_aux"):
                # model-agnostic convention: a dict output marked
                # _loss_only_aux feeds ONLY the criterion (e.g. GPT's
                # fused head+CE passes the tied weight) — returning it
                # from the compiled step would materialize those
                # tensors as extra program outputs every step
                outs = ()
            return l_arr.astype(jnp.float32), (_unwrap(outs), new_buf)
        return loss_fn

    @property
    def guard(self):
        return self._guard

    @guard.setter
    def guard(self, g):
        # the guarded and plain steps have different signatures; a
        # stale executable from the other mode would mis-bind args.
        # The scaler state belongs to the outgoing guard's scaler —
        # a new guard's scaler re-initializes from ITS init scale
        self._guard = g
        self._train_fn = None
        self._multi_fns = {}
        self._scaler_state = None

    def attach_guard(self, guard):
        """Attach (or with None, detach) a resilience.TrainGuard: the
        next train_batch builds the matching step variant."""
        self.guard = guard
        return guard

    def enable_grad_norm(self):
        """Ask the compiled train step to also output the global grad
        L2 norm (Engine.last_grad_norm, synced lazily). Takes effect
        when the step next builds: enabling before the first batch
        (TelemetryCallback does this at train begin) is free; enabling
        mid-run deliberately does NOT drop an already-compiled step —
        that rebuild would be exactly the unexpected retrace the
        tracer exists to catch."""
        self.collect_grad_norm = True

    def _build_guarded_fn(self):
        """Guarded train step (resilience.TrainGuard's compiled half).

        Same single-dispatch structure as _build_train_fn plus, fused
        into the SAME XLA program (the finite-checks are reductions
        over tensors the step already produced — no extra launch):

        - `fault_scale` scalar multiplied into the loss pre-autodiff
          (1.0 normally; the nan_grads injector passes NaN, poisoning
          loss and every grad at once);
        - an all-finite flag over loss + every gradient leaf;
        - param/buffer/optimizer updates MASKED by that flag — a bad
          step is a perfect no-op on model state (the host also skips
          the opt_step increment, so Adam bias correction and the
          GradScaler never see skipped steps);
        - optional GradScaler state threaded through: loss scaled
          pre-grad, grads unscaled pre-check, dynamic scale updated
          from the found-inf flag (functional_update).
        """
        network = self.network
        loss_layer = self.loss
        opt = self.optimizer
        clip = getattr(opt, "_grad_clip", None)
        amp_dt = self.amp_dtype
        trainable_keys = self._trainable_keys()
        grad_shardings = self._grad_shardings(trainable_keys)
        make_loss_fn = self._make_loss_fn
        collect_gnorm = self.collect_grad_norm
        self._train_fn_collects_gnorm = collect_gnorm
        scaler = self.guard.scaler if self.guard is not None else None
        use_scaler = scaler is not None
        if use_scaler:
            from ..amp import GradScaler as _GS
            s_incr, s_decr = scaler._incr_ratio, scaler._decr_ratio
            s_incr_n, s_decr_n = scaler._incr_every, scaler._decr_every

        def train_step(params, buffers, opt_state, scaler_state, lr,
                       step_i, opt_step_i, rng, fault_scale, inputs,
                       labels):
            rng = jax.random.fold_in(rng, step_i)
            frozen = {k: v for k, v in params.items()
                      if k not in trainable_keys}
            live = {k: v for k, v in params.items() if k in trainable_keys}
            loss_fn = make_loss_fn(network, loss_layer, amp_dt, frozen,
                                   buffers, inputs, labels, rng)

            def guarded_loss(p):
                l, (outs, new_buf) = loss_fn(p)
                l = l * fault_scale
                ls = l * scaler_state["scale"] if use_scaler else l
                return ls, (l, outs, new_buf)

            (_, (loss_v, outs, new_buf)), grads = jax.value_and_grad(
                guarded_loss, has_aux=True)(live)
            if use_scaler:
                inv = 1.0 / scaler_state["scale"]
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings)
            with jax.named_scope("guard"):
                ok = jnp.isfinite(loss_v)
                for g in jax.tree_util.tree_leaves(grads):
                    ok = ok & jnp.all(jnp.isfinite(g))
            new_live, new_opt, gnorm = _clip_and_update(
                opt, clip, collect_gnorm, live, grads, opt_state, lr,
                opt_step_i)

            def mask(new, old):
                # elementwise select, NOT arithmetic: NaNs in the
                # discarded branch must not propagate
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o)
                    if hasattr(n, "dtype") else n, new, old)

            with jax.named_scope("guard"):
                new_live = mask(new_live, live)
                new_opt = mask(new_opt, opt_state)
                new_buf = mask(new_buf, buffers)
                if use_scaler:
                    scaler_state = _GS.functional_update(
                        scaler_state, ~ok, incr_ratio=s_incr,
                        decr_ratio=s_decr, incr_every=s_incr_n,
                        decr_every=s_decr_n)
            return ({**frozen, **new_live}, new_buf, new_opt,
                    scaler_state, loss_v, ok, gnorm, outs)

        donate = (0, 1, 2) if self.donate else ()
        return self.tracer.jit(
            "train_step_guarded", train_step, donate_argnums=donate,
            **self._pin_state_layout(self._params, self._buffers,
                                     self._opt_state, rest=5))

    def _build_train_fn(self):
        if self.guard is not None:
            return self._build_guarded_fn()
        network = self.network
        loss_layer = self.loss
        opt = self.optimizer
        clip = getattr(opt, "_grad_clip", None)
        amp_dt = self.amp_dtype
        trainable_keys = self._trainable_keys()
        grad_shardings = self._grad_shardings(trainable_keys)
        make_loss_fn = self._make_loss_fn
        collect_gnorm = self.collect_grad_norm
        self._train_fn_collects_gnorm = collect_gnorm

        def train_step(params, buffers, opt_state, lr, step_i, opt_step_i,
                       rng, inputs, labels):
            # per-step randomness folds from a CONSTANT base key inside the
            # compiled step — splitting on the host would cost device ops
            # (and, on a remote backend, round trips) every iteration.
            # step_i counts CALLS (unique rng per batch); opt_step_i counts
            # optimizer UPDATES (Adam bias correction) — they differ once
            # gradient accumulation has run in the same session.
            rng = jax.random.fold_in(rng, step_i)
            frozen = {k: v for k, v in params.items()
                      if k not in trainable_keys}
            live = {k: v for k, v in params.items() if k in trainable_keys}
            loss_fn = make_loss_fn(network, loss_layer, amp_dt, frozen,
                                   buffers, inputs, labels, rng)
            (loss_v, (outs, new_buf)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(live)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(
                    grads, grad_shardings)
            new_live, new_opt, gnorm = _clip_and_update(
                opt, clip, collect_gnorm, live, grads, opt_state, lr,
                opt_step_i)
            return ({**frozen, **new_live}, new_buf, new_opt, loss_v,
                    gnorm, outs)

        donate = (0, 1, 2) if self.donate else ()
        return self.tracer.jit(
            "train_step", train_step, donate_argnums=donate,
            **self._pin_state_layout(self._params, self._buffers,
                                     self._opt_state, rest=3))

    def _build_accum_fns(self):
        """Gradient accumulation as TWO compiled programs (ref: the
        reference's gradient_merge / accumulate_steps): `grad_fn` runs
        forward+backward for one microbatch and adds into a donated
        fp32 accumulator; `apply_fn` averages, clips and applies the
        optimizer once per k microbatches. Splitting keeps each program
        static — no data-dependent 'is this the k-th call' inside jit."""
        network = self.network
        loss_layer = self.loss
        opt = self.optimizer
        clip = getattr(opt, "_grad_clip", None)
        amp_dt = self.amp_dtype
        trainable_keys = self._trainable_keys()
        grad_shardings = self._grad_shardings(trainable_keys)
        make_loss_fn = self._make_loss_fn

        donate = self.donate

        def grad_step(params, buffers, acc, step_i, rng, inputs, labels):
            rng = jax.random.fold_in(rng, step_i)
            frozen = {k: v for k, v in params.items()
                      if k not in trainable_keys}
            live = {k: v for k, v in params.items() if k in trainable_keys}
            loss_fn = make_loss_fn(network, loss_layer, amp_dt, frozen,
                                   buffers, inputs, labels, rng)
            (loss_v, (outs, new_buf)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(live)
            with jax.named_scope("grad_accum"):
                grads32 = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                if grad_shardings is not None:
                    # keep the fp32 accumulator sharded too — a
                    # replicated accumulator would undo ZeRO-2's memory win
                    grads32 = jax.lax.with_sharding_constraint(
                        grads32, grad_shardings)
                acc_out = jax.tree_util.tree_map(
                    lambda a, g: a + g, acc, grads32)
            return acc_out, new_buf, loss_v, outs

        def apply_step(params, opt_state, acc, n_micro, lr, step_i):
            frozen = {k: v for k, v in params.items()
                      if k not in trainable_keys}
            live = {k: v for k, v in params.items() if k in trainable_keys}
            with jax.named_scope("grad_accum"):
                grads = jax.tree_util.tree_map(
                    lambda a, p: (a / n_micro).astype(p.dtype), acc, live)
            new_live, new_opt, _ = _clip_and_update(
                opt, clip, False, live, grads, opt_state, lr, step_i)
            if not donate:
                # nothing to alias into without donation — returning a
                # zero tree would just be a param-size transient
                return {**frozen, **new_live}, new_opt, None
            # return the accumulator ZEROED: the donated acc buffer gets
            # an in-place output alias (no param-size dead donation — the
            # source of the 'donated buffers were not usable' warning)
            # and the next window starts from it without re-allocating
            new_acc = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return {**frozen, **new_live}, new_opt, new_acc

        grad_jit = self.tracer.jit(
            "grad_step", grad_step,
            donate_argnums=(2,) if self.donate else ())
        apply_jit = self.tracer.jit(
            "apply_step", apply_step,
            donate_argnums=(0, 1, 2) if self.donate else (),
            **self._pin_state_layout(self._params, self._opt_state,
                                     rest=1))
        return grad_jit, apply_jit

    def _ensure_opt_state(self):
        """Lazy optimizer-state init shared by the fused and accumulation
        paths — including the set_state_dict pending-leaves restore."""
        if self._opt_state is not None:
            return
        if self.mesh is not None:
            self._params = self._on_mesh(self._params)
            self._buffers = self._on_mesh(self._buffers)
            self.network.load_raw_state(self._params, self._buffers)
        trainable = {n: self._params[n]
                     for n, p in self.network.named_parameters()
                     if p.trainable and n in self._params}
        self._opt_state = self.optimizer.init_state(trainable)
        pending = getattr(self.optimizer, "_pending_state_leaves", None)
        if pending is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._opt_state)
            if len(pending) == len(leaves):
                self._opt_state = jax.tree_util.tree_unflatten(
                    treedef, pending)
            self.optimizer._pending_state_leaves = None
        self._opt_state = self._on_mesh(self._opt_state)
        self._apply_zero_placement()

    def _on_mesh(self, tree):
        """`tree` committed to the mesh BEFORE the first compile. State
        created on one device (optimizer moments, a model never passed
        to shard_model) comes back from the step laid out over the mesh,
        so the second call would see new input shardings and compile the
        whole step again. A leaf keyed by a parameter's name takes that
        parameter's sharding (Adam moments mirror their weight);
        anything else not yet on the mesh is replicated over it."""
        mesh = self.mesh
        if mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(mesh, PartitionSpec())
        params = self._params

        def on_mesh(a):
            sh = getattr(a, "sharding", None)
            return isinstance(sh, NamedSharding) and sh.mesh == mesh

        def place(path, a):
            if not hasattr(a, "sharding") or on_mesh(a):
                return a
            like = params.get(getattr(path[-1], "key", None)) \
                if path else None
            if like is not None and like is not a and on_mesh(like) \
                    and like.shape == a.shape:
                return jax.device_put(a, like.sharding)
            return jax.device_put(a, replicated)

        return jax.tree_util.tree_map_with_path(place, tree)

    def _pin_state_layout(self, *state, rest):
        """jit kwargs that hand each state output back laid out exactly
        like its input; the `rest` trailing outputs stay XLA's choice.
        Left alone, XLA may return e.g. ZeRO-updated parameters still
        dp-sharded, and the next call would compile the whole step again
        for the new input layout. Nothing to pin off-mesh."""
        if self.mesh is None:
            return {}

        def layout(tree):
            return jax.tree_util.tree_map(
                lambda a: getattr(a, "sharding", None), tree)
        return {"out_shardings": tuple(layout(t) for t in state)
                + (None,) * rest}

    def train_batch_accum(self, inputs, labels, apply_update):
        """One microbatch of gradient accumulation; pass
        apply_update=True on the last microbatch to run the optimizer on
        the averaged gradients. Returns (loss, outs, applied)."""
        if self.guard is not None:
            raise ValueError(
                "TrainGuard covers the fused train_batch path only — "
                "gradient accumulation splits the step into two "
                "programs and a half-guarded window would mask grads "
                "but not the accumulator. Detach (engine.guard = None)"
                " or use accumulate_grad_batches=1.")
        if self.network.training is False:
            self.network.train()
        self._ensure_opt_state()
        if self._grad_fn is None:
            self._grad_fn, self._apply_fn = self._build_accum_fns()
        in_arrs = self._shard_batch(_unwrap(list(inputs)))
        lab_arrs = self._shard_batch(_unwrap(list(labels)))
        self._step += 1
        if self._acc_grads is None:
            # zeros-init at window start keeps grad_step a single trace
            # (an acc=None variant would be a second compiled program).
            # Under ZeRO the zeros are created ON their grad shardings —
            # a replicated fp32 accumulator would cost full-model memory
            # per device, the exact thing stage 2 shards away
            trainable_keys = self._trainable_keys()
            shardings = self._grad_shardings(trainable_keys)
            self._acc_grads = {}
            for k, v in self._params.items():
                if k not in trainable_keys:
                    continue
                z = jnp.zeros(v.shape, jnp.float32)
                if shardings is not None and k in shardings:
                    z = jax.device_put(z, shardings[k])
                self._acc_grads[k] = z
        self._acc_grads, self._buffers, loss_v, outs = self._grad_fn(
            self._params, self._buffers, self._acc_grads,
            np.int32(self._step), self._rng_key, in_arrs, lab_arrs)
        # this path computes no global grad norm: clear the fused-step
        # value so telemetry never reports a stale one as current
        self.last_grad_norm = None
        self._micro_count += 1
        applied = False
        if apply_update:
            applied = self._apply_accum()
        return loss_v, outs, applied

    def _apply_accum(self):
        if not self._micro_count or self._acc_grads is None:
            return False
        lr = np.float32(self._lr_now())
        self._opt_step += 1
        self._params, self._opt_state, new_acc = self._apply_fn(
            self._params, self._opt_state, self._acc_grads,
            np.float32(self._micro_count), lr, np.int32(self._opt_step))
        # under donation, new_acc is the zeroed (still correctly
        # ZeRO-sharded) accumulator aliased in place — keep it so the
        # next window starts without re-allocating; without donation
        # apply_step returns None (retention would just pin an extra
        # param-size fp32 buffer)
        self._acc_grads = new_acc
        self._micro_count = 0
        if self.donate:
            self.network.load_raw_state(self._params, self._buffers)
        return True

    def flush_accum(self):
        """Apply any partially-accumulated window (epoch end, early stop,
        num_iters cutoff) so tail microbatch gradients are never dropped
        or leaked into the next fit. Returns True if an update ran.

        Also drops the retained zeroed accumulator: at a flush boundary
        (fit exit, path switch) training may be followed by eval/serving,
        where a param-size fp32 buffer held for reuse is pure overhead."""
        applied = self._apply_accum()
        self._acc_grads = None
        return applied

    def reset_accum_window(self):
        """Drop any half-accumulated gradient window WITHOUT applying it.
        Call after restoring params/opt state from a checkpoint: grads
        computed against the pre-restore parameters must not be averaged
        into the first post-restore update."""
        self._acc_grads = None
        self._micro_count = 0

    def _build_eval_fn(self):
        network = self.network
        loss_layer = self.loss

        def eval_step(params, buffers, inputs, labels):
            outs = functional_call(network, params, buffers, *inputs)
            outs_t = outs if isinstance(outs, (list, tuple)) else [outs]
            l_arr = None
            if loss_layer is not None and labels:
                with jax.named_scope("loss"):
                    l = loss_layer(*outs_t, *labels)
                l_arr = (l._value if isinstance(l, Tensor) else l).astype(jnp.float32)
            return _unwrap(outs), l_arr

        return self.tracer.jit("eval_step", eval_step)

    # ------------------------------------------------------------------
    def _lr_now(self):
        opt = self.optimizer
        if opt is None:
            return 0.0
        lr = opt._lr
        if isinstance(lr, LRScheduler):
            return float(lr())
        return float(lr)

    def train_batch(self, inputs, labels):
        """One optimizer step. inputs/labels: lists of Tensors/arrays."""
        if self.guard is not None:
            return self._train_batch_guarded(inputs, labels)
        if self.network.training is False:
            self.network.train()
        self._ensure_opt_state()
        if self._micro_count:
            # a pending accumulation window must not leak into (or be
            # invalidated by) a fused step — apply the partial window now;
            # flush_accum (not _apply_accum) so the path switch also
            # drops the retained accumulator buffer
            self.flush_accum()
        if self._train_fn is None:
            self._train_fn = self._build_train_fn()
        in_arrs = self._shard_batch(_unwrap(list(inputs)))
        lab_arrs = self._shard_batch(_unwrap(list(labels)))
        # host-side numpy scalars: they ride along with the execute call
        # instead of costing standalone device ops each step
        lr = np.float32(self._lr_now())
        self._step += 1
        self._opt_step += 1
        (self._params, self._buffers, self._opt_state, loss_v,
         gnorm, outs) = self._train_fn(
            self._params, self._buffers, self._opt_state,
            lr, np.int32(self._step),
            np.int32(self._opt_step), self._rng_key,
            in_arrs, lab_arrs)
        self.last_grad_norm = gnorm if self._train_fn_collects_gnorm \
            else None
        # donation deleted the old param/buffer jax arrays: rebind the live
        # Parameter tensors to the new ones so direct network access (eager
        # forward, state_dict, .numpy()) stays valid mid-fit
        if self.donate:
            self.network.load_raw_state(self._params, self._buffers)
        return loss_v, outs

    def _train_batch_guarded(self, inputs, labels):
        """train_batch through the TrainGuard: guarded step dispatch
        with transient-error retry, host-synced finite flag, skip/
        snapshot/rollback bookkeeping. Returns (loss, outs) like
        train_batch — on a skipped step the loss is the (non-finite)
        observed value and model state is unchanged."""
        from ..resilience import faults
        from ..resilience.retry import call_with_retries
        guard = self.guard
        if self.network.training is False:
            self.network.train()
        self._ensure_opt_state()
        if self._micro_count:
            self.flush_accum()
        if self._train_fn is None:
            self._train_fn = self._build_train_fn()
        if guard.scaler is not None and self._scaler_state is None:
            from ..amp import GradScaler
            self._scaler_state = GradScaler.functional_init(
                guard.scaler._scale)
        guard.before_first_step(self)
        in_arrs = self._shard_batch(_unwrap(list(inputs)))
        lab_arrs = self._shard_batch(_unwrap(list(labels)))
        lr = np.float32(self._lr_now())
        self._step += 1
        step = self._step
        # injection seams: NaN-poison scalar rides the stable step
        # signature (no recompile); slow/dispatch faults drill the
        # watchdog + retry paths
        fault_scale = np.float32(faults.nan_scale(step))
        faults.maybe_sleep("slow_step", step)

        def dispatch():
            # injected transients fire BEFORE the execute call, so a
            # retry re-submits un-consumed (un-donated) buffers
            faults.maybe_raise("dispatch_error", step)
            return self._train_fn(
                self._params, self._buffers, self._opt_state,
                self._scaler_state, lr, np.int32(step),
                np.int32(self._opt_step + 1), self._rng_key,
                fault_scale, in_arrs, lab_arrs)

        from ..resilience.retry import retryable_for
        (self._params, self._buffers, self._opt_state,
         self._scaler_state, loss_v, ok_flag, gnorm,
         outs) = call_with_retries(
            dispatch, retries=guard.retries,
            retryable=retryable_for(self.donate),
            base_delay=guard.retry_base_delay, stats=guard.retry_stats)
        self.last_grad_norm = gnorm if self._train_fn_collects_gnorm \
            else None
        # ONE host sync for the flag (Model.train_batch syncs the loss
        # anyway); the tentative opt_step+1 the step saw is only
        # committed on a good step, so skips never advance Adam's bias
        # correction
        ok = bool(np.asarray(ok_flag))
        if ok:
            self._opt_step += 1
        if self.donate:
            self.network.load_raw_state(self._params, self._buffers)
        guard.after_step(self, ok)
        return loss_v, outs

    def train_batch_multi(self, inputs, labels, lr_values=None):
        """Run K optimizer steps in ONE device dispatch: inputs/labels
        are lists of STACKED arrays [K, batch, ...] and the K steps run
        inside a compiled lax.scan.

        TPU-native perf lever: each dispatch to a (remote) backend costs
        ~ms of latency; a K-step scan amortizes it K-fold. Semantics match K train_batch calls exactly (per-step rng
        folding, update counters), with the learning rate CONSTANT
        across the window unless lr_values [K] supplies a schedule; the
        LR scheduler object is advanced by the caller per update as
        usual. A pending gradient-accumulation window is flushed first.
        Returns (losses [K], None) — per-step model outputs are not
        materialized (that would double-compute the last forward); use
        train_batch when outputs/metrics are needed."""
        if self.guard is not None:
            raise ValueError(
                "TrainGuard and train_batch_multi are mutually "
                "exclusive: the guarded step's signature (fault scalar,"
                " scaler state, finite flag) does not fit the K-step "
                "scan closure. Use train_batch, or detach the guard "
                "(engine.guard = None).")
        if self.network.training is False:
            self.network.train()
        self._ensure_opt_state()
        if self._micro_count:
            self.flush_accum()
        if self._train_fn is None:
            self._train_fn = self._build_train_fn()
        in_arrs = self._shard_batch_stacked(_unwrap(list(inputs)))
        lab_arrs = self._shard_batch_stacked(_unwrap(list(labels)))
        lead = {a.shape[0] for a in jax.tree_util.tree_leaves(
            (in_arrs, lab_arrs)) if hasattr(a, "shape") and a.ndim >= 1}
        if len(lead) != 1:
            # validate BEFORE touching counters: a failed call must not
            # skew _step/_opt_step (rng folds + Adam bias correction)
            raise ValueError(
                f"stacked inputs/labels disagree on K: {sorted(lead)}")
        k = int(next(iter(lead)))
        if lr_values is None:
            lrs = np.full((k,), self._lr_now(), np.float32)
        else:
            lrs = np.asarray(lr_values, np.float32)
            if lrs.shape != (k,):
                raise ValueError(f"lr_values must have shape ({k},)")
        # cache key includes the train_fn identity: any site that
        # rebuilds _train_fn (resume/re-placement) invalidates these
        # closures implicitly, with no second attribute to remember
        cache_key = (k, id(self._train_fn))
        multi = self._multi_fns.get(cache_key)
        if multi is None:
            fn = self._train_fn

            def multi_step(params, buffers, opt_state, lrs, step0,
                           opt_step0, rng, ins, labs):
                def body(carry, xs):
                    p, b, s = carry
                    i, lr_i, xi, yi = xs
                    p, b, s, loss_i, _gn, _ = fn(
                        p, b, s, lr_i, step0 + i, opt_step0 + i, rng,
                        list(xi), list(yi))
                    return (p, b, s), loss_i
                (p, b, s), losses = jax.lax.scan(
                    body, (params, buffers, opt_state),
                    (jnp.arange(k, dtype=jnp.int32), lrs,
                     tuple(ins), tuple(labs)))
                # one extra forward for the last step's outputs would
                # double-compute; callers needing per-step outputs
                # should use train_batch
                return p, b, s, losses

            multi = self.tracer.jit(
                "train_step_multi", multi_step,
                donate_argnums=(0, 1, 2) if self.donate else (),
                **self._pin_state_layout(self._params, self._buffers,
                                         self._opt_state, rest=1))
            if len(self._multi_fns) > 8:
                self._multi_fns.clear()
            self._multi_fns[cache_key] = multi
        step0, opt_step0 = self._step + 1, self._opt_step + 1
        self._step += k
        self._opt_step += k
        (self._params, self._buffers, self._opt_state, losses) = multi(
            self._params, self._buffers, self._opt_state, lrs,
            np.int32(step0), np.int32(opt_step0), self._rng_key,
            in_arrs, lab_arrs)
        # the scan discards per-step grad norms: clear the fused-step
        # value so telemetry never reports a stale one as current
        self.last_grad_norm = None
        if self.donate:
            self.network.load_raw_state(self._params, self._buffers)
        return losses, None

    def _shard_batch_stacked(self, arrs):
        """dp placement for [K, batch, ...] stacks: batch is dim 1
        (tree-mapped like _shard_batch, so nested containers work)."""
        if self.mesh is None or "dp" not in self.mesh.axis_names:
            return arrs
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(self.mesh, PartitionSpec(None, "dp"))
        ndp = self.mesh.shape["dp"]

        def place(a):
            if not (hasattr(a, "ndim") and a.ndim >= 2):
                return a
            if a.shape[1] % ndp:
                raise ValueError(
                    f"stacked batch dim {a.shape[1]} not divisible by "
                    f"the dp mesh axis ({ndp})")
            return jax.device_put(a, sh)
        return jax.tree_util.tree_map(place, arrs)

    def eval_batch(self, inputs, labels=()):
        if self.network.training:
            self.network.eval()
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn()
        # shard the eval batch over dp exactly like train_batch — else
        # Model.evaluate/predict on a dp mesh silently runs replicated
        outs, loss_v = self._eval_fn(
            self._params, self._buffers,
            self._shard_batch(_unwrap(list(inputs)), allow_ragged=True),
            self._shard_batch(_unwrap(list(labels)), allow_ragged=True))
        return loss_v, outs

    def predict_batch(self, inputs):
        _, outs = self.eval_batch(inputs, ())
        return outs

    def _apply_zero_placement(self):
        """GroupSharded/ZeRO placement (stage 1: opt state; stage 3: +
        params). Must precede _build_train_fn so the grad sharding
        constraints are computed from the placed params."""
        gs = getattr(self.optimizer, "_group_sharded", None)
        if gs is None or self._opt_state is None:
            return
        from ..distributed.fleet.sharding import shard_tree
        self._opt_state = shard_tree(self._opt_state, gs.mesh, gs.axis)
        if gs.shard_params:
            self._params = shard_tree(self._params, gs.mesh, gs.axis)
            self.network.load_raw_state(self._params, self._buffers)

    # state ------------------------------------------------------------
    def opt_state_dict(self):
        return {"state": self._opt_state, "step": self._step,
                "opt_step": self._opt_step}

    def load_opt_state_dict(self, d):
        self._opt_state = self._on_mesh(d["state"])
        self._step = d["step"]
        # older checkpoints predate the separate update counter; the
        # fused path kept it == step
        self._opt_step = d.get("opt_step", d["step"])
        self.reset_accum_window()
        if self.guard is not None:
            # snapshots taken before the restore are now the WRONG
            # last-good state — a rollback must never resurrect them;
            # the ring reseeds from the restored state on first step
            self.guard.ring.clear()
        # resume path: re-apply ZeRO placement and rebuild the compiled
        # programs so baked-in grad constraints / frozen-param constants
        # match the (re)placed params — the accumulation programs bake
        # the same state as the fused one
        if getattr(self.optimizer, "_group_sharded", None) is not None:
            self._apply_zero_placement()
            self._train_fn = None
            self._multi_fns = {}
            self._grad_fn = None
            self._apply_fn = None
