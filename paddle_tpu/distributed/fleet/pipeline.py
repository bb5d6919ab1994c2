"""Pipeline parallelism over the 'pp' mesh axis.

ref parity: python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py
(PipelineParallel with FThenB / 1F1B microbatch schedules and p2p.send/recv
of activations between stage ranks) and meta_parallel/parallel_layers/
pp_layers.py (PipelineLayer / LayerDesc stage partitioning).

TPU-native design — the whole pipeline is ONE jitted SPMD program:

- stages live along the 'pp' axis of the device Mesh; stage parameters are
  stacked on a leading [pp] dim and shard_map hands each device its slice
  (where the reference materialises only the local stage's Layers per rank).
- microbatches march through a lax.scan over T = n_micro + S - 1 ticks;
  activations hop stage i -> i+1 by lax.ppermute over ICI (the reference's
  p2p send/recv pairs). The S-1 extra ticks are the pipeline bubble —
  identical cost shape to the reference's warmup/drain; drained stages
  compute on zeros (SPMD lock-step means the FLOPs happen either way).
- num_virtual_pipeline_stages / pipeline_apply(n_virtual=v) selects the
  interleaved schedule: each device holds v chunks (global stage c*S + s)
  and activations ride a ring ppermute, shrinking the bubble fraction to
  (S-1)/(n_micro*v + S - 1) — see interleaved_schedule/pipeline_cost for
  the tick math, which is what the CPU accounting tests pin down.
- backward is jax.grad *through* the scan: ppermute transposes to the
  reverse shift. Schedule note: this compiles the FThenB dataflow; the
  reference's 1F1B is an op-ORDERING policy for memory, which under XLA
  belongs to the compiler's scheduler — its memory benefit is delivered
  here by per-microbatch jax.checkpoint (activations for at most one
  microbatch per stage are live at a time), not by hand-ordering ops.
- all other mesh axes (dp/mp/sp) stay *auto*: GSPMD keeps partitioning the
  batch and the tensor-parallel weights inside each stage, so dp x mp x pp
  hybrids compose with no extra code.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...nn.layer import Layer


def stack_stage_params(per_stage: Sequence):
    """Stack S equal-structure per-stage pytrees on a new leading [pp] dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def unstack_stage_params(stacked, n_stages: int):
    return [jax.tree_util.tree_map(lambda a: a[i], stacked)
            for i in range(n_stages)]


def _varying(x, axis):
    """Type a scan carry as varying over the manual `axis` (the ticks
    write per-stage values into it)."""
    return jax.lax.pcast(x, (axis,), to="varying")


def _pipeline_local(stage_params, x, *, stage_fn, n_stages, n_micro,
                    axis, remat):
    """Runs INSIDE shard_map over `axis`: stage_params leaves are the
    local [1, ...] shard, x is the full (pp-replicated) batch."""
    stage = jax.lax.axis_index(axis)
    local = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    mb = x.shape[0] // n_micro
    micro = x.reshape((n_micro, mb) + x.shape[1:])
    f = jax.checkpoint(stage_fn) if remat else stage_fn

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    n_ticks = n_micro + n_stages - 1

    def tick(carry, t):
        act, outbuf = carry
        # past the last microbatch stage 0 feeds zeros (the drain ticks);
        # their outputs are never harvested
        inj = jnp.where(t < n_micro, micro[jnp.minimum(t, n_micro - 1)],
                        jnp.zeros_like(micro[0]))
        act = jnp.where(stage == 0, inj, act)
        out = f(local, act)
        oidx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        keep = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
        outbuf = outbuf.at[oidx].set(
            jnp.where(keep, out, outbuf[oidx]))
        nxt = jax.lax.ppermute(out, axis, fwd_perm) if n_stages > 1 else out
        return (nxt, outbuf), None

    act0 = _varying(jnp.zeros_like(micro[0]), axis)
    outbuf0 = _varying(jnp.zeros_like(micro), axis)
    (_, outbuf), _ = jax.lax.scan(tick, (act0, outbuf0),
                                  jnp.arange(n_ticks))
    # replicate the last stage's outputs to every pp rank so downstream
    # (loss, metrics) sees a pp-consistent value
    outbuf = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outbuf, jnp.zeros_like(outbuf)),
        axis)
    return outbuf.reshape((n_micro * mb,) + x.shape[1:])


def interleaved_schedule(u: int, p: int, v: int):
    """The interleaved ('virtual pipeline') schedule as pure math.

    A device at tick t works on diagonal u = t - device_index; the same
    diagonal maps to the same (microbatch, chunk) on every device, so a
    microbatch's chunk-c pass flows device 0 -> p-1 on consecutive
    ticks, then wraps (ring ppermute) to device 0 as chunk c+1.
    Microbatches run in groups of p; a device's local timeline tiles one
    group's p*v chunk-slots back to back, so it is never double-booked.
    Returns (micro_index, chunk_index); micro_index may be out of
    [0, n_micro) — such slots are drain/warmup bubble.

    ref parity: Megatron-style interleaved schedule of
    fleet.meta_parallel pp_utils (num_virtual_pipeline_stages); total
    ticks = ceil(m/p)*p*v + p - 1, i.e. bubble (p-1)/(m*v + p - 1) of
    total at p | m — v times smaller than FThenB's (p-1)/(m + p - 1).
    """
    pv = p * v
    k, q = divmod(u, pv)            # group, phase (floor semantics)
    return k * p + (q % p), q // p


def pipeline_cost(n_stages: int, n_micro: int, n_virtual: int = 1):
    """Tick/FLOP accounting for the compiled schedules (CPU-checkable —
    the hardware-independent part of the pipeline's cost model).

    Returns ticks (scan length), chunk_time (fraction of a full stage
    per tick), total_time in stage-time units, ideal_time, and
    bubble_fraction = 1 - ideal/total."""
    p, v, m = n_stages, n_virtual, n_micro
    if v == 1:
        ticks = m + p - 1
    else:
        groups = -(-m // p)
        ticks = groups * p * v + p - 1
    chunk_time = 1.0 / v
    total = ticks * chunk_time
    ideal = float(m)                # m stage-times per device
    return {"ticks": ticks, "chunk_time": chunk_time,
            "total_time": total, "ideal_time": ideal,
            "bubble_fraction": 1.0 - ideal / total}


def _pipeline_local_interleaved(stage_params, x, *, stage_fn, n_stages,
                                n_chunks, n_micro, axis, remat):
    """Interleaved virtual-stage schedule; runs INSIDE shard_map over
    `axis`. stage_params leaves are the local [v, ...] chunk shards
    (device s holds global stages c*p + s, c in [0, v))."""
    p, v, m = n_stages, n_chunks, n_micro
    s = jax.lax.axis_index(axis)
    mb = x.shape[0] // m
    micro = x.reshape((m, mb) + x.shape[1:])
    f = jax.checkpoint(stage_fn) if remat else stage_fn

    ring = [(i, (i + 1) % p) for i in range(p)]
    # ONE formula governs the compiled scan length and the CPU-tested
    # cost model — they must not drift apart
    n_ticks = pipeline_cost(p, m, v)["ticks"]
    pv = p * v

    def tick(carry, t):
        act, outbuf = carry
        u = t - s                   # diagonal; <0 during this device's warmup
        k = jnp.floor_divide(u, pv)
        q = jnp.mod(u, pv)          # floor semantics keep q >= 0
        c = q // p                  # chunk this device runs now
        j = k * p + (q % p)         # microbatch on the diagonal
        live = jnp.logical_and(j >= 0, j < m)
        jc = jnp.clip(j, 0, m - 1)
        chunk = jax.tree_util.tree_map(
            lambda a: jnp.take(a, jnp.clip(c, 0, v - 1), axis=0),
            stage_params)
        inject = jnp.logical_and(jnp.logical_and(s == 0, c == 0), live)
        act = jnp.where(inject, micro[jc], act)
        out = f(chunk, act)
        harvest = jnp.logical_and(
            jnp.logical_and(s == p - 1, c == v - 1), live)
        outbuf = outbuf.at[jc].set(jnp.where(harvest, out, outbuf[jc]))
        nxt = jax.lax.ppermute(out, axis, ring) if p > 1 else out
        return (nxt, outbuf), None

    act0 = _varying(jnp.zeros_like(micro[0]), axis)
    outbuf0 = _varying(jnp.zeros_like(micro), axis)
    (_, outbuf), _ = jax.lax.scan(tick, (act0, outbuf0),
                                  jnp.arange(n_ticks))
    outbuf = jax.lax.psum(
        jnp.where(s == p - 1, outbuf, jnp.zeros_like(outbuf)), axis)
    return outbuf.reshape((m * mb,) + x.shape[1:])


def pipeline_apply(mesh, stage_params, x, stage_fn: Callable, *,
                   n_micro: int, axis: str = "pp", remat: bool = True,
                   n_virtual: int = 1):
    """Run x through the pipeline stages laid over mesh axis `axis`.

    stage_params: pytree whose leaves have leading dim S_total
    (stack_stage_params), where S_total = mesh.shape[axis] * n_virtual;
    stage g's params sit at row g (stage-major).
    stage_fn: (params_one_stage, act) -> act, same act shape in/out
    x: [B, ...] global batch, B % n_micro == 0. Differentiable end to end.
    n_virtual > 1 selects the interleaved schedule (each device holds
    n_virtual chunks; bubble shrinks ~n_virtual-fold — see
    pipeline_cost)."""
    p = mesh.shape[axis]
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"n_micro {n_micro}")
    if n_virtual > 1:
        lead = {a.shape[0] for a in
                jax.tree_util.tree_leaves(stage_params)}
        if lead != {p * n_virtual}:
            # jnp.take would silently clip out-of-range rows — a wrong
            # stack size must fail loudly, not duplicate stages
            raise ValueError(
                f"stage_params leading dim must be p*n_virtual = "
                f"{p * n_virtual} (p={p} devices x {n_virtual} chunks); "
                f"got {sorted(lead)}")
        # device-major re-rowing: shard_map splits the leading p*v dim
        # contiguously, so device s must own rows [s*v, (s+1)*v) =
        # its chunks (global stages c*p + s) in chunk order
        import numpy as _np
        perm = _np.asarray([c * p + s_ for s_ in range(p)
                            for c in range(n_virtual)])
        stage_params = jax.tree_util.tree_map(
            lambda a: jnp.take(a, perm, axis=0), stage_params)
        local_fn, local_kw = _pipeline_local_interleaved, dict(
            stage_fn=stage_fn, n_stages=p, n_chunks=n_virtual,
            n_micro=n_micro, axis=axis, remat=remat)
    else:
        local_fn, local_kw = _pipeline_local, dict(
            stage_fn=stage_fn, n_stages=p, n_micro=n_micro, axis=axis,
            remat=remat)
    # each rank holds its stage rows; every other mesh axis stays auto
    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params)
    fn = jax.shard_map(
        functools.partial(local_fn, **local_kw), mesh=mesh,
        in_specs=(param_specs, P()), out_specs=P(),
        axis_names=frozenset({axis}), check_vma=False)
    # jitted even when called eagerly: jax 0.9.0's eager shard_map with
    # check_vma=False re-shards outputs over EVERY mesh axis and then
    # rejects its own out_specs for touching the auto ones ("out_specs
    # refers to 'dp'"). Under an enclosing jit this inner one inlines.
    # tpulint: disable-next-line=TRC01
    return jax.jit(fn)(stage_params, x)


class LayerDesc:
    """ref: pp_layers.py LayerDesc — deferred layer construction so each
    stage only materialises its own sublayers."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build(self):
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    def __init__(self, key, layer_cls, *args, shared_weight_attr="weight",
                 **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.key = key
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """ref: pp_layers.py PipelineLayer — takes a flat stack of equal-shape
    blocks and runs them pipelined over the 'pp' mesh axis.

    TPU-native: all blocks are materialised (single controller owns the
    logical model); forward stacks their params and calls pipeline_apply.
    Off-mesh (no 'pp' axis) it runs the blocks sequentially, which is the
    numerical reference for the tests.
    """

    def __init__(self, layers, num_stages=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=1,
                 num_virtual_pipeline_stages=None, topology=None):
        super().__init__()
        from ...nn.layers_common import LayerList
        built, shared = [], {}
        for l in layers:
            layer = l.build() if isinstance(l, LayerDesc) else l
            if isinstance(l, SharedLayerDesc):
                # ref pp_layers.py: same key => physically tied weight.
                # Later occurrences alias the first's parameter Tensor, so
                # both stages' param trees hold the SAME object and eager
                # backward accumulates both contributions onto it.
                if l.key in shared:
                    setattr(layer, l.shared_weight_attr,
                            getattr(shared[l.key], l.shared_weight_attr))
                else:
                    shared[l.key] = layer
            built.append(layer)
        self.shared_layers = shared
        self.blocks = LayerList(built)
        self.num_stages = num_stages
        self.loss_fn = loss_fn
        self.recompute = bool(recompute_interval)
        self.num_virtual = int(num_virtual_pipeline_stages or 1)
        self._descs = layers

    def _stage_slices(self, n_stages):
        n = len(self.blocks)
        if n % n_stages:
            raise ValueError(
                f"{n} blocks not divisible into {n_stages} equal stages; "
                "equal-structure stages are required for the stacked "
                "pipeline (pad with Identity blocks)")
        per = n // n_stages
        return [list(range(i * per, (i + 1) * per))
                for i in range(n_stages)]

    def forward(self, x, n_micro=None, mesh=None):
        from ...tensor import Tensor
        from ..mesh import get_mesh
        from ...autograd import apply_op
        mesh = mesh or get_mesh()
        if mesh is None or "pp" not in mesh.axis_names or \
                mesh.shape["pp"] == 1:
            for blk in self.blocks:
                x = blk(x)
            return x
        p = mesh.shape["pp"]
        n_stages = (self.num_stages or p) * self.num_virtual
        slices = self._stage_slices(n_stages)
        per = len(slices[0])

        # per-stage trees of the LIVE parameter Tensors — stacking happens
        # inside `run` (jnp.stack is differentiable), so eager backward
        # deposits grads on the blocks' own Parameters, and a weight
        # shared across stages (SharedLayerDesc) appears as one repeated
        # Tensor whose grads accumulate.
        per_stage_t = [[dict(self.blocks[i].named_parameters()) for i in s]
                       for s in slices]
        leaves_t, treedef = jax.tree_util.tree_flatten(
            per_stage_t, is_leaf=lambda t: isinstance(t, Tensor))
        blocks = self.blocks

        def stage_fn(params_list, act):
            from ...nn.layer import functional_call
            for j in range(per):
                out = functional_call(blocks[j], params_list[j], {},
                                      Tensor(act))
                act = out._value if isinstance(out, Tensor) else out
            return act

        def run(arr, *leaves):
            per_stage = jax.tree_util.tree_unflatten(treedef, leaves)
            stacked = stack_stage_params(per_stage)
            return pipeline_apply(mesh, stacked, arr, stage_fn,
                                  n_micro=n_micro or p,
                                  remat=self.recompute,
                                  n_virtual=self.num_virtual)

        if isinstance(x, Tensor):
            return apply_op(run, x, *leaves_t)
        return run(x, *[t._value for t in leaves_t])
