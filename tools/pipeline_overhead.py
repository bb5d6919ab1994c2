"""Single-chip pipeline schedule-overhead A/B.

The interleaved pipeline's bubble win — (S-1)/(m*v+S-1) vs FThenB's
(S-1)/(m+S-1) — is CPU-pinned tick *math* (pipeline_cost); what the
cost model ignores is the compiled schedule's per-tick overhead: the
lax.scan step, the out-buffer dynamic-update-slice, the warmup/drain
predication, and (interleaved only) the per-tick jnp.take gather of the
chunk's params from the stacked [v, ...] store. One chip can bound all
of those: with p=1 the ppermute hop drops out, so

    overhead/tick = (T_schedule - T_sequential) / n_ticks

isolates exactly the machinery the cost model assumes free. A ring hop
is the one term this cannot see; the multi-chip dryrun certifies that
path's correctness, and its cost is ICI-bandwidth math, not schedule
machinery.

ref parity: fleet.meta_parallel PipelineParallel schedules; the
reference's analogous question is p2p/schedule overhead per microbatch
vs GPU compute time.

Emits one JSON line:
  {"metric": "pipeline_tick_overhead", "sequential_ms": ..,
   "fthenb": {...}, "interleaved_v2": {...}, ...}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def stage_chain(n):
    """stage_fn(params, act): act through n MLP blocks (params is a
    list of n {'up','down'} dicts). ≈ a transformer block's MLP — two
    [D,4D]/[4D,D] matmuls + residual + rms-normish elementwise —
    realistic per-tick compute."""
    import jax
    import jax.numpy as jnp

    def fn(params, x):
        for w in params:
            h = jnp.einsum("bd,df->bf", x, w["up"])
            h = jax.nn.gelu(h)
            h = jnp.einsum("bf,fd->bd", h, w["down"])
            x = x + h
            x = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + 1e-6)
        return x
    return fn


def measure(fn, *args, reps=5, warmup=2):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes on the CPU backend; same code "
                         "path")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--n-micro", type=int, default=8)
    ap.add_argument("--layers-per-stage", type=int, default=4)
    args = ap.parse_args()

    if args.smoke:
        import _cpu_env  # noqa: F401  (forces cpu before jax import)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    import numpy as np

    from paddle_tpu.distributed.fleet.pipeline import (
        pipeline_apply, pipeline_cost, stack_stage_params)

    if not args.smoke and jax.default_backend() != "tpu":
        sys.exit("pipeline_overhead times the device and needs a TPU; "
                 "--smoke runs the CPU plumbing check (not device numbers)")
    B, D = (16, 64) if args.smoke else (64, 2048)
    B = args.batch or B
    D = args.d_model or D
    m = args.n_micro
    L = args.layers_per_stage  # layers in ONE stage (v=2 splits them)
    if L % 2:
        sys.exit("--layers-per-stage must be even (v=2 splits the stage)")
    dtype = jnp.float32 if args.smoke else jnp.bfloat16

    key = jax.random.PRNGKey(0)
    layers = []
    for _ in range(L):
        key, k1, k2 = jax.random.split(key, 3)
        layers.append({
            "up": jax.random.normal(k1, (D, 4 * D), dtype) * (D ** -0.5),
            "down": (jax.random.normal(k2, (4 * D, D), dtype)
                     * ((4 * D) ** -0.5)),
        })
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D), dtype)
    mesh = Mesh(np.array(jax.devices()[:1]), ("pp",))

    # sequential reference: same L layers, full batch, no schedule
    # machinery — what XLA compiles when there is no pipeline
    print(f"[pipeline_overhead] B={B} D={D} m={m} L={L} "
          f"backend={jax.default_backend()}", file=sys.stderr, flush=True)
    seq = jax.jit(stage_chain(L))
    t_seq = measure(seq, layers, x)
    results = {"sequential_ms": round(t_seq * 1e3, 3)}
    print(f"[pipeline_overhead] sequential {t_seq*1e3:.3f} ms",
          file=sys.stderr, flush=True)

    # FThenB (v=1): 1 stage x m microbatches (ticks = m); interleaved
    # (v=2): 2 chunks of L/2 layers (ticks = 2m + per-tick param take)
    half = L // 2
    variants = (
        ("fthenb", 1, stack_stage_params([layers]), stage_chain(L)),
        ("interleaved_v2", 2,
         stack_stage_params([layers[:half], layers[half:]]),
         stage_chain(half)),
    )
    ref = seq(layers, x)
    for name, v, sp, sfn in variants:
        fn = jax.jit(lambda p, xx, _sfn=sfn, _v=v: pipeline_apply(
            mesh, p, xx, _sfn, n_micro=m, remat=False, n_virtual=_v))
        t = measure(fn, sp, x)
        print(f"[pipeline_overhead] {name} {t*1e3:.3f} ms",
              file=sys.stderr, flush=True)
        ticks = pipeline_cost(1, m, v)["ticks"]
        got = fn(sp, x)
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                    - got.astype(jnp.float32))))
        results[name] = {
            "ms": round(t * 1e3, 3),
            "ticks": ticks,
            "overhead_ms_per_tick": round((t - t_seq) / ticks * 1e3, 4),
            "overhead_frac": round((t - t_seq) / t_seq, 4),
            "max_abs_err_vs_sequential": err,
        }

    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name in ("fthenb", "interleaved_v2"):
        if results[name]["max_abs_err_vs_sequential"] > tol:
            print(f"[pipeline_overhead] {name} DIVERGES from sequential "
                  f"by {results[name]['max_abs_err_vs_sequential']}",
                  file=sys.stderr, flush=True)
            print(json.dumps({"metric": "pipeline_tick_overhead",
                              "value": None, "unit": "ms/tick",
                              "vs_baseline": None,
                              "error": f"{name} diverges", **results}),
                  flush=True)
            return 1
    out = {"metric": "pipeline_tick_overhead",
           "value": results["interleaved_v2"]["overhead_ms_per_tick"],
           "unit": "ms/tick", "vs_baseline": None,
           "batch": B, "d_model": D, "n_micro": m,
           "layers_per_stage": L, "backend": jax.default_backend(),
           **results}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
