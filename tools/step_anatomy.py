"""Step anatomy: where a training step's time goes (fwd / bwd / opt).

Times three separately-jitted programs on the bench config:
  loss        = forward + criterion                 (fwd)
  grad        = value_and_grad of the same          (fwd + bwd)
  train_batch = the Engine's full step              (+ clip/opt/amp)
and reports seconds plus the deltas (bwd = grad - loss, opt+misc =
full - grad). The 2026-07-30 anatomy (fwd 78.6 ms / bwd 143.5 ms /
AdamW 22.8 ms at gpt3-345M b8 s1024) was produced by hand; this makes
it a one-command campaign stage so each lever (fused qkv, scan layers)
can be localized to the phase it moves.

Usage: python tools/step_anatomy.py [--model gpt|gpt-1.3b] [--batch N]
         [--seq N] [--fused-qkv] [--scan-layers] [--smoke]
Prints one JSON line. ref parity: paddle.profiler's kernel breakdown.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("gpt", "gpt-1.3b"), default="gpt")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=10,
                    help="timed iterations per program (>= 1)")
    ap.add_argument("--fused-qkv", action="store_true")
    ap.add_argument("--fused-ln", action="store_true")
    ap.add_argument("--chunked-ce", type=int, default=0)
    ap.add_argument("--scan-layers", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")
    if args.smoke:
        import _cpu_env  # noqa: F401
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import build_engine
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.tensor import Tensor

    if not args.smoke and jax.default_backend() != "tpu":
        sys.exit("step_anatomy times the device and needs a TPU; --smoke "
                 "runs the CPU plumbing check (not device numbers)")
    if args.smoke:
        cfg, batch, seq = "gpt-tiny", 2, 64
    elif args.model == "gpt-1.3b":
        cfg, batch, seq = "gpt3-1.3B", 4, 1024
    else:
        cfg, batch, seq = "gpt3-345M", 8, 1024
    batch = args.batch or batch
    seq = args.seq or seq
    big = args.model == "gpt-1.3b" and not args.smoke
    eng = build_engine(cfg, batch, seq, amp=not args.smoke,
                      recompute=big, moment_dtype="bfloat16" if big else None,
                      scan_layers=args.scan_layers,
                      fused_qkv=args.fused_qkv, fused_ln=args.fused_ln,
                      chunked_ce=args.chunked_ce)
    model, crit = eng.network, eng.loss
    params, buffers = model.raw_state()
    rng = np.random.default_rng(0)
    vocab = model.config.vocab_size
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    amp_dt = None if args.smoke else jnp.bfloat16

    # the Engine's own forward+loss closure (single source of truth for
    # the AMP cast / buffer-dtype-restore) so the fwd and fwd+bwd
    # programs measure EXACTLY the computation inside the full step
    inner = Engine._make_loss_fn(model, crit, amp_dt, {}, buffers,
                                 [Tensor(ids)], [Tensor(labels)],
                                 jax.random.PRNGKey(0))

    def scalar_loss(p):
        return inner(p)[0]

    fwd = jax.jit(scalar_loss)
    grad = jax.jit(jax.value_and_grad(scalar_loss))

    def timeit(fn, sync):
        sync(fn())                      # compile + warm
        sync(fn())
        t0 = time.perf_counter()
        for _ in range(args.steps):
            r = fn()
        sync(r)
        return (time.perf_counter() - t0) / args.steps

    t_fwd = timeit(lambda: fwd(params), lambda r: float(r))
    t_grad = timeit(lambda: grad(params),
                    lambda r: float(r[0]))
    # full engine step LAST (it donates params — they are consumed)
    loss, _ = eng.train_batch([ids], [labels])    # compile
    loss.block_until_ready()  # the warm step must finish before the
    # timer starts
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss, _ = eng.train_batch([ids], [labels])
    loss.block_until_ready()
    t_full = (time.perf_counter() - t0) / args.steps
    print(json.dumps({
        "metric": "gpt_step_anatomy", "config": cfg,
        "batch": batch, "seq": seq,
        "fused_qkv": args.fused_qkv, "scan_layers": args.scan_layers,
        "fused_ln": args.fused_ln, "chunked_ce": args.chunked_ce,
        "fwd_ms": round(t_fwd * 1e3, 2),
        "fwd_bwd_ms": round(t_grad * 1e3, 2),
        "full_step_ms": round(t_full * 1e3, 2),
        "bwd_ms": round((t_grad - t_fwd) * 1e3, 2),
        "opt_misc_ms": round((t_full - t_grad) * 1e3, 2),
        "tokens_per_sec": round(batch * seq / t_full, 1),
        "backend": jax.default_backend(),
    }), flush=True)


if __name__ == "__main__":
    main()
