"""chip_smoke.py — the standing proof that paddle_tpu starts on the chip.

    python chip_smoke.py                  # every phase, one process
    python chip_smoke.py --phases kernels,serve

Drives the two main paths through the entry points a user calls, at the
full published width of models the repo ships, with seeded random
weights and seeded inputs generated here (no dataset, no network):

- fit      README quickstart: paddle.Model(LeNet()).fit on synthetic
           MNIST, evaluate, save/load round trip.
- kernels  every kernel under paddle_tpu/ops/pallas/ compiled natively
           at the shapes the full-width models use, against its
           jax.numpy reference.
- train    GPTForCausalLM gpt3-345M, b8 x s1024, bf16 AMP, flash
           attention, AdamW, through Engine.train_batch.
- serve    ServingEngine over gpt2-en (page_size 128, max_seq_len 1024,
           8 slots, bf16 cache): warmup, then a 16-request wave, on the
           default attention path (plain XLA, the pool read in place)
           and with the paged Pallas kernel.
- mesh     (>= 4 devices) gpt3-345M width, depth cut to 8 layers, on
           dp2 x mp2, ZeRO over dp4 and the pipelined trunk on mp2 x pp2,
           one process driving all chips, against its own one-chip step.

The script refuses to run anywhere but a TPU: it exits non-zero before
doing any work when `jax.devices()[0].platform != "tpu"`. A failing
phase is recorded with its traceback and the run exits 1; nothing is
downgraded to a pass and no kernel check falls back to a reference.
The last stdout line is the verdict, exactly
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`,
with the device as jax reports it. The line before it,
`chip_smoke: report {...}`, is one JSON object with the versions and,
per phase, ok / wall seconds / compile seconds.

One process owns the chip: nothing here starts a child process.
"""
from __future__ import annotations

import argparse
import faulthandler
import functools
import gc
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

PHASES = ("fit", "kernels", "train", "serve", "mesh")

# whole-run ceiling (the driver allows 1200 s): past it every thread's
# stack is dumped and the process exits, so a hung compile names itself
DEADLINE_S = 1150

# normalised max error |got - want|_inf / |want|_inf tolerated per
# storage dtype. bf16 keeps 8 mantissa bits (2^-8 = 4e-3 per rounding,
# a few roundings deep); a kernel that masks, pages or tiles wrongly is
# off by O(1).
TOL = {"float32": 1e-2, "bfloat16": 5e-2, "int8": 5e-2}
# |first-step loss (mesh) - first-step loss (one chip)|: same seed, same
# batch, bf16 compute with a different reduction order (loss ~ 11)
MESH_LOSS_ATOL = 5e-2

SIZES = {
    "fit": dict(batch=256, epochs=2),           # the README quickstart
    "train": dict(cfg="gpt3-345M", batch=8, seq=1024, steps=5),
    "serve": dict(cfg="gpt2-en", page_size=128, max_seq_len=1024, slots=8,
                  requests=16, prompt_lo=64, prompt_hi=700, new_tokens=64,
                  # serve-lfm2-closed64's expert layer at a decode step:
                  # (token rows, h, m, experts, picks a token)
                  experts=(64, 2048, 1792, 32, 4)),
    "kernels": dict(
        flash=[  # (b, s, h, d, causal, kv_lens, dropout)
            (8, 1024, 16, 64, True, False, 0.0),    # gpt3-345M train
            (8, 1024, 8, 128, True, False, 0.0),    # d=128 heads
            (8, 1024, 12, 64, False, True, 0.0),    # padded encoder batch
            (8, 1024, 16, 64, True, False, 0.1),    # in-kernel dropout
            # serve-axk1-closed32's prefill (heads padded to 256: the tiled
            # forward) and the split backward no cell runs
            (1, 1024, 64, 256, True, True, 0.0),
        ],
        decode=dict(b=8, s=1024, h=12, d=64),       # gpt2-en dense cache
        paged=[  # (b, hkv, g, d, page_size, max_pages)
            (8, 12, 1, 64, 128, 8),                 # gpt2-en serve phase
            (8, 8, 4, 64, 128, 8),                  # GQA, 32 q heads
            (12, 16, 1, 128, 128, 16),              # gpt3-1.3B serve cell
        ],
        # (slots, heads, row width, v_width, page_size, max_pages)
        latent=[(32, 64, 576, 512, 128, 32)],       # serve-axk1-closed32
        ln=dict(rows=8192, hidden=(768, 1024)),
        adamw=(50304, 1024),                        # the embedding leaf
        conv=[  # (m, cin, cout, residual): ResNet-50 b256 bottlenecks
            (256 * 14 * 14, 256, 1024, True),
            (256 * 14 * 14, 1024, 256, False),
            (256 * 28 * 28, 128, 512, True),
            (256 * 7 * 7, 512, 2048, True),
        ]),
    # depth cut from 24: each layout is one cold SPMD compile, and on the
    # four-chip host every second of it is charged four times
    "mesh": dict(cfg="gpt3-345M", layers=8, batch=8, seq=1024, steps=3,
                 n_micro=4),
}


# --------------------------------------------------------------------------
# run-wide instrumentation: compile seconds and the pallas_call log
# --------------------------------------------------------------------------

class CompileClock:
    """Sums jax's own compile events. backend_compile_duration wraps
    compile_or_get_cached, so a persistent-cache hit shows up as a short
    compile plus one cache_hits event."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compile_s, self.compiles, self.cache_hits)


class PallasLog:
    """Records (kernel name, interpret) for every pallas_call built, by
    standing in for jax.experimental.pallas.pallas_call — the call the
    repo's one kernel entry (ops/pallas/_common.py) resolves at call
    time. On a TPU nothing on these paths may be interpreted."""

    def __init__(self):
        from jax.experimental import pallas as pl
        self.calls = []
        real = pl.pallas_call

        def recording(kernel, *args, interpret=False, **kwargs):
            fn = getattr(kernel, "func", kernel)
            self.calls.append((getattr(fn, "__name__", repr(fn)),
                               bool(interpret)))
            return real(kernel, *args, interpret=interpret, **kwargs)

        pl.pallas_call = recording

    def since(self, mark):
        return self.calls[mark:]


def _native_only(calls, what):
    interpreted = sorted({n for n, interp in calls if interp})
    if interpreted:
        raise AssertionError(
            f"{what}: pallas_call built with interpret=True on a TPU: "
            f"{interpreted}")


@jax.jit
def _nerr_on_device(g, w):
    g, w = g.astype(jnp.float32), w.astype(jnp.float32)
    err = jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-6)
    return jnp.where(jnp.all(jnp.isfinite(g)), err, jnp.inf)


def _nerr(got, want):
    """Normalised max error, computed on the device; inf when `got` has
    a non-finite value."""
    return float(_nerr_on_device(jnp.asarray(got), jnp.asarray(want)))


def _tree_nerr(got, want):
    return max(_nerr(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                           jax.tree_util.tree_leaves(want)))


# --------------------------------------------------------------------------
# fit
# --------------------------------------------------------------------------

def phase_fit(sz, ctx):
    import os
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.io import native
    from paddle_tpu.metric import Accuracy
    from paddle_tpu.vision.datasets import MNIST
    from paddle_tpu.vision.models import LeNet

    def build():
        net = LeNet()
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.Adam(1e-3, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(), Accuracy())
        return model

    paddle.seed(42)
    # the DataLoader shuffles with numpy's global generator, which
    # paddle.seed leaves alone (as in the reference); unseeded, accuracy
    # after a short fit moves by tens of points from run to run
    np.random.seed(42)
    prefetcher = "native" if native.native_available() else "python-fallback"
    print(f"fit: DataLoader prefetch served by: {prefetcher}", flush=True)
    model = build()
    model.fit(MNIST(mode="train"), epochs=sz["epochs"],
              batch_size=sz["batch"], verbose=0)
    test = MNIST(mode="test")
    res = model.evaluate(test, batch_size=sz["batch"], verbose=0)
    acc = float(res["acc"])
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "lenet")
        model.save(path)
        twin = build()
        twin.load(path)
        acc2 = float(twin.evaluate(test, batch_size=sz["batch"],
                                   verbose=0)["acc"])
    # two seeded epochs reach ~0.89 (chance is 0.1)
    if not acc > 0.5:
        raise AssertionError(f"fit: accuracy {acc} after "
                             f"{sz['epochs']} epochs, expected > 0.5")
    if acc2 != acc:
        raise AssertionError(f"fit: save/load changed accuracy "
                             f"{acc} -> {acc2}")
    return {"acc": round(acc, 4), "prefetcher": prefetcher}


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _attention_ref(q, k, v, causal, lens, dropout, seed):
    """f32 reference attention. Dropout uses the kernel's own counter
    hash so the same elements are dropped (the denominator comes from
    the un-dropped probabilities, flash-attn v2 order)."""
    from paddle_tpu.ops.pallas.flash_attention import _dropout_keep
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf, kf, vf = (jnp.swapaxes(a, 1, 2).astype(jnp.float32)
                  for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / (d ** 0.5)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s,
                      -jnp.inf)
    if lens is not None:
        s = jnp.where(jnp.arange(sk)[None, None, None, :]
                      < lens[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    if dropout:
        keep = jax.vmap(lambda bh: _dropout_keep(
            jnp.int32(seed), bh, 0, 0, (sq, sk), sq, sk, sk, dropout))(
                jnp.arange(b * h, dtype=jnp.int32)).reshape(b, h, sq, sk)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vf), 1, 2)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _rand_on_device(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _rand(key, shape, dtype, scale=1.0):
    return _rand_on_device(key, tuple(shape), jnp.dtype(dtype).name,
                           float(scale))


def _exact(fn, *args):
    """Run a jnp reference with full-precision matmuls: on a TPU the
    default f32 matmul is a single bf16 pass, which would put the
    reference's own rounding into the comparison."""
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _value_and_grads(fn, args, cot_key):
    """(outputs, grads wrt args) of sum(out * fixed random cotangent),
    jitted — one compile per kernel case."""

    def scalar(*a):
        out = fn(*a)
        leaves = jax.tree_util.tree_leaves(out)
        keys = jax.random.split(cot_key, len(leaves))
        tot = sum(jnp.sum(o.astype(jnp.float32)
                          * jax.random.normal(kk, o.shape, jnp.float32))
                  for o, kk in zip(leaves, keys))
        return tot, out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def phase_kernels(sz, ctx):
    from paddle_tpu.nlp.paged_cache import (LatentCacheSpec,
                                            latent_paged_attention,
                                            paged_attention_ref,
                                            quantize_rows)
    from paddle_tpu.ops.attention import reference_attention
    from paddle_tpu.ops.pallas import conv_bn_act, fused_ln
    from paddle_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _fit_block, flash_attention,
        flash_decode, tile_counts)
    from paddle_tpu.ops.pallas.flash_decode import paged_flash_decode
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update

    log = ctx["pallas"]
    results = []
    key = jax.random.PRNGKey(0)

    def case(name, dtype, run, **facts):
        """run() -> normalised error. A compiler refusal or a mismatch is
        recorded and the remaining cases still run; the phase fails at
        the end if any case did."""
        mark = len(log.calls)
        t0 = time.perf_counter()
        row = {"kernel": name, "dtype": dtype, "tol": TOL[dtype], **facts}
        try:
            row["err"] = run()
            calls = log.since(mark)
            _native_only(calls, name)
            if not calls:
                raise AssertionError(
                    "no pallas_call was built — the entry took its jnp "
                    "fallback at this shape")
            row["ok"] = bool(row["err"] <= TOL[dtype])
        except Exception as e:  # noqa: BLE001 — recorded, phase fails below
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:1500]
            traceback.print_exc()
        row["seconds"] = round(time.perf_counter() - t0, 2)
        print(f"kernels: {json.dumps(row)}", flush=True)
        results.append(row)
        gc.collect()

    # -- flash attention fwd + bwd ---------------------------------------
    for i, (b, s, h, d, causal, use_lens, drop) in enumerate(sz["flash"]):
        def run(b=b, s=s, h=h, d=d, causal=causal, use_lens=use_lens,
                drop=drop, i=i):
            ks = jax.random.split(jax.random.fold_in(key, i), 4)
            q, k, v = (_rand(kk, (b, s, h, d), jnp.bfloat16)
                       for kk in ks[:3])
            lens = None
            if use_lens:
                lens = jnp.asarray(
                    [s - (j * s) // (2 * b) for j in range(b)], jnp.int32)
            got = _value_and_grads(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, kv_lens=lens, dropout_p=drop,
                    dropout_seed=7), (q, k, v), ks[3])
            want = _exact(
                _value_and_grads,
                lambda q, k, v: _attention_ref(q, k, v, causal, lens, drop,
                                               7), (q, k, v), ks[3])
            return _tree_nerr(got, want)
        blocks = (_fit_block(s, DEFAULT_BLOCK_Q, d),
                  _fit_block(s, DEFAULT_BLOCK_K, d))
        # (plain, masked, skipped) score tiles a head, before kv_lens
        case(f"flash_attention fwd+bwd b{b} s{s} h{h} d{d} "
             f"causal={causal} kv_lens={use_lens} dropout={drop}",
             "bfloat16", run, blocks=blocks,
             tile_counts=tile_counts(s, s, *blocks, causal))

    # -- dense flash decode ----------------------------------------------
    dc = sz["decode"]
    for dtype in ("float32", "bfloat16"):
        def run(dtype=dtype):
            b, s, h, d = dc["b"], dc["s"], dc["h"], dc["d"]
            ks = jax.random.split(jax.random.fold_in(key, 100), 3)
            q = _rand(ks[0], (b, 1, h, d), dtype)
            k, v = (_rand(kk, (b, s, h, d), dtype) for kk in ks[1:])
            lens = jnp.asarray([1 + (j * (s - 1)) // (b - 1)
                                for j in range(b)], jnp.int32)
            got = jax.jit(flash_decode)(q, k, v, lens)
            want = _exact(jax.jit(lambda q, k, v: reference_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), kv_lens=lens)), q, k, v)
            return _nerr(got, want)
        case(f"flash_decode b{dc['b']} s{dc['s']} h{dc['h']} d{dc['d']}",
             dtype, run)

    # -- paged flash decode ----------------------------------------------
    for (b, hkv, g, d, ps, mp) in sz["paged"]:
        for dtype in ("float32", "bfloat16", "int8"):
            def run(b=b, hkv=hkv, g=g, d=d, ps=ps, mp=mp, dtype=dtype):
                n_pages = 1 + b * mp
                ks = jax.random.split(jax.random.fold_in(key, 200 + g), 4)
                q = _rand(ks[0], (b, hkv, g, d), jnp.float32)
                kp, vp = (_rand(kk, (hkv, n_pages, ps, d), jnp.float32)
                          for kk in ks[1:3])
                k_scale = v_scale = None
                if dtype == "int8":
                    kp, k_scale = quantize_rows(kp)
                    vp, v_scale = quantize_rows(vp)
                else:
                    kp, vp = kp.astype(dtype), vp.astype(dtype)
                # every slot owns a shuffled private set of pages
                table = (1 + jax.random.permutation(ks[3], b * mp)
                         ).reshape(b, mp).astype(jnp.int32)
                cap = ps * mp
                lens = jnp.asarray([1 + (j * (cap - 1)) // (b - 1)
                                    for j in range(b)], jnp.int32)
                got = jax.jit(paged_flash_decode)(
                    q, kp, vp, table, lens, k_scale, v_scale)
                want = _exact(jax.jit(paged_attention_ref),
                              q, kp, vp, table, lens, k_scale, v_scale)
                return _nerr(got, want)
            case(f"paged_flash_decode b{b} hkv{hkv} g{g} d{d} ps{ps} "
                 f"pages/slot {mp}", dtype, run)

    # -- paged latent decode (absorbed form) -----------------------------
    for (b, h, w, vw, ps, mp) in sz["latent"]:
        def run(b=b, h=h, w=w, vw=vw, ps=ps, mp=mp):
            ks = jax.random.split(jax.random.fold_in(key, 300), 3)
            wp = LatentCacheSpec(w).pool_width
            q = _rand(ks[0], (b, h, w), jnp.float32)
            # every row of the pool holds numbers, the trash page's too:
            # nothing past a slot's length may reach its result
            pool = _rand(ks[1], (1 + b * mp, ps, wp), jnp.bfloat16)
            pool = pool.at[:, :, w:].set(0)
            # every slot owns a shuffled private set of pages, but slot 0:
            # inactive, an all-trash table row and length 0
            table = (1 + jax.random.permutation(ks[2], b * mp)
                     ).reshape(b, mp).astype(jnp.int32).at[0].set(0)
            cap = ps * mp
            # then one key, a page, a page and one, the full table; the
            # rest spread between
            lens = jnp.asarray([0, 1, ps, ps + 1, cap] + [
                1 + (j * 7919) % cap for j in range(5, b)], jnp.int32)
            got = jax.jit(lambda *a: latent_paged_attention(
                *a, vw, 0.1, use_flash=True))(q, pool, table, lens)
            want = _exact(jax.jit(lambda *a: latent_paged_attention(
                *a, vw, 0.1)), q, pool, table, lens)
            if bool(jnp.any(got[0] != 0)):
                raise AssertionError("the inactive slot's row is not zero")
            return _nerr(got, want)
        case(f"latent_decode b{b} h{h} w{w} v{vw} ps{ps} pages/slot {mp}",
             "bfloat16", run)

    # -- fused residual add + LayerNorm, both variants, fwd + bwd --------
    for hidden in sz["ln"]["hidden"]:
        for fn_name in ("fused_add_layer_norm", "fused_add_layer_norm_y"):
            def run(hidden=hidden, fn_name=fn_name):
                n = sz["ln"]["rows"]
                ks = jax.random.split(jax.random.fold_in(key, hidden), 5)
                x, r = (_rand(kk, (n, hidden), jnp.bfloat16)
                        for kk in ks[:2])
                gamma = 1.0 + _rand(ks[2], (hidden,), jnp.bfloat16, 0.1)
                beta = _rand(ks[3], (hidden,), jnp.bfloat16, 0.1)
                fused = getattr(fused_ln, fn_name)

                def ref(x, r, gamma, beta):
                    y, s = fused_ln._reference(x, r, gamma, beta, 1e-5)
                    return (y, s) if fn_name == "fused_add_layer_norm" \
                        else y
                got = _value_and_grads(
                    lambda *a: fused(*a, 1e-5), (x, r, gamma, beta), ks[4])
                want = _exact(_value_and_grads, ref, (x, r, gamma, beta),
                              ks[4])
                return _tree_nerr(got, want)
            case(f"{fn_name} rows{sz['ln']['rows']} h{hidden} fwd+bwd",
                 "bfloat16", run)

    # -- fused AdamW update ----------------------------------------------
    def run_adamw():
        shape = sz["adamw"]
        ks = jax.random.split(jax.random.fold_in(key, 300), 4)
        p = _rand(ks[0], shape, jnp.float32, 0.02)
        m = _rand(ks[1], shape, jnp.float32, 1e-3)
        v = jnp.square(_rand(ks[2], shape, jnp.float32, 1e-3))
        g = _rand(ks[3], shape, jnp.float32, 1e-2)
        hyper = dict(beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01,
                     decoupled=True)
        lr, step = jnp.float32(1e-4), 3

        def ref(p, m, v, g, lr, bc1, bc2):
            m2 = 0.9 * m + (1 - 0.9) * g
            v2 = 0.999 * v + (1 - 0.999) * g * g
            upd = lr * (m2 / bc1) / (jnp.sqrt(v2 / bc2) + 1e-8) \
                + lr * 0.01 * p
            return p - upd, m2, v2
        bc = (jnp.float32(1 - 0.9 ** step), jnp.float32(1 - 0.999 ** step))
        got = jax.jit(lambda *a: fused_adamw_update(*a, **hyper))(
            p, m, v, g, lr, *bc)
        want = jax.jit(ref)(p, m, v, g, lr, *bc)
        # the update is lr-sized: compare the step taken, not p itself
        return max(_nerr(got[0] - p, want[0] - p), _nerr(got[1], want[1]),
                   _nerr(got[2], want[2]))
    case(f"fused_adamw_update {sz['adamw'][0]}x{sz['adamw'][1]}",
         "float32", run_adamw)

    # -- fused 1x1 conv + BN + ReLU (+ residual) -------------------------
    for (m, cin, cout, residual) in sz["conv"]:
        def run(m=m, cin=cin, cout=cout, residual=residual):
            ks = jax.random.split(jax.random.fold_in(key, 400 + cout), 6)
            x = _rand(ks[0], (m, cin), jnp.bfloat16)
            w = _rand(ks[1], (cin, cout), jnp.bfloat16, cin ** -0.5)
            scale = 1.0 + _rand(ks[2], (cout,), jnp.float32, 0.1)
            shift = _rand(ks[3], (cout,), jnp.float32, 0.1)
            args = (x, w, scale, shift)
            if residual:
                args += (_rand(ks[4], (m, cout), jnp.bfloat16),)

            def fused(x, w, scale, shift, *res):
                return conv_bn_act.fused_conv1x1_bn_act(
                    x, w, scale, shift, res[0] if res else None, True)

            def ref(x, w, scale, shift, *res):
                return conv_bn_act._reference(
                    x, w, scale, shift, res[0] if res else None, True)
            return _tree_nerr(_value_and_grads(fused, args, ks[5]),
                              _exact(_value_and_grads, ref, args, ks[5]))
        case(f"fused_conv1x1_bn_act m{m} {cin}->{cout} "
             f"residual={residual} fwd+bwd", "bfloat16", run)

    failed = [r["kernel"] for r in results if not r["ok"]]
    if failed:
        raise AssertionError(f"kernels: {len(failed)} of {len(results)} "
                             f"failed: {failed}")
    return {"cases": len(results),
            "max_err": max(r["err"] for r in results)}


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def _train_batch_arrays(vocab, batch, seq):
    import numpy as np
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    return ids, labels


def _timed_steps(what, eng, ids, labels, steps, clock):
    """First call (trace + compile + run), then `steps` steady steps on
    the same batch, each window closed with block_until_ready. The
    steady steps must compile nothing: a step that comes back with a
    new layout recompiles without retracing, which only jax's own
    compile events show."""
    t0 = time.perf_counter()
    loss, _ = eng.train_batch([ids], [labels])
    losses = [float(loss.block_until_ready())]
    first_s = time.perf_counter() - t0
    compiles = clock.compiles
    step_s = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, _ = eng.train_batch([ids], [labels])
        losses.append(float(loss.block_until_ready()))
        step_s.append(time.perf_counter() - t0)
    if clock.compiles != compiles:
        raise AssertionError(
            f"{what}: {clock.compiles - compiles} compile(s) during the "
            f"{steps} steady steps (step seconds {step_s})")
    return losses, first_s, step_s


def _median_ms(seconds):
    return round(1e3 * sorted(seconds)[len(seconds) // 2], 2)


def _check_losses(what, losses):
    import math
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall on a repeated "
                             f"batch: {losses}")


def phase_train(sz, ctx):
    import paddle_tpu as paddle
    from bench import build_engine
    from paddle_tpu.ops.attention import flash_attention_available

    log = ctx["pallas"]
    mark = len(log.calls)
    paddle.seed(0)
    eng = build_engine(sz["cfg"], sz["batch"], sz["seq"], amp=True)
    cfg = eng.network.config
    ids, labels = _train_batch_arrays(cfg.vocab_size, sz["batch"],
                                      sz["seq"])
    losses, first_s, step_s = _timed_steps("train", eng, ids, labels,
                                           sz["steps"], ctx["clock"])
    _check_losses("train", losses)

    traces = eng.tracer.counts()
    if traces.get("train_step") != 1:
        raise AssertionError(f"train: expected one trace of train_step, "
                             f"got {traces}")
    qshape = (sz["batch"], sz["seq"], cfg.num_attention_heads, cfg.head_dim)
    if not flash_attention_available(qshape, qshape, None, 0.0):
        raise AssertionError(f"train: flash_attention_available said no "
                             f"for {qshape}")
    calls = log.since(mark)
    _native_only(calls, "train")
    built = {n for n, _ in calls}
    # heads of 64: the resident forward and the one backward kernel
    missing = {"_fwd_resident_kernel", "_dkv_dq_kernel"} - built
    if missing:
        raise AssertionError(f"train: the compiled step holds no Mosaic "
                             f"flash-attention call for {sorted(missing)} "
                             f"(built: {sorted(built)})")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is None:
        raise AssertionError(f"train: memory_stats() has no "
                             f"peak_bytes_in_use: {sorted(stats)}")
    print(f"train: losses {[round(x, 4) for x in losses]}; first call "
          f"{first_s:.1f}s; steady step {_median_ms(step_s)} ms; "
          f"peak_bytes_in_use {peak}", flush=True)
    return {"first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            "first_call_s": round(first_s, 2),
            "step_ms_median": _median_ms(step_s),
            "flash_pallas_calls": len(calls),
            "peak_bytes_in_use": int(peak)}


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def _paged_step_logits(model, tokens, pages, table, positions, use_flash):
    """One batched single-token forward through the paged cache — the
    body of the engine's decode step — returning the last-row logits."""
    from paddle_tpu.autograd import no_grad
    from paddle_tpu.nlp.paged_cache import PagedLayerCache
    from paddle_tpu.nn.layer import functional_call
    from paddle_tpu.tensor import Tensor
    params, buffers = model.raw_state()

    @jax.jit
    def step(params, pages):
        caches = [PagedLayerCache(k, v, table, positions,
                                  use_flash=use_flash) for k, v in pages]
        with no_grad():
            logits, _ = functional_call(
                model, params, buffers, Tensor(tokens[:, None]),
                use_cache=False, cache=caches,
                cache_index=Tensor(positions))
        arr = logits._value if isinstance(logits, Tensor) else logits
        return arr[:, -1].astype(jnp.float32)

    return step(params, pages)


def phase_serve(sz, ctx):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp.gpt import GPTForCausalLM, _resolve_config
    from paddle_tpu.nlp.serving import ServingEngine

    log = ctx["pallas"]
    paddle.seed(0)
    model = GPTForCausalLM(_resolve_config(
        sz["cfg"], hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    cfg = model.config
    rng = np.random.default_rng(0)
    lens = np.linspace(sz["prompt_lo"], sz["prompt_hi"],
                       sz["requests"]).astype(int)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)) for n in lens]

    out = {}
    waves = {}
    for label, use_flash in (("default", None), ("paged_kernel", True)):
        mark = len(log.calls)
        eng = ServingEngine(
            model, max_slots=sz["slots"], page_size=sz["page_size"],
            max_seq_len=sz["max_seq_len"], cache_dtype="bfloat16",
            use_flash=use_flash)
        if use_flash and not eng.use_flash:
            raise AssertionError("serve: use_flash=True did not arm the "
                                 "paged kernel")
        # use_flash=None is the plain-XLA path, and a fully provisioned
        # pool is read in place (ops/attention.paged_flash_available)
        want = "paged_kernel" if use_flash else "in_place"
        said = eng.health()["decode_attention"]
        if said != want:
            raise AssertionError(f"serve[{label}]: health() names "
                                 f"{said!r}, expected {want!r}")
        t0 = time.perf_counter()
        warmed = eng.warmup(buckets=[int(n) for n in lens])
        warm_s = time.perf_counter() - t0
        frozen = eng.compile_counts()
        t0 = time.perf_counter()
        rids = [eng.submit(p, sz["new_tokens"]) for p in prompts]
        done = {r["id"]: r for r in eng.run_to_completion()}
        wave_s = time.perf_counter() - t0
        after = eng.compile_counts()
        for rid in rids:
            r = done[rid]
            if r["status"] != "ok" or len(r["tokens"]) != sz["new_tokens"]:
                raise AssertionError(
                    f"serve[{label}]: request {rid} resolved "
                    f"{r['status']} with {len(r['tokens'])} tokens")
        if after != frozen:
            raise AssertionError(f"serve[{label}]: compiled after warmup: "
                                 f"{frozen} -> {after}")
        if eng.tracer.unexpected_retraces():
            raise AssertionError(f"serve[{label}]: unexpected retraces "
                                 f"{eng.tracer.report()}")
        eng.close()
        if eng.free_page_count != eng.num_pages - 1:
            raise AssertionError(
                f"serve[{label}]: {eng.free_page_count} of "
                f"{eng.num_pages - 1} pages free after close()")
        calls = log.since(mark)
        _native_only(calls, f"serve[{label}]")
        kernel_built = any(n == "_decode_kernel" for n, _ in calls)
        if bool(use_flash) != kernel_built:
            raise AssertionError(
                f"serve[{label}]: paged Pallas kernel built="
                f"{kernel_built}, expected {bool(use_flash)}")
        waves[label] = [done[rid]["tokens"] for rid in rids]
        toks = sz["requests"] * sz["new_tokens"]
        out[label] = {"warmup_s": round(warm_s, 2),
                      "wave_s": round(wave_s, 2),
                      "buckets": warmed, "programs": sum(after.values()),
                      "wave_tok_s": round(toks / wave_s, 1)}
        print(f"serve[{label}]: effective use_flash={eng.use_flash}; "
              f"{json.dumps(out[label])}", flush=True)
        del eng
        gc.collect()

    # agreement of the two attention paths, judged on logits of one
    # batched decode step over the same paged state (seeded random
    # weights put logits near ties, so sampled tokens are not the test)
    b, mp = sz["slots"], sz["max_seq_len"] // sz["page_size"]
    kv_heads, hd = cfg.num_attention_heads, cfg.head_dim
    key = jax.random.PRNGKey(1)
    pages = []
    for layer in range(cfg.num_hidden_layers):
        kk, kvv = jax.random.split(jax.random.fold_in(key, layer))
        shape = (kv_heads, 1 + b * mp, sz["page_size"], hd)
        pages.append((_rand(kk, shape, jnp.bfloat16, 0.5),
                      _rand(kvv, shape, jnp.bfloat16, 0.5)))
    table = (1 + jnp.arange(b * mp, dtype=jnp.int32)).reshape(b, mp)
    positions = jnp.asarray(lens[:: max(1, len(lens) // b)][:b], jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b,)), jnp.int32)
    ref = _paged_step_logits(model, tokens, pages, table, positions, False)
    ker = _paged_step_logits(model, tokens, pages, table, positions, True)
    err = _nerr(ker, ref)
    same = np.mean([a == b_ for wa, wb in zip(waves["default"],
                                              waves["paged_kernel"])
                    for a, b_ in zip(wa, wb)])
    print(f"serve: decode-step logits, kernel vs reference: normalised "
          f"max error {err:.3e} (tol {TOL['bfloat16']}); sampled tokens "
          f"identical across the two waves: {same:.3f}", flush=True)
    if not err <= TOL["bfloat16"]:
        raise AssertionError(f"serve: paged kernel logits differ from the "
                             f"reference path by {err}")
    out["logits_err"] = err
    out["token_agreement"] = round(float(same), 4)
    out["experts_err"] = _experts_paths_agree(sz["experts"], log)
    return out


def _experts_paths_agree(shape, log):
    """The held experts at one real width, both paths of nlp/moe.py on the
    chip: the `grouped_experts` kernel (what `held_experts` takes here)
    against the sorted `ragged_dot` products."""
    from paddle_tpu.nlp import moe
    t, h, m, experts, k = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    u = _rand(ks[0], (t, h), jnp.float32)
    w, idx = jax.lax.top_k(jax.random.uniform(ks[1], (t, experts)), k)
    idx = idx.astype(jnp.int32)
    w_gate_up = _rand(ks[2], (experts, h, 2 * m), jnp.bfloat16, h ** -0.5)
    w_down = _rand(ks[3], (experts, m, h), jnp.bfloat16, m ** -0.5)
    live = jnp.arange(t) < t - 3
    if moe.experts_path(t, h, m) != "streamed":
        raise AssertionError(f"serve: {t} rows at {h} x {m} do not take "
                             f"the streaming kernel on this backend")
    mark = len(log.calls)
    with moe.recorded_paths() as seen:
        got, aux = jax.jit(moe.held_experts)(u, idx, w, w_gate_up, w_down,
                                             0, live)
    _native_only(log.since(mark), "serve[experts]")
    if seen != ["streamed"] or not log.since(mark):
        raise AssertionError(f"serve: held_experts took {seen}, kernels "
                             f"{log.since(mark)}")
    key = jnp.where(live[:, None], idx, experts).reshape(-1)
    want, sizes, _ = jax.jit(moe._ragged)(u, key, w, w_gate_up, w_down)
    if [int(x) for x in aux] != [int(sizes.sum()), int((sizes > 0).sum()),
                                 t - 3]:
        raise AssertionError(f"serve: the kernel's path counted {aux}")
    diff = float(jnp.max(jnp.abs(got - want)))
    top = float(jnp.max(jnp.abs(want)))
    print(f"serve: held experts at {t} rows, {experts} experts {h} x {m}, "
          f"grouped_experts vs ragged_dot: largest difference {diff:.3e} "
          f"of {top:.3f}", flush=True)
    if not diff <= 1e-3 * top:
        raise AssertionError(f"serve: grouped_experts differs from the "
                             f"ragged_dot path by {diff} of {top}")
    return diff / top


# --------------------------------------------------------------------------
# mesh
# --------------------------------------------------------------------------

def _device_bytes(devices):
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in devices]


def _check_spread(what, arrays, devices, sharded_min=1):
    """Every array lives on all `devices`; at least `sharded_min` of them
    hold less than the whole array per device."""
    want = set(devices)
    sharded = 0
    for name, a in arrays:
        got = a.sharding.device_set
        if got != want:
            raise AssertionError(f"{what}: {name} lives on {len(got)} "
                                 f"devices, expected {len(want)}")
        if a.addressable_shards[0].data.shape != a.shape:
            sharded += 1
    if sharded < sharded_min:
        raise AssertionError(f"{what}: {sharded} of {len(arrays)} arrays "
                             f"are split across devices, expected >= "
                             f"{sharded_min}")
    return sharded


def _check_balance(what, devices):
    used = _device_bytes(devices)
    if min(used) <= 0 or max(used) > 2 * min(used):
        raise AssertionError(f"{what}: bytes_in_use per device is "
                             f"unbalanced: {used}")
    return used


def phase_mesh(sz, ctx):
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 4:
        print(f"mesh: {len(devices)} device, not run", flush=True)
        return {"skipped": f"{len(devices)} device"}
    devices = devices[:4]

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.mpu import shard_model
    from paddle_tpu.distributed.fleet.sharding import group_sharded_parallel
    from paddle_tpu.distributed.mesh import set_mesh
    from paddle_tpu.hapi.engine import Engine
    from paddle_tpu.nlp.gpt import (GPTForCausalLM, GPTForCausalLMPipe,
                                    GPTPretrainingCriterion, _resolve_config)
    from paddle_tpu.optimizer import AdamW

    # published width, heads, vocab, batch and sequence; depth cut (see
    # SIZES): every sub-phase is one cold SPMD compile on four chips
    cfg = _resolve_config(
        sz["cfg"], num_hidden_layers=sz["layers"], hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, use_flash_attention=True)
    ids, labels = _train_batch_arrays(cfg.vocab_size, sz["batch"],
                                      sz["seq"])
    log = ctx["pallas"]
    out, failed = {}, []

    def build(cls=GPTForCausalLM, **kw):
        paddle.seed(0)      # same seed -> the same weights in every build
        model = cls(cfg, **kw)
        model.train()
        opt = AdamW(learning_rate=1e-4, weight_decay=0.01,
                    parameters=model.parameters())
        return model, opt

    def engine(model, opt, mesh=None):
        return Engine(model, loss=GPTPretrainingCriterion(), optimizer=opt,
                      mesh=mesh, amp_dtype=jnp.bfloat16)

    # the one-chip first-step loss every mesh layout must reproduce
    model, opt = build()
    loss, _ = engine(model, opt).train_batch([ids], [labels])
    ref_loss = float(loss.block_until_ready())
    print(f"mesh: one-chip first-step loss {ref_loss:.4f} "
          f"({sz['layers']} layers)", flush=True)
    del model, opt, loss
    gc.collect()

    def run(label, mesh, model, opt, sharded_params_min):
        mark = len(log.calls)
        eng = engine(model, opt, mesh)
        with mesh:
            losses, first_s, step_s = _timed_steps(
                f"mesh[{label}]", eng, ids, labels, sz["steps"],
                ctx["clock"])
        _check_losses(f"mesh[{label}]", losses)
        if abs(losses[0] - ref_loss) > MESH_LOSS_ATOL:
            raise AssertionError(
                f"mesh[{label}]: first-step loss {losses[0]} vs one-chip "
                f"{ref_loss} (atol {MESH_LOSS_ATOL})")
        if sum(eng.tracer.counts().values()) != 1:
            raise AssertionError(f"mesh[{label}]: expected one trace, "
                                 f"got {eng.tracer.counts()}")
        _native_only(log.since(mark), f"mesh[{label}]")
        row = {"first_loss": round(losses[0], 4),
               "last_loss": round(losses[-1], 4),
               "first_call_s": round(first_s, 2),
               "step_ms_median": _median_ms(step_s),
               "params_split": _check_spread(
                   f"mesh[{label}] params",
                   [(n, p._value) for n, p in model.named_parameters()],
                   devices, sharded_params_min),
               "bytes_in_use": _check_balance(f"mesh[{label}]", devices)}
        return eng, row

    def dp_mp():
        # GSPMD shardings from shard_model
        mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "mp"))
        model, opt = build()
        shard_model(model, mesh)
        return run("dp2xmp2", mesh, model, opt, sharded_params_min=1)[1]

    def zero():
        # ZeRO stage 2: optimizer state + grads sharded over dp
        mesh = set_mesh(Mesh(np.array(devices), ("dp",)))
        model, opt = build()
        model, opt, _ = group_sharded_parallel(model, opt, level="os_g",
                                               mesh=mesh)
        eng, row = run("zero_os_g_dp4", mesh, model, opt,
                       sharded_params_min=0)
        leaves = [(str(i), a) for i, a in enumerate(
            jax.tree_util.tree_leaves(eng.opt_state_dict()["state"]))
            if getattr(a, "ndim", 0)]
        row["opt_state_split"] = _check_spread(
            "mesh[zero_os_g_dp4] optimizer state", leaves, devices,
            sharded_min=len(leaves) // 2)
        return row

    def mp_pp():
        # decoder trunk pipelined over pp, weights split over mp
        mesh = Mesh(np.array(devices).reshape(2, 2), ("mp", "pp"))
        model, opt = build(GPTForCausalLMPipe, mesh=mesh,
                           n_micro=sz["n_micro"])
        shard_model(model, mesh)
        return run("mp2xpp2", mesh, model, opt, sharded_params_min=1)[1]

    for label, sub in (("dp2xmp2", dp_mp), ("zero_os_g_dp4", zero),
                       ("mp2xpp2", mp_pp)):
        try:
            out[label] = sub()
        except Exception as e:  # noqa: BLE001 — recorded, phase fails below
            traceback.print_exc()
            out[label] = {"error": f"{type(e).__name__}: {e}"[:800]}
            failed.append(label)
        print(f"mesh[{label}]: {json.dumps(out[label])}", flush=True)
        gc.collect()
    if failed:
        raise AssertionError(f"mesh: failed layouts {failed}: "
                             f"{json.dumps(out)[:1500]}")
    out["ref_loss"] = round(ref_loss, 4)
    return out


# --------------------------------------------------------------------------

def device_facts():
    """The device as jax reports it; the verdict line's `device`."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def verdict_line(ok, device):
    """The last stdout line: these keys and no others (the driver's chip
    check parses it and refuses anything else)."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma list out of {','.join(PHASES)}")
    args = ap.parse_args(argv)
    wanted = [p for p in args.phases.split(",") if p]
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")

    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_run = time.perf_counter()

    # first thing, before jax initialises: where compiled programs live
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import importlib.metadata as md

    import jaxlib
    device = device_facts()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu, "python": sys.version.split()[0]}
    print(f"chip_smoke: device {json.dumps(device)} versions "
          f"{json.dumps(versions)} compile_cache {cache_dir}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found platform="
                 f"{device['platform']!r} ({device['kind']}); nothing was "
                 f"run")

    from paddle_tpu.observability.introspect import resolve_peak_flops
    peak, peak_src = resolve_peak_flops()
    print(f"chip_smoke: resolve_peak_flops() -> {peak} ({peak_src})",
          flush=True)

    clock = CompileClock()
    ctx = {"pallas": PallasLog(), "clock": clock}
    funcs = {"fit": phase_fit, "kernels": phase_kernels,
             "train": phase_train, "serve": phase_serve, "mesh": phase_mesh}
    phases = {}
    for name in PHASES:
        if name not in wanted:
            continue
        print(f"chip_smoke: phase {name} ...", flush=True)
        c0 = clock.snapshot()
        t0 = time.perf_counter()
        row = {"ok": True}
        try:
            row.update(funcs[name](SIZES[name], ctx) or {})
        except Exception as e:  # noqa: BLE001 — recorded; the run exits 1
            traceback.print_exc()
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"[:800]}
        c1 = clock.snapshot()
        row["wall_s"] = round(time.perf_counter() - t0, 2)
        row["compile_s"] = round(c1[0] - c0[0], 2)
        row["compiles"] = c1[1] - c0[1]
        row["cache_hits"] = c1[2] - c0[2]
        phases[name] = row
        print(f"chip_smoke: phase {name} "
              f"{'ok' if row['ok'] else 'FAILED'} wall {row['wall_s']}s "
              f"compile {row['compile_s']}s ({row['compiles']} compiles, "
              f"{row['cache_hits']} cache hits)", flush=True)
        gc.collect()

    ok = all(r["ok"] for r in phases.values())
    faulthandler.cancel_dump_traceback_later()
    report = {
        "ok": ok, "device": device, "versions": versions,
        "peak_flops": peak, "peak_flops_source": peak_src,
        "compile_cache": cache_dir,
        "wall_s": round(time.perf_counter() - t_run, 2),
        "compile_s": round(clock.compile_s, 2),
        "cache_hits": clock.cache_hits,
        "phases": phases}
    print(f"chip_smoke: report {json.dumps(report)}", flush=True)
    print(verdict_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
