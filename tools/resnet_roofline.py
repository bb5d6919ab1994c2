"""Measured roofline for the ResNet-50 conv segments ("close or
experimentally bound the gap" — this produces the bound).

For each distinct conv shape in the ResNet-50 forward (dominated by the
1x1 convs diagnosed as bandwidth-bound), times an isolated
jitted conv+BN+ReLU block at the training batch size and reports:
  - achieved TFLOP/s vs the 197 TFLOP/s bf16 MXU peak
  - achieved GB/s (input + weight + output bytes) vs the 819 GB/s HBM
    peak of one v5e chip
  - which roof binds (arithmetic intensity vs the ridge point)

One JSON line per segment + a summary line; structure runs on CPU with
--smoke (tiny shapes) so the tool itself is testable without the TPU.

Usage: python tools/resnet_roofline.py [--batch 256] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HBM_PEAK_GBS = 819.0
MXU_PEAK_TFLOPS = 197.0


# (name, in_c, out_c, k, stride, spatial_in) — the distinct conv shapes
# of ResNet-50 at 224x224 (each appears `count` times per forward)
RESNET50_SEGMENTS = [
    ("stem7x7", 3, 64, 7, 2, 224, 1),
    ("s1_1x1a", 64, 64, 1, 1, 56, 1),
    ("s1_3x3", 64, 64, 3, 1, 56, 3),
    ("s1_1x1b", 64, 256, 1, 1, 56, 3),
    ("s1_1x1r", 256, 64, 1, 1, 56, 2),
    ("s1_proj", 64, 256, 1, 1, 56, 1),
    ("s2_1x1a", 256, 128, 1, 1, 56, 1),
    ("s2_proj", 256, 512, 1, 2, 56, 1),
    ("s2_3x3s2", 128, 128, 3, 2, 56, 1),
    ("s2_1x1b", 128, 512, 1, 1, 28, 4),
    ("s2_1x1r", 512, 128, 1, 1, 28, 3),
    ("s2_3x3", 128, 128, 3, 1, 28, 3),
    ("s3_1x1a", 512, 256, 1, 1, 28, 1),
    ("s3_proj", 512, 1024, 1, 2, 28, 1),
    ("s3_3x3s2", 256, 256, 3, 2, 28, 1),
    ("s3_1x1b", 256, 1024, 1, 1, 14, 6),
    ("s3_1x1r", 1024, 256, 1, 1, 14, 5),
    ("s3_3x3", 256, 256, 3, 1, 14, 5),
    ("s4_1x1a", 1024, 512, 1, 1, 14, 1),
    ("s4_proj", 1024, 2048, 1, 2, 14, 1),
    ("s4_3x3s2", 512, 512, 3, 2, 14, 1),
    ("s4_1x1b", 512, 2048, 1, 1, 7, 3),
    ("s4_1x1r", 2048, 512, 1, 1, 7, 2),
    ("s4_3x3", 512, 512, 3, 1, 7, 2),
]


def segment_cost(batch, in_c, out_c, k, stride, spatial_in, dtype_bytes=2):
    """(flops, bytes) of one conv at the given shape (NCHW bf16)."""
    out_sp = spatial_in // stride
    flops = 2 * batch * out_c * out_sp * out_sp * in_c * k * k
    bytes_ = dtype_bytes * (
        batch * in_c * spatial_in * spatial_in      # activations in
        + in_c * out_c * k * k                      # weights
        + batch * out_c * out_sp * out_sp)          # activations out
    return flops, bytes_


def bench_segment(batch, in_c, out_c, k, stride, spatial_in, reps=20):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng0 = np.random.default_rng(1)
    cw = jnp.asarray(rng0.standard_normal((out_c, in_c, k, k)) * 0.05,
                     jnp.bfloat16)

    @jax.jit
    def f(x, w):
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(k // 2, k // 2)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            preferred_element_type=jnp.float32)
        return jax.nn.relu(y).astype(jnp.bfloat16)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (batch, in_c, spatial_in, spatial_in)), jnp.bfloat16)
    out = f(x, cw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(x, cw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes on CPU: exercises the tool, the "
                    "numbers are meaningless")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    segments = RESNET50_SEGMENTS
    batch = args.batch
    if args.smoke:
        segments = [("smoke1x1", 8, 16, 1, 1, 8, 1),
                    ("smoke3x3", 8, 8, 3, 1, 8, 1)]
        batch = 4

    ridge = MXU_PEAK_TFLOPS * 1e12 / (HBM_PEAK_GBS * 1e9)  # FLOPs/byte
    total_t = total_flops = total_bytes = roof_t = 0.0
    rows = []
    for name, ic, oc, k, s, sp, count in segments:
        dt = bench_segment(batch, ic, oc, k, s, sp)
        flops, bytes_ = segment_cost(batch, ic, oc, k, s, sp)
        roof_t += max(flops / (MXU_PEAK_TFLOPS * 1e12),
                      bytes_ / (HBM_PEAK_GBS * 1e9)) * count
        ai = flops / bytes_
        row = {
            "segment": name, "count": count,
            "tflops": round(flops / dt / 1e12, 1),
            "gbs": round(bytes_ / dt / 1e9, 1),
            "ai_flops_per_byte": round(ai, 1),
            "bound": "compute" if ai > ridge else "bandwidth",
            "pct_of_roof": round(100 * max(
                (flops / dt / 1e12) / MXU_PEAK_TFLOPS,
                (bytes_ / dt / 1e9) / HBM_PEAK_GBS), 1),
            "ms": round(dt * 1e3, 3),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        total_t += dt * count
        total_flops += flops * count
        total_bytes += bytes_ * count

    # roof_t (accumulated above) is the experimentally-bound ceiling:
    # every segment running exactly AT its binding roof
    print(json.dumps({
        "metric": "resnet50_conv_stack_roofline",
        "measured_ms": round(total_t * 1e3, 1),
        "roofline_ms": round(roof_t * 1e3, 1),
        "roof_utilization": round(roof_t / total_t, 3) if total_t else 0,
        "agg_tflops": round(total_flops / total_t / 1e12, 1),
        "agg_gbs": round(total_bytes / total_t / 1e9, 1),
        "implied_img_per_sec_ceiling": round(batch / roof_t, 0),
        "note": "fwd conv stack only; x3 for training (fwd+bwd) and add "
                "BN/elementwise passes for the full step bound",
    }), flush=True)


if __name__ == "__main__":
    main()
