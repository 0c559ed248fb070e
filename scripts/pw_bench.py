"""Standalone Pallas pointwise-kernel bench + numerics, real TPU.

For each hot 1x1-conv shape from the b=128 ResNet-50 trace, times the
Pallas dgrad/wgrad kernels with the RTT-cancelling on-device-loop harness
from scripts/roofline.py and prints achieved GB/s against the chip's
measured ~650 GB/s streaming ceiling. The XLA-side comparison numbers come
from the in-step trace (docs/PERF.md r3) — do NOT time
vjp-of-conv inside an on-device loop here: the conv closure's operands
become program constants baked into the executable at compile time
(minutes per program; docs/PERF.md methodology note).

    python scripts/pw_bench.py [--shapes stage1] [--check]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from roofline import per_iter
from distributed_tensorflow_tpu.ops.pointwise_conv import (
    _dgrad_pallas,
    _wgrad_pallas,
)

# (B, HW, K, N) — the 1x1 layers that dominate the trace, heaviest first.
SHAPES = {
    "stage1": [
        (128, 56, 256, 64),   # Conv_0 blocks 1-2: dgrad was 1.2-1.5 ms
        (128, 56, 64, 256),   # Conv_2 / proj: dgrad 0.6-0.7 ms, wgrad 0.55 ms
        (128, 56, 64, 64),    # block 0 Conv_0
    ],
    "stage2": [
        (128, 28, 512, 128),
        (128, 28, 128, 512),
    ],
    "stage34": [
        (128, 14, 1024, 256),
        (128, 14, 256, 1024),
        (128, 7, 2048, 512),
        (128, 7, 512, 2048),
    ],
}


def conv_nhwc(x, w4):
    return jax.lax.conv_general_dilated(
        x, w4, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def check_numerics(b, hw, k, n):
    key = jax.random.key(0)
    x = jax.random.normal(key, (64, k), jnp.bfloat16)
    g = jax.random.normal(key, (64, n), jnp.bfloat16)
    w = jax.random.normal(key, (k, n), jnp.bfloat16)
    dx = _dgrad_pallas(g, w, interpret=False)
    dw = _wgrad_pallas(x, g, interpret=False)
    dx_ref = jnp.dot(g.astype(jnp.float32), w.astype(jnp.float32).T)
    dw_ref = jnp.dot(x.astype(jnp.float32).T, g.astype(jnp.float32))
    err_dx = float(jnp.max(jnp.abs(dx.astype(jnp.float32) - dx_ref)))
    err_dw = float(jnp.max(jnp.abs(dw - dw_ref)))
    rng = float(jnp.max(jnp.abs(dx_ref))), float(jnp.max(jnp.abs(dw_ref)))
    print(f"  numerics k={k} n={n}: max|d_dx|={err_dx:.4f} (range {rng[0]:.1f}), "
          f"max|d_dw|={err_dw:.4f} (range {rng[1]:.1f})")


def bench_shape(b, hw, k, n):
    m = b * hw * hw
    key = jax.random.key(0)
    x4 = jax.random.normal(key, (b, hw, hw, k), jnp.bfloat16)
    w4 = jax.random.normal(key, (1, 1, k, n), jnp.bfloat16)
    g4 = jax.random.normal(key, (b, hw, hw, n), jnp.bfloat16)
    x2, g2, w2 = x4.reshape(m, k), g4.reshape(m, n), w4[0, 0]

    bytes_dgrad = g2.nbytes + w2.nbytes + m * k * 2
    bytes_wgrad = x2.nbytes + g2.nbytes + k * n * 4

    eps = jnp.bfloat16(1e-8)

    # All large arrays ride the loop carry (never closures — they would
    # become program constants baked into the executable at compile time).
    def pl_dgrad(g, w):
        dx = _dgrad_pallas(g, w, interpret=False)
        return (g * (1 + eps * dx[0, 0]), w)

    def pl_wgrad(g, x):
        dw = _wgrad_pallas(x, g, interpret=False)
        return (g * (1 + eps * dw[0, 0].astype(g.dtype)), x)

    est = bytes_dgrad / 300e9
    rows = []
    for name, body, args, nbytes in [
        ("pl_dgrad", pl_dgrad, (g2, w2), bytes_dgrad),
        ("pl_wgrad", pl_wgrad, (g2, x2), bytes_wgrad),
    ]:
        sec, _ = per_iter(body, args, est_iter_sec=est, target_sec=0.5, repeats=3)
        rows.append((name, sec * 1e3, nbytes / sec / 1e9))
    print(f"shape M={m} K={k} N={n}:")
    for name, ms, gbps in rows:
        print(f"  {name:>10}: {ms:7.3f} ms  {gbps:6.1f} GB/s")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="stage1",
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()
    groups = list(SHAPES) if args.shapes == "all" else [args.shapes]
    if args.check:
        for gname in groups:
            for (b, hw, k, n) in SHAPES[gname]:
                check_numerics(b, hw, k, n)
    for gname in groups:
        for (b, hw, k, n) in SHAPES[gname]:
            bench_shape(b, hw, k, n)


if __name__ == "__main__":
    main()
