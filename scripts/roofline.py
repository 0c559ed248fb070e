"""Measure this chip's *achievable* roofline: matmul peak and HBM bandwidth.

VERDICT r2 (Missing #1 / Weak #1) says the claimed ~0.37 MFU HBM ceiling for
the ResNet-50 step is "asserted arithmetic, not demonstrated".  This script
turns the two numbers that arithmetic rests on into measurements:

1. **Achievable matmul FLOP/s** — big square bf16 matmuls (the best case the
   MXU ever sees).  If this lands well under the nominal 197 TF/s (v5e), every
   MFU number in the repo is being divided by a peak this chip cannot reach.
2. **Achievable HBM bandwidth** — streaming ops at several working-set sizes
   (copy = 1R+1W, BN-apply = 1R+1W elementwise, reduce = 1R) plus the actual
   train-mode BatchNorm chain at real ResNet-50 trace shapes.

Methodology (the part r2 got wrong): every dispatch and every end of a
timed window has a fixed host-side cost that dwarfs device time for small
ops, so a timed region is ONE dispatch that loops K times on device
(lax.fori_loop) and ends in block_until_ready.  Per-iteration cost is then
the difference of a K- and a K/2-iteration window over K/2, with K large
enough that the overhead is <5%.

Usage: python scripts/roofline.py [--json out.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _fetch(out):
    # End of a timed window: the device has finished, nothing is copied.
    jax.block_until_ready(out)


def run_window(fn, args, repeats=5):
    """Median wall seconds of one dispatch of fn (already jitted)."""
    out = fn(*args)
    _fetch(out)  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        _fetch(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def device_loop(body, k):
    """One jitted dispatch running `body` k times on device via fori_loop."""

    @jax.jit
    def run(*args):
        return jax.lax.fori_loop(0, k, lambda i, a: body(*a), args)

    return run


def per_iter(body, args, est_iter_sec, target_sec=1.5, repeats=5):
    """Seconds per body() iteration, the window's fixed cost cancelled.

    Dispatching a window and waiting for its end costs a fixed host-side
    time that dwarfs device time for small ops.  So: run one dispatch of
    a k-iteration on-device fori_loop sized from `est_iter_sec` to
    ~`target_sec` of device time, and one of k/2; the (t_k - t_half)/(k/2)
    difference cancels the fixed cost exactly, and the window length keeps
    its jitter under a few percent.  Self-corrects once if the
    estimate was off by >4x.
    """
    for _ in range(2):
        k = max(8, int(target_sec / est_iter_sec)) & ~1
        t_k = run_window(device_loop(body, k), args, repeats=repeats)
        t_half = run_window(device_loop(body, k // 2), args, repeats=repeats)
        sec = max(t_k - t_half, 1e-9) / (k // 2)
        if 0.25 * target_sec < t_k - t_half < 4 * target_sec:
            break
        est_iter_sec = max(sec, 1e-7)
    return sec, t_half


def bench_matmul(n: int):
    w = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
    y = jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16)

    def body(y, w):
        return jnp.tanh(y @ w), w  # tanh keeps values bounded across chaining

    sec, _ = per_iter(body, (y, w), est_iter_sec=2 * n**3 / 100e12)
    return {"n": n, "ms": sec * 1e3, "tflops": 2 * n**3 / sec / 1e12}


def bench_stream(name, body, nbytes, shape, dtype):
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    sec, _ = per_iter(body, (x,), est_iter_sec=nbytes(x) / 400e9)
    return {"kind": name, "mb": x.nbytes / 1e6, "ms": sec * 1e3,
            "gbps": nbytes(x) / sec / 1e9}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None, choices=["matmul", "stream", "bn"])
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")
    results = {"device": dev.device_kind, "matmul": [], "stream": [], "bn": []}

    # fixed cost of a window: one dispatch of a trivial program + its end
    t_triv = run_window(device_loop(lambda x: (x + 1.0,), 1),
                        (jnp.zeros((8, 128), jnp.float32),))
    results["window_fixed_cost_ms"] = t_triv * 1e3
    print(f"dispatch + end-of-window fixed cost: {t_triv*1e3:.1f} ms")

    if args.only in (None, "matmul"):
        print("\n== achievable matmul peak (bf16, on-device chained matmuls) ==")
        for n in (2048, 4096, 8192):
            r = bench_matmul(n)
            results["matmul"].append(r)
            print(f"  {n:>6}^3: {r['ms']:8.3f} ms/matmul  {r['tflops']:7.1f} TF/s")

    if args.only in (None, "stream"):
        _stream(results)
    if args.only in (None, "bn"):
        _bn(results)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"\nwrote {args.json}")


def _stream(results):
    print("\n== achievable HBM bandwidth (bf16 streaming, on-device loops) ==")
    one = jnp.bfloat16(1.0)
    cases = [
        ("copy(1R1W)", lambda x: (x + one,), lambda x: 2 * x.nbytes),
        ("bn_apply(1R1W)",
         lambda x: (jax.nn.relu(x * jnp.bfloat16(1.0003) + jnp.bfloat16(0.001)),),
         lambda x: 2 * x.nbytes),
        # reduce writes only a scalar; loop-carry re-scales x so the loop body
        # still reads the full array each iteration: 1R per iter.
        ("reduce(1R)",
         lambda x: (x * (1.0 + 1e-12 * jnp.sum(x.astype(jnp.float32))).astype(x.dtype),),
         lambda x: 2 * x.nbytes),  # actually 1R + 1W of the rescale output
    ]
    for mb in (256, 1024):
        n_elems = mb * 1024 * 1024 // 2
        shape = (n_elems // 1024, 1024)
        for name, body, nbytes in cases:
            r = bench_stream(name, body, nbytes, shape, jnp.bfloat16)
            results["stream"].append(r)
            print(f"  {name:>14} {r['mb']:7.0f} MB: {r['ms']:8.3f} ms  {r['gbps']:7.1f} GB/s")


def _bn(results):
    print("\n== train-mode BatchNorm+ReLU chain at ResNet-50 shapes (b=128) ==")
    for (b, h, c) in ((128, 56, 256), (128, 28, 512), (128, 14, 1024)):
        x0 = jax.random.normal(jax.random.key(0), (b, h, h, c), jnp.bfloat16)
        scale = jnp.ones((c,), jnp.float32)
        bias = jnp.zeros((c,), jnp.float32)

        def body(x, scale, bias):
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean)
            y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
            return jax.nn.relu(y).astype(jnp.bfloat16), scale, bias

        sec, _ = per_iter(body, (x0, scale, bias), est_iter_sec=3 * x0.nbytes / 300e9)
        # XLA two-pass stats + separate normalize: expected 3 passes (2R+1W).
        gbps3 = 3 * x0.nbytes / sec / 1e9
        results["bn"].append({"shape": [b, h, h, c], "mb": x0.nbytes / 1e6,
                              "ms": sec * 1e3, "gbps_at_3pass": gbps3})
        print(f"  bn_train[{b},{h},{h},{c}] ({x0.nbytes/1e6:.0f} MB): {sec*1e3:8.3f} ms"
              f"  -> {gbps3:6.1f} GB/s if 3-pass (2R1W)")


if __name__ == "__main__":
    main()
