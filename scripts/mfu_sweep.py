"""MFU experiment: batch sweep + step-time breakdown for the ResNet-50 bench.

Run on the real TPU: python scripts/mfu_sweep.py --batches 64 128 256 [--profile]
Parses the xprof trace (trace.json.gz) and prints top device ops by self time.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

FLOPS_PER_IMAGE = 3 * 4.09e9


def timed(step, state, batch, rng, n_steps):
    for _ in range(3):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])
    return time.perf_counter() - t0, state


def analyze_trace(trace_dir):
    paths = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)
    if not paths:
        print("no trace.json.gz found under", trace_dir)
        return
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # Find TPU device pids (track names containing "TPU" / "/device:")
    pid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
    dev_pids = {p for p, n in pid_names.items() if "TPU" in n or "/device" in n.lower()}
    tot = defaultdict(float)
    cnt = defaultdict(int)
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            tot[e["name"]] += e.get("dur", 0)
            cnt[e["name"]] += 1
    grand = sum(tot.values())
    print(f"--- trace {os.path.basename(path)}: {grand / 1e3:.1f} ms total device time ---")
    for name, us in sorted(tot.items(), key=lambda kv: -kv[1])[:30]:
        print(f"{us / 1e3:9.2f} ms  {100 * us / max(grand, 1):5.1f}%  x{cnt[name]:<4d} {name[:110]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--batches", type=int, nargs="+", default=[64, 128, 256])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--model", default="resnet50", choices=["resnet50", "inception"])
    ap.add_argument("--pw-backend", default="conv",
                    choices=["conv", "pallas", "fused"],
                    help="ResNet 1x1-conv path (fused = r4 conv+BN+ReLU "
                    "backward kernels, ops/fused_conv_bn.py)")
    args = ap.parse_args()

    from distributed_tensorflow_tpu.models import ResNet50
    from distributed_tensorflow_tpu.parallel import collectives as coll
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.objectives import (
        init_model,
        make_classification_loss,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    mesh = build_mesh({"data": -1})
    n = len(jax.devices())
    if args.model == "inception":
        from distributed_tensorflow_tpu.models import InceptionV3

        if args.pw_backend == "pallas":
            raise SystemExit(
                "--model inception supports --pw-backend conv|fused only "
                "(the r3 'pallas' 1x1 path is ResNet-specific)"
            )
        # Inception-v3 at 299x299: ~5.73 GFLOP/image fwd (standard count).
        model = InceptionV3(
            num_classes=1000,
            dtype=jnp.bfloat16,
            aux_logits=False,
            fused=args.pw_backend == "fused",
        )
        hw, flops_per_image = 299, 3 * 5.73e9
    else:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        if args.pw_backend != "conv":
            import dataclasses
            model = dataclasses.replace(model, pw_backend=args.pw_backend)
        hw, flops_per_image = 224, FLOPS_PER_IMAGE
    if args.remat:
        import dataclasses
        model = dataclasses.replace(model, remat=True)
    params, model_state = init_model(
        model, jax.random.key(0), jnp.zeros((1, hw, hw, 3), jnp.float32)
    )
    # Host copies: device state gets donated inside the sweep loop.
    params = jax.device_get(params)
    model_state = jax.device_get(model_state)
    tx = optax.sgd(0.1, momentum=0.9)

    for b in args.batches:
        state = place_state(create_train_state(params, tx, model_state), mesh)
        step = make_train_step(make_classification_loss(model), tx, mesh)
        gb = b * n
        rng0 = np.random.default_rng(0)
        batch = coll.shard_batch(
            {
                "image": rng0.normal(size=(gb, hw, hw, 3)).astype(np.float32),
                "label": np.zeros((gb,), np.int32),
            },
            mesh,
        )
        rng = jax.random.key(0)
        # 60 steps so the fixed cost of ending the window
        # (scripts/roofline.py) is a small part of the per-step time.
        n_steps = 60
        try:
            dt, state = timed(step, state, batch, rng, n_steps)
        except Exception as e:  # OOM etc.
            print(f"b={b}: FAILED {type(e).__name__}: {str(e)[:300]}")
            continue
        ips = n_steps * gb / dt / n
        mfu = ips * flops_per_image / 197e12
        print(
            f"b={b}/chip: {ips:.1f} img/s/chip, {dt / n_steps * 1e3:.1f} ms/step, mfu={mfu:.3f}",
            flush=True,
        )
        if args.profile:
            trace_dir = f"/tmp/mfu_trace_b{b}"
            with jax.profiler.trace(trace_dir):
                dt, state = timed(step, state, batch, rng, 5)
            analyze_trace(trace_dir)


if __name__ == "__main__":
    main()
