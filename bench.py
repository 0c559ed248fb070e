"""Headline benchmark — ONE JSON line for the driver protocol.

Default workload (r5, VERDICT r4 Weak #3): BERT-base pretraining at L=512
— the transformer config is the axis where the measured chip ceiling is
actually approachable (docs/PERF.md r5: MFU 0.360 -> 0.600 this round,
recipe campaign + layout-native packed flash kernels), where the conv
workloads sit at a measured structural ~0.17 plateau (docs/PERF.md r3/r4
CASE CLOSED). ``BENCH_WORKLOAD=resnet50`` selects the unchanged ResNet-50
line (rounds 1-4's default); ``BENCH_WORKLOAD=bert`` still works and
equals the default.

Prints ONE JSON line: ``{"metric", "value", "unit", "vs_baseline"}``.
``vs_baseline`` is measured MFU / 0.55 — the reference repo publishes no
numbers (BASELINE.json "published": {}, SURVEY.md §6), so the ≥55% MFU
target from BASELINE.json:5 is the baseline bar.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

# ResNet-50 at 224x224: ~4.09 GFLOP forward per image (the standard count);
# fwd+bwd ~= 3x forward.
FLOPS_PER_IMAGE = 3 * 4.09e9

# Per-chip peak bf16 FLOP/s for MFU accounting, keyed by jax's
# ``device_kind`` (Google Cloud TPU documentation, system architecture pages
# of each generation). The one table: scripts/bench_bert.py reads it too.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def require_tpu() -> list:
    """The TPU devices of this process. A benchmark number from any other
    backend would be written under a device metric's name, so there is no
    fallback: no TPU, no run."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmarks run on a TPU only; jax found {devices[0].platform!r} "
            f"({devices[0].device_kind})"
        )
    return devices


def chip_peak_flops(device) -> float:
    """Per-chip peak bf16 FLOP/s of ``device``. A chip that is not in the
    table is an error, not a guess."""
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"no peak FLOP/s known for device_kind {device.device_kind!r}; "
            f"add it to bench.PEAK_FLOPS with its source (known: "
            f"{sorted(PEAK_FLOPS)})"
        ) from None


def main():
    from distributed_tensorflow_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    workload = os.environ.get("BENCH_WORKLOAD", "bert")
    if workload not in ("bert", "resnet50"):
        raise SystemExit(f"BENCH_WORKLOAD must be 'bert' or 'resnet50', got {workload!r}")
    if workload == "bert":
        # Transformer workload (BASELINE.json:11) — the r5 default.
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_bert

        bench_bert.driver_line()
        return
    from distributed_tensorflow_tpu.data import synthetic_image_classification
    from distributed_tensorflow_tpu.models import ResNet50
    from distributed_tensorflow_tpu.parallel import collectives as coll
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.objectives import (
        init_model,
        make_classification_loss,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    devices = require_tpu()
    n = len(devices)
    peak = chip_peak_flops(devices[0])
    # b=128/chip won the r2 batch sweep (scripts/mfu_sweep.py: 0.136 @ 64,
    # 0.158 @ 128, 0.156 @ 256, 0.147 @ 512 on v5e).
    per_chip_batch = int(os.environ.get("BENCH_BATCH", 128))
    image_hw = 224
    global_batch = per_chip_batch * n

    mesh = build_mesh({"data": -1})
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    params, model_state = init_model(
        model, jax.random.key(0), jnp.zeros((1, image_hw, image_hw, 3), jnp.float32)
    )
    tx = optax.sgd(0.1, momentum=0.9)
    state = place_state(create_train_state(params, tx, model_state), mesh)
    step = make_train_step(make_classification_loss(model), tx, mesh)

    ds = synthetic_image_classification(
        global_batch, (image_hw, image_hw, 3), 1000, seed=0
    )
    rng = jax.random.key(0)

    # BENCH_FEED=stream: feed every step a fresh host-assembled batch
    # through the async prefetch stage (data/prefetch.py) instead of one
    # resident device batch — measures end-to-end throughput WITH the feed
    # in the loop (vs the default device-only number). BENCH_PREFETCH sets
    # the lookahead depth (0 = synchronous feed, the r5-era behavior).
    # BENCH_INPUT_DTYPE=bfloat16 narrows the assembled image batch at
    # copy-out (data/loader.py out_dtype), halving host->device image
    # bytes — the feed-side lever for the r19 input-path study.
    feed_mode = os.environ.get("BENCH_FEED", "")
    input_dtype = os.environ.get("BENCH_INPUT_DTYPE", "float32")
    if feed_mode == "stream":
        from distributed_tensorflow_tpu.data import device_batches
        from distributed_tensorflow_tpu.data.prefetch import prefetch

        depth = int(os.environ.get("BENCH_PREFETCH", "2"))
        stream = prefetch(
            device_batches(ds, mesh, global_batch, seed=0, out_dtype=input_dtype),
            depth,
        )
    elif feed_mode:
        raise SystemExit(f"BENCH_FEED must be '' or 'stream', got {feed_mode!r}")
    else:
        stream = None
        batch = coll.shard_batch({"image": ds.images, "label": ds.labels}, mesh)

    # A timed region ends when the device has finished the last step's
    # outputs, not when they were enqueued.
    def window(n_steps):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step(state, batch if stream is None else next(stream), rng)
        jax.block_until_ready((state, metrics))
        return time.perf_counter() - t0

    window(3)  # compile + 2 steady steps

    # Long windows minus short ones: the median difference cancels whatever
    # fixed cost ends a window, and the spread is reported.
    n_long, n_short = 60, 1
    reps = 3
    longs = sorted(window(n_long) for _ in range(reps))
    shorts = sorted(window(n_short) for _ in range(reps))
    per_step = (longs[reps // 2] - shorts[reps // 2]) / (n_long - n_short)
    spread = (longs[-1] - longs[0]) / longs[reps // 2]
    if stream is not None:
        stream.close()

    images_per_sec_chip = global_batch / per_step / n
    mfu = images_per_sec_chip * FLOPS_PER_IMAGE / peak
    peak_note = f"peak={peak / 1e12:.0f}T"
    # Ceiling context (docs/PERF.md r3 "measured roofline"): this model's
    # arithmetic intensity (~90 flops/byte at ideal traffic) x the chip's
    # measured ~650 GB/s HBM bandwidth caps MFU at ~0.30 on a v5e —
    # the 0.55 target presumes a bandwidth/FLOP ratio this chip lacks.
    # The r4 kernel campaign (docs/PERF.md "CASE CLOSED") measured seven
    # custom-kernel configurations, all losing to XLA's in-context codegen:
    # ~0.17 is the practical max for this conv+BN model on this chip. The
    # same engine reaches 0.60 MFU on matmul-dominated BERT at L=512
    # (bench_bert.py, r5 packed-flash config) — which is why the driver
    # default workload is the transformer since r5.
    ceil_note = (
        "meas-roofline-ceiling~0.30, practical-max~0.17 per docs/PERF.md r4 "
        "kernel study; driver default is the transformer workload since r5"
    )
    print(
        json.dumps(
            {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": round(images_per_sec_chip, 2),
                "unit": f"images/sec/chip (bf16, b={per_chip_batch}/chip, "
                f"{image_hw}x{image_hw}, {n}x {devices[0].device_kind}, "
                f"mfu={mfu:.3f}, median of {reps}x{n_long}-step windows, "
                f"spread={spread:.1%}, "
                + (
                    f"feed=stream+prefetch{stream.depth} in={input_dtype}, "
                    if stream is not None
                    else "feed=resident, "
                )
                + f"{peak_note}, {ceil_note})",
                "vs_baseline": round(mfu / 0.55, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
