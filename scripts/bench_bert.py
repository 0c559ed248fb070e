"""BERT-base pretraining throughput + MFU on the real chip (VERDICT r2 #3).

The reference's transformer workload (BASELINE.json:11) gets its own
number: full MLM+NSP train step (fwd+bwd+psum+AdamW), bf16 compute,
synthetic token batches, at L=128 and L=512. Matmul-dominated, so this also
bounds how much of the ResNet-50 MFU gap is conv/BN-specific vs framework
overhead (docs/PERF.md r3: ResNet's ceiling is HBM-bandwidth ~0.30; BERT's
arithmetic intensity is far higher, so its MFU should approach the MXU
roofline if the framework isn't the problem).

FLOPs accounting (exact matmul inventory per token, fwd; train = 3x):
  per layer: QKVO 8d^2 + FFN 4*d*ff;  attention 4*L*d
  heads: MLM transform 2d^2 + tied decoder 2*d*V  (computed at every
  position, as the model does)
Embedding lookups/LayerNorms/softmax excluded (not matmuls) — consistent
with the standard 6ND convention, making the reported MFU mildly
conservative.

    python scripts/bench_bert.py            # both geometries
    BENCH_WORKLOAD=bert python bench.py     # driver-compatible single line
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bench import chip_peak_flops, require_tpu


def train_flops_per_token(cfg, L: int) -> float:
    d, ff, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    per_layer = 8 * d * d + 4 * d * ff + 4 * L * d
    fwd = cfg.num_layers * per_layer + 2 * d * d + 2 * d * V
    return 3.0 * fwd


def build_step(L: int, per_chip_batch: int, attn_impl: str = "dense"):
    """The benched program: BERT-base's production train step with one
    resident synthetic batch. Returns ``(step, state, batch, rng, cfg)``;
    ``step(state, batch, rng) -> (state, metrics)`` donates the state."""
    from distributed_tensorflow_tpu.models.bert import (
        BertForPreTraining,
        bert_base,
        make_bert_pretraining_loss,
    )
    from distributed_tensorflow_tpu.parallel import collectives as coll
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.step import place_state

    gb = per_chip_batch * len(require_tpu())
    mesh = build_mesh({"data": -1})
    cfg = bert_base(dtype=jnp.bfloat16, max_position=max(512, L), attn_impl=attn_impl)
    model = BertForPreTraining(cfg)
    rng0 = np.random.default_rng(0)
    ids = rng0.integers(0, cfg.vocab_size, size=(gb, L)).astype(np.int32)
    mlm_targets = np.where(
        rng0.random((gb, L)) < 0.15,
        rng0.integers(0, cfg.vocab_size, size=(gb, L)),
        -1,
    ).astype(np.int32)
    batch = coll.shard_batch(
        {
            "input_ids": ids,
            "attention_mask": np.ones((gb, L), np.int32),
            "token_type_ids": np.zeros((gb, L), np.int32),
            "mlm_targets": mlm_targets,
            "nsp_label": rng0.integers(0, 2, size=(gb,)).astype(np.int32),
        },
        mesh,
    )
    params = model.init(
        jax.random.key(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), jnp.int32),
        jnp.zeros((1, L), jnp.int32),
        train=False,
    )["params"]
    tx = optax.adamw(1e-4, weight_decay=0.01)
    state = place_state(create_train_state(params, tx, {}), mesh)
    # clip_norm=1.0: the canonical BERT recipe the CLI preset trains with —
    # the benched step is the production step (r5; earlier rounds measured
    # without the clip reduce, a ~1 ms/step difference).
    step = make_train_step(
        make_bert_pretraining_loss(model), tx, mesh, clip_norm=1.0
    )
    # The trainer's PRNG policy (rbg on TPU): dropout RNG is real work at
    # this geometry — threefry costs +36 ms/step (245.1 vs 208.9 ms
    # measured r5, L=512 b=48) generating ~100M dropout bits in software.
    from distributed_tensorflow_tpu.train import make_rng

    return step, state, batch, make_rng(0), cfg


def bench_config(
    L: int, per_chip_batch: int, n_long: int = 40, attn_impl: str = "dense"
) -> dict:
    devices = require_tpu()
    peak = chip_peak_flops(devices[0])  # an unknown chip fails before the run
    step, state, batch, rng, cfg = build_step(L, per_chip_batch, attn_impl)
    n = len(devices)
    gb = per_chip_batch * n

    def window(k):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(k):
            state, metrics = step(state, batch, rng)
        jax.block_until_ready((state, metrics))
        return time.perf_counter() - t0

    window(3)  # compile + warm
    reps = 3
    longs = sorted(window(n_long) for _ in range(reps))
    shorts = sorted(window(1) for _ in range(reps))
    per_step = (longs[reps // 2] - shorts[reps // 2]) / (n_long - 1)
    spread = (longs[-1] - longs[0]) / longs[reps // 2]

    tokens_per_sec_chip = gb * L / per_step / n
    mfu = tokens_per_sec_chip * train_flops_per_token(cfg, L) / peak
    return {
        "L": L,
        "attn": attn_impl,
        "per_chip_batch": per_chip_batch,
        "ms_per_step": round(per_step * 1e3, 2),
        "tokens_per_sec_per_chip": round(tokens_per_sec_chip, 0),
        "mfu": round(mfu, 4),
        "spread": round(spread, 4),
    }


def main():
    from distributed_tensorflow_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    results = [
        bench_config(128, 64, attn_impl="auto"),   # auto -> dense at 128
        bench_config(512, 24, attn_impl="auto"),   # auto -> flash at 512
        # b=64 / b=24 won the latest re-sweeps (knees MOVE when step
        # overhead falls: r4 b=128/48 -> recipe campaign b=64/32 ->
        # packed flash kernels b=64/24 — docs/PERF.md r5 tables; same
        # configs driver_line reports)
    ]
    for r in results:
        print(json.dumps(r))
    return results


def driver_line():
    """One-line JSON for the driver protocol (bench.py's r5 default)."""
    # b=24/chip won the sweep under the r5 PACKED flash kernels (mfu
    # 0.586 @ 16, 0.600 @ 24, 0.585 @ 32, 0.564 @ 48; b=24 re-measured
    # at 0.6003 with 0.05% spread over 60-step windows). Knee history —
    # it moves every time the step gets leaner: r4 recipe b=48 ->
    # recipe campaign b=32 -> layout-native flash b=24 (docs/PERF.md r5
    # bucket tables and the packed-kernel section).
    r = bench_config(512, 24, attn_impl="auto")  # auto -> flash at L=512
    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "metric": "bert_base_train_tokens_per_sec_per_chip",
                "value": r["tokens_per_sec_per_chip"],
                "unit": f"tokens/sec/chip (bf16, L=512, b={r['per_chip_batch']}/chip, "
                f"flash attn, AdamW+clip1.0, rbg dropout rng, {dev.device_kind}, "
                f"mfu={r['mfu']:.3f}, median windows, spread={r['spread']:.1%}, "
                f"peak={chip_peak_flops(dev) / 1e12:.0f}T; "
                f"conv context: resnet50 mfu~0.17 structural plateau "
                f"via BENCH_WORKLOAD=resnet50, docs/PERF.md)",
                "vs_baseline": round(r["mfu"] / 0.55, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
