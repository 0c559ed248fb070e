"""Flash-attention vs dense attention on the real TPU (VERDICT r1 weak #5).

Runs the Pallas kernel compiled (not interpreted) on TPU, checks numerics
against dense_attention, and times fwd+bwd at BERT-base geometry for
L in {512, 2048}. Output decides the bert_base preset's attn_impl default.

    python scripts/flash_bench.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def bench(f, *args, n=40):
    """Median of 3 n-dispatch windows minus a 1-dispatch window: cancels the
    fixed cost of ending a window (scripts/roofline.py methodology)."""

    def window(k):
        t0 = time.perf_counter()
        for _ in range(k):
            out = f(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    window(2)  # compile + warm
    longs = sorted(window(n) for _ in range(3))
    shorts = sorted(window(1) for _ in range(3))
    return (longs[1] - shorts[1]) / (n - 1)


def main():
    from distributed_tensorflow_tpu.ops.flash_attention import flash_attention
    from distributed_tensorflow_tpu.parallel.ring_attention import dense_attention

    on_tpu = jax.default_backend() == "tpu"
    print(f"backend={jax.default_backend()} devices={jax.devices()}")
    B, H, D = 8, 12, 64
    rng = np.random.default_rng(0)
    results = {}
    for L in (512, 2048):
        q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16)
        mask = jnp.ones((B, L), bool)

        def loss_dense(q, k, v):
            return dense_attention(q, k, v, mask).astype(jnp.float32).sum()

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, mask).astype(jnp.float32).sum()

        # Correctness on this backend (compiled kernel on TPU).
        od = dense_attention(q, k, v, mask)
        of = flash_attention(q, k, v, mask)
        err = float(jnp.max(jnp.abs(od.astype(jnp.float32) - of.astype(jnp.float32))))
        print(f"L={L}: max|dense-flash| = {err:.4f}")
        assert err < 0.1, "flash kernel diverges from dense"

        fd = jax.jit(jax.value_and_grad(loss_dense, argnums=(0, 1, 2)))
        td = bench(fd, q, k, v)
        # attention flops: 2 matmuls fwd (2*B*H*L^2*D each x2 flops) + ~2.5x bwd
        flops = 3.5 * 2 * 2 * B * H * L * L * D
        print(f"L={L}: dense {td * 1e3:.2f} ms ({flops / td / 1e12:.1f} TF/s)",
              flush=True)
        best = None
        for bq in (128, 256, 512):
            for bk in (128, 256, 512):
                if bq > L or bk > L:
                    continue

                def loss_flash_b(q, k, v, bq=bq, bk=bk):
                    return (
                        flash_attention(q, k, v, mask, block_q=bq, block_k=bk)
                        .astype(jnp.float32)
                        .sum()
                    )

                ff = jax.jit(jax.value_and_grad(loss_flash_b, argnums=(0, 1, 2)))
                tf_ = bench(ff, q, k, v)
                print(
                    f"  flash bq={bq} bk={bk}: {tf_ * 1e3:.2f} ms "
                    f"({flops / tf_ / 1e12:.1f} TF/s) speedup x{td / tf_:.2f}",
                    flush=True,
                )
                if best is None or tf_ < best[0]:
                    best = (tf_, bq, bk)
        results[L] = (td, best[0])
        print(f"L={L}: best flash bq={best[1]} bk={best[2]} "
              f"x{td / best[0]:.2f} vs dense", flush=True)
    if on_tpu:
        rec = "flash" if all(tf_ <= td for td, tf_ in results.values()) else "dense"
        print(f"at L>=512 the winner is: attn_impl={rec} "
              "(attn_impl='auto' applies the measured L>=256 crossover)")


if __name__ == "__main__":
    main()
