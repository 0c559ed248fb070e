"""Times the length-aware decode-attention kernel (ops/decode_attention.py)
against the mask form it replaces (models/kvcache.paired_attention) at the
reasoning cell's geometry, on the chip:

    python scripts/table_attention_bench.py [--blocks 64,128,256] [--seed N]

128 slots x 1,536 positions x 1,280 lanes in bfloat16, 40 query heads; the
lengths are the cell's mid-run mix (a fifth of the slots idle, the rest a
prompt of ~190 plus a uniform share of an answer of 256-1,024), then every
slot full, then every slot idle. Prints one JSON line a case: ms a call
(median of 20 after a warm-up), the blocks moved and GB/s over them. Refuses
to run off the TPU: a time from the interpreter is not a time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tensorflow_tpu.models import kvcache  # noqa: E402
from distributed_tensorflow_tpu.ops.decode_attention import (  # noqa: E402
    table_attention,
)

S, L, C, N_Q, D = 128, 1536, 1280, 40, 64


def _time(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times)) * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", default="64,128,256")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit("table_attention_bench measures the chip; this is "
                 f"{jax.default_backend()}")
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (S, N_Q, D), jnp.bfloat16)
    k = jax.random.normal(kk, (S, L, C), jnp.bfloat16)
    v = jax.random.normal(kv, (S, L, C), jnp.bfloat16)
    lam = jnp.float32(0.4)
    answer = rng.integers(256, 1025, S)
    mixed = np.minimum(
        rng.integers(32, 400, S) + (rng.random(S) * answer).astype(int), L
    )
    mixed[rng.random(S) < 0.2] = 0
    cases = {
        "mixed": mixed, "full": np.full(S, L), "idle": np.zeros(S, int),
    }
    # eight readers in one program, each one's query the last one's output,
    # as the step has them
    def chain(read):
        def run(q, k, v, lengths):
            out = jnp.zeros((S, N_Q // 2, 2 * D), jnp.float32)
            for _ in range(8):
                qi = q + out.reshape(S, N_Q, D).astype(q.dtype) * 1e-3
                out = read(qi, k, v, lengths)
            return out
        return jax.jit(run)

    def mask_form(q, k, v, lengths):
        valid = jnp.arange(L) < lengths[:, None]
        return kvcache.paired_attention(q, {"k": k, "v": v}, valid, lam)

    forms = {"mask": chain(mask_form)}
    for block in (int(b) for b in args.blocks.split(",")):
        forms[f"kernel{block}"] = chain(
            lambda q, k, v, n, block=block: table_attention(
                q, k, v, n, lam, block=block
            )
        )
    want = None
    for name, lengths in cases.items():
        n = jnp.asarray(lengths, jnp.int32)
        for form, fn in forms.items():
            ms = _time(fn, q, k, v, n) / 8
            block = int(form[6:]) if form != "mask" else L
            moved = int(np.sum(-(-lengths // block))) * block * C * 2 * 2
            out = np.asarray(fn(q, k, v, n), np.float32)
            if form == "mask":
                want = out
            print(json.dumps({
                "case": name, "form": form, "ms_a_reader": round(ms, 4),
                "GB_moved": round(moved / 1e9, 4),
                "GB_per_s": round(moved / 1e9 / (ms / 1e3), 1) if ms else None,
                "live_positions": int(lengths.sum()),
                "max_abs_diff_to_mask": float(np.abs(out - want).max()),
                "finite": bool(np.isfinite(out).all()),
            }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
