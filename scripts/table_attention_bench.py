"""Times the length-aware decode-attention kernels (ops/decode_attention.py)
against the mask forms they replace (models/kvcache.py), on the chip:

    python scripts/table_attention_bench.py
        [--cell reasoning|longdoc|chat|docqa] [--blocks 64,128,256] [--seed N]

``reasoning``: ``table_attention`` against ``paired_attention`` at the
reasoning cell's geometry — 128 slots x 1,536 positions x 1,280 lanes in
bfloat16, 40 query heads, eight readers of one table; the lengths are the
cell's mid-run mix (a fifth of the slots idle, the rest a prompt of ~190 plus
a uniform share of an answer of 256-1,024), then every slot full, then every
slot idle. ``longdoc`` and ``chat``: ``row_attention`` against
``_attend(select_rows(..))`` over a stacked table, a reader a layer — four
layers of 16 x 4,608 x 3,840 (30 heads of 128; a fifth idle, the rest a
prompt of 512-4,096 plus a share of an answer of 128-512) and twelve of 128 x
384 x 768 (12 heads of 64; ten slots live). ``docqa``:
``latent_row_attention`` against ``latent_attention``'s mask form over the
stacked latent table, a reader a layer — seven layers of 128 x 4,608 x 640
(16 heads; 24 slots live, each a prompt of 512-4,096 plus a share of an
answer of 64-512). Prints one JSON line a case: ms
a call (median of 20 after a warm-up), the blocks moved and GB/s over them.
Refuses to run off the TPU: a time from the interpreter is not a time.
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
    latent_row_attention,
    row_attention,
    table_attention,
)

S, L, C, N_Q, D = 128, 1536, 1280, 40, 64
# the new-row form's cells: layers, slots, positions, heads, head size
ROW_CELLS = {"longdoc": (4, 16, 4608, 30, 128), "chat": (12, 128, 384, 12, 64)}
# the latent form's: layers, slots, positions, heads, row lanes, live slots
LATENT_CELL = (7, 128, 4608, 16, 640, 24)


def _time(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times)) * 1e3


def _report(cases, forms, args, whole: int, row_bytes: int, readers: int,
            sides: int = 2):
    """One line a case and form. ``cases``: name -> (what the read is given
    a slot, the table positions it has to move a slot); ``forms``: the jitted
    chains of ``readers`` reads, the mask form first; ``sides``: tables a
    block is read from (K and V, or one latent table)."""
    want = None
    for name, (given, held) in cases.items():
        n = jnp.asarray(given, jnp.int32)
        for form, fn in forms.items():
            ms = _time(fn, *args, n) / readers
            block = int(form[6:]) if form != "mask" else whole
            moved = int(np.sum(-(-held // block))) * block * row_bytes * sides
            out = np.asarray(fn(*args, n), np.float32)[held > 0]
            if form == "mask":
                want = out
            print(json.dumps({
                "case": name, "form": form, "ms_a_reader": round(ms, 4),
                "GB_moved": round(moved / 1e9, 4),
                "GB_per_s": round(moved / 1e9 / (ms / 1e3), 1) if ms else None,
                "live_positions": int(held.sum()),
                "max_abs_diff_to_mask": float(
                    np.abs(out - want).max(initial=0.0)
                ),
                "finite": bool(np.isfinite(out).all()),
            }), flush=True)


def _row_cell(cell: str, blocks, seed: int) -> None:
    """``row_attention`` a layer against the select and the mask form."""
    layers, s, l, n_q, d = ROW_CELLS[cell]
    rng = np.random.default_rng(seed)
    kq, kk, kv, kr = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (s, n_q, d), jnp.bfloat16)
    k = jax.random.normal(kk, (layers, s, l, n_q * d), jnp.bfloat16)
    v = jax.random.normal(kv, (layers, s, l, n_q * d), jnp.bfloat16)
    rows = jax.random.normal(kr, (2, s, n_q * d), jnp.bfloat16)
    if cell == "longdoc":
        mixed = np.minimum(
            rng.integers(512, 4097, s)
            + (rng.random(s) * rng.integers(128, 513, s)).astype(int), l - 1
        )
        mixed[rng.random(s) < 0.2] = l  # the sentinel: an idle lane
    else:
        mixed = np.full(s, l)
        mixed[rng.choice(s, 10, replace=False)] = rng.integers(8, 300, 10)
    cases = {
        name: (at, np.where(at < l, at, 0))  # the row itself is an operand
        for name, at in {
            "mixed": mixed, "full": np.full(s, l - 1), "idle": np.full(s, l),
        }.items()
    }

    def chain(read):
        def run(q, k, v, rows, position):
            out = jnp.zeros((s, n_q, d), jnp.float32)
            for layer in range(layers):
                qi = q + out.astype(q.dtype) * 1e-3
                out = read(qi, k, v, rows, position, layer).astype(
                    jnp.float32
                )
            return out
        return jax.jit(run)

    def mask_form(q, k, v, rows, position, layer):
        return kvcache.cached_attention(
            q, {"k": k[layer], "v": v[layer]}, position,
            {"k": rows[0], "v": rows[1]},
        )

    forms = {"mask": chain(mask_form)}
    for block in blocks:
        forms[f"kernel{block}"] = chain(
            lambda q, k, v, rows, at, layer, block=block: row_attention(
                q, k, v, at, rows[0], rows[1], layer=layer, block=block
            )
        )
    _report(cases, forms, (q, k, v, rows), l, n_q * d * 2, layers)


def _latent_cell(blocks, seed: int) -> None:
    """``latent_row_attention`` a layer against the mask form of
    ``latent_attention`` over that layer."""
    layers, s, l, n_q, r, live = LATENT_CELL
    rng = np.random.default_rng(seed)
    kq, kt, kr = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (s, n_q, r), jnp.bfloat16)
    table = jax.random.normal(kt, (layers, s, l, r), jnp.bfloat16)
    row = jax.random.normal(kr, (s, r), jnp.bfloat16)
    scale = 0.114721  # DeepSeek-V2-Lite's softmax scale
    mixed = np.full(s, l)
    mixed[rng.choice(s, live, replace=False)] = np.minimum(
        rng.integers(512, 4097, live)
        + (rng.random(live) * rng.integers(64, 513, live)).astype(int), l - 1
    )
    cases = {
        name: (at, np.where(at < l, at, 0))
        for name, at in {
            "mixed": mixed, "full": np.full(s, l - 1), "idle": np.full(s, l),
        }.items()
    }

    def chain(read):
        def run(q, table, row, position):
            out = jnp.zeros((s, n_q, r), jnp.float32)
            for layer in range(layers):
                qi = q + out.astype(q.dtype) * 1e-3
                out = read(qi, table, row, position, layer)
            return out
        return jax.jit(run)

    forms = {"mask": chain(
        lambda q, table, row, at, layer: kvcache.latent_attention(
            q, table[layer], at, row, scale
        )
    )}
    for block in blocks:
        forms[f"kernel{block}"] = chain(
            lambda q, table, row, at, layer, block=block: latent_row_attention(
                q, table, at, row, layer=layer, scale=scale, block=block
            )
        )
    _report(cases, forms, (q, table, row), l, r * 2, layers, sides=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="reasoning",
                    choices=["reasoning", *ROW_CELLS, "docqa"])
    ap.add_argument("--blocks", default="64,128,256")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        sys.exit("table_attention_bench measures the chip; this is "
                 f"{jax.default_backend()}")
    blocks = [int(b) for b in args.blocks.split(",")]
    if args.cell == "docqa":
        _latent_cell(blocks, args.seed)
        return 0
    if args.cell != "reasoning":
        _row_cell(args.cell, blocks, args.seed)
        return 0
    rng = np.random.default_rng(args.seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(args.seed), 3)
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
        name: (lengths, lengths) for name, lengths in {
            "mixed": mixed, "full": np.full(S, L), "idle": np.zeros(S, int),
        }.items()
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
    for block in blocks:
        forms[f"kernel{block}"] = chain(
            lambda q, k, v, n, block=block: table_attention(
                q, k, v, n, lam, block=block
            )
        )
    _report(cases, forms, (q, k, v), L, C * 2, 8)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
