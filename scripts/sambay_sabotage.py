"""Does the comparison that decides ``correct`` in the cell
``phi4_mini_flash.reason_steady`` catch a wrong computation? (PERF.md, PR 35.)

On the chip, at the published widths, two ways:

``cell``: the cell's own runner (``benchmarks/runners/serve_sambay.py::run``:
128 slots, the cell's traffic, one admission tier, ``_check`` and ``correct``)
on a copy of the
configuration that keeps the scan state in bfloat16 — the nearest precision
below the float32 the configuration states. Nothing else differs, so the
line it prints is the cell's, with ``correct`` false if the limit holds.

    python scripts/sambay_sabotage.py cell --variant state_bf16 --seed N [--seconds S]

``side``: in one process, the weights from one seed, then for the path as it
is and for six ways of getting it wrong — the scan state kept in bfloat16,
every projection's input rounded to float8 (the type below the configured
bfloat16), a window of 511, a window of 513, lambda dropped from the differential
attention, the conv tail taken at the padded end of the bucket — a small
engine (one bucket, eight slots) serves ``--streams`` prompts of ~160 tokens
for 640 tokens each through ``serve.Client``, and their streams are scored by
the runner's ``reference_gaps`` as the cell scores its own (the first and the
last ``check.positions / 2`` emitted tokens of each), against the reference
at the configuration's own sizes. One JSON line a variant: ``score_gaps``, to
be read against ``check.logit_tolerance`` of the cell. (The window and the
conv tail cannot go through ``cell``: there the reference is computed from
the same configuration as the served model.)

    python scripts/sambay_sabotage.py side [--seed N] [--only served,window_511] [--streams 8]
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL = "phi4_mini_flash.reason_steady"
CELL_FILE = ROOT / "benchmarks/workloads" / f"{CELL}.json"
VARIANTS = ("served", "state_bf16", "mxu_fp8", "window_511", "window_513",
            "lambda_dropped", "conv_tail_at_padded_end")


def _drop_lambda(params, n_layers: int, head_dim: int):
    """``lam = exp(q1 . k1) - exp(q2 . k2) + lam_init`` made 0 through the
    weights: ``q1 = 0`` and ``q2 . k2 = log(1 + lam_init)``."""
    import jax.numpy as jnp

    out = dict(params)
    for l in range(n_layers):
        mixer = params[f"layer_{l}"]["mixer"]
        if "lambda_q1" not in mixer:
            continue
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * l)
        c = math.sqrt(math.log(1.0 + lam_init) / head_dim)
        like = mixer["lambda_q1"]
        mixer = {
            **mixer,
            "lambda_q1": jnp.zeros_like(like),
            "lambda_q2": jnp.full_like(like, c),
            "lambda_k2": jnp.full_like(like, c),
        }
        out[f"layer_{l}"] = {**params[f"layer_{l}"], "mixer": mixer}
    return out


def _cell(args) -> int:
    """The cell's runner on a configuration that differs in ``--variant``."""
    from benchmarks import common
    from benchmarks.runners import serve_sambay as runner

    config = json.loads(Path(args.config).read_text())
    if args.variant == "state_bf16":
        config["run"]["state_dtype"] = "bfloat16"
    elif args.variant != "served":
        raise SystemExit(f"`cell` runs served or state_bf16, not {args.variant!r}")
    # one admission tier: four programs to compile, not ten; a row's state
    # does not depend on its batchmates
    config["serving"]["max_batch"] = 1
    result = runner.run(common.Run(
        name=CELL, workload=json.loads(Path(args.workload).read_text()),
        config=config,
        seed=args.seed, seconds=args.seconds, trace=False,
        t_start=time.monotonic(), trace_dir="",
    ))
    print(json.dumps({
        "variant": args.variant, "seed": args.seed, "seconds": args.seconds,
        "correct": bool(result["correct"]), "failed": result["failed"],
        "attempted": result["attempted"],
    }), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("cell", "side"))
    ap.add_argument("--seed", type=int, default=2147483929)
    ap.add_argument("--variant", default="state_bf16", help="cell: served or state_bf16")
    ap.add_argument("--seconds", type=float, default=40.0, help="cell: the window")
    ap.add_argument("--only", default=",".join(VARIANTS), help="side: which variants")
    ap.add_argument("--tokens", type=int, default=640)
    ap.add_argument("--bucket", type=int, default=256)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--config", default=str(
        ROOT / "benchmarks/configs/phi4_mini_flash.json"
    ), help="a toy file for a rehearsal on the CPU")
    ap.add_argument("--workload", default=str(CELL_FILE), help="likewise")
    args = ap.parse_args(argv)
    if args.mode == "cell":
        return _cell(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.runners import serve_sambay as runner
    from distributed_tensorflow_tpu.models import sambay
    from distributed_tensorflow_tpu.runtime import enable_compile_cache
    from distributed_tensorflow_tpu.serve import CausalLMEngine, Client
    from distributed_tensorflow_tpu.serve.batcher import BatcherConfig

    enable_compile_cache()
    config = json.loads(Path(args.config).read_text())
    check = json.loads(Path(args.workload).read_text())["check"]
    model = sambay.SambaY(runner.model_config(config))
    params = jax.jit(
        lambda key: sambay.sambay_init_params(
            model, key, jnp.dtype(config["run"]["weight_dtype"])
        )
    )(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(5, config["vocab_size"], n).astype(np.int32)
        # each padded to the one bucket: 131, 160, 187, 254 of 256, ...
        for n in (
            int((0.515, 0.625, 0.73, 0.995)[i % 4] * args.bucket) - i // 4
            for i in range(args.streams)
        )
    ]
    plain_call, plain_dense = sambay.Mamba.__call__, sambay._dense

    class Fp8Dense(type(plain_dense(model.cfg, 1))):
        """A projection whose input is rounded to float8 (e4m3) on its way
        into the MXU: the nearest type below the configured bfloat16."""

        def __call__(self, x):
            return super().__call__(
                x.astype(jnp.float8_e4m3fn).astype(self.dtype)
            )

    def fp8_dense(cfg, features, use_bias=False, **kw):
        like = plain_dense(cfg, features, use_bias, **kw)
        return Fp8Dense(**{
            f: getattr(like, f)
            for f in ("features", "use_bias", "dtype", "kernel_init", "dot_general")
        })

    for variant in args.only.split(","):
        overrides, served_params = {}, params
        sambay.Mamba.__call__, sambay._dense = plain_call, plain_dense
        if variant == "state_bf16":
            overrides["state_dtype"] = jnp.bfloat16
        elif variant == "mxu_fp8":
            sambay._dense = fp8_dense
        elif variant.startswith("window_"):
            overrides["sliding_window"] = config["sliding_window"] + (
                int(variant.split("_")[1]) - 512
            )
        elif variant == "lambda_dropped":
            served_params = _drop_lambda(
                params, config["num_hidden_layers"],
                config["hidden_size"] // config["num_attention_heads"],
            )
        elif variant == "conv_tail_at_padded_end":
            sambay.Mamba.__call__ = lambda self, h, mask, lengths: plain_call(
                self, h, mask, jnp.full_like(lengths, h.shape[1])
            )
        elif variant != "served":
            raise SystemExit(f"no variant {variant!r}")
        t0 = time.monotonic()
        engine = CausalLMEngine(
            sambay.SambaY(runner.model_config(config, **overrides)),
            served_params, None, buckets=(args.bucket,), slots=8, max_batch=1,
            max_new_tokens=args.tokens,
        )
        client = Client(engine, BatcherConfig(max_batch=1))
        try:
            futures = [
                client.submit({"input_ids": p, "max_new_tokens": args.tokens})
                for p in prompts
            ]
            streams = [
                (p, f.result(timeout=600)["tokens"])
                for p, f in zip(prompts, futures)
            ]
        finally:
            client.close()
        del engine, client
        # against the reference with the weights and sizes AS CONFIGURED
        gaps = runner.reference_gaps(config, params, streams, check["positions"])
        score = runner.score_gaps(gaps)
        print(json.dumps({
            "variant": variant, **score, "tolerance": check["logit_tolerance"],
            "fails": score["mean_logit_gap"] > check["logit_tolerance"],
            "tokens": [len(t) for _, t in streams],
            "seconds": time.monotonic() - t0,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
