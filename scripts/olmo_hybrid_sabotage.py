"""Does the comparison that decides ``correct`` in the cell
``olmo_hybrid_7b.longdoc_steady`` catch a wrong computation? (PERF.md, PR 37.)

:func:`sabotaged` makes models/olmo_hybrid.py compute one thing wrong for as
long as the ``with`` lasts: ``beta`` without its factor 2, ``alpha`` dropped
(no decay), ``q`` and ``k`` left unnormalised, the matrix state not carried
across the first chunk boundary, the conv tail taken at a chunk's padded end,
the K norm of full attention left out, every projection's input rounded to
float8 on its way into the MXU (the type below the configured bfloat16). The
reference is not touched, so a run of the cell's runner inside it must print
``correct: false`` (benchmarks/tests/test_serve_olmo_hybrid.py does that at a
toy size on the CPU).

On the chip, at the published widths, in one process: the weights from one
seed, then for the path as it is and for each wrong one a small engine (the
cell's chunk of 512, eight slots) serves ``--streams`` prompts of 1,800-2,300
tokens for 256 tokens each through ``serve.Client``, and their streams are
scored by the runner's ``reference_gaps`` as the cell scores its own. One JSON
line a variant: ``score_gaps``, to be read against ``check.logit_tolerance``.

    python scripts/olmo_hybrid_sabotage.py [--seed N] [--only served,alpha_dropped] [--streams 8]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL = "olmo_hybrid_7b.longdoc_steady"
VARIANTS = ("served", "beta_without_2", "alpha_dropped", "qk_unnormalised",
            "state_not_carried", "conv_tail_at_padded_end", "k_norm_left_out",
            "mxu_fp8")


@contextlib.contextmanager
def sabotaged(variant: str):
    """models/olmo_hybrid.py with ``variant`` wrong, restored on the way out."""
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import olmo_hybrid as m

    saved = {
        (m, "_unit"): m._unit, (m, "_dense"): m._dense,
        (m.DeltaMixer, "_project"): m.DeltaMixer._project,
        (m.DeltaMixer, "__call__"): m.DeltaMixer.__call__,
        (m.FullAttention, "project"): m.FullAttention.project,
        (m.OlmoHybrid, "prefill_chunk"): m.OlmoHybrid.prefill_chunk,
    }
    plain = {name: fn for (_, name), fn in saved.items()}
    if variant == "beta_without_2":
        def project(self, x):
            qkv, z, g, beta = plain["_project"](self, x)
            return qkv, z, g, 0.5 * beta
        m.DeltaMixer._project = project
    elif variant == "alpha_dropped":
        def project(self, x):
            qkv, z, g, beta = plain["_project"](self, x)
            return qkv, z, jnp.zeros_like(g), beta
        m.DeltaMixer._project = project
    elif variant == "qk_unnormalised":
        m._unit = lambda x: x
    elif variant == "state_not_carried":
        def prefill_chunk(self, input_ids, positions, cache):
            second = positions[:, 0] == input_ids.shape[1]
            ssm = cache["state"]["ssm"]
            ssm = jnp.where(second.reshape(1, -1, 1, 1, 1), 0, ssm)
            state = {**cache["state"], "ssm": ssm.astype(ssm.dtype)}
            return plain["prefill_chunk"](
                self, input_ids, positions, {**cache, "state": state}
            )
        m.OlmoHybrid.prefill_chunk = prefill_chunk
    elif variant == "conv_tail_at_padded_end":
        def call(self, x, mask, state):
            out, new = plain["__call__"](self, x, mask, state)
            taps = self.cfg.linear_conv_kernel_dim - 1
            old = state["conv"].reshape(x.shape[0], taps, -1)
            padded = jnp.concatenate([old, self._project(x)[0]], axis=1)
            return out, {**new, "conv": padded[:, -taps:].reshape(
                state["conv"].shape)}
        m.DeltaMixer.__call__ = call
    elif variant == "k_norm_left_out":
        def project(self, x):
            q, kv = plain["project"](self, x)
            k = jnp.split(self.qkv(x), 3, axis=-1)[1]
            return q, {**kv, "k": k.astype(kv["k"].dtype)}
        m.FullAttention.project = project
    elif variant == "mxu_fp8":
        class Fp8Dense(type(plain["_dense"](m.OlmoHybridConfig(), 1))):
            def __call__(self, x):
                # saturating, as a float8 pipeline casts: e4m3 has no
                # infinity, and 448 is passed deep in a gated FFN
                x = jnp.clip(x, -448.0, 448.0)
                return super().__call__(
                    x.astype(jnp.float8_e4m3fn).astype(self.dtype)
                )

        def dense(cfg, features):
            like = plain["_dense"](cfg, features)
            return Fp8Dense(**{
                f: getattr(like, f) for f in
                ("features", "use_bias", "dtype", "kernel_init", "dot_general")
            })
        m._dense = dense
    elif variant != "served":
        raise SystemExit(f"no variant {variant!r}")
    try:
        yield
    finally:
        for (owner, name), fn in saved.items():
            setattr(owner, name, fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483929)
    ap.add_argument("--only", default=",".join(VARIANTS))
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--config", default=str(
        ROOT / "benchmarks/configs/olmo_hybrid_7b.json"
    ), help="a toy file for a rehearsal on the CPU")
    ap.add_argument("--workload", default=str(
        ROOT / "benchmarks/workloads" / f"{CELL}.json"), help="likewise")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.runners import serve_olmo_hybrid as runner
    from distributed_tensorflow_tpu.models import olmo_hybrid
    from distributed_tensorflow_tpu.runtime import enable_compile_cache
    from distributed_tensorflow_tpu.serve import CausalLMEngine, Client
    from distributed_tensorflow_tpu.serve.batcher import BatcherConfig

    enable_compile_cache()
    config = json.loads(Path(args.config).read_text())
    check = json.loads(Path(args.workload).read_text())["check"]
    serving = config["serving"]
    model = olmo_hybrid.OlmoHybrid(runner.model_config(config))
    params = jax.jit(
        lambda key: olmo_hybrid.olmo_hybrid_init_params(
            model, key, jnp.dtype(config["run"]["weight_dtype"])
        )
    )(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    chunk = serving["prefill_chunk"]
    # three and a half to four and a half chunks, the last one partial
    lengths = [int((3.55 + 0.13 * i) * chunk) - i for i in range(args.streams)]
    prompts = [
        rng.integers(5, config["vocab_size"], n).astype(np.int32)
        for n in lengths
    ]
    scored = min(check["positions"], args.tokens)
    for variant in args.only.split(","):
        t0 = time.monotonic()
        with sabotaged(variant):
            engine = CausalLMEngine(
                olmo_hybrid.OlmoHybrid(runner.model_config(config)), params,
                None, buckets=tuple(serving["buckets"]), slots=8, max_batch=1,
                max_new_tokens=serving["max_new_tokens"], prefill_chunk=chunk,
            )
            client = Client(engine, BatcherConfig(max_batch=1))
            try:
                futures = [
                    client.submit({"input_ids": p, "max_new_tokens": args.tokens})
                    for p in prompts
                ]
                streams = [
                    (p, f.result(timeout=900)["tokens"])
                    for p, f in zip(prompts, futures)
                ]
            finally:
                client.close()
            engine.release_cache()
        # against the reference, which no variant touches
        gaps = runner.reference_gaps(config, params, streams, scored)
        score = runner.score_gaps(gaps)
        print(json.dumps({
            "variant": variant, **score, "tolerance": check["logit_tolerance"],
            "fails": score["mean_logit_gap"] > check["logit_tolerance"],
            "prompts": lengths, "seconds": time.monotonic() - t0,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
