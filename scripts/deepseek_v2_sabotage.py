"""Does the comparison that decides ``correct`` in the cell
``deepseek_v2_lite.docqa_steady`` catch a wrong computation? (PERF.md, Findings.)

:func:`sabotaged` makes models/deepseek_v2.py compute one thing wrong for as
long as the ``with`` lasts: the attention's scale without YaRN's ``m^2``,
plain rotary frequencies in place of YaRN's, ``k^pe`` rotated at its
position inside the chunk instead of its absolute one, the latent's RMSNorm
(``kv_a_layernorm``) left out, the top-6 gates renormalised, the shared
experts left out, the routed experts through ``moe_apply``'s capacity of
1.25 (rows over it dropped), the latent table in float8_e4m3 (the type below
the configured bfloat16); and, as the control of the precision, the whole
served model one precision below its configured bfloat16
(``precision_below``: every weight rounded to float8_e4m3 and the latent
table in it). The reference is not touched, so a run of the
cell's runner inside it must print ``correct: false``
(benchmarks/tests/test_serve_deepseek_v2.py does that at a toy size on the
CPU).

On the chip, at the published widths, in one process: the weights from one
seed, then for the path as it is and for each wrong one a small engine (the
cell's chunk of 512, eight slots) serves ``--streams`` prompts of 1,800-2,300
tokens for 256 tokens each through ``serve.Client``, and their streams are
scored by the runner's ``reference_gaps`` as the cell scores its own. One JSON
line a variant: ``score_gaps``, to be read against ``check.logit_tolerance``
(or the variant's ``error``: a variant that fails does not stop the rest).

    python scripts/deepseek_v2_sabotage.py [--seed N] [--only served,plain_rope] [--streams 8]
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL = "deepseek_v2_lite.docqa_steady"
VARIANTS = ("served", "sigma_without_m2", "plain_rope", "k_pe_at_chunk_position",
            "kv_a_norm_left_out", "topk_renormalised", "shared_experts_left_out",
            "capacity_dropping", "latent_fp8", "precision_below")


def _to_float8(params):
    """Every floating weight rounded to float8_e4m3fn, kept in its own type,
    in the place of ``params``: one weight at a time, donated, so the chip
    holds one copy of the 8 GB and one weight's scratch."""
    import jax
    import jax.numpy as jnp

    rounded = jax.jit(lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype),
                      donate_argnums=0)
    return jax.tree.map(
        lambda x: rounded(x) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


@contextlib.contextmanager
def sabotaged(variant: str):
    """models/deepseek_v2.py with ``variant`` wrong, restored on the way out.
    Yields what the served weights become; ``precision_below``'s takes the
    caller's, who draws them again from the seed for the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.models import deepseek_v2 as m
    from distributed_tensorflow_tpu.parallel import moe

    saved = {
        (m, "softmax_scale"): m.softmax_scale,
        (m, "yarn_frequencies"): m.yarn_frequencies,
        (m, "moe_dropless"): m.moe_dropless,
        (m.LatentAttention, "project"): m.LatentAttention.project,
        (m.MoE, "__call__"): m.MoE.__call__,
        (m.DeepseekV2, "cache_layout"): m.DeepseekV2.cache_layout,
    }
    plain = {name: fn for (_, name), fn in saved.items()}
    if variant == "sigma_without_m2":
        m.softmax_scale = lambda cfg: cfg.q_head_dim ** -0.5
    elif variant == "plain_rope":
        def frequencies(cfg):
            half = cfg.qk_rope_head_dim // 2
            return (cfg.rope_theta ** (-np.arange(half) / half)).astype(np.float32)
        m.yarn_frequencies = frequencies
    elif variant == "k_pe_at_chunk_position":
        def project(self, x, positions):
            q_nope, q_pe, row = plain["project"](self, x, positions)
            if positions.ndim == 2:  # a prompt's chunk: from its first lane
                _, _, rel = plain["project"](self, x, positions - positions[:, :1])
                lanes = slice(self.cfg.kv_lora_rank, self.cfg.latent_width)
                row = row.at[..., lanes].set(rel[..., lanes])
            return q_nope, q_pe, row
        m.LatentAttention.project = project
    elif variant == "kv_a_norm_left_out":
        def project(self, x, positions):
            q_nope, q_pe, row = plain["project"](self, x, positions)
            r = self.cfg.kv_lora_rank
            return q_nope, q_pe, row.at[..., :r].set(self.kv_a(x)[..., :r])
        m.LatentAttention.project = project
    elif variant == "topk_renormalised":
        m.moe_dropless = lambda *a, **kw: plain["moe_dropless"](
            *a, **{**kw, "renormalize": True})
    elif variant == "shared_experts_left_out":
        def call(self, u):
            y, choice = plain["__call__"](self, u)
            g, up = jnp.split(self.shared_gate_up(u), 2, axis=-1)
            return y - self.shared_down(jax.nn.silu(g) * up), choice
        m.MoE.__call__ = call
    elif variant == "capacity_dropping":
        def capped(x, logits, experts, k, **_):
            def ffn(p, t):
                g, u = jnp.split(jnp.dot(t, p["gate_up"],
                                         preferred_element_type=jnp.float32), 2, -1)
                h = (jax.nn.silu(g) * u).astype(t.dtype)
                return jnp.dot(h, p["down"], preferred_element_type=jnp.float32
                               ).astype(t.dtype)
            y, _ = moe.moe_apply(ffn, experts, logits, x, axis_name=None,
                                 capacity_factor=1.25, topk=k)
            return y.astype(jnp.float32), jax.lax.top_k(logits, k)[1]
        m.moe_dropless = capped
    elif variant in ("latent_fp8", "precision_below"):
        m.DeepseekV2.cache_layout = lambda self, kv_dtype: plain["cache_layout"](
            self, "float8_e4m3fn")
    elif variant != "served":
        raise SystemExit(f"no variant {variant!r}")
    try:
        yield _to_float8 if variant == "precision_below" else (lambda p: p)
    finally:
        for (owner, name), fn in saved.items():
            setattr(owner, name, fn)


def _serve(variant, config, params, prompts, tokens):
    """``[(prompt, emitted tokens)]`` of ``prompts`` served by a small engine
    (eight slots, the cell's chunk) with ``variant`` wrong. Weights the
    variant made in the place of ``params`` are deleted on the way out: the
    chip holds the weights once, and the caller draws them again."""
    import jax

    from benchmarks.runners import serve_deepseek_v2 as runner
    from distributed_tensorflow_tpu.models import deepseek_v2
    from distributed_tensorflow_tpu.serve import CausalLMEngine, Client
    from distributed_tensorflow_tpu.serve.batcher import BatcherConfig

    serving = config["serving"]
    with sabotaged(variant) as served:
        weights = served(params)
        engine = CausalLMEngine(
            deepseek_v2.DeepseekV2(runner.model_config(config)), weights,
            None, buckets=tuple(serving["buckets"]), slots=8, max_batch=1,
            max_new_tokens=serving["max_new_tokens"],
            prefill_chunk=serving["prefill_chunk"],
        )
        client = Client(engine, BatcherConfig(max_batch=1))
        try:
            futures = [
                client.submit({"input_ids": p, "max_new_tokens": tokens})
                for p in prompts
            ]
            return [(p, f.result(timeout=900)["tokens"])
                    for p, f in zip(prompts, futures)]
        finally:
            client.close()
            engine.release_cache()
            if weights is not params:  # rounded in the caller's place: let go
                for x in jax.tree.leaves(weights):
                    x.delete()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483929)
    ap.add_argument("--only", default=",".join(VARIANTS))
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--config", default=str(
        ROOT / "benchmarks/configs/deepseek_v2_lite.json"
    ), help="a toy file for a rehearsal on the CPU")
    ap.add_argument("--workload", default=str(
        ROOT / "benchmarks/workloads" / f"{CELL}.json"), help="likewise")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.runners import serve_deepseek_v2 as runner
    from distributed_tensorflow_tpu.models import deepseek_v2
    from distributed_tensorflow_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    config = json.loads(Path(args.config).read_text())
    check = json.loads(Path(args.workload).read_text())["check"]
    serving = config["serving"]
    model = deepseek_v2.DeepseekV2(runner.model_config(config))
    init = jax.jit(
        lambda key: deepseek_v2.deepseek_v2_init_params(
            model, key, jnp.dtype(config["run"]["weight_dtype"])
        )
    )
    params = init(jax.random.key(args.seed))
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
        gc.collect()  # the last variant's engine, and its programs
        t0 = time.monotonic()
        try:
            streams = _serve(variant, config, params, prompts, args.tokens)
            if variant == "precision_below":  # its weights were rounded in place
                params = init(jax.random.key(args.seed))
            # against the reference, which no variant touches
            gaps, routing = runner.reference_gaps(config, model, params,
                                                  streams, scored)
        except Exception as e:  # noqa: BLE001 - reported, and the next one runs
            print(json.dumps({"variant": variant, "error": repr(e)[:2000],
                              "seconds": time.monotonic() - t0}), flush=True)
            continue
        score = runner.score_gaps(gaps)
        print(json.dumps({
            "variant": variant, **score, "tolerance": check["logit_tolerance"],
            "fails": score["mean_logit_gap"] > check["logit_tolerance"],
            "routing": routing, "prompts": lengths,
            "seconds": time.monotonic() - t0,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
