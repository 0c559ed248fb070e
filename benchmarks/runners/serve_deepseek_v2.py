"""Runner ``serve_deepseek_v2``: runners/serve.py's open-loop traffic against
a DeepSeek-V2 decoder (models/deepseek_v2.py; DeepSeek-V2-Lite cut in depth)
served through ``CausalLMEngine`` and ``serve.Client`` with CHUNKED prefill:
multi-head latent attention whose one 576-lane row a position is the whole
cache, and 64 routed experts, dropless top-6, beside two shared ones.

What differs from ``serve_olmo_hybrid`` is the model that is built, what the
info line says of the step and of the routing, and the reference: the
generator, the offer, the reduction, the warm-up and the sweep are imported
from runners/serve.py as they are, ``score_gaps`` from runners/serve_sambay.py,
and ``requests`` (one order of arrivals and sizes for every seed, from
``shape_seed``), ``_probe`` and ``_scratch_bytes`` from
runners/serve_olmo_hybrid.py. ``correct`` = no failed request, no compilation
inside the window, one prompt served twice gives the same tokens, and
agreement with the reference: for ``check.requests`` seeded finished requests
of at least ``check.reach`` positions, prompt + emitted tokens go
teacher-forced through benchmarks/references/deepseek_v2_lite.py (plain
``jax.numpy``, float32, 'highest' matmul precision, latent attention
DECOMPRESSED where the served decode is absorbed, every expert over every
token, no cache, no code shared with the model), and at ``check.positions``
emitted tokens of each — the first half right after the prompt, the last half
where the answer has run longest — the token's logit is read against that
position's maximum IN THE REFERENCE'S LOGITS; the larger half's MEAN gap must
not pass ``check.logit_tolerance`` (``score_gaps``). On the chip the float32
model is 16 GB, so it is never whole: the engine's cache is let go first, and
the reference runs layer by layer with one layer's weights in float32 at a
time (an MoE layer is 2.34 GB), one sequence at a time padded to the cache's
length, the head applied to the scored positions only, in blocks of the
vocabulary.

Routing, on the info line (``check.routing``): at the scored positions, how
many rows each expert received in the reference (max and mean, per MoE
layer), and how many of the top-6 choices the served model's own forward
(``DeepseekV2.routes``: the served weights and precision, teacher-forced)
made differently from the reference's, with the reference's margin between
its sixth and seventh probability where they differ: a near-tie is where
bfloat16 may choose the other expert.

Tolerance. The served path computes in bfloat16 through 7 layers, keeps the
latent rows in bfloat16, and routes each token to 6 of 64 experts: where the
reference's sixth and seventh experts lie closer than the router's error, the
served path takes the other one, a jump in that token's layer output. Where
the reference's two largest logits lie closer than the error a logit carries,
the served arg-max is the other one, and the gap is their distance.
``check.logit_tolerance`` sits between the largest mean the served path
shows over its seeds on the chip and the least that a wrong computation shows
(PERF.md, Findings; ``scripts/deepseek_v2_sabotage.py``).

The module imports the model before anything touches the device, so a
checkout that lacks models/deepseek_v2.py fails at once, with a non-zero exit.
"""

from __future__ import annotations

import functools
import importlib
import time

from distributed_tensorflow_tpu.models.deepseek_v2 import (
    DeepseekV2,
    DeepseekV2Config,
    deepseek_v2_init_params,
)

from benchmarks import common, flops, traffic
from benchmarks.runners.serve import _offer, _reduce, _sweep, _warm
from benchmarks.runners.serve_olmo_hybrid import _probe, _scratch_bytes, requests
from benchmarks.runners.serve_sambay import score_gaps

#: the end-to-end metrics this runner measures (its result's "end_to_end")
MEASURES = {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s",
            "setup_s"}


def model_config(config: dict, **overrides) -> DeepseekV2Config:
    import jax.numpy as jnp

    if config["q_lora_rank"] is not None:
        raise ValueError("models/deepseek_v2.py projects queries directly")
    if (config["scoring_func"], config["topk_method"]) != ("softmax", "greedy"):
        raise ValueError("models/deepseek_v2.py routes softmax, greedy top-k")
    rs = config["rope_scaling"]
    return DeepseekV2Config(**{
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "intermediate_size": config["intermediate_size"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "n_routed_experts": config["n_routed_experts"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "n_shared_experts": config["n_shared_experts"],
        "first_k_dense_replace": config["first_k_dense_replace"],
        "moe_layer_freq": config["moe_layer_freq"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "rms_norm_eps": config["rms_norm_eps"],
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(rs["factor"]),
        "original_max_position": rs["original_max_position_embeddings"],
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]),
        "mscale": float(rs["mscale"]),
        "mscale_all_dim": float(rs["mscale_all_dim"]),
        "max_position": config["max_position_embeddings"],
        "dtype": jnp.dtype(config["run"]["compute_dtype"]),
        **overrides,
    })


def _build(run: common.Run, watch: common.Stopwatch):
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.runtime import enable_compile_cache
    from distributed_tensorflow_tpu.serve import Client
    from distributed_tensorflow_tpu.serve.batcher import BatcherConfig
    from distributed_tensorflow_tpu.serve.engine import CausalLMEngine

    cache_dir = enable_compile_cache()
    watch.lap("imports")
    compiles = common.CompileCounter()
    devices = common.require_devices(run)
    watch.lap("device")

    serving = run.config["serving"]
    model = DeepseekV2(model_config(run.config))
    weight_dtype = jnp.dtype(run.config["run"]["weight_dtype"])
    # One jitted call from the seed, in the type the weights are served in.
    params = jax.jit(
        lambda key: deepseek_v2_init_params(model, key, weight_dtype)
    )(jax.random.key(run.seed))
    jax.block_until_ready(params)
    watch.lap("init")

    engine = CausalLMEngine(
        model, params, None, buckets=tuple(serving["buckets"]),
        slots=run.workload["slots"], max_batch=serving["max_batch"],
        max_new_tokens=serving["max_new_tokens"],
        prefill_chunk=serving["prefill_chunk"],
    )
    client = Client(engine, BatcherConfig(max_batch=serving["max_batch"]))
    watch.lap("compile_grid")
    return params, engine, client, devices, compiles, cache_dir


def run(run: common.Run):
    watch = common.Stopwatch(run.t_start)
    params, engine, client, devices, compiles, cache_dir = _build(run, watch)
    vocab = run.config["vocab_size"]
    try:
        _warm(client, engine, vocab)
        watch.lap("warmup")
        if run.sweep:
            _sweep(run, client, vocab)
            return None

        offer = requests(run.traffic, run.seed, run.seconds, vocab)
        setup_s = time.monotonic() - run.t_start
        with compiles:
            records, t0 = _offer(
                client, offer,
                trace_dir=run.trace_dir if run.trace else None,
                trace_after=0.25 * run.seconds,
                trace_for=min(4.0, 0.4 * run.seconds),
            )
        window_peak_bytes = common.peak_bytes_in_use(devices)
        red = _reduce(records, t0, run.seconds)
        picked, same = _probe(run, client, records)
        status = client.batcher.status()
    finally:
        client.close()

    grid = engine.grid_status()
    scratch = _scratch_bytes(engine)
    # the largest program's: a chunk runs with every buffer live, as a step does
    temp_bytes = max(scratch.values())
    about_engine = dict(
        slots=engine.slots, cache_len=engine.cache_len,
        prefill_chunk=engine.prefill_chunk_size,
        memory_registered=engine.memory.snapshot()["components"],
        decode_step_must_move=_step_bytes(run, engine, devices),
    )
    # the float32 reference does not fit beside 5 GB of cache
    engine.release_cache()
    check = _check(run, engine.model, params, picked, same)

    spans = red.pop("spans")
    correct = (
        check["ok"] and compiles.in_window == 0
        and red["failed"] == 0 and red["out_tokens"] > 0
    )
    common.info(
        "serve", cell=run.name, platform=devices[0].platform, chips=len(devices),
        rate_rps=run.traffic["rate_rps"], offered=traffic.offered(offer),
        setup_s=setup_s, setup_parts=watch.parts, cache_dir=cache_dir,
        grid_cells=grid["cells_total"], grid_compile_s=grid["compile_seconds_total"],
        compiles_total=compiles.total, compiles_in_window=compiles.in_window,
        memory_stats_peak_bytes=window_peak_bytes,
        program_temp_bytes=scratch,
        # a pause of the machine (PERF.md, Findings) shows here: every
        # request due while it lasted is submitted late by what was left of it
        lateness_max_ms=1e3 * max(r["t_submit"] - r["due"] for r in records),
        batcher_status=status, check=check, **about_engine, **red,
    )
    return {
        "correct": correct,
        "attempted": red["attempted"],
        "failed": red["failed"],
        "end_to_end": {
            "ttft_p50_ms": (red["ttft_p50_ms"], "ms"),
            "ttft_p95_ms": (red["ttft_p95_ms"], "ms"),
            "tpot_p95_ms": (red["tpot_p95_ms"], "ms"),
            "serve_tokens_per_s": (red["serve_tokens_per_s"], "tokens/s"),
            "setup_s": (setup_s, "s"),
        },
        "spans": spans,
        "job": {},
        "devices": devices,
        "window_peak_bytes": window_peak_bytes,
        "program_temp_bytes": temp_bytes,
    }


def _step_bytes(run: common.Run, engine, devices) -> dict:
    """What one decode step over the whole slot table has to move, from
    shapes: the weights once (the embedding's rows but for the slots' own;
    at the cell's live lanes every routed expert is touched), and the latent
    table whole, which the plain masked read passes over — and the time that
    takes at the chip's published memory bandwidth. Information to read
    beside ``engine.decode_device_ms``, not a metric."""
    cfg = engine.model.cfg
    weights = engine.memory.snapshot()["components"]["lm_params"]
    experts = cfg.moe_layers * cfg.n_routed_experts * 3 * cfg.hidden_size \
        * cfg.moe_intermediate_size * 2
    parts = {
        "routed_experts": experts,
        "other_weights_without_the_embedding": weights - experts
        - cfg.vocab_size * cfg.hidden_size * 2,
        "latent_table": engine.cache_groups["cache.latent"][0],
    }
    out = {"bytes": parts, "total_bytes": sum(parts.values())}
    if not run.rehearsal:
        bw = flops.chip_peaks(devices[0].device_kind)["hbm_bytes_per_s"]
        out["ms_at_peak_bandwidth"] = {
            k: 1e3 * v / bw for k, v in {**parts, "total": out["total_bytes"]}.items()
        }
    return out


def _check(run: common.Run, model, params, picked, same) -> dict:
    """Determinism (``_probe``) and agreement with the reference, outside the
    window and after the engine has let go of its cache (module docstring)."""
    spec = run.workload["check"]
    if picked is None:
        return {"ok": False, "reason": same}
    gaps, routing = reference_gaps(
        run.config, model, params,
        [(rec["req"].prompt, rec["result"]["tokens"]) for rec in picked],
        spec["positions"],
    )
    score = score_gaps(gaps)
    ok = same and score["mean_logit_gap"] <= spec["logit_tolerance"]
    return {"ok": bool(ok), "deterministic": same, **score,
            "tolerance": spec["logit_tolerance"], "requests": len(picked),
            "routing": routing}


VOCAB_BLOCKS = 8  # the head, over this many slices of the vocabulary's rows


def reference_gaps(config: dict, model, params, streams, scored: int):
    """``([len(streams), scored], routing)``: for the first and the last
    ``scored / 2`` emitted tokens of each ``(prompt, emitted tokens)``, how
    far the token's logit lies below its position's maximum in the
    reference's logits (0 where the served token is the reference's own
    choice), and what the routers did at those positions (module docstring).
    ``params`` is the served tree as it lies on the device; a layer's weights
    become float32 inside the layer's program and nowhere else. Every
    sequence is padded to the cache's length, so the programs have one shape
    whatever was served."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = importlib.import_module(f"benchmarks.references.{config['name']}")
    serving = config["serving"]
    length = serving["buckets"][-1] + serving["max_new_tokens"]
    k = config["num_experts_per_tok"]
    experts = config["n_routed_experts"]
    half = scored // 2
    blocks = {}  # kind -> the layer's jitted program

    def layer(l, p, x, mask):
        kind = ref.mixer_kind(config, l)
        if kind not in blocks:
            blocks[kind] = jax.jit(functools.partial(ref.block, config, kind))
        return blocks[kind](p, x, mask)

    head = params["lm_head"]
    vocab = head.shape[0]
    assert vocab % VOCAB_BLOCKS == 0, (vocab, VOCAB_BLOCKS)
    width = vocab // VOCAB_BLOCKS

    @jax.jit
    def gap(head, x, positions, tokens):
        """Of one row: maximum - the emitted token's logit at ``positions``,
        the head applied to those positions only, a block of the
        vocabulary's rows at a time."""
        at = x[positions]
        best = jnp.full(positions.shape, -jnp.inf, jnp.float32)
        chosen = jnp.zeros(positions.shape, jnp.float32)
        for start in range(0, vocab, width):
            logits = ref.logits(head[start:start + width], at)
            own = jnp.take_along_axis(
                logits, jnp.clip(tokens - start, 0, width - 1)[:, None], axis=1
            )[:, 0]
            inside = (tokens >= start) & (tokens < start + width)
            chosen = jnp.where(inside, own, chosen)
            best = jnp.maximum(best, logits.max(axis=-1))
        return best - chosen

    served_routes = jax.jit(functools.partial(model.apply, method="routes"))
    final_norm = jax.jit(functools.partial(ref.final_norm, config))
    out = np.zeros((len(streams), 2 * half), np.float32)
    load, differ, compared, margins = None, 0, 0, []
    for i, (prompt, tokens) in enumerate(streams):  # one sequence at a time
        n = len(prompt) + len(tokens)
        ids = np.zeros((1, length), np.int32)
        ids[0, :n] = np.concatenate([np.asarray(prompt), np.asarray(tokens)])
        mask = jnp.asarray(np.arange(length)[None] < n)
        # emitted token j was chosen at position len(prompt) + j - 1
        j = np.r_[0:half, len(tokens) - half:len(tokens)]
        at = len(prompt) + j - 1
        served = np.asarray(served_routes({"params": params}, jnp.asarray(ids),
                                          mask))[:, 0, at]  # [moe, scored, k]
        x = ref.embed(params, jnp.asarray(ids))
        probs = []
        for l in range(config["num_hidden_layers"]):
            x, p = layer(l, params[f"layer_{l}"], x, mask)
            if p is not None:
                probs.append(np.asarray(p[0, at]))
        probs = np.stack(probs)  # [moe, scored, experts]
        order = np.argsort(-probs, axis=-1, kind="stable")
        top = order[..., :k]
        counts = np.stack([np.bincount(t.ravel(), minlength=experts) for t in top])
        load = counts if load is None else load + counts
        same_sets = np.array([[len(set(a) & set(b)) for a, b in zip(la, lb)]
                              for la, lb in zip(top, served)])
        differ += int((k - same_sets).sum())
        compared += int(same_sets.size * k)
        sixth = np.take_along_axis(probs, order[..., k - 1:k], -1)[..., 0]
        seventh = np.take_along_axis(probs, order[..., k:k + 1], -1)[..., 0]
        margins.extend((sixth - seventh)[same_sets < k].tolist())
        x = final_norm(params, x)
        out[i] = np.asarray(gap(
            head, x[0], jnp.asarray(at),
            jnp.asarray(np.asarray(tokens, np.int32)[j]),
        ))
    routing = {
        "rows_an_expert_max": load.max(axis=1).tolist(),
        "rows_an_expert_mean": load.mean(axis=1).tolist(),
        "choices_compared": compared,
        "choices_differing": differ,
        "largest_reference_margin_where_differing": max(margins, default=None),
    }
    return out, routing
