"""Runner ``serve_olmo_hybrid``: runners/serve.py's open-loop traffic against
an Olmo-Hybrid decoder (models/olmo_hybrid.py; Olmo-Hybrid-7B cut in depth)
served through the same ``CausalLMEngine`` and ``serve.Client`` with CHUNKED
prefill: a prompt of thousands of tokens enters ``serving.prefill_chunk``
positions at a time between decode steps, the delta rule's matrix state and
the conv tails carried from chunk to chunk in the row's slot.

What differs from ``serve`` is the model that is built, the engine's
``prefill_chunk``, and the comparison that decides ``correct``: the generator,
the offer, the reduction, the warm-up and the sweep are imported from
runners/serve.py as they are, and ``score_gaps`` from runners/serve_sambay.py.
``correct`` = no failed request, no compilation inside the window, one prompt
served twice gives the same tokens, and agreement with the reference: for
``check.requests`` seeded finished requests of at least ``check.reach``
positions, prompt + emitted tokens go teacher-forced through
benchmarks/references/olmo_hybrid_7b.py (plain ``jax.numpy``, float32,
'highest' matmul precision, the recurrence a position at a time, no cache, no
code shared with the model), and at ``check.positions`` emitted tokens of each
— the first half right after the prompt, where a state left wrong by a chunk
shows; the last half where the recurrence has run longest — the token's logit
is read against that position's maximum IN THE REFERENCE'S LOGITS. The MEAN of
those gaps over each half is taken, and the larger of the two must not pass
``check.logit_tolerance`` (``score_gaps``). On the chip the float32 model is
16.4 GB, so it is never whole: the engine's cache is let go first, the
reference runs layer by layer with one layer's weights in float32 at a time,
one sequence at a time padded to the cache's length (one shape to compile),
and the head is applied to the scored positions only, in blocks of the
vocabulary.

Tolerance. The served path computes in bfloat16 (8 bits of mantissa) through
16 layers, keeps K and V in bfloat16 and the matrix state in float32, and ends
in a 3,840-wide untied head. Every branch is RMS-normed on its way into the
residual stream, so a layer's rounding enters at full weight. Where the
reference's two largest logits lie closer than the error a logit carries, the
served arg-max is the other one, and the gap is their distance, else it is 0;
the mean gap grows as the square of the logit error and does not grow with
the number of positions scored. ``check.logit_tolerance`` sits between the
largest mean the served path shows over its seeds on the chip and the least
that a wrong computation shows (PERF.md section 6, PR 37;
``scripts/olmo_hybrid_sabotage.py``): beta without its factor 2, alpha
dropped, q and k unnormalised, the state not carried across one chunk
boundary, a conv tail taken at a chunk's padded end, the K norm left out,
float8 into the MXU.

Order. benchmarks/traffic.py draws one set of gaps and sizes from the cell's
``shape_seed`` and lets ``--seed`` shuffle their order. Here a request lives a
quarter of the window and rides beside the chunks of whoever arrives during
its life, so the order alone moved ``tpot_p95_ms`` by 5-9% between seeds
(PERF.md section 6, PR 37: a model of the batcher's loop gives each reading
from the order) and the program by nothing. ``requests`` therefore takes the
order from ``shape_seed`` too: every seed offers the same requests at the same
moments, and draws its own token ids (and weights).

The module imports the model before anything touches the device, so a
checkout that lacks models/olmo_hybrid.py fails at once, with a non-zero exit.
"""

from __future__ import annotations

import functools
import importlib
import time

from distributed_tensorflow_tpu.models.olmo_hybrid import (
    LINEAR,
    OlmoHybrid,
    OlmoHybridConfig,
    layer_kinds,
    olmo_hybrid_init_params,
)

from benchmarks import common, flops, traffic
from benchmarks.runners.serve import _offer, _payload, _reduce, _sweep, _warm
from benchmarks.runners.serve_sambay import score_gaps

#: the end-to-end metrics this runner measures (its result's "end_to_end")
MEASURES = {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s",
            "setup_s"}


def model_config(config: dict, **overrides) -> OlmoHybridConfig:
    import jax.numpy as jnp

    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("models/olmo_hybrid.py pairs a key head with a value head")
    recipe = config["run"]
    return OlmoHybridConfig(**{
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "intermediate_size": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "layer_types": tuple(config["layer_types"]),
        "linear_num_heads": config["linear_num_key_heads"],
        "linear_key_head_dim": config["linear_key_head_dim"],
        "linear_value_head_dim": config["linear_value_head_dim"],
        "linear_conv_kernel_dim": config["linear_conv_kernel_dim"],
        "linear_allow_neg_eigval": config["linear_allow_neg_eigval"],
        "max_position": config["max_position_embeddings"],
        "rms_norm_eps": config["rms_norm_eps"],
        "dtype": jnp.dtype(recipe["compute_dtype"]),
        "state_dtype": jnp.dtype(recipe["state_dtype"]),
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
    model = OlmoHybrid(model_config(run.config))
    weight_dtype = jnp.dtype(run.config["run"]["weight_dtype"])
    # One jitted call from the seed, in the type the weights are served in.
    params = jax.jit(
        lambda key: olmo_hybrid_init_params(model, key, weight_dtype)
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


def requests(spec: dict, seed: int, seconds: float, vocab: int):
    """``traffic.generate``'s requests in the one order that ``shape_seed``
    gives, their token ids drawn from ``seed`` (module docstring, Order)."""
    import numpy as np

    plan = traffic.generate(spec, spec["shape_seed"], seconds, vocab)
    rng = np.random.default_rng(int(seed) & ((1 << 63) - 1))
    low = int(spec["token_ids"]["low"])
    return [
        r._replace(prompt=rng.integers(low, vocab, len(r.prompt), dtype=np.int64)
                   .astype(np.int32))
        for r in plan
    ]


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
    check = _check(run, params, picked, same)

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
        # a pause of the machine (PERF.md section 6, PR 28) shows here: every
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


def _scratch_bytes(engine) -> dict:
    """What each of the engine's programs reserves beside its operands, by
    cell. The engine has no public handle on its chunk executables (PERF.md
    section 7): ``_chunk_compiled`` is read as runners/serve.py reads
    ``_decode_compiled``."""
    out = {"decode": engine.decode_scratch_bytes or 0}
    for (tier, chunk), exe in engine._chunk_compiled.items():
        try:
            out[f"chunk/t{tier}/c{chunk}"] = int(
                exe.memory_analysis().temp_size_in_bytes
            )
        except Exception:  # noqa: BLE001 — a backend without the analysis
            pass
    return out


def _step_bytes(run: common.Run, engine, devices) -> dict:
    """What one decode step over the whole slot table has to move, from
    shapes: the weights once (the embedding's rows but for the slots' own),
    every full layer's table once, the state read and written — and the time
    that takes at the chip's published memory bandwidth. Information to read
    beside ``engine.decode_device_ms``, not a metric."""
    cfg = engine.model.cfg
    groups = {name: nbytes for name, (nbytes, _) in engine.cache_groups.items()}
    weights = engine.memory.snapshot()["components"]["lm_params"]
    parts = {
        "weights_without_the_embedding": weights
        - cfg.vocab_size * cfg.hidden_size * 2,
        "kv_tables": groups["cache.full"],
        "state_read_and_written": 2 * groups["cache.state"],
    }
    out = {"bytes": parts, "linear_layers": layer_kinds(cfg).count(LINEAR),
           "total_bytes": sum(parts.values())}
    if not run.rehearsal:
        bw = flops.chip_peaks(devices[0].device_kind)["hbm_bytes_per_s"]
        out["ms_at_peak_bandwidth"] = {
            k: 1e3 * v / bw for k, v in {**parts, "total": out["total_bytes"]}.items()
        }
    return out


def _probe(run: common.Run, client, records):
    """What the check needs of the live server: the seeded sample of finished
    requests it will score, and whether one of their prompts served twice
    gives the same tokens. The same number of positions is scored in every
    run: of ``check.requests`` requests of at least ``check.reach`` positions
    the first and the last ``check.positions / 2`` emitted tokens."""
    import numpy as np

    spec = run.workload["check"]
    done = [
        r for r in records
        if r.get("result") is not None and not r["refused"]
        and len(r["result"]["tokens"]) >= spec["positions"]
        and len(r["req"].prompt) + len(r["result"]["tokens"]) >= spec["reach"]
    ]
    if len(done) < spec["requests"]:
        return None, (
            f"{len(done)} finished requests of at least {spec['reach']} "
            f"positions, {spec['requests']} are scored"
        )
    rng = np.random.default_rng(run.seed & ((1 << 63) - 1))
    picked = [done[i] for i in rng.choice(len(done), spec["requests"],
                                          replace=False)]
    probe = picked[0]["req"]
    a = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    b = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    return picked, list(a["tokens"]) == list(b["tokens"])


def _check(run: common.Run, params, picked, same) -> dict:
    """Determinism (``_probe``) and agreement with the reference, outside the
    window and after the engine has let go of its cache (module docstring)."""
    spec = run.workload["check"]
    if picked is None:
        return {"ok": False, "reason": same}
    gaps = reference_gaps(
        run.config, params,
        [(rec["req"].prompt, rec["result"]["tokens"]) for rec in picked],
        spec["positions"],
    )
    score = score_gaps(gaps)
    ok = same and score["mean_logit_gap"] <= spec["logit_tolerance"]
    return {"ok": bool(ok), "deterministic": same, **score,
            "tolerance": spec["logit_tolerance"], "requests": len(picked)}


VOCAB_BLOCKS = 8  # the head, over this many slices of the vocabulary's rows


def reference_gaps(config: dict, params, streams, scored: int):
    """``[len(streams), scored]``: for the first and the last ``scored / 2``
    emitted tokens of each ``(prompt, emitted tokens)``, how far the token's
    logit lies below its position's maximum in the reference's logits (0
    where the served token is the reference's own choice). ``params`` is the
    served tree as it lies on the device; a layer's weights become float32
    inside the layer's program and nowhere else. Every sequence is padded to
    the cache's length, so the programs have one shape whatever was served."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = importlib.import_module(f"benchmarks.references.{config['name']}")
    serving = config["serving"]
    length = serving["buckets"][-1] + serving["max_new_tokens"]
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

    final_norm = jax.jit(functools.partial(ref.final_norm, config))
    out = np.zeros((len(streams), 2 * half), np.float32)
    for i, (prompt, tokens) in enumerate(streams):  # one sequence at a time
        n = len(prompt) + len(tokens)
        ids = np.zeros((1, length), np.int32)
        ids[0, :n] = np.concatenate([np.asarray(prompt), np.asarray(tokens)])
        mask = jnp.asarray(np.arange(length)[None] < n)
        x = ref.embed(params, jnp.asarray(ids))
        for l in range(config["num_hidden_layers"]):
            x = layer(l, params[f"layer_{l}"], x, mask)
        x = final_norm(params, x)
        # emitted token j was chosen at position len(prompt) + j - 1
        j = np.r_[0:half, len(tokens) - half:len(tokens)]
        out[i] = np.asarray(gap(
            head, x[0], jnp.asarray(len(prompt) + j - 1),
            jnp.asarray(np.asarray(tokens, np.int32)[j]),
        ))
    return out
