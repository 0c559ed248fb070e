"""Runner ``serve_sambay``: runners/serve.py's open-loop traffic against a
SambaY decoder-hybrid-decoder (models/sambay.py; Phi-4-mini-flash-reasoning)
served through the same ``CausalLMEngine`` and ``serve.Client``.

What differs from ``serve`` is the model that is built, and the comparison
that decides ``correct``: the generator, the offer, the reduction, the warm-up
and the sweep are imported from runners/serve.py as they are. ``correct`` =
no failed request, no compilation inside the window, one prompt served twice
gives the same tokens, and agreement with the reference: for
``check.requests`` seeded finished requests, prompt + emitted tokens go
teacher-forced through benchmarks/references/phi4_mini_flash.py (plain
``jax.numpy``, float32, 'highest' matmul precision, no cache, no code shared
with the model), and at ``check.positions`` emitted tokens of each — the
first half right after the prompt, the last half at least a window into the
sequence — the token's logit is read against that position's maximum IN THE
REFERENCE'S LOGITS. The MEAN of those gaps over each half (always the same
number of positions: 2 x 1,024 in the cell) is taken, and the larger of the
two must not pass ``check.logit_tolerance`` (``score_gaps``). On the chip the float32 model is 15.4 GB and all
the logits 9.8 GB, so neither is ever whole: the reference runs layer by
layer with one layer's weights in float32 at a time, one sequence at a time
padded to the cache's length (one shape to compile), and the head is applied
to the scored positions only, in blocks of the vocabulary.

Tolerance. The served path computes in bfloat16 (8 bits of mantissa) through
32 pre-norm layers, keeps K and V in bfloat16 and the scan state in float32,
and ends in a 2,560-wide tied head whose logits spread by about 1. A logit
then carries an error of a few hundredths; where the reference's two largest
logits lie closer than that the served arg-max is the other one, and the gap
is their distance, else it is 0. The mean gap grows as the square of the
logit error and, unlike the worst gap, does not grow with the number of
positions scored (the worst and the 99th percentile are printed beside it and
decide nothing). ``check.logit_tolerance`` sits between the largest mean the
served path shows over its seeds on the chip and the least that a wrong
computation shows, a window of 511 (PERF.md section 6, PR 35;
``scripts/sambay_sabotage.py``): a window of 513, every projection's input
rounded to float8, lambda dropped and the conv tail taken at the padded end
of the bucket read higher. The scan state kept in bfloat16 does NOT: through
this runner at the cell's geometry, on three seeds, it reads as the served
path does, by the mean, the 99th percentile and the worst gap alike.

The module imports the model before anything touches the device, so a
checkout that lacks models/sambay.py fails at once, with a non-zero exit.
"""

from __future__ import annotations

import functools
import importlib
import time

from distributed_tensorflow_tpu.models.sambay import (
    SambaY,
    SambaYConfig,
    layer_kinds,
    sambay_init_params,
)

from benchmarks import common, flops, traffic
from benchmarks.runners.serve import _offer, _payload, _reduce, _sweep, _warm


def model_config(config: dict, **overrides) -> SambaYConfig:
    import jax.numpy as jnp

    recipe = config["run"]
    return SambaYConfig(**{
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "intermediate_size": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "sliding_window": config["sliding_window"],
        "mb_per_layer": config["mb_per_layer"],
        "max_position": config["max_position_embeddings"],
        "layer_norm_eps": config["layer_norm_eps"],
        "d_state": config["d_state"], "d_conv": config["d_conv"],
        "expand": config["expand"], "dt_rank": config["dt_rank"],
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
    model = SambaY(model_config(run.config))
    weight_dtype = jnp.dtype(run.config["run"]["weight_dtype"])
    # One jitted call from the seed, in the type the weights are served in.
    params = jax.jit(
        lambda key: sambay_init_params(model, key, weight_dtype)
    )(jax.random.key(run.seed))
    jax.block_until_ready(params)
    watch.lap("init")

    engine = CausalLMEngine(
        model, params, None, buckets=tuple(serving["buckets"]),
        slots=run.workload["slots"], max_batch=serving["max_batch"],
        max_new_tokens=serving["max_new_tokens"],
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

        requests = traffic.generate(run.traffic, run.seed, run.seconds, vocab)
        setup_s = time.monotonic() - run.t_start
        with compiles:
            records, t0 = _offer(
                client, requests,
                trace_dir=run.trace_dir if run.trace else None,
                trace_after=0.25 * run.seconds,
                trace_for=min(4.0, 0.4 * run.seconds),
            )
        window_peak_bytes = common.peak_bytes_in_use(devices)
        red = _reduce(records, t0, run.seconds)
        check = _check(run, params, client, records)
        status = client.batcher.status()
    finally:
        client.close()

    spans = red.pop("spans")
    correct = (
        check["ok"] and compiles.in_window == 0
        and red["failed"] == 0 and red["out_tokens"] > 0
    )
    grid = engine.grid_status()
    temp_bytes = engine.decode_scratch_bytes or 0
    common.info(
        "serve", cell=run.name, platform=devices[0].platform, chips=len(devices),
        slots=engine.slots, cache_len=engine.cache_len, buckets=engine.buckets,
        rate_rps=run.traffic["rate_rps"], offered=traffic.offered(requests),
        setup_s=setup_s, setup_parts=watch.parts, cache_dir=cache_dir,
        grid_cells=grid["cells_total"], grid_compile_s=grid["compile_seconds_total"],
        compiles_total=compiles.total, compiles_in_window=compiles.in_window,
        memory_stats_peak_bytes=window_peak_bytes,
        memory_registered=engine.memory.snapshot()["components"],
        decode_program_temp_bytes=temp_bytes,
        decode_step_must_move=_step_bytes(run, engine, devices),
        # a pause of the machine (PERF.md section 6, PR 28) shows here: every
        # request due while it lasted is submitted late by what was left of it
        lateness_max_ms=1e3 * max(r["t_submit"] - r["due"] for r in records),
        batcher_status=status, check=check, **red,
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
    shapes: the weights once, the full table once for each layer that reads
    it, the rings once, the state read and written — and the time that takes
    at the chip's published memory bandwidth. Information to read beside
    ``engine.decode_device_ms``, not a metric."""
    kinds = layer_kinds(engine.model.cfg)
    groups = {name: nbytes for name, (nbytes, _) in engine.cache_groups.items()}
    readers = kinds.count("full") + kinds.count("cross")
    parts = {
        "weights": engine.memory.snapshot()["components"]["lm_params"],
        "full_table_x_readers": groups["cache.full"] * readers,
        "window_rings": groups["cache.window"],
        "state_read_and_written": 2 * groups["cache.state"],
    }
    out = {"bytes": parts, "full_table_readers": readers,
           "total_bytes": sum(parts.values())}
    if not run.rehearsal:
        bw = flops.chip_peaks(devices[0].device_kind)["hbm_bytes_per_s"]
        out["ms_at_peak_bandwidth"] = {
            k: 1e3 * v / bw for k, v in {**parts, "total": out["total_bytes"]}.items()
        }
    return out


def _check(run: common.Run, params, client, records) -> dict:
    """Determinism and agreement with the reference, outside the window
    (module docstring). The same number of positions is scored in every run:
    of ``check.requests`` seeded finished requests the first and the last
    ``check.positions / 2`` emitted tokens — the first follow the prompt, where
    a state left wrong by prefill shows; the last lie at least a whole window
    into a sequence, where every ring has wrapped and the recurrence has run
    longest."""
    import numpy as np

    spec = run.workload["check"]
    scored = spec["positions"]
    reach = run.config["sliding_window"] + scored // 2
    done = [
        r for r in records
        if r.get("result") is not None and not r["refused"]
        and len(r["result"]["tokens"]) >= scored
        and len(r["req"].prompt) + len(r["result"]["tokens"]) >= reach
    ]
    if len(done) < spec["requests"]:
        return {"ok": False, "reason": f"{len(done)} finished requests of at "
                f"least {reach} positions, {spec['requests']} are scored"}
    rng = np.random.default_rng(run.seed & ((1 << 63) - 1))
    picked = [done[i] for i in rng.choice(len(done), spec["requests"],
                                          replace=False)]
    probe = picked[0]["req"]
    a = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    b = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    same = list(a["tokens"]) == list(b["tokens"])
    gaps = reference_gaps(
        run.config, params,
        [(rec["req"].prompt, rec["result"]["tokens"]) for rec in picked],
        scored,
    )
    score = score_gaps(gaps)
    ok = same and score["mean_logit_gap"] <= spec["logit_tolerance"]
    return {"ok": bool(ok), "deterministic": same, **score,
            "tolerance": spec["logit_tolerance"], "requests": len(picked)}


def score_gaps(gaps) -> dict:
    """What is compared with the tolerance: the larger of the two halves'
    means. A state left wrong by prefill shows in the first half only and a
    wrong window in the last only, so one mean over both would halve either.
    The rest is printed beside it and decides nothing: the worst and the 99th
    percentile grow with the number of positions scored."""
    import numpy as np

    half = gaps.shape[1] // 2
    first, last = float(gaps[:, :half].mean()), float(gaps[:, half:].mean())
    return {"mean_logit_gap": max(first, last),
            "mean_first_half": first, "mean_last_half": last,
            "positions_scored": int(gaps.size),
            "p99_logit_gap": float(np.quantile(gaps, 0.99)),
            "worst_logit_gap": float(gaps.max()),
            "share_not_the_reference_s_choice": float((gaps > 0).mean())}


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
    n_layers = config["num_hidden_layers"]
    half = scored // 2
    blocks = {}  # (kind, keeps_memory) -> the layer's jitted program

    def layer(l, p, x, mask, carry):
        key = (ref.mixer_kind(config, l), l == n_layers // 2)
        if key not in blocks:
            blocks[key] = jax.jit(functools.partial(ref.block, config, *key))
        return blocks[key](jnp.float32(l), p, x, mask, carry)

    table = params["embed"]["embedding"]
    vocab = table.shape[0]
    assert vocab % VOCAB_BLOCKS == 0, (vocab, VOCAB_BLOCKS)
    width = vocab // VOCAB_BLOCKS

    @jax.jit
    def gap(table, x, positions, tokens):
        """Of one row: maximum - the emitted token's logit at ``positions``,
        the head applied to those positions only, a block of the
        vocabulary's rows at a time."""
        at = x[positions]
        best = jnp.full(positions.shape, -jnp.inf, jnp.float32)
        chosen = jnp.zeros(positions.shape, jnp.float32)
        for start in range(0, vocab, width):
            logits = ref.logits(table[start:start + width], at)
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
        x, carry = ref.embed(params, jnp.asarray(ids)), {}
        for l in range(n_layers):
            x, carry = layer(l, params[f"layer_{l}"], x, mask, carry)
        x = final_norm(params, x)
        # emitted token j was chosen at position len(prompt) + j - 1
        j = np.r_[0:half, len(tokens) - half:len(tokens)]
        out[i] = np.asarray(gap(
            table, x[0], jnp.asarray(len(prompt) + j - 1),
            jnp.asarray(np.asarray(tokens, np.int32)[j]),
        ))
    return out
