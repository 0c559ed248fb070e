"""Runner ``serve``: open-loop request traffic against the in-process server.

One process holds the chip: weights are made on the device from the seed,
``CausalLMEngine`` compiles its grid, ``serve.Client`` runs the continuous
batcher, and this module offers requests on the schedule ``traffic.py`` drew,
each timed from when it was *due* (so a stall is charged to every request it
delays). The exact per-request ``future.phases`` and ``latency_s`` that the
batcher stamps are the only thing read from the program.

``--sweep r1,r2,...`` runs the same window at each rate in one process and
prints a table: a rate is sustained when nothing is refused, everything
completes, and the median queue wait of the window's last quarter is at most
twice that of its first (with a 1 ms floor under both).
"""

from __future__ import annotations

import threading
import time

from benchmarks import common, traffic
from benchmarks import reduce as R
from benchmarks import trace as tracelib

DRAIN_TIMEOUT_S = 60.0


def _build(run: common.Run, watch: common.Stopwatch):
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import CausalLM, CausalLMConfig
    from distributed_tensorflow_tpu.runtime import enable_compile_cache
    from distributed_tensorflow_tpu.serve import Client
    from distributed_tensorflow_tpu.serve.batcher import BatcherConfig
    from distributed_tensorflow_tpu.serve.engine import CausalLMEngine

    cache_dir = enable_compile_cache()
    watch.lap("imports")
    compiles = common.CompileCounter()
    devices = common.require_devices(run)
    watch.lap("device")

    cfg, recipe, serving = run.config, run.config["run"], run.config["serving"]
    model = CausalLM(CausalLMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        dtype=jnp.dtype(recipe["compute_dtype"]),
    ))
    weight_dtype = jnp.dtype(recipe["weight_dtype"])
    length = cfg["max_position_embeddings"]

    def make_params(key):
        params = model.init(
            key, jnp.zeros((1, length), jnp.int32), jnp.ones((1, length), bool)
        )["params"]
        return jax.tree.map(lambda x: x.astype(weight_dtype), params)

    # One jitted call from the seed, in the type the weights are served in.
    params = jax.jit(make_params)(jax.random.key(run.seed))
    jax.block_until_ready(params)
    watch.lap("init")

    engine = CausalLMEngine(
        model, params, None, buckets=tuple(serving["buckets"]),
        slots=run.workload["slots"], max_batch=serving["max_batch"],
        max_new_tokens=serving["max_new_tokens"],
    )
    client = Client(engine, BatcherConfig(max_batch=serving["max_batch"]))
    watch.lap("compile_grid")
    return model, params, engine, client, devices, compiles, cache_dir


def _payload(prompt, max_new: int) -> dict:
    return {"input_ids": prompt, "max_new_tokens": int(max_new)}


def _warm(client, engine, vocab: int) -> None:
    """One request through every prompt bucket, then a burst as wide as the
    admission batch, so every grid cell the traffic can reach has run once
    and every lazy host path has been taken."""
    import numpy as np

    rng = np.random.default_rng(0)
    futs = [
        client.submit(_payload(rng.integers(5, vocab, b, dtype=np.int64).astype(np.int32), 4))
        for b in engine.buckets
    ]
    for f in futs:
        f.result(timeout=120)
    futs = [
        client.submit(_payload(rng.integers(5, vocab, 8, dtype=np.int64).astype(np.int32), 4))
        for _ in range(2 * engine.max_batch)
    ]
    for f in futs:
        f.result(timeout=120)


def _offer(client, requests, *, trace_dir=None, trace_after=0.0, trace_for=0.0):
    """Send each request when it is due; wait for all of them. Returns one
    record per request and the window's clock readings."""
    from distributed_tensorflow_tpu.serve.batcher import Backpressure

    tracer_thread = None
    if trace_dir:
        def capture():
            time.sleep(trace_after)
            tracelib.start(trace_dir)
            time.sleep(trace_for)
            tracelib.stop()

        # Its own thread: starting and stopping the profiler takes seconds
        # and must not hold up the arrivals.
        tracer_thread = threading.Thread(target=capture, name="bench-trace")

    records = []
    t0 = time.monotonic()
    if tracer_thread:
        tracer_thread.start()
    for req in requests:
        due = t0 + req.due_s
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t_submit = time.monotonic()
        try:
            fut = client.submit(_payload(req.prompt, req.max_new_tokens))
            refused = None
        except Backpressure as e:
            fut, refused = None, f"Backpressure: {e}"
        records.append({"req": req, "due": due, "t_submit": t_submit,
                        "future": fut, "refused": refused})
    deadline = time.monotonic() + DRAIN_TIMEOUT_S
    for rec in records:
        fut = rec["future"]
        if fut is None:
            continue
        try:
            rec["result"] = fut.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception as e:  # noqa: BLE001 — any failure is a failed request
            rec["refused"] = f"{type(e).__name__}: {e}"
    if tracer_thread:
        tracer_thread.join()
    return records, t0


def _reduce(records, t0: float, seconds: float) -> dict:
    """The window's numbers from the per-request records."""
    ttft, tpot, lateness, ends, waits, spans = [], [], [], [], [], {}
    out_tokens = failed = 0
    held = 0.0  # token-seconds of KV that requests really occupied
    # A request that failed or was refused misses any limit: it enters the
    # tails at the drain time-out, not at nothing.
    miss = DRAIN_TIMEOUT_S
    for rec in records:
        lateness.append(rec["t_submit"] - rec["due"])
        fut, res = rec["future"], rec.get("result")
        if res is None or rec["refused"]:
            failed += 1
            ttft.append(miss)
            tpot.append(miss)
            continue
        ph = fut.phases
        n = res["n_tokens"]
        out_tokens += n
        ttft.append((rec["t_submit"] - rec["due"]) + ph["queue_wait"] + ph["prefill"])
        spans.setdefault("ttft", []).append(ttft[-1])
        if n > 1:
            tpot.append(ph["decode"] / (n - 1))
        ends.append(rec["t_submit"] + fut.latency_s)
        held += (len(rec["req"].prompt) + n / 2) * (ph["prefill"] + ph["decode"])
        for k, v in ph.items():
            spans.setdefault(k, []).append(v)
        waits.append(ph["queue_wait"])  # in due order
    # The window of the rate: from the first request's due time until the
    # last offered request has completed — all the work over all its time.
    # (Counting only completions inside --seconds made the rate swing 3%
    # with the order of the sizes: a request takes seconds, so a quarter of
    # the tokens are in flight when the arrivals end. PERF.md, PR 26.)
    window_s = (max(ends) - t0) if ends else seconds
    q = max(1, len(waits) // 4)
    first_q = R.median(waits[:q]) if waits else None
    last_q = R.median(waits[-q:]) if waits else None
    return {
        "attempted": len(records), "failed": failed, "out_tokens": out_tokens,
        "window_s": window_s, "kv_tokens_held_mean": held / window_s,
        "ttft_p95_ms": 1e3 * R.percentile(ttft, 95),
        "ttft_p50_ms": 1e3 * R.percentile(ttft, 50),
        "tpot_p95_ms": 1e3 * R.percentile(tpot, 95) if tpot else None,
        "tpot_p50_ms": 1e3 * R.percentile(tpot, 50) if tpot else None,
        "serve_tokens_per_s": out_tokens / window_s,
        "lateness_p95_ms": 1e3 * R.percentile(lateness, 95),
        "queue_wait_first_quarter_ms": None if first_q is None else 1e3 * first_q,
        "queue_wait_last_quarter_ms": None if last_q is None else 1e3 * last_q,
        "sustained": bool(
            failed == 0 and waits
            and max(last_q, 1e-3) <= 2.0 * max(first_q, 1e-3)
        ),
        "spans": spans,
    }


def run(run: common.Run):
    watch = common.Stopwatch(run.t_start)
    model, params, engine, client, devices, compiles, cache_dir = _build(run, watch)
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
        check = _check(run, model, params, client, records)
        status = client.batcher.status()
    finally:
        client.close()

    spans = red.pop("spans")
    correct = (
        check["ok"] and compiles.in_window == 0
        and red["failed"] == 0 and red["out_tokens"] > 0
    )
    grid = engine.grid_status()
    # Scratch of the decode program, which runs while every buffer is live.
    # The engine has no public handle on its executables (PERF.md §7).
    decode_exe = getattr(engine, "_decode_compiled", None)
    temp_bytes = int(decode_exe.memory_analysis().temp_size_in_bytes) if decode_exe else 0
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


def _sweep(run: common.Run, client, vocab: int) -> None:
    rows = []
    for i, rate in enumerate(run.sweep):
        requests = traffic.generate(run.traffic, run.seed + i, run.seconds, vocab,
                                    rate_rps=rate)
        records, t0 = _offer(client, requests)
        red = _reduce(records, t0, run.seconds)
        red.pop("spans")
        rows.append({"rate_rps": rate, **red})
        common.info("sweep_row", **rows[-1])
        time.sleep(1.0)
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    common.info("sweep", cell=run.name, seconds=run.seconds, rows=rows,
                highest_sustained_rps=knee,
                four_fifths=None if knee is None else 0.8 * knee)


def _check(run: common.Run, model, params, client, records) -> dict:
    """Two checks, outside the window.

    Determinism: one prompt served twice gives the same tokens.

    Agreement: for a few seeded finished requests, prompt + emitted tokens
    go teacher-forced through ``CausalLM.__call__`` with the same weights in
    float32 under 'highest' matmul precision — no engine, no cache, no
    batcher — and every emitted token's logit must lie within
    ``check.logit_tolerance`` of that position's maximum. Logits, not
    arg-max: with random weights the largest logit changes on rounding.

    Tolerance: the served path computes in bfloat16 (8 bits of mantissa)
    through 12 post-LN blocks and a 768-wide tied head, so a logit carries an
    error of a few hundredths; the tolerance is a small multiple of the
    largest gap seen on the chip (PERF.md), far under the spread of the
    logits themselves (~1), so a wrong cache position, a dropped layer or a
    lower precision fails it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = run.workload["check"]
    done = [r for r in records if r.get("result") is not None and not r["refused"]]
    if not done:
        return {"ok": False, "reason": "no request finished"}
    rng = np.random.default_rng(run.seed & ((1 << 63) - 1))
    picked = [done[i] for i in rng.choice(len(done), min(spec["requests"], len(done)),
                                          replace=False)]
    probe = picked[0]["req"]
    a = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    b = client.call(_payload(probe.prompt, min(16, probe.max_new_tokens)))
    same = list(a["tokens"]) == list(b["tokens"])

    length = run.config["serving"]["buckets"][-1] + run.config["serving"]["max_new_tokens"]
    ids = np.zeros((len(picked), length), np.int32)
    mask = np.zeros((len(picked), length), bool)
    for i, rec in enumerate(picked):
        seq = list(rec["req"].prompt) + list(rec["result"]["tokens"])
        ids[i, : len(seq)] = seq
        mask[i, : len(seq)] = True
    ref_model = type(model)(dataclasses.replace(model.cfg, dtype=jnp.float32))
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(ref_model.apply)({"params": params32}, ids, mask))
    worst = 0.0
    for i, rec in enumerate(picked):
        p = len(rec["req"].prompt)
        for j, tok in enumerate(rec["result"]["tokens"]):
            row = logits[i, p + j - 1]
            worst = max(worst, float(row.max() - row[tok]))
    ok = same and worst <= spec["logit_tolerance"]
    return {"ok": bool(ok), "deterministic": same, "worst_logit_gap": worst,
            "tolerance": spec["logit_tolerance"], "requests": len(picked)}
