"""Runner ``train``: a pretraining job driven by the program's own loop.

The job is assembled as ``cli/train.py`` assembles the ``bert_base`` preset:
``SyntheticMLM`` -> ``mlm_device_batches`` -> ``data.prefetch.prefetch`` feed
``train.loop.fit`` over ``make_train_step(..., clip_norm=)`` on a mesh built
from the devices that are there (``data=-1``), so one chip and four chips run
the same code. The benchmark adds only the seed, the clock and the trace.

The measured window is one call of ``fit`` that ``should_stop`` ends after
``--seconds``; it is closed by ``block_until_ready`` on the final state, and
``train_tokens_per_s`` is every token of every step it ran over all its time.
"""

from __future__ import annotations

import math
import time

from benchmarks import common, flops
from benchmarks import trace as tracelib


def _model_config(cfg: dict, seq_len: int, *, dtype, attn_impl):
    from distributed_tensorflow_tpu.models.bert import BertConfig

    if seq_len > cfg["max_position_embeddings"]:
        raise SystemExit(f"seq_len {seq_len} exceeds the configuration's positions")
    return BertConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        dtype=dtype,
        attn_impl=attn_impl,
    )


def assemble(cfg: dict, seq_len: int, mesh):
    """The job as ``cli/train.py`` assembles the ``bert_base`` preset, from
    a configuration file: ``(model, tx, make_state, train_step)``.
    ``make_state(key)`` builds the whole TrainState and is meant to be
    jitted; ``rehearse_compile.py`` compiles the same pieces for a described
    chip."""
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.cli.train import _decay_mask
    from distributed_tensorflow_tpu.data.text import bert_batch_specs
    from distributed_tensorflow_tpu.models.bert import (
        BertForPreTraining,
        make_bert_pretraining_loss,
    )
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step

    recipe = cfg["run"]
    model = BertForPreTraining(_model_config(
        cfg, seq_len, dtype=jnp.dtype(recipe["compute_dtype"]),
        attn_impl=recipe["attn_impl"],
    ))
    tx = optax.adamw(
        recipe["learning_rate"], weight_decay=recipe["weight_decay"], mask=_decay_mask
    )

    def make_state(key):
        z = jnp.zeros((1, seq_len), jnp.int32)
        params = model.init(key, z, jnp.ones((1, seq_len), bool), z, train=False)["params"]
        return create_train_state(params, tx, {})

    step = make_train_step(
        make_bert_pretraining_loss(model), tx, mesh,
        batch_spec=bert_batch_specs(mesh), clip_norm=recipe["clip_norm"],
    )
    return model, tx, make_state, step


def run(run: common.Run) -> dict:
    watch = common.Stopwatch(run.t_start)
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.data.prefetch import prefetch
    from distributed_tensorflow_tpu.data.text import (
        SyntheticMLM,
        SyntheticMLMConfig,
        mlm_device_batches,
    )
    from distributed_tensorflow_tpu.obs.metrics import FeedMetrics
    from distributed_tensorflow_tpu.obs.trace import Tracer
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.runtime import enable_compile_cache
    from distributed_tensorflow_tpu.train import fit, make_rng

    cache_dir = enable_compile_cache()
    watch.lap("imports")
    compiles = common.CompileCounter()
    devices = common.require_devices(run)
    n_chips = len(devices)
    mesh = build_mesh({"data": -1})
    watch.lap("device")

    cfg, job = run.config, run.traffic
    seq_len, per_chip = job["seq_len"], job["per_chip_batch"]
    global_batch = per_chip * n_chips
    model, tx, make_state, step = assemble(cfg, seq_len, mesh)

    # Weights, optimizer slots and step counter in ONE jitted call from the
    # seed, born replicated on the mesh: no host copy, no per-leaf compile.
    state = jax.jit(make_state, out_shardings=NamedSharding(mesh, P()))(
        jax.random.key(run.seed)
    )
    jax.block_until_ready(state)
    watch.lap("init")

    corpus = SyntheticMLM(
        SyntheticMLMConfig(vocab_size=cfg["vocab_size"], seq_len=seq_len,
                           seed=run.seed % (2**31))
    )
    feed = FeedMetrics()
    batches = prefetch(
        mlm_device_batches(corpus, mesh, global_batch, seed=run.seed % (2**31)),
        job["prefetch"], metrics=feed,
    )
    rng = make_rng(run.seed % (2**31))
    losses: dict[int, float] = {}
    step_done_at: dict[int, float] = {}  # seconds from process start

    def record_loss(step_no, _state, fetched):
        step_done_at[step_no] = time.monotonic() - run.t_start
        if "loss" in fetched:
            losses[step_no] = fetched["loss"]

    # Warm-up: a first fit of a few steps on the same stream compiles the
    # step, fills the prefetch queue and syncs every step (log_every=1).
    warm = job["warmup_steps"]
    state, _ = fit(state, step, batches, num_steps=warm, rng=rng, log_every=1,
                   hooks=(record_loss,), feed_metrics=feed)
    jax.block_until_ready(state)
    watch.lap("compile_and_warmup")

    # ------------------------------------------------------------- window
    log_every = job["log_every"]
    tracer = Tracer(buffer_size=1 << 16, enabled=run.trace)
    trace_state = {"on": False, "done": False, "t_on": 0.0}
    trace_after = 0.25 * run.seconds
    trace_for = min(4.0, 0.4 * run.seconds)

    def trace_hook(step_no, _state, _fetched):
        # At the log cadence the loop has just fetched this step's metrics:
        # the device queue is empty, so the capture holds whole steps.
        now = time.monotonic()
        if not trace_state["on"] and not trace_state["done"] and now - t0 >= trace_after:
            tracelib.start(run.trace_dir)
            trace_state.update(on=True, t_on=time.monotonic())
        elif trace_state["on"] and now - trace_state["t_on"] >= trace_for:
            tracelib.stop()
            trace_state.update(on=False, done=True)

    hooks = (record_loss, trace_hook) if run.trace else (record_loss,)
    setup_s = time.monotonic() - run.t_start
    t0 = time.monotonic()
    t_end = t0 + run.seconds
    with compiles:
        state, _ = fit(
            state, step, batches, num_steps=10**9, rng=rng, log_every=log_every,
            hooks=hooks, feed_metrics=feed, tracer=tracer,
            should_stop=lambda: time.monotonic() >= t_end,
        )
        jax.block_until_ready(state)
        t1 = time.monotonic()
    if trace_state["on"]:
        tracelib.stop()
    window_peak_bytes = common.peak_bytes_in_use(devices)
    steps = int(state.step) - warm
    spare_batch = next(batches)
    batches.close()
    window_s = t1 - t0
    tokens = steps * global_batch * seq_len
    tokens_per_s = tokens / window_s

    spans: dict[str, list[float]] = {}
    for s in tracer.drain():
        if s.t1 is not None and s.t0 >= t0 and s.t1 <= t1:
            spans.setdefault(s.name, []).append(s.t1 - s.t0)

    # ------------------------------------------------- outside the window
    window_losses = [v for k, v in sorted(losses.items()) if k > warm]
    finite = bool(window_losses) and all(math.isfinite(v) for v in losses.values())
    check = _check(run, model, state, corpus)
    correct = finite and check["ok"] and compiles.in_window == 0 and steps > 0

    flops_tok = flops.train_flops_per_token(cfg, seq_len)
    mfu = None
    if not run.rehearsal:
        peaks = flops.chip_peaks(devices[0].device_kind)
        mfu = tokens_per_s * flops_tok / (n_chips * peaks["bf16_flops_per_s"])
    # The step's scratch as the compiler reserved it (a cache hit, not a
    # compile): memory_stats does not count it (common.device_report).
    ma = step.lower(state, spare_batch, rng).compile().memory_analysis()
    compiled_bytes = {
        "arguments": int(ma.argument_size_in_bytes),
        "outputs": int(ma.output_size_in_bytes),
        "temporaries": int(ma.temp_size_in_bytes),
        "aliased": int(ma.alias_size_in_bytes),
    }
    sorted_losses = sorted(losses.items())
    common.info(
        "train", cell=run.name, platform=devices[0].platform, chips=n_chips,
        mesh=dict(mesh.shape), global_batch=global_batch, seq_len=seq_len,
        steps=steps, window_s=window_s, step_ms=1e3 * window_s / max(steps, 1),
        train_tokens_per_s=tokens_per_s, train_flops_per_token=flops_tok, mfu=mfu,
        setup_s=setup_s, setup_parts=watch.parts, cache_dir=cache_dir,
        warmup_steps_done_at_s=[step_done_at.get(i) for i in range(1, warm + 1)],
        compiles_total=compiles.total, compiles_in_window=compiles.in_window,
        loss_step_1=losses.get(1), loss_step_10=losses.get(10),
        loss_last=sorted_losses[-1] if sorted_losses else None,
        host_wait_ms_mean=1e3 * feed.host_wait.summary().get("mean", 0.0),
        memory_stats_peak_bytes=window_peak_bytes,
        memory_analysis_step=compiled_bytes, check=check,
    )
    return {
        "correct": correct,
        "attempted": steps,
        "failed": 0 if finite else sum(not math.isfinite(v) for v in losses.values()),
        "end_to_end": {
            "train_tokens_per_s": (tokens_per_s, "tokens/s"),
            "setup_s": (setup_s, "s"),
        },
        "spans": spans,
        "job": {"seq_len": seq_len, "per_chip_batch": per_chip},
        "devices": devices,
        "window_peak_bytes": window_peak_bytes,
        "program_temp_bytes": compiled_bytes["temporaries"],
    }


def logit_error(got, want, valid):
    """How far two sets of logits ``[rows, positions, vocabulary]`` lie
    apart, position by position: ``|got - want|_2 / |want - mean(want)|_2``
    over the vocabulary, the error of a row of logits as a share of what
    that row has to say. Returns ``(worst, mean)`` over the positions that
    ``valid`` marks."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1)
    scale = jnp.linalg.norm(want - want.mean(-1, keepdims=True), axis=-1)
    rel = jnp.where(valid, err / scale, 0.0)
    return rel.max(), rel.sum() / valid.sum()


def _check(run, model, state, corpus) -> dict:
    """The MLM logits of a few seeded rows, at every position, through the
    step's own model (the cell's compute type and attention, eval mode)
    against the same weights in float32 with dense attention under 'highest'
    matmul precision — a path that shares no kernel and no reduced precision
    with the first. Compared by :func:`logit_error`, position by position,
    and the worst position decides: a mean over positions or a loss would
    cancel the rounding and hide a fault that touches few positions.

    Tolerance (``check.logit_rel_tolerance``): bfloat16 rounds every
    operation to 2^-9 (0.2%), and through 12 post-LN blocks and the 768-wide
    tied head a row of logits lands about 1% from float32 (the worst
    position of 8 x 512 on the chip is in PERF.md); the tolerance is about
    three times that. A float8 path rounds to 2^-4, 32 times coarser, and
    would read some tens of per cent; a kernel that drops a block of keys
    or a wrong mask moves the positions it touches by their whole scale."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.models.bert import BertForPreTraining

    spec = run.workload["check"]
    batch = corpus.batch(spec["rows"], seed=(run.seed % (2**31), 424242))
    batch = {k: np.asarray(v) for k, v in batch.items()}
    params = jax.device_get(state.params) if len(jax.devices()) > 1 else state.params

    def mlm_logits(m):
        return jax.jit(lambda p, b: m.apply(
            {"params": p}, b["input_ids"], b["attention_mask"], b["token_type_ids"],
            train=False)[0])

    got = mlm_logits(model)(params, batch)
    ref_model = BertForPreTraining(
        dataclasses.replace(model.cfg, dtype=jnp.float32, attn_impl="dense")
    )
    with jax.default_matmul_precision("highest"):
        want = mlm_logits(ref_model)(params, batch)
    worst, mean = map(float, jax.jit(logit_error)(got, want, batch["attention_mask"]))
    tol = spec["logit_rel_tolerance"]
    return {"ok": bool(np.isfinite(worst) and worst <= tol),
            "worst_rel_error": worst, "mean_rel_error": mean, "tolerance": tol,
            "rows": spec["rows"], "positions": int(batch["attention_mask"].sum())}
