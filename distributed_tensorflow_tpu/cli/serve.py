"""``python -m distributed_tensorflow_tpu.cli.serve --config=<workload>``.

Serve a trained checkpoint behind the dynamic micro-batcher: rebuild the
workload's model exactly as training did (same preset + overrides), restore
the newest checkpoint from ``--ckpt-dir`` directly onto the serving mesh,
AOT-compile the forward per sequence bucket / image geometry, and expose it
over HTTP (serve/server.py routes).

The serving mesh defaults to DP-only (one chip per replica). ``--tp`` /
``--pp`` / ``--ep`` (or an explicit ``--mesh data=2,model=4``) shard each
BERT engine across that many chips — Megatron tensor parallelism,
GPipe pipeline stages, expert-parallel MoE — with the remainder going to
data parallelism. The restore template carries the target layout's
shardings, so the checkpoint reads straight into place with no
single-device staging. A mesh that doesn't fit the available devices
degrades to single-chip DP with a warning, never an XLA shape error.

The config flags MUST match the training run's — the checkpoint template is
rebuilt from them (same optimizer, same staleness; for pipeline/MoE runs
also ``--pp`` / ``--moe-experts`` / ``--moe-topk``), and a mismatched tree
fails loudly at restore rather than serving garbage.

``--selftest N`` runs N synthetic requests through the in-process
:class:`Client` instead of binding a port (CI smoke; also a quick "does
this checkpoint answer" check) and prints the metrics snapshot.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging

import numpy as np

logger = logging.getLogger(__name__)


def _resolve_mesh_spec(args, n_devices: int):
    """Serving mesh spec from ``--mesh`` / ``--tp/--pp/--ep`` -> (spec,
    fell_back). Requests that cannot fit ``n_devices`` degrade to
    single-chip DP with a warning — never an XLA shape error at startup."""
    from distributed_tensorflow_tpu.parallel.mesh import MeshSpec
    from distributed_tensorflow_tpu.serve.engine import plan_serve_mesh

    if args.mesh:
        try:
            spec = {}
            for part in args.mesh.split(","):
                name, _, size = part.partition("=")
                spec[name.strip()] = int(size)
            MeshSpec(spec).resolve(n_devices)  # loud fit check, result unused
            return spec, False
        except ValueError as e:
            logger.warning(
                "--mesh %r does not fit the %d available devices (%s); "
                "falling back to single-chip data-parallel serving",
                args.mesh, n_devices, e,
            )
            return {"data": -1}, True
    return plan_serve_mesh(
        tp=args.tp, pp=args.pp, ep=args.ep, n_devices=n_devices
    )


def build_serving_client(cfg, args):
    """Workload config -> (Client, payload_maker) over the restored ckpt."""
    import jax

    from distributed_tensorflow_tpu.ckpt import restore_serving_state
    from distributed_tensorflow_tpu.cli.train import _make_tx
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.obs.slo import SloSpec
    from distributed_tensorflow_tpu.parallel.mesh import (
        build_mesh,
        data_axes,
        initialize_runtime,
    )
    from distributed_tensorflow_tpu.obs.trace import Tracer
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        BertInferenceEngine,
        CausalLMEngine,
        Client,
        ImageClassifierEngine,
    )
    from distributed_tensorflow_tpu.train import create_train_state
    from distributed_tensorflow_tpu.train.step import (
        make_state_specs,
        place_state,
    )

    initialize_runtime()
    # Serving mesh: DP-only by default; --mesh/--tp/--pp/--ep add model
    # axes (BERT engines shard over them; see serve/engine.py). The
    # builders hand back the axis-free model either way — the engine binds
    # the axes itself — plus param_specs when the layout shards params.
    spec, _ = _resolve_mesh_spec(args, len(jax.devices()))
    mesh = build_mesh(spec)
    pieces = cfg.build(cfg)(mesh)
    if "image_shape" in pieces and set(mesh.axis_names) - set(data_axes(mesh)):
        # Model parallelism is a BERT feature: an image config on a mesh
        # with model axes would just compute redundantly across them —
        # rebuild DP-only instead of silently wasting the chips.
        logger.warning(
            "--tp/--pp/--ep apply to BERT configs only; serving %s "
            "data-parallel", cfg.name,
        )
        mesh = build_mesh({"data": -1})
        pieces = cfg.build(cfg)(mesh)

    # The restore template: a TrainState built exactly like training's
    # (same tx -> same opt_state slots, same staleness -> same grad ring),
    # placed in the TARGET serving layout — param_specs present means the
    # mesh shards params, and tensorstore then restores every shard
    # directly into place (no single-device staging round-trip).
    tx, _ = _make_tx(cfg)
    host_state = create_train_state(
        pieces["params"],
        tx,
        pieces["model_state"],
        staleness=cfg.staleness if cfg.mode == "stale" else 0,
    )
    state_specs = None
    if pieces.get("param_specs") is not None:
        state_specs = make_state_specs(host_state, tx, pieces["param_specs"])
    template = place_state(host_state, mesh, state_specs)
    # The flight recorder exists BEFORE restore so the ckpt_restore event
    # (step, reclaimed bytes) is the first entry in any later dump.
    fbuf = getattr(args, "flight_buffer", 2048)
    recorder = FlightRecorder(
        capacity=fbuf,
        enabled=fbuf > 0,
        dump_dir=getattr(args, "dump_dir", "") or None,
    )
    weight_dtype = getattr(args, "weight_dtype", "") or None
    kv_dtype = getattr(args, "kv_dtype", "") or None
    if weight_dtype is not None and "image_shape" in pieces:
        raise ValueError(
            "--weight-dtype is not supported for image serving (the "
            "classifier forward has no dequantize step)"
        )
    if kv_dtype is not None and not pieces.get("decode"):
        raise ValueError(
            "--kv-dtype only applies to causal-LM decode serving "
            "(nothing else owns a KV cache)"
        )
    params, model_state, step = restore_serving_state(
        args.ckpt_dir, template, recorder=recorder,
        weight_dtype=weight_dtype,
    )
    logger.info(
        "restored %s step %d for serving (mesh %s)",
        cfg.name, step, dict(mesh.shape),
    )

    if "image_shape" in pieces:
        shape = pieces["image_shape"]
        engine = ImageClassifierEngine(
            pieces["model"],
            params,
            model_state,
            mesh,
            image_shape=shape,
            max_batch=args.max_batch,
            batch_tiers=tuple(args.batch_tiers),
            top_k=args.top_k,
        )

        def make_payload(rng: np.random.Generator) -> dict:
            return {"image": rng.standard_normal(shape).astype(np.float32)}

    elif pieces.get("decode"):
        engine = CausalLMEngine(
            pieces["model"],
            params,
            mesh,
            buckets=tuple(args.buckets),
            slots=args.slots,
            max_batch=args.max_batch,
            batch_tiers=tuple(args.batch_tiers),
            max_new_tokens=args.max_new_tokens,
            prefix_cache_mb=args.prefix_cache_mb,
            block_tokens=args.block_tokens,
            prefill_chunk=args.prefill_chunk,
            spec_tokens=args.spec_tokens,
            spec_min_match=args.spec_min_match,
            spec_backoff=args.spec_backoff,
            # Disaggregated-serving roles move KV-page chains between
            # engines; the export/import executables are compiled at
            # startup like the rest of the grid.
            kv_transfer=bool(getattr(args, "disagg_role", "")),
            # Live stream migration compiles the slot-page export/import
            # pair so in-flight generations can checkpoint off their
            # slots and resume on a peer (see DEPLOY.md "Migrating live
            # streams").
            stream_migrate=bool(getattr(args, "stream_migrate", False)),
            # restore_serving_state already quantized/cast the params;
            # the ctor detects the quantized tree and plans the KV
            # storage dtype (see DEPLOY.md "Quantized serving").
            weight_dtype=weight_dtype,
            kv_dtype=kv_dtype,
        )
        vocab = pieces["model"].cfg.vocab_size

        def make_payload(rng: np.random.Generator) -> dict:
            l = int(rng.integers(4, engine.buckets[-1] + 1))
            return {
                "input_ids": rng.integers(5, vocab, size=l),
                "max_new_tokens": int(
                    rng.integers(1, args.max_new_tokens + 1)
                ),
            }

    else:
        engine = BertInferenceEngine(
            pieces["model"],
            params,
            mesh,
            buckets=tuple(args.buckets),
            max_batch=args.max_batch,
            batch_tiers=tuple(args.batch_tiers),
            weight_dtype=weight_dtype,
        )
        vocab = pieces["model"].cfg.vocab_size

        def make_payload(rng: np.random.Generator) -> dict:
            l = int(rng.integers(4, engine.buckets[-1] + 1))
            ids = rng.integers(5, vocab, size=l)
            return {"input_ids": ids, "mlm_targets": ids}

    # Span tracing is always-on-capable: --trace-buffer 0 turns it into
    # branch-cheap no-ops at every call site.
    buf = getattr(args, "trace_buffer", 4096)
    # Declared SLOs drive /sloz burn rates and the /healthz degraded
    # overlay; the Client inserts the latency threshold as an explicit
    # histogram bound so windowed attainment at it is exact.
    slo = SloSpec(
        latency_threshold_ms=getattr(args, "slo_p99_ms", 0.0),
        latency_target=getattr(args, "slo_target", 0.99),
        availability_target=getattr(args, "slo_availability", 0.0),
    )
    client = Client(
        engine,
        BatcherConfig(
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            max_queue=args.max_queue,
            max_in_flight=args.max_in_flight,
            bucket_queues=args.bucket_queues,
            sched=getattr(args, "sched", "fifo"),
            preempt=getattr(args, "preempt", False),
            preempt_margin_ms=getattr(args, "preempt_margin_ms", 20.0),
            default_priority=getattr(args, "default_priority", 1),
        ),
        tracer=Tracer(buffer_size=buf, enabled=buf > 0),
        slo=slo,
        admission="flush" if getattr(args, "flush_admission", False)
        else "continuous",
        recorder=recorder,
        warmup_ready_fraction=getattr(args, "warmup_ready_fraction", 1.0),
        # Deployment identity for the router's hot-swap verification:
        # defaults to the restored step so a rolled checkpoint is visible
        # on /healthz without any operator input.
        tag=getattr(args, "tag", None) or f"ckpt-{step}",
    )
    return client, make_payload


def _selftest(client, make_payload, n: int) -> int:
    rng = np.random.default_rng(0)
    futures = [client.submit(make_payload(rng)) for _ in range(n)]
    results = [f.result(timeout=120) for f in futures]
    assert len(results) == n
    snap = client.metrics.snapshot()
    print(json.dumps(snap, indent=2, default=float))
    logger.info("selftest ok: %d requests served", n)
    return 0


def main(argv: list[str] | None = None):
    from distributed_tensorflow_tpu.cli.train import PRESETS
    from distributed_tensorflow_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(
        description="serve a trained checkpoint (dynamic-batching inference)"
    )
    parser.add_argument("--config", required=True, choices=sorted(PRESETS))
    parser.add_argument("--ckpt-dir", required=True,
                        help="training checkpoint directory (newest step served)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--tag", default=None,
                        help="deployment tag surfaced on /healthz (default "
                             "ckpt-<restored step>); the router's hot-swap "
                             "drill asserts it after a rolling restart")
    parser.add_argument("--port", type=int, default=8000,
                        help="0 = ephemeral (logged at startup)")
    parser.add_argument("--buckets", type=int, nargs="+",
                        default=[128, 256, 512],
                        help="sequence-length buckets (clamped to the "
                        "model's max_position); one executable each")
    parser.add_argument("--max-batch", type=int, default=8,
                        help="largest executable batch size / flush size")
    parser.add_argument("--batch-tiers", type=int, nargs="+",
                        default=[1, 2, 4, 8],
                        help="batch-size tiers to AOT-compile (clamped to "
                        "--max-batch); a partial flush runs the smallest "
                        "tier that fits instead of padding to max-batch")
    parser.add_argument("--max-in-flight", type=int, default=2,
                        help="batches dispatched but not yet fetched; >1 "
                        "overlaps host assembly with device compute")
    parser.add_argument("--bucket-queues", action="store_true",
                        help="queue per sequence bucket so short requests "
                        "flush together instead of padding to a long "
                        "batchmate's bucket")
    parser.add_argument("--max-delay-ms", type=float, default=8.0,
                        help="flush a partial batch after this wait")
    parser.add_argument("--max-queue", type=int, default=64,
                        help="queue bound; beyond -> 429 + Retry-After")
    parser.add_argument("--top-k", type=int, default=5,
                        help="classes returned per classify request")
    # Decode engine (causal-LM presets; see DEPLOY.md "Continuous-batching
    # decode"). Requests admit into KV-cache slots mid-flight between
    # decode steps unless --flush-admission pins static batching.
    parser.add_argument("--slots", type=int, default=8,
                        help="KV-cache slots = max concurrently decoding "
                        "sequences (one fixed decode executable at this "
                        "width)")
    parser.add_argument("--max-new-tokens", type=int, default=32,
                        help="generation cap per request (requests may ask "
                        "for less; also sizes the per-slot cache pages)")
    parser.add_argument("--prefix-cache-mb", type=float, default=0.0,
                        help="device bytes (MiB) for the prefix-cache KV "
                        "page pool; shared prompt heads prefill once and "
                        "admissions reuse the cached pages (0 disables; "
                        "see DEPLOY.md \"Prefix-cache KV reuse\")")
    parser.add_argument("--block-tokens", type=int, default=16,
                        help="tokens per prefix-cache page; prompts share "
                        "whole pages only, so smaller blocks match more "
                        "but index/gather more")
    parser.add_argument("--prefill-chunk", type=int, default=0,
                        help="prefill prompts in chunks of at most this "
                        "many tokens, interleaved with decode steps so "
                        "long-prompt admission bounds in-flight requests' "
                        "inter-token latency (0 = monolithic prefill "
                        "unless --prefix-cache-mb is set)")
    parser.add_argument("--spec-tokens", type=int, default=0,
                        help="speculative-decoding draft length k: verify "
                        "up to k n-gram-drafted tokens per slot in one "
                        "[slots, k+1] forward, emitting the accepted run "
                        "as multiple tokens per step (0 disables; output "
                        "is bit-identical either way — see DEPLOY.md "
                        "\"Speculative decoding\")")
    parser.add_argument("--spec-min-match", type=int, default=2,
                        help="shortest history n-gram the drafter may "
                        "match; longer = fewer but better drafts")
    parser.add_argument("--spec-backoff", type=float, default=0.25,
                        help="per-slot acceptance-EMA threshold below "
                        "which speculation backs off to plain decode "
                        "(re-probing periodically)")
    # Quantized serving (see DEPLOY.md "Quantized serving"): checkpoints
    # stay fp32 on disk; --weight-dtype int8 quantizes kernels at
    # restore (per-output-channel absmax, dequantized inside the
    # matmul), --kv-dtype int8 stores KV pages as int8 + per-position
    # scales (~3.5x more decode slots per HBM byte).
    parser.add_argument("--weight-dtype", default="",
                        choices=["", "float32", "bfloat16", "int8"],
                        help="serving dtype for restored params: int8 = "
                        "per-channel quantize at restore (fp32 kernel "
                        "HBM reclaimed, logged by the restore); empty "
                        "keeps the config dtype")
    parser.add_argument("--kv-dtype", default="",
                        choices=["", "float32", "bfloat16", "int8"],
                        help="KV-cache storage dtype (causal-LM decode "
                        "only): int8 pages carry per-position scales "
                        "through prefill, decode, the prefix cache, and "
                        "the KV wire format; empty keeps the config "
                        "dtype")
    # Disaggregated prefill/decode serving (see DEPLOY.md "Disaggregated
    # serving"): run this process as ONE role of a prefill/decode pair.
    # A decode-role server compiles the KV-page import executable and
    # accepts chains on POST /v1/kv_transfer (serve/disagg.py wire
    # format); a prefill-role server is an ordinary chunked-prefill
    # engine whose operators cap max_new_tokens at 1 and ship the
    # published pages with serve.disagg.post_kv_transfer.
    parser.add_argument("--disagg-role", default="",
                        choices=["", "prefill", "decode"],
                        help="disaggregated-serving role; decode requires "
                        "--prefix-cache-mb > 0 (the adopted chains land "
                        "in the prefix-cache page pool)")
    parser.add_argument("--kv-transfer-budget-mb", type=float, default=64.0,
                        help="bytes-in-flight cap (MiB) for inbound KV-page "
                        "transfers on a decode-role server; transfers "
                        "beyond it queue briefly then shed with 429 + "
                        "Retry-After (the sender re-prefills instead)")
    # Live decode-stream migration (see DEPLOY.md "Migrating live
    # streams"): compile the slot-page export/import executables, accept
    # migrated streams on POST /v1/stream_migrate, and export every live
    # stream to survivors on POST /migratez (the router drives both
    # during hot_swap deadline expiry and failover).
    parser.add_argument("--stream-migrate", action="store_true",
                        help="enable live decode-stream migration: mount "
                        "POST /v1/stream_migrate + /v1/stream_wait "
                        "(receive side) and POST /migratez (export side); "
                        "causal-LM engines only")
    parser.add_argument("--fault-plan", default="",
                        help="serving-side fault-injection plan (drills): "
                        "'seed=..,dispatch_error=N,slow_decode_step=N,"
                        "wire_corrupt=N,probe_timeout=N,replica_kill=N' or "
                        "a FaultPlan JSON path; injected into the decode "
                        "loop and migration wire path "
                        "(serve/faultinject.py)")
    parser.add_argument("--fault-steps", type=int, default=1000,
                        help="decode-step horizon --fault-plan events are "
                        "placed within when the spec is key=value form")
    parser.add_argument("--flush-admission", action="store_true",
                        help="admit new requests only when the slot table "
                        "is EMPTY (static batching; the A/B baseline for "
                        "continuous admission)")
    # Priority-preemptive scheduling (see DEPLOY.md "Priority &
    # preemption"): requests may carry "priority" (class 0 = most urgent)
    # and "deadline_ms" (TTFT deadline relative to enqueue) on
    # /v1/generate; EDF admission orders the queue by them, and --preempt
    # parks a lower-priority slot (KV lanes into prefix-pool pages,
    # resume via resume_tokens replay) when a deadline would be missed.
    parser.add_argument("--sched", default="fifo",
                        choices=["fifo", "edf"],
                        help="admission order: fifo (arrival) or edf "
                        "(earliest deadline first within priority class)")
    parser.add_argument("--preempt", action="store_true",
                        help="preempt a lower-priority decode slot when a "
                        "queued deadline holder would otherwise miss its "
                        "deadline (requires --sched edf; preempted "
                        "streams resume bit-identically)")
    parser.add_argument("--preempt-margin-ms", type=float, default=20.0,
                        help="preempt when now + margin crosses a queued "
                        "request's deadline — headroom for the park + "
                        "re-prefill round trip")
    parser.add_argument("--default-priority", type=int, default=1,
                        help="priority class for requests that don't send "
                        "one (0 = most urgent; keep the default above 0 "
                        "so explicit high-priority traffic can outrank "
                        "the unlabelled crowd)")
    # Multi-chip serving mesh (BERT engines; see DEPLOY.md "Multi-chip
    # serving"). A layout that doesn't fit the device count falls back to
    # single-chip DP with a warning.
    parser.add_argument("--mesh", default="",
                        help="explicit serving mesh, e.g. 'data=2,model=4' "
                        "(axes from parallel.mesh.AXIS_ORDER; one axis may "
                        "be -1). Overrides --tp/--pp/--ep")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel (Megatron) chips per engine; "
                        "must divide num_heads and intermediate_size")
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline-parallel stages per engine; the "
                        "checkpoint must be a --pipeline-parallel=N run "
                        "(stacked encoder)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel chips per engine; needs a "
                        "--moe-experts checkpoint divisible by it")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="training run's --moe-experts (MoE ckpts)")
    parser.add_argument("--moe-topk", type=int, default=1,
                        help="training run's --moe-topk")
    parser.add_argument("--global-batch", type=int, default=0,
                        help="training run's --global-batch (only needed "
                        "when the preset default doesn't match, e.g. "
                        "pipeline runs validating microbatch divisibility)")
    # Model-geometry overrides — MUST match the training run's.
    parser.add_argument("--bert-layers", type=int, default=0)
    parser.add_argument("--bert-hidden", type=int, default=0)
    parser.add_argument("--bert-vocab", type=int, default=0)
    parser.add_argument("--image-size", type=int, default=0)
    parser.add_argument("--staleness", type=int, default=-1,
                        help="training run's staleness (stale-mode ckpts)")
    # Declared SLOs (0 disables a dimension): /sloz reports attainment +
    # error-budget burn; a paging-level burn turns /healthz "degraded".
    parser.add_argument("--slo-p99-ms", type=float, default=0.0,
                        help="latency SLO threshold in ms: --slo-target of "
                        "requests must complete within it (0 = no latency "
                        "SLO)")
    parser.add_argument("--slo-target", type=float, default=0.99,
                        help="target fraction for the latency SLO "
                        "(e.g. 0.99 = p99 under --slo-p99-ms)")
    parser.add_argument("--slo-availability", type=float, default=0.0,
                        help="availability SLO target fraction, e.g. 0.999 "
                        "(0 = no availability SLO)")
    parser.add_argument("--trace-dir", default="",
                        help="where POST /profilez drops jax.profiler "
                        "captures; also receives a Chrome span trace at "
                        "shutdown (GET /tracez drains spans live)")
    parser.add_argument("--trace-buffer", type=int, default=4096,
                        help="span ring-buffer size (0 disables tracing: "
                        "every span call becomes a cheap no-op)")
    # Black-box flight recorder (see OBS.md "Flight recorder"): a bounded
    # ring of structured lifecycle events, dumped with a full observability
    # snapshot on engine failure / paging SLO burn / POST /debugz/dump.
    parser.add_argument("--flight-buffer", type=int, default=2048,
                        help="flight-recorder event ring size (0 disables "
                        "the recorder: every record call becomes a cheap "
                        "no-op and /debugz/dump answers 503)")
    parser.add_argument("--dump-dir", default="",
                        help="where flight-recorder dumps land as "
                        "timestamped JSON (empty: POST /debugz/dump "
                        "returns the snapshot inline; automatic triggers "
                        "have nowhere to write and are skipped)")
    parser.add_argument("--warmup-ready-fraction", type=float, default=1.0,
                        help="/healthz reports 'starting' (HTTP 503) until "
                        "this fraction of the AOT executable grid is "
                        "compiled; routers should withhold traffic until "
                        "ready (see DEPLOY.md \"Warmup-gated readiness\")")
    parser.add_argument("--selftest", type=int, default=0,
                        help="serve N synthetic requests in-process and "
                        "exit (no HTTP socket)")
    args = parser.parse_args(argv)
    if args.disagg_role == "decode" and args.prefix_cache_mb <= 0:
        parser.error("--disagg-role decode requires --prefix-cache-mb > 0 "
                     "(adopted KV-page chains land in the prefix-cache "
                     "page pool)")

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
    )
    cfg = PRESETS[args.config]
    overrides = {}
    for k in ("bert_layers", "bert_hidden", "bert_vocab", "image_size",
              "global_batch"):
        if getattr(args, k):
            overrides[k] = getattr(args, k)
    if args.moe_experts:
        overrides["moe_experts"] = args.moe_experts
        overrides["moe_topk"] = args.moe_topk
    if args.pp > 1:
        # Stacked-encoder checkpoints need the stacked template even when
        # the mesh falls back to no pipeline axis (sequential scan).
        overrides["pipeline_parallel"] = args.pp
    if args.staleness >= 0:
        overrides["staleness"] = args.staleness
        overrides["mode"] = "stale" if args.staleness else "sync"
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    client, make_payload = build_serving_client(cfg, args)
    try:
        if args.selftest:
            return _selftest(client, make_payload, args.selftest)
        from distributed_tensorflow_tpu.serve import build_http_server

        kv_receiver = transfer_budget = None
        if args.disagg_role == "decode":
            from distributed_tensorflow_tpu.serve.disagg import (
                TransferBudget,
                make_kv_receiver,
            )

            transfer_budget = TransferBudget(
                int(args.kv_transfer_budget_mb * 1024 * 1024)
            )
            kv_receiver = make_kv_receiver(
                client.batcher,
                client.engine,
                budget=transfer_budget,
                metrics=client.metrics,
                recorder=client.recorder,
            )
            logger.info(
                "disaggregated decode role: accepting KV-page chains on "
                "POST /v1/kv_transfer (budget %.1f MiB in flight)",
                args.kv_transfer_budget_mb,
            )
        elif args.disagg_role == "prefill":
            logger.info(
                "disaggregated prefill role: operators should cap "
                "max_new_tokens at 1 and ship published pages with "
                "serve.disagg.post_kv_transfer"
            )
        stream_receiver = migrator = None
        if args.stream_migrate:
            if not hasattr(client.engine, "decode"):
                parser.error("--stream-migrate applies to causal-LM "
                             "(decode) presets only")
            from distributed_tensorflow_tpu.serve.disagg import (
                TransferBudget,
                make_stream_receiver,
                migrate_streams,
            )

            # Inbound stream payloads share the KV-transfer budget when a
            # disagg decode role already sized one; otherwise size a
            # dedicated pool from the same flag.
            if transfer_budget is None:
                transfer_budget = TransferBudget(
                    int(args.kv_transfer_budget_mb * 1024 * 1024)
                )
            stream_receiver = make_stream_receiver(
                client.batcher,
                client.engine,
                budget=transfer_budget,
                metrics=client.metrics,
                recorder=client.recorder,
            )

            def migrator(targets):
                return migrate_streams(
                    client.batcher,
                    client.engine,
                    targets,
                    metrics=client.metrics,
                    recorder=client.recorder,
                    fault_injector=client.batcher.fault_injector,
                )

            logger.info(
                "live stream migration enabled: POST /v1/stream_migrate "
                "(budget %.1f MiB in flight), /v1/stream_wait, /migratez",
                args.kv_transfer_budget_mb,
            )
        if args.fault_plan:
            from distributed_tensorflow_tpu.serve.faultinject import (
                FaultInjector,
                FaultPlan,
            )

            plan = FaultPlan.parse(
                args.fault_plan, num_steps=args.fault_steps
            )
            client.batcher.fault_injector = FaultInjector(
                plan, recorder=client.recorder
            )
            logger.info(
                "serving fault plan armed: %d scheduled events (seed %s)",
                len(plan.events), plan.seed,
            )
        server = build_http_server(
            client, args.host, args.port, trace_dir=args.trace_dir or None,
            kv_receiver=kv_receiver, transfer_budget=transfer_budget,
            stream_receiver=stream_receiver, migrator=migrator,
        )
        logger.info(
            "ready on http://%s:%d (POST /v1/%s; GET /healthz /sloz "
            "/statusz /memz /compilez /tracez /metrics?format=prom, "
            "POST /profilez /drainz /debugz/dump)",
            *server.server_address,
            "classify" if hasattr(client.engine, "image_shape")
            else "generate" if hasattr(client.engine, "decode")
            else "mlm",
        )
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            logger.info("shutting down")
        finally:
            server.server_close()
        return 0
    finally:
        client.close()
        if args.trace_dir and client.tracer.enabled:
            from pathlib import Path

            out = client.tracer.export(Path(args.trace_dir) / "serve_trace.json")
            logger.info("wrote span trace to %s", out)


if __name__ == "__main__":
    raise SystemExit(main())
