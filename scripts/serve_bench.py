"""Serving latency/throughput vs offered load (the ISSUE acceptance bench).

Open-loop load generator over the in-process serving stack: a tiny BERT
engine (random init — this measures the SERVING machinery, not model
quality; point --ckpt-dir at a real run to serve trained weights), the
dynamic micro-batcher, and per-request latency measured enqueue→reply.

Open-loop matters: requests arrive on a fixed schedule regardless of how
fast replies come back, so queueing delay shows up in the tail instead of
being hidden by a closed feedback loop. At each offered load the report
gives achieved throughput, p50/p99 latency, mean batch occupancy (how well
the batcher is packing the executable grid), padded-rows-wasted (executable
rows burned on inert padding), the per-tier dispatch distribution, and the
rejection count (backpressure engaging past saturation). Before the sweep a
CLOSED-loop single-stream pass measures occupancy-1 throughput — the number
the tiered-AOT grid exists to improve (a lone request runs a 1-row
executable instead of a max-batch-row one).

    JAX_PLATFORMS=cpu python scripts/serve_bench.py
    python scripts/serve_bench.py --loads 100 400 1600 --duration 3
    python scripts/serve_bench.py --batch-tiers 8        # fixed-batch baseline
    python scripts/serve_bench.py --bucket-queues --json results.json
    python scripts/serve_bench.py --quick                # CI smoke (~seconds)

Mesh-compare mode (--mesh-layouts) replaces the load sweep: the SAME
model/params serve under several mesh layouts (``single``, ``dp``, and
dash-joined ``tpN``/``ppN``/``epN`` combos, e.g. ``tp2`` or ``tp2-ep2``),
each layout gets a numerics parity probe against the first layout (the
fast-path tolerances) plus closed-loop and one open-loop throughput point,
and the table reports per-replica throughput and padded rows per layout.
Layouts that do not fit the host (device count, head/expert/layer
divisibility) are skipped with a note, not failed — except under
``--quick``, where a parity mismatch or a throughput collapse vs the
baseline layout exits nonzero (the CI regression tripwire).

    python scripts/serve_bench.py --mesh-layouts single dp tp2 tp4
    python scripts/serve_bench.py --quick --mesh-layouts single tp2

Decode mode (--decode) replaces the load sweep with the continuous-batching
A/B: a mixed prompt-length/output-length generation workload runs twice
through the SAME slot-table batcher — once with continuous admission
(requests join the in-flight decode batch as slots free) and once with
``admission="flush"`` (the static-batching baseline: admit only into an
empty table). The engine is a simulated-step stub whose per-step device
cost is fixed (``--sim-step-ms``) and whose token streams are closed-form
functions of the prompt, so the A/B is deterministic on CPU, isolates the
SCHEDULING policy, and cannot trade correctness for speed — every stream
is checked. Each mode reports a saturated closed-loop backlog drain
(tokens/s, TTFT, ITL, mean live slots per step) and one open-loop point at
``--loads[0]`` requests/s. Before the A/B, a real (tiny) causal-LM engine
decodes a mixed backlog and every token stream must match a cache-free
full-forward greedy reference. Under ``--quick`` the run exits nonzero on
a parity/stream mismatch, phase-sum divergence >25%, continuous tokens/s
below 1.5x flush, or continuous TTFT p50 above flush (the CI gate the
docs/PERF.md round-11 numbers are recorded from).

    JAX_PLATFORMS=cpu python scripts/serve_bench.py --decode
    python scripts/serve_bench.py --decode --slots 16 --sim-step-ms 5
    python scripts/serve_bench.py --decode --quick   # CI gate (~seconds)

Decode mode also runs two prefix/chunk A/Bs (round 12):

* **Prefix-cache A/B** — a Zipf shared-prefix workload (a few hot prompt
  heads, random tails) runs through a REAL tiny causal-LM engine twice:
  prefix cache + chunked prefill ON vs the legacy cold path. Streams must
  be bit-identical; the table reports hit rate, prompt tokens saved,
  TTFT p50/p99, and tokens/s. ``--quick`` gates parity and a nonzero hit
  rate (the perf ratios are recorded in docs/PERF.md from full runs).
* **Chunked-prefill ITL A/B** — a sim engine whose prefill cost is
  proportional to tokens prefilled decodes a short-prompt backlog while
  long prompts admit mid-flight, once with a bounded prefill chunk and
  once monolithic. ``--quick`` gates the chunked arm's decode ITL p99
  during admission to <= 2x its long-prompt-free steady state.

And a speculative-decoding A/B (round 14): the SAME real tiny engine
serves two workloads — repetitive (fixed-point prompts embedding the
model's own continuation, so the n-gram drafter genuinely predicts it)
and adversarial-random (the drafter never matches; adaptive backoff must
protect the stream) — once with ``--spec-tokens`` speculation and once
without. Streams must be bit-identical between arms (exact-match
acceptance is the whole point); the table reports acceptance rate,
tokens/s, and ITL p50 per workload. ``--quick`` gates parity plus the
adversarial floor: spec-on tokens/s >= 0.9x spec-off on the random
workload (backoff must make speculation nearly free when it can't win).
The repetitive-workload speedup is recorded in docs/PERF.md round 14
from full runs, not gated in CI (dispatch jitter at CI size).

Fleet mode (--fleet, round 16) replaces the load sweep with the
replicated-router chaos drill: the bench spawns N REAL replica server
processes (this same script re-entered with ``--replica-serve PORT``,
each a tiny causal-LM engine with the prefix cache on), fronts them with
``serve.router.Router``, and drives a bursty Zipf shared-head traffic
trace through the door. Mid-trace a seeded ``FaultPlan`` (``host_drop``)
SIGKILLs one replica — the router must fail the in-flight requests over
to survivors and restart the victim within its progress-aware budget —
and after the trace a rolling checkpoint hot-swap (tag v1 -> v2) runs
under continuing traffic. ``--quick`` is the CI gate (make fleet-quick):
ZERO failed non-shed requests across kill and swap, victim restarted
within ``--restart-budget-s``, every replica on the new tag, p99 latency
bounded — best-of-3 on the timing gates (loadavg/core printed on
retries), correctness accumulated unconditionally across every attempt.
Full runs add the prefix-affinity A/B: the same trace through an
affinity-routed fleet vs a load-only spray fleet, with the per-replica
KV pool sized so ONE replica can hold ONE hot head — affinity partitions
the fleet-wide cache (hits), spray thrashes it (evictions) — and the
TTFT p50 ratio is recorded in docs/PERF.md round 16.

    python scripts/serve_bench.py --fleet --quick   # CI chaos gate
    python scripts/serve_bench.py --fleet           # + affinity A/B

Migration mode (--migrate, ISSUE 18) is the live decode-stream migration
gate: two REAL migration-enabled engines in-process plus one subprocess
replica (this script re-entered), all fronted by an adopt-mode router,
with every decode step paced by a seeded ``slow_decode_step`` fault plan
so streams are genuinely mid-generation when the drills land. Three
drills: (1) kill — streams migrate onto the subprocess replica which is
then SIGKILLed before they finish, so the router's stream_wait fails and
each stream REPLAYS with its ``resume_tokens`` prefix; (2) drain-migrate
— a draining victim's live streams export over the v2 wire onto the
survivor and the victim's drain wall is measured; (3) drain-and-wait —
the same load drains naturally (the baseline hot-swap pays). Gates,
correctness accumulated unconditionally across attempts: every stream
bit-identical to its uninterrupted reference (zero tokens lost or
duplicated), at least one stream actually migrated per drill, at least
one replay retry after the kill, and the migrate-drain wall strictly
below BOTH the longest stream's natural completion and the
drain-and-wait baseline (the victim is freed without waiting out the
longest generation).

    JAX_PLATFORMS=cpu python scripts/serve_bench.py --migrate --quick
    python scripts/serve_bench.py --migrate
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _build_model(args, *, pipeline_parallel: int = 1):
    """Tiny bench model + random-init params, shared across engines so
    mesh-compare layouts provably serve identical weights."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.bert import (
        BertConfig,
        BertForPreTraining,
    )

    extra = {}
    if pipeline_parallel > 1:
        extra["pipeline_parallel"] = pipeline_parallel  # stacked encoder
    if args.moe_experts:
        extra["moe_experts"] = args.moe_experts
        extra["moe_topk"] = 1
    cfg = BertConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_heads=max(2, args.hidden // 16),
        intermediate_size=4 * args.hidden,
        max_position=max(args.buckets),
        **extra,
    )
    model = BertForPreTraining(cfg)
    L = cfg.max_position
    variables = model.init(
        jax.random.key(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
        jnp.zeros((1, L), jnp.int32),
        train=False,
    )
    return cfg, model, variables["params"]


def build_client(args):
    from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
    from distributed_tensorflow_tpu.obs.slo import SloSpec
    from distributed_tensorflow_tpu.obs.timeseries import bounds_with
    from distributed_tensorflow_tpu.obs.trace import Tracer
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        BertInferenceEngine,
        Client,
    )

    cfg, model, params = _build_model(args)
    if args.ckpt_dir:
        # Serve real weights: restore expects the training template; the
        # bench only rebuilds bare params, so accept plain-SGD runs here.
        import optax

        from distributed_tensorflow_tpu.ckpt import restore_serving_state
        from distributed_tensorflow_tpu.train import create_train_state

        template = create_train_state(params, optax.sgd(0.1), {})
        params, _, step = restore_serving_state(args.ckpt_dir, template)
        print(f"# serving checkpoint step {step} from {args.ckpt_dir}")

    engine = BertInferenceEngine(
        model,
        params,
        buckets=tuple(args.buckets),
        max_batch=args.max_batch,
        batch_tiers=tuple(args.batch_tiers),
    )
    # Tracing on iff --trace-dir: the same run then doubles as the
    # enabled-vs-disabled overhead measurement (docs/PERF.md).
    tracing = bool(args.trace_dir)
    slo = SloSpec(
        latency_threshold_ms=args.slo_p99_ms,
        latency_target=args.slo_target,
        availability_target=args.slo_availability,
    )
    # --no-windowed: the A/B knob for the windowed-metrics overhead
    # measurement (docs/PERF.md) — same run, windowed hot-path observes off.
    metrics = None
    if args.no_windowed:
        metrics = ServeMetrics(
            windowed=False,
            latency_bounds=bounds_with(args.slo_p99_ms / 1e3),
        )
    client = Client(
        engine,
        BatcherConfig(
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            max_queue=args.max_queue,
            max_in_flight=args.max_in_flight,
            bucket_queues=args.bucket_queues,
        ),
        metrics=metrics,
        tracer=Tracer(buffer_size=args.trace_buffer, enabled=tracing),
        slo=slo,
    )
    return client, cfg.vocab_size


def make_payloads(vocab: int, buckets, n: int = 256) -> list[dict]:
    """Pre-generated request pool (generation must not gate the load loop)."""
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        l = int(rng.integers(8, max(buckets) + 1))
        ids = rng.integers(5, vocab, size=l)
        out.append({"input_ids": ids, "mlm_targets": ids})
    return out


def run_single_stream(client, payloads, duration_s: float) -> dict:
    """Closed-loop occupancy-1 throughput: submit one, wait, repeat.

    Every request flushes alone (deadline trigger), so this measures the
    cost of serving a lone request — the padding-waste worst case the
    batch-tier grid targets.
    """
    t0 = time.monotonic()
    served = 0
    while time.monotonic() - t0 < duration_s:
        client.call(payloads[served % len(payloads)], timeout=120)
        served += 1
    wall = time.monotonic() - t0
    return {"served": served, "wall_s": wall, "rps": served / wall}


def run_load(client, payloads, offered_rps: float, duration_s: float) -> dict:
    """Open-loop: submit on schedule, never wait for replies in the loop."""
    from distributed_tensorflow_tpu.serve import Backpressure

    interval = 1.0 / offered_rps
    futures, rejected = [], 0
    t0 = time.monotonic()
    n = int(offered_rps * duration_s)
    for i in range(n):
        target = t0 + i * interval
        now = time.monotonic()
        if target > now:
            time.sleep(target - now)
        t_sub = time.monotonic()
        try:
            futures.append((t_sub, client.submit(payloads[i % len(payloads)])))
        except Backpressure:
            rejected += 1
    # Latency comes from the batcher's own enqueue→reply histogram, not
    # this collection loop (which would add collector skew).
    for _, f in futures:
        f.result(timeout=120)
    t_end = time.monotonic()
    served = len(futures)
    # Exact per-request latency log (stamped by the batcher at delivery):
    # the ground truth the windowed-histogram SLO math is checked against.
    exact = [
        f.latency_s for _, f in futures
        if getattr(f, "latency_s", None) is not None
    ]
    return {
        "offered_rps": offered_rps,
        "submitted": n,
        "served": served,
        "rejected": rejected,
        "achieved_rps": served / (t_end - t0),
        "wall_s": t_end - t0,
        "_exact_latency_s": exact,
    }


# ----------------------------------------------------------- decode mode


class SimStepEngine:
    """Pure-python decode engine with a FIXED per-step device cost.

    Token k of a request is a closed-form function of (prompt, k), so any
    admission schedule — solo, joined mid-flight, after slot reuse — must
    deliver identical streams; the A/B cannot trade correctness for speed.
    Every dispatched step (prefill or decode) burns ``step_ms`` of wall
    clock in ``fetch_step`` regardless of how many slots are live — the
    device-cost model under which continuous batching pays: a step over a
    mostly-idle slot table costs the same as over a full one, so tokens/s
    is proportional to mean occupancy and the A/B flips ONLY the
    admission policy.
    """

    layout = "sim-step"

    def __init__(self, *, slots: int, max_batch: int, max_new_tokens: int,
                 step_ms: float):
        import threading

        self.slots = slots
        self.max_batch = max_batch
        self.max_new_tokens = max_new_tokens
        self.step_s = step_ms / 1e3
        self._lock = threading.Lock()
        # slot -> (prompt_sum, steps_taken); written only by the decode-loop
        # thread (the batcher's single-dispatcher contract), read by its
        # fetch thread. Never cleared on finish — the real engine's cache
        # pages aren't either, the next occupant overwrites them.
        self._state: dict[int, tuple[int, int]] = {}

    @staticmethod
    def token(prompt_sum: int, k: int) -> int:
        return (prompt_sum + 7 * k) % 50 + 5

    def validate(self, payload: dict) -> None:
        pass

    def bucket_for(self, n: int) -> int:
        for b in (32, 64, 128):
            if n <= b:
                return b
        return 128

    def prefill(self, admissions: list[dict]):
        with self._lock:
            toks = []
            for a in admissions:
                psum = int(np.sum(a["input_ids"]))
                self._state[a["slot"]] = (psum, 1)
                toks.append(self.token(psum, 0))
        return toks

    def decode(self, lengths, active, temps, seeds):
        with self._lock:
            toks = np.zeros(self.slots, np.int64)
            for slot, is_active in enumerate(active):
                if is_active and slot in self._state:
                    psum, k = self._state[slot]
                    toks[slot] = self.token(psum, k)
                    self._state[slot] = (psum, k + 1)
        return toks

    def fetch_step(self, handle):
        time.sleep(self.step_s)  # the simulated device step
        return np.asarray(handle)


def make_decode_payloads(n: int, *, max_new: int, vocab: int = 512,
                         seed: int = 0) -> list[dict]:
    """Mixed-length generation pool: prompts 4..32 tokens; output budgets
    are heavy-tailed — 3/4 short turns of 2..max_new/4 tokens, 1/4 long
    generations of 3/4*max_new..max_new — the length mix real decode
    traffic shows and the one static flush-batching is worst at: every
    flush batch lasts as long as its LONGEST member, so one long
    generation strands the seven finished slots beside it."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        plen = int(rng.integers(4, 33))
        if rng.random() < 0.75:
            budget = int(rng.integers(2, max(3, max_new // 4)))
        else:
            budget = int(rng.integers(max(2, 3 * max_new // 4), max_new + 1))
        out.append({
            "input_ids": rng.integers(5, vocab, size=plen),
            "max_new_tokens": budget,
        })
    return out


def _sim_expected(payload: dict) -> list[int]:
    psum = int(np.sum(payload["input_ids"]))
    return [
        SimStepEngine.token(psum, k)
        for k in range(payload["max_new_tokens"])
    ]


class SimChunkedEngine(SimStepEngine):
    """Chunked-prefill twin of :class:`SimStepEngine`: prefill cost is
    PROPORTIONAL to the tokens prefilled (``token_cost_ms`` each, the
    cost model under which monolithic long-prompt admission stalls the
    decode loop), dispatched through the batcher's ``prefill_chunks``
    path. ``prefill_chunk`` is the bound under test — pass the max prompt
    length to get the monolithic baseline arm through the same code."""

    def __init__(self, *, slots: int, max_batch: int, max_new_tokens: int,
                 step_ms: float, prefill_chunk: int, token_cost_ms: float):
        super().__init__(slots=slots, max_batch=max_batch,
                         max_new_tokens=max_new_tokens, step_ms=step_ms)
        self.prefill_chunk_size = prefill_chunk
        self.prefix_cache = None
        self.token_cost_s = token_cost_ms / 1e3

    def prefill_chunks(self, rows: list[dict]):
        with self._lock:
            toks, worst = [], 0.0
            for r in rows:
                worst = max(worst, int(r["n_tokens"]) * self.token_cost_s)
                if int(r["start"]) + int(r["n_tokens"]) >= int(r["length"]):
                    psum = int(np.sum(r["input_ids"]))
                    self._state[int(r["slot"])] = (psum, 1)
                    toks.append(self.token(psum, 0))
                else:
                    toks.append(0)  # mid-prompt lane: nobody reads it
        return ("chunk", worst, toks)

    def fetch_step(self, handle):
        if isinstance(handle, tuple) and handle[0] == "chunk":
            time.sleep(handle[1])
            return np.asarray(handle[2])
        return super().fetch_step(handle)


class SimDisaggEngine(SimChunkedEngine):
    """:class:`SimChunkedEngine` plus the two surfaces the disaggregated
    roles need: a REAL :class:`KVBlockPool` as ``prefix_cache`` (chains
    carry no pages — sim tokens are a closed-form function of the full
    prompt, so pool-only adoption is exact) and the no-op
    ``insert_prefix`` the batcher's publish path calls on a final chunk."""

    def __init__(self, *, pool_blocks: int, block_tokens: int, **kw):
        super().__init__(**kw)
        from distributed_tensorflow_tpu.serve import KVBlockPool

        self.prefix_cache = KVBlockPool(
            pool_blocks, block_tokens, bytes_per_block=2048
        )

    def insert_prefix(self, slot: int, new) -> None:
        pass  # no device pages to publish; the pool index IS the state


def make_prefix_payloads(n: int, *, heads: int, head_len: int,
                         tail_lens: tuple[int, int], max_new: int,
                         vocab: int = 64, seed: int = 0) -> list[dict]:
    """Zipf shared-prefix workload: ``heads`` hot prompt heads of
    ``head_len`` tokens (system prompts / few-shot preambles), each
    request picks one Zipf(1.1)-distributed and appends a random tail of
    ``tail_lens`` tokens — the traffic shape prefix caching pays for:
    most requests re-prefill a head some earlier request already paid."""
    rng = np.random.default_rng(seed)
    pool = [rng.integers(5, vocab, size=head_len) for _ in range(heads)]
    out = []
    for _ in range(n):
        h = pool[min(int(rng.zipf(1.1)) - 1, heads - 1)]
        tail = rng.integers(5, vocab, size=int(rng.integers(*tail_lens)))
        out.append({
            "input_ids": np.concatenate([h, tail]),
            "max_new_tokens": int(rng.integers(2, max_new + 1)),
        })
    return out


def _decode_parity_probe(n_requests: int) -> tuple[bool, float, dict]:
    """Numerics tripwire ahead of the sim A/B: a real (tiny) causal-LM
    engine decodes a mixed backlog through the continuous batcher — more
    requests than slots, so admissions join mid-flight — and every token
    stream must equal a cache-free full-forward greedy reference. Returns
    ``(parity_ok, max_phase_divergence, grid_status)`` with the divergence
    measured on the REAL engine's phase spans (queue_wait/prefill/decode
    vs wall) and the grid digest from the engine's AOT compiles."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=48,
    )
    model = CausalLM(cfg)
    L = cfg.max_position
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
    )["params"]
    engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8,
    )

    rng = np.random.default_rng(7)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(3, 14))
        reqs.append({
            "input_ids": rng.integers(5, cfg.vocab_size, size=plen),
            "max_new_tokens": int(rng.integers(2, 9)),
        })
    refs = []
    for r in reqs:
        toks = [int(t) for t in r["input_ids"]]
        out = []
        for _ in range(r["max_new_tokens"]):
            x = jnp.asarray([toks], jnp.int32)
            logits = model.apply(
                {"params": params}, x, jnp.ones((1, len(toks)), bool)
            )
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            toks.append(nxt)
        refs.append(out)

    with Client(engine, BatcherConfig(max_batch=2)) as client:
        futs = [client.submit(dict(r)) for r in reqs]
        results = [f.result(timeout=300) for f in futs]
    ok, max_div = True, 0.0
    for r, ref, f in zip(results, refs, futs):
        if r["tokens"] != ref:
            ok = False
            print(f"# parity mismatch: got {r['tokens']} want {ref}",
                  file=sys.stderr)
        if f.latency_s:
            max_div = max(
                max_div,
                abs(sum(f.phases.values()) - f.latency_s) / f.latency_s,
            )
    return ok, max_div, engine.grid_status()


def _run_decode_point(args, admission: str, payloads: list[dict],
                      open_rps: float) -> dict:
    """One arm of the A/B: fresh sim engine + batcher in the given
    admission mode, a saturated closed-loop backlog drain, then one
    open-loop offered-load point. Token streams are checked against the
    closed form; phase sums are checked against wall latency."""
    from distributed_tensorflow_tpu.serve import BatcherConfig, Client

    eng = SimStepEngine(
        slots=args.slots, max_batch=args.max_batch,
        max_new_tokens=args.max_new_tokens, step_ms=args.sim_step_ms,
    )
    client = Client(
        eng,
        BatcherConfig(
            max_batch=args.max_batch, max_queue=args.max_queue,
            max_in_flight=args.max_in_flight,
            max_delay_ms=args.max_delay_ms,
        ),
        admission=admission,
    )
    m = client.metrics
    mismatched, max_div = 0, 0.0
    try:
        client.call(payloads[0], timeout=120)  # warm the thread machinery
        # ------- closed loop: saturated backlog drain (peak tokens/s)
        m.ttft.reset()
        m.itl.reset()
        steps0 = m.decode_steps.value
        t0 = time.monotonic()
        futs = [client.submit(dict(p)) for p in payloads]
        results = [f.result(timeout=600) for f in futs]
        wall = time.monotonic() - t0
        for p, f, r in zip(payloads, futs, results):
            if r["tokens"] != _sim_expected(p):
                mismatched += 1
            if f.latency_s:
                max_div = max(
                    max_div,
                    abs(sum(f.phases.values()) - f.latency_s) / f.latency_s,
                )
        toks = sum(r["n_tokens"] for r in results)
        steps = m.decode_steps.value - steps0
        snap = m.snapshot()
        backlog = {
            "requests": len(results),
            "tokens": toks,
            "wall_s": wall,
            "tokens_per_s": toks / wall,
            "ttft_p50_ms": snap["ttft_ms"]["p50"],
            "ttft_p99_ms": snap["ttft_ms"]["p99"],
            "itl_p50_ms": snap["itl_ms"]["p50"],
            "itl_p99_ms": snap["itl_ms"]["p99"],
            "decode_steps": steps,
            # decode-fetched tokens per step = how full the table ran
            # (prefill delivers each request's first token).
            "mean_live_slots": (toks - len(results)) / steps if steps else 0.0,
        }
        # ------- open loop: fixed offered request schedule
        m.ttft.reset()
        m.itl.reset()
        tokens0 = m.tokens.value
        load = run_load(client, payloads, open_rps, args.duration)
        load.pop("_exact_latency_s", None)
        snap = m.snapshot()
        open_row = {
            "offered_rps": load["offered_rps"],
            "submitted": load["submitted"],
            "served": load["served"],
            "rejected": load["rejected"],
            "achieved_rps": load["achieved_rps"],
            "tokens_per_s": (m.tokens.value - tokens0) / load["wall_s"],
            "ttft_p50_ms": snap["ttft_ms"]["p50"],
            "ttft_p99_ms": snap["ttft_ms"]["p99"],
            "itl_p50_ms": snap["itl_ms"]["p50"],
        }
    finally:
        client.close()
    return {
        "admission": admission,
        "backlog": backlog,
        "open_loop": open_row,
        "mismatched_streams": mismatched,
        "max_phase_divergence": max_div,
    }


def _run_prefix_cache_ab(args) -> dict:
    """Prefix-cache A/B on a REAL tiny engine: the same Zipf shared-prefix
    stream runs cache-on (prefix pool + chunked prefill) and cache-off
    (legacy monolithic prefill); streams must be bit-identical and the
    cache arm reports hit rate / tokens saved / TTFT / tokens/s."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )

    if args.quick:
        # CI shape: gate correctness (parity, nonzero hit rate), not perf
        # — at this size dispatch overhead swamps the prefill compute the
        # cache saves, so the ratios are meaningless.
        geo = dict(hidden=32, layers=2, heads=2, maxpos=48,
                   buckets=(8, 32), head_len=24, tails=(3, 8),
                   chunk=16, bt=4, mb=0.25, n=16)
    else:
        # Perf shape (docs/PERF.md round 12): heads long enough that
        # re-prefilling one costs real compute — the regime prefix
        # caching exists for.
        geo = dict(hidden=128, layers=4, heads=4, maxpos=384,
                   buckets=(32, 256), head_len=192, tails=(3, 16),
                   chunk=64, bt=16, mb=8.0, n=48)
    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=geo["hidden"],
        num_layers=geo["layers"], num_heads=geo["heads"],
        intermediate_size=4 * geo["hidden"], max_position=geo["maxpos"],
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    n = geo["n"]
    payloads = make_prefix_payloads(
        n, heads=3, head_len=geo["head_len"], tail_lens=geo["tails"],
        max_new=6, vocab=cfg.vocab_size,
    )
    # One warm request per distinct head primes the trie (and both arms'
    # dispatch machinery) outside the measured window.
    seen, warm_idx = set(), []
    for i, p in enumerate(payloads):
        key = tuple(int(t) for t in p["input_ids"][:geo["head_len"]])
        if key not in seen:
            seen.add(key)
            warm_idx.append(i)

    arms = {}
    for name, kw in (
        ("cache_on", dict(prefix_cache_mb=geo["mb"],
                          block_tokens=geo["bt"],
                          prefill_chunk=geo["chunk"])),
        ("cache_off", {}),
    ):
        engine = CausalLMEngine(
            model, params, buckets=geo["buckets"], slots=4, max_batch=2,
            max_new_tokens=8, **kw,
        )
        with Client(
            engine,
            BatcherConfig(max_batch=2, max_queue=4 * n, max_in_flight=2),
        ) as client:
            m = client.metrics
            for i in warm_idx:
                client.call(dict(payloads[i]), timeout=300)
            m.ttft.reset()
            lk0, h0, sv0 = (m.prefix_lookups.value, m.prefix_hits.value,
                            m.prefix_tokens_saved.value)
            t0 = time.monotonic()
            futs = [client.submit(dict(p)) for p in payloads]
            results = [f.result(timeout=600) for f in futs]
            wall = time.monotonic() - t0
            snap = m.snapshot()
            lookups = m.prefix_lookups.value - lk0
            hits = m.prefix_hits.value - h0
            arms[name] = {
                "streams": [r["tokens"] for r in results],
                "requests": n,
                "wall_s": wall,
                "tokens_per_s": sum(r["n_tokens"] for r in results) / wall,
                "ttft_p50_ms": snap["ttft_ms"]["p50"],
                "ttft_p99_ms": snap["ttft_ms"]["p99"],
                "hit_rate": hits / lookups if lookups else 0.0,
                "tokens_saved": m.prefix_tokens_saved.value - sv0,
                "kv_pool_bytes": snap["kv_pool_bytes"],
            }
    on, off = arms["cache_on"], arms["cache_off"]
    mismatched = sum(
        a != b for a, b in zip(on.pop("streams"), off.pop("streams"))
    )
    return {
        "workload": {"requests": n, "heads": 3,
                     "head_len": geo["head_len"],
                     "hidden": geo["hidden"], "layers": geo["layers"],
                     "prefill_chunk": geo["chunk"],
                     "block_tokens": geo["bt"]},
        "cache_on": on,
        "cache_off": off,
        "mismatched_streams": mismatched,
        "ttft_p50_ratio": (
            off["ttft_p50_ms"] / on["ttft_p50_ms"]
            if on["ttft_p50_ms"] else 1.0
        ),
        "tokens_per_s_ratio": (
            on["tokens_per_s"] / off["tokens_per_s"]
            if off["tokens_per_s"] else 1.0
        ),
    }


def _run_chunked_itl_ab(args) -> dict:
    """Chunked-prefill ITL A/B (sim): a short-prompt decode backlog keeps
    the slot table busy; long prompts then admit mid-flight. The chunked
    arm prefills them ``chunk`` tokens per loop iteration interleaved
    with decode steps; the monolithic arm stalls every in-flight slot for
    the whole prompt. Reported per arm: steady-state decode ITL p99 (no
    long prompts) vs ITL p99 during long-prompt admission."""
    from distributed_tensorflow_tpu.serve import BatcherConfig, Client

    rng = np.random.default_rng(3)
    n_short = 16 if args.quick else 48
    shorts = [
        {
            "input_ids": rng.integers(5, 512, size=int(rng.integers(4, 17))),
            "max_new_tokens": int(rng.integers(6, 13)),
        }
        for _ in range(n_short)
    ]
    longs = [
        {
            "input_ids": rng.integers(5, 512, size=224),
            "max_new_tokens": 4,
        }
        for _ in range(3)
    ]
    chunk_bound = 16
    token_cost_ms = args.sim_step_ms / 32.0
    arms = {}
    mismatched = 0
    for name, chunk in (("chunked", chunk_bound), ("monolithic", 256)):
        eng = SimChunkedEngine(
            slots=8, max_batch=4, max_new_tokens=16,
            step_ms=args.sim_step_ms, prefill_chunk=chunk,
            token_cost_ms=token_cost_ms,
        )
        client = Client(
            eng,
            BatcherConfig(max_batch=4, max_queue=1024, max_in_flight=2),
        )
        m = client.metrics
        try:
            client.call(dict(shorts[0]), timeout=120)
            m.itl.reset()
            futs = [client.submit(dict(p)) for p in shorts]
            res = [f.result(timeout=600) for f in futs]
            mismatched += sum(
                r["tokens"] != _sim_expected(p)
                for p, r in zip(shorts, res)
            )
            steady = m.snapshot()["itl_ms"]["p99"]
            m.itl.reset()
            futs = [client.submit(dict(p)) for p in shorts + longs]
            res = [f.result(timeout=600) for f in futs]
            mismatched += sum(
                r["tokens"] != _sim_expected(p)
                for p, r in zip(shorts + longs, res)
            )
            admit = m.snapshot()["itl_ms"]["p99"]
        finally:
            client.close()
        arms[name] = {
            "prefill_chunk": chunk,
            "steady_itl_p99_ms": steady,
            "admission_itl_p99_ms": admit,
            "itl_p99_ratio": admit / steady if steady else float("inf"),
        }
    return {
        "config": {
            "short_requests": n_short,
            "long_prompt_tokens": 224,
            "token_cost_ms": token_cost_ms,
        },
        "arms": arms,
        "mismatched_streams": mismatched,
    }


def _run_spec_ab(args) -> dict:
    """Speculative-decoding A/B on a REAL tiny engine: repetitive and
    adversarial-random workloads each run spec-on and spec-off; streams
    must be bit-identical (exact-match acceptance), the repetitive
    workload shows the win, the random one bounds the overhead."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=48,
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]

    def greedy(prompt, n):
        toks = [int(t) for t in prompt]
        out = []
        for _ in range(n):
            x = jnp.asarray([toks], jnp.int32)
            logits = model.apply(
                {"params": params}, x, jnp.ones((1, len(toks)), bool)
            )
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            toks.append(nxt)
        return out

    def predictive_prompt(seed, n_new=10, plen=16):
        # Fixed point: embed the model's OWN continuation after a marker
        # token, end the prompt with the marker again — the drafter's
        # suffix match then proposes exactly what the model will emit.
        rng = np.random.default_rng(seed)
        t = int(rng.integers(5, 64))
        c = greedy(rng.integers(5, 64, size=plen), n_new)
        for _ in range(6):
            p = [int(rng.integers(5, 64)), t] + c + [
                int(x) for x in rng.integers(5, 64, size=plen - 3 - len(c))
            ] + [t]
            c2 = greedy(p, n_new)
            if c2 == c:
                break
            c = c2
        return np.array(p, np.int32)

    n_rep = 8 if args.quick else 24
    n_rnd = 8 if args.quick else 24
    distinct = [predictive_prompt(s) for s in (3, 5, 9, 13)]
    rep = [
        {"input_ids": distinct[i % len(distinct)], "max_new_tokens": 10}
        for i in range(n_rep)
    ]
    rng = np.random.default_rng(0)
    rnd = [
        {
            "input_ids": rng.integers(5, 64, size=int(rng.integers(8, 15))),
            "max_new_tokens": 6,
        }
        for _ in range(n_rnd)
    ]
    workloads = {"repetitive": rep, "random": rnd}

    arms = {}
    nondeterministic = 0
    for name, spec_k in (("spec_on", 4), ("spec_off", 0)):
        engine = CausalLMEngine(
            model, params, buckets=(8, 16), slots=4, max_batch=2,
            max_new_tokens=12, spec_tokens=spec_k,
        )
        # max_in_flight=1 for BOTH arms: overlapped dispatch hides HOST
        # latency, which on a CPU-sized model is the whole step cost —
        # verify steps can't pipeline (the next draft depends on this
        # verify's outcome), so depth-2 overlap would hand the plain arm
        # a ~2x host-side advantage a real accelerator doesn't have
        # (device step time is serial either way). Depth 1 compares the
        # thing speculation actually changes: steps per token.
        with Client(
            engine, BatcherConfig(max_batch=2, max_queue=256,
                                  max_in_flight=1),
        ) as client:
            m = client.metrics
            client.call(dict(rep[0]), timeout=300)  # warm the machinery
            rows = {}
            for wname, wl in workloads.items():
                # Best-of-2 drains: walls here are tens of ms, so one
                # scheduler hiccup would otherwise dominate the ratio
                # (same shape as the recorder-overhead A/B). Streams are
                # checked on EVERY attempt — only the clock gets retries.
                best = None
                for _ in range(2):
                    m.itl.reset()
                    d0, a0 = m.draft_tokens.value, m.accepted_tokens.value
                    t0 = time.monotonic()
                    futs = [client.submit(dict(p)) for p in wl]
                    results = [f.result(timeout=600) for f in futs]
                    wall = time.monotonic() - t0
                    drafted = m.draft_tokens.value - d0
                    accepted = m.accepted_tokens.value - a0
                    row = {
                        "streams": [r["tokens"] for r in results],
                        "requests": len(wl),
                        "wall_s": wall,
                        "tokens_per_s": (
                            sum(r["n_tokens"] for r in results) / wall
                        ),
                        "itl_p50_ms": m.snapshot()["itl_ms"]["p50"],
                        "acceptance_rate": (
                            accepted / drafted if drafted else 0.0
                        ),
                    }
                    if best is not None and (
                        row["streams"] != best["streams"]
                    ):
                        nondeterministic += 1
                    if (
                        best is None
                        or row["tokens_per_s"] > best["tokens_per_s"]
                    ):
                        best = row
                rows[wname] = best
            rows["tokens_per_step"] = (
                client.batcher.status()["tokens_per_step"]
            )
        arms[name] = rows
    on, off = arms["spec_on"], arms["spec_off"]
    mismatched = nondeterministic + sum(
        sum(a != b for a, b in zip(on[w].pop("streams"),
                                   off[w].pop("streams")))
        for w in workloads
    )
    return {
        "config": {"spec_tokens": 4, "repetitive_requests": n_rep,
                   "random_requests": n_rnd, "repetitive_max_new": 10,
                   "random_max_new": 6},
        "spec_on": on,
        "spec_off": off,
        "mismatched_streams": mismatched,
        "repetitive_tokens_per_s_ratio": (
            on["repetitive"]["tokens_per_s"]
            / off["repetitive"]["tokens_per_s"]
            if off["repetitive"]["tokens_per_s"] else 1.0
        ),
        "random_tokens_per_s_ratio": (
            on["random"]["tokens_per_s"] / off["random"]["tokens_per_s"]
            if off["random"]["tokens_per_s"] else 1.0
        ),
    }


def _run_quant_ab(args) -> dict:
    """Quantized-serving A/B on a REAL tiny engine (--decode --quant).

    Three measurements, all against the fp32 arm as reference:

    * **quality** — a teacher-forced per-step probe: the fp32 engine's
      greedy continuations become the reference; every step re-submits
      the reference prefix to the int8 engine (weights AND KV int8) with
      ``max_new_tokens=2``, so token 1 checks the prefill forward
      (dequant-in-matmul weights) and token 2 checks a decode step read
      from the int8 KV cache. Teacher forcing is the point: free-running
      agreement cascades after one flip and measures luck, not error.
      A model-level full-forward probe adds the logit MAE of int8
      weights alone.
    * **memory** — each arm owns a private MemoryRegistry; the /memz
      deltas (bytes per component, ``bytes_saved_vs_fp32``) and the
      slots-at-fixed-HBM-budget ratio (fp32 arm's slot-cache bytes
      divided by the int8 arm's per-slot bytes) are deterministic
      arithmetic, so the >=1.7x gate holds unconditionally.
    * **wire** — each arm's engine+batcher mounts a real
      ``make_kv_receiver``; a chain serialized from the OTHER arm's page
      geometry must refuse (WireError) in both directions while the
      same-dtype buffer adopts. Cross-dtype KV adoption fails closed.
    """
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.models.quant import (
        dequantize_params,
        quantize_params,
    )
    from distributed_tensorflow_tpu.obs.memory import MemoryRegistry
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )
    from distributed_tensorflow_tpu.serve.disagg import (
        WireError,
        make_kv_receiver,
        serialize_chain,
    )

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=64,
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]

    # Model-level weight probe: full forward fp32 vs dequantized int8
    # kernels — per-position top-1 agreement and the raw logit MAE
    # (teacher-forced by construction: every position conditions on the
    # given sequence, not on earlier predictions).
    rng = np.random.default_rng(11)
    probe = jnp.asarray(rng.integers(5, 64, size=(8, 24)), jnp.int32)
    pmask = jnp.ones(probe.shape, bool)
    ref_logits = model.apply({"params": params}, probe, pmask)
    dq = dequantize_params(quantize_params(params), cfg.dtype)
    q_logits = model.apply({"params": dq}, probe, pmask)
    logit_mae = float(jnp.mean(jnp.abs(q_logits - ref_logits)))
    logit_mean_abs = float(jnp.mean(jnp.abs(ref_logits)))
    weight_top1 = float(jnp.mean(
        (jnp.argmax(q_logits, -1) == jnp.argmax(ref_logits, -1))
        .astype(jnp.float32)
    ))

    n_prompts = 4 if args.quick else 8
    n_steps = 6 if args.quick else 10
    prompts = [
        rng.integers(5, 64, size=int(rng.integers(8, 15)))
        for _ in range(n_prompts)
    ]
    slots = 4

    def build_arm(weight_dtype, kv_dtype):
        registry = MemoryRegistry()
        engine = CausalLMEngine(
            model, params, buckets=(16, 32), slots=slots, max_batch=2,
            max_new_tokens=n_steps + 2, prefix_cache_mb=0.25,
            block_tokens=4, kv_transfer=True,
            weight_dtype=weight_dtype, kv_dtype=kv_dtype,
            memory=registry,
        )
        client = Client(
            engine, BatcherConfig(max_batch=2, max_queue=256,
                                  max_in_flight=1),
        )
        return engine, client, registry

    def drain(client):
        t0 = time.monotonic()
        futs = [
            client.submit(
                {"input_ids": p, "max_new_tokens": n_steps}
            ) for p in prompts
        ]
        results = [f.result(timeout=600) for f in futs]
        wall = time.monotonic() - t0
        return results, {
            "wall_s": wall,
            "tokens_per_s": sum(r["n_tokens"] for r in results) / wall,
        }

    arms, engines, clients, registries = {}, {}, {}, {}
    for name, (wd, kd) in (
        ("fp32", (None, None)), ("int8", ("int8", "int8")),
    ):
        engine, client, registry = build_arm(wd, kd)
        engines[name], clients[name], registries[name] = (
            engine, client, registry
        )
        client.call(
            {"input_ids": prompts[0], "max_new_tokens": 2}, timeout=300,
        )  # warm the machinery before the clock starts
        results, perf = drain(client)
        arms[name] = {
            "streams": [r["tokens"] for r in results],
            **perf,
            "weight_dtype": engine.weight_dtype,
            "kv_dtype": engine.kv_dtype,
            "kv_bytes_per_token": engine.kv_bytes_per_token(),
            "slot_page_bytes": engine.slot_page_bytes,
        }

    # Teacher-forced per-step agreement through the int8 ENGINE: token 1
    # of each probe exercises prefill (int8 weights), token 2 a decode
    # step over the int8 KV the prefill scatter quantized.
    agree = total = 0
    int8_client = clients["int8"]
    for p, ref in zip(prompts, arms["fp32"]["streams"]):
        for t in range(len(ref) - 1):
            forced = np.concatenate([p, np.asarray(ref[:t], np.int64)])
            out = int8_client.call(
                {"input_ids": forced, "max_new_tokens": 2}, timeout=300,
            )["tokens"]
            agree += (out[0] == ref[t]) + (out[1] == ref[t + 1])
            total += 2
    top1_agreement = agree / total if total else 1.0

    # Memory ledger + the fixed-HBM-budget slot arithmetic. The budget is
    # the fp32 arm's slot-cache reservation; dividing it by the int8
    # arm's per-slot bytes says how many slots the SAME HBM would hold
    # quantized — deterministic, so the gate is unconditional.
    mem = {}
    for name, registry in registries.items():
        snap = registry.snapshot()
        mem[name] = {
            "components": snap["components"],
            "component_dtypes": snap["component_dtypes"],
            "bytes_saved_vs_fp32": snap["bytes_saved_vs_fp32"],
            "bytes_saved_vs_fp32_total": snap["bytes_saved_vs_fp32_total"],
        }
    kv_budget = mem["fp32"]["components"]["kv_slot_cache"]
    slots_at_budget = kv_budget // arms["int8"]["slot_page_bytes"]
    slots_ratio = slots_at_budget / slots

    # Wire-format cross-refusal through the REAL receivers.
    def chain_buf(engine, seed):
        meta = engine.page_meta()
        wrng = np.random.default_rng(seed)
        bt = meta["block_tokens"]
        shape = (meta["num_layers"], 1, bt, meta["heads"],
                 meta["head_dim"])

        def side():
            if meta["dtype"] == "int8":
                return {
                    "q": wrng.integers(-127, 128, shape, dtype=np.int8),
                    "s": wrng.random(shape[:3], dtype=np.float32),
                }
            return wrng.random(shape, dtype=np.float32)

        ids = list(wrng.integers(5, 64, size=bt))
        return serialize_chain(
            ids, side(), side(),
            {k: v for k, v in meta.items() if k != "max_chain"},
        )

    receivers = {
        name: make_kv_receiver(clients[name].batcher, engines[name])
        for name in arms
    }
    cross_refusals = {}
    for src, dst in (("int8", "fp32"), ("fp32", "int8")):
        try:
            receivers[dst](chain_buf(engines[src], seed=5))
            cross_refusals[f"{src}_to_{dst}"] = "ADOPTED (FAIL)"
        except WireError as e:
            cross_refusals[f"{src}_to_{dst}"] = f"refused: {str(e)[:90]}"
    same_dtype_adopts = {}
    for name in arms:
        out = receivers[name](chain_buf(engines[name], seed=6))
        same_dtype_adopts[name] = out["adopted_blocks"]

    for client in clients.values():
        client.close()

    for arm in arms.values():
        arm.pop("streams")
    return {
        "config": {
            "prompts": n_prompts, "steps": n_steps, "slots": slots,
            "model": {"hidden": 32, "layers": 2, "heads": 2, "vocab": 64},
        },
        "arms": arms,
        "weight_logit_mae": logit_mae,
        "weight_logit_mean_abs": logit_mean_abs,
        "weight_top1_agreement": weight_top1,
        "top1_agreement": top1_agreement,
        "teacher_forced_comparisons": total,
        "memory": mem,
        "kv_budget_bytes": kv_budget,
        "slots_at_fp32_budget": int(slots_at_budget),
        "slots_ratio": slots_ratio,
        "wire_cross_refusals": cross_refusals,
        "wire_same_dtype_adopted_blocks": same_dtype_adopts,
        "tokens_per_s_ratio": (
            arms["int8"]["tokens_per_s"] / arms["fp32"]["tokens_per_s"]
            if arms["fp32"]["tokens_per_s"] else 1.0
        ),
    }


def run_quant(args) -> int:
    """The quantized-serving A/B (--decode --quant)."""
    print("# quantized serving A/B: real tiny engine, fp32 vs int8 "
          "weights + int8 KV (teacher-forced per-step agreement)")
    q = _run_quant_ab(args)

    hdr = (
        f"{'arm':>6} {'weights':>8} {'kv':>8} {'tok/s':>8} "
        f"{'KV B/token':>11} {'slot bytes':>11}"
    )
    print(hdr)
    print("-" * len(hdr))
    for name in ("fp32", "int8"):
        a = q["arms"][name]
        print(
            f"{name:>6} {a['weight_dtype']:>8} {a['kv_dtype']:>8} "
            f"{a['tokens_per_s']:>8.1f} {a['kv_bytes_per_token']:>11d} "
            f"{a['slot_page_bytes']:>11d}"
        )
    rel_mae = (
        q["weight_logit_mae"] / q["weight_logit_mean_abs"]
        if q["weight_logit_mean_abs"] else 0.0
    )
    print(
        f"\nquality: teacher-forced top-1 agreement "
        f"{q['top1_agreement']:.4f} over "
        f"{q['teacher_forced_comparisons']} step comparisons; "
        f"weight-only full-forward top-1 "
        f"{q['weight_top1_agreement']:.4f}, logit MAE "
        f"{q['weight_logit_mae']:.4g} "
        f"({100 * rel_mae:.2f}% of mean |logit|)"
    )
    saved = q["memory"]["int8"]["bytes_saved_vs_fp32_total"]
    print(
        f"memory: int8 arm saves {saved / 1024:.1f} KiB vs fp32 "
        f"({q['memory']['int8']['bytes_saved_vs_fp32']}); at the fp32 "
        f"arm's {q['kv_budget_bytes']} B slot-cache budget the int8 "
        f"cache holds {q['slots_at_fp32_budget']} slots "
        f"({q['slots_ratio']:.2f}x the configured "
        f"{q['config']['slots']})"
    )
    for pair, outcome in q["wire_cross_refusals"].items():
        print(f"wire {pair}: {outcome}")
    print(f"wire same-dtype adoption: "
          f"{q['wire_same_dtype_adopted_blocks']}")

    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"mode": "quant", **q}, fh, indent=2)
        print(f"# wrote {args.json}")

    # Quality, the slot arithmetic, and wire fail-closed are
    # UNCONDITIONAL gates (quick and full): none of them measures
    # wall-clock, so machine load cannot excuse a miss.
    ok = True
    if q["top1_agreement"] < 0.99:
        print(f"FAIL: teacher-forced top-1 agreement "
              f"{q['top1_agreement']:.4f} < 0.99 — int8 weights+KV are "
              "changing greedy decisions", file=sys.stderr)
        ok = False
    if rel_mae > 0.05:
        print(f"FAIL: int8-weight logit MAE is {100 * rel_mae:.2f}% of "
              "mean |logit| (>5%) — per-channel scales are off",
              file=sys.stderr)
        ok = False
    if q["slots_ratio"] < 1.7:
        print(f"FAIL: int8 KV admits only {q['slots_ratio']:.2f}x slots "
              "at the fp32 HBM budget (<1.7x)", file=sys.stderr)
        ok = False
    for pair, outcome in q["wire_cross_refusals"].items():
        if not outcome.startswith("refused"):
            print(f"FAIL: cross-dtype KV chain {pair} was adopted — "
                  "the wire must fail closed", file=sys.stderr)
            ok = False
    for name, adopted in q["wire_same_dtype_adopted_blocks"].items():
        if adopted < 1:
            print(f"FAIL: same-dtype chain adoption on the {name} arm "
                  f"adopted {adopted} blocks", file=sys.stderr)
            ok = False
    return 0 if ok else 1


def run_decode(args) -> int:
    """The continuous-batching decode A/B (--decode)."""
    payloads = make_decode_payloads(
        args.decode_requests, max_new=args.max_new_tokens, vocab=args.vocab
    )
    open_rps = args.loads[0]

    print("# decode parity probe: real tiny causal-LM engine, greedy, "
          "mid-flight admissions vs full-forward reference")
    parity_ok, parity_div, grid = _decode_parity_probe(3 if args.quick else 6)
    print(f"# parity {'ok' if parity_ok else 'FAIL'}, real-engine phase "
          f"divergence {100 * parity_div:.1f}%")
    _print_grid_summary(grid)

    # The continuous-vs-flush A/B feeds the round-11 perf gate. The
    # single-shot >=1.5x assertion was flaky under machine load (CHANGES.md
    # PR 14: 1.38-1.43x on the seed with a busy host), so --quick runs it
    # best-of-N with load-aware retries. Only the throughput threshold gets
    # extra rolls of the wall-clock dice: stream bit-parity accumulates
    # across EVERY attempt and stays an unconditional gate below.
    ab_attempts = 3 if args.quick else 1
    mismatched = 0
    best = None
    for attempt in range(1, ab_attempts + 1):
        rows = {
            admission: _run_decode_point(args, admission, payloads, open_rps)
            for admission in ("continuous", "flush")
        }
        cont, flsh = rows["continuous"], rows["flush"]
        speedup = (
            cont["backlog"]["tokens_per_s"] / flsh["backlog"]["tokens_per_s"]
            if flsh["backlog"]["tokens_per_s"] else float("inf")
        )
        ttft_ratio = (
            cont["backlog"]["ttft_p50_ms"] / flsh["backlog"]["ttft_p50_ms"]
            if flsh["backlog"]["ttft_p50_ms"] else 1.0
        )
        mismatched += cont["mismatched_streams"] + flsh["mismatched_streams"]
        if best is None or speedup > best[1]:
            best = (rows, speedup, ttft_ratio)
        if speedup >= 1.5 and ttft_ratio <= 1.05:
            break
        if attempt < ab_attempts:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(
                f"# A/B attempt {attempt}/{ab_attempts}: {speedup:.2f}x "
                f"tokens/s, ttft p50 {ttft_ratio:.2f}x at loadavg/core "
                f"{load:.2f} — retrying"
            )
    rows, speedup, ttft_ratio = best

    hdr = (
        f"{'admission':>11} {'tok/s':>8} {'ttft p50':>9} {'ttft p99':>9} "
        f"{'itl p50':>8} {'itl p99':>8} {'steps':>6} {'live/step':>9} "
        f"{'wall s':>7}"
    )
    print(f"\nbacklog drain ({args.decode_requests} mixed requests, "
          f"{args.slots} slots, {args.sim_step_ms:g} ms/step):")
    print(hdr)
    print("-" * len(hdr))
    for name, r in rows.items():
        b = r["backlog"]
        print(
            f"{name:>11} {b['tokens_per_s']:>8.0f} "
            f"{b['ttft_p50_ms']:>9.1f} {b['ttft_p99_ms']:>9.1f} "
            f"{b['itl_p50_ms']:>8.2f} {b['itl_p99_ms']:>8.2f} "
            f"{b['decode_steps']:>6d} {b['mean_live_slots']:>9.2f} "
            f"{b['wall_s']:>7.2f}"
        )
    print(f"\nopen loop ({open_rps:g} req/s offered, "
          f"{args.duration:g}s):")
    hdr = (
        f"{'admission':>11} {'tok/s':>8} {'achieved rps':>13} "
        f"{'rejected':>9} {'ttft p50':>9} {'ttft p99':>9} {'itl p50':>8}"
    )
    print(hdr)
    print("-" * len(hdr))
    for name, r in rows.items():
        o = r["open_loop"]
        print(
            f"{name:>11} {o['tokens_per_s']:>8.0f} "
            f"{o['achieved_rps']:>13.1f} {o['rejected']:>9d} "
            f"{o['ttft_p50_ms']:>9.1f} {o['ttft_p99_ms']:>9.1f} "
            f"{o['itl_p50_ms']:>8.2f}"
        )

    cont, flsh = rows["continuous"], rows["flush"]
    max_div = max(
        parity_div,
        cont["max_phase_divergence"],
        flsh["max_phase_divergence"],
    )
    print(
        f"\ncontinuous vs flush: {speedup:.2f}x tokens/s, "
        f"ttft p50 {ttft_ratio:.2f}x, max phase divergence "
        f"{100 * max_div:.1f}%"
    )

    print("\n# prefix-cache A/B: real tiny engine, Zipf shared-prefix "
          "workload, cache-on (KV pool + chunked prefill) vs cache-off")
    prefix = _run_prefix_cache_ab(args)
    hdr = (
        f"{'arm':>10} {'tok/s':>8} {'ttft p50':>9} {'ttft p99':>9} "
        f"{'hit rate':>9} {'tok saved':>10} {'pool KiB':>9}"
    )
    print(hdr)
    print("-" * len(hdr))
    for name in ("cache_on", "cache_off"):
        a = prefix[name]
        print(
            f"{name:>10} {a['tokens_per_s']:>8.1f} "
            f"{a['ttft_p50_ms']:>9.1f} {a['ttft_p99_ms']:>9.1f} "
            f"{a['hit_rate']:>9.2f} {a['tokens_saved']:>10d} "
            f"{a['kv_pool_bytes'] / 1024:>9.1f}"
        )
    print(
        f"prefix cache vs cold: ttft p50 "
        f"{prefix['ttft_p50_ratio']:.2f}x better, tokens/s "
        f"{prefix['tokens_per_s_ratio']:.2f}x, "
        f"{prefix['mismatched_streams']} mismatched streams"
    )

    print("\n# chunked-prefill ITL A/B: sim engine, long-prompt admission "
          "against a short-prompt decode backlog")
    itl = _run_chunked_itl_ab(args)
    hdr = (
        f"{'arm':>11} {'chunk':>6} {'steady itl p99':>15} "
        f"{'admission itl p99':>18} {'ratio':>6}"
    )
    print(hdr)
    print("-" * len(hdr))
    for name in ("chunked", "monolithic"):
        a = itl["arms"][name]
        print(
            f"{name:>11} {a['prefill_chunk']:>6d} "
            f"{a['steady_itl_p99_ms']:>15.2f} "
            f"{a['admission_itl_p99_ms']:>18.2f} "
            f"{a['itl_p99_ratio']:>6.2f}"
        )

    print("\n# speculative-decoding A/B: real tiny engine, n-gram "
          "drafting + batched verify (k=4) vs plain decode")
    # Same load-flakiness discipline as the continuous-vs-flush gate
    # above: the random-workload floor measures wall-clock throughput on
    # a shared CI box, so --quick takes the best of up to 3 attempts.
    # Stream parity stays unconditional — mismatches accumulate across
    # ALL attempts and any one of them fails the run.
    spec_attempts = 3 if args.quick else 1
    spec_mismatched = 0
    spec = None
    for attempt in range(1, spec_attempts + 1):
        cand = _run_spec_ab(args)
        spec_mismatched += cand["mismatched_streams"]
        if spec is None or (
            cand["random_tokens_per_s_ratio"]
            > spec["random_tokens_per_s_ratio"]
        ):
            spec = cand
        if spec["random_tokens_per_s_ratio"] >= 0.9:
            break
        load = os.getloadavg()[0] / (os.cpu_count() or 1)
        print(
            f"# spec A/B attempt {attempt}/{spec_attempts}: random "
            f"{cand['random_tokens_per_s_ratio']:.2f}x tokens/s at "
            f"loadavg/core {load:.2f} — retrying"
        )
    hdr = (
        f"{'arm':>9} {'workload':>11} {'tok/s':>8} {'itl p50':>8} "
        f"{'acceptance':>11}"
    )
    print(hdr)
    print("-" * len(hdr))
    for arm in ("spec_on", "spec_off"):
        for wname in ("repetitive", "random"):
            a = spec[arm][wname]
            print(
                f"{arm:>9} {wname:>11} {a['tokens_per_s']:>8.1f} "
                f"{a['itl_p50_ms']:>8.2f} {a['acceptance_rate']:>11.2f}"
            )
    print(
        f"speculation vs plain: repetitive "
        f"{spec['repetitive_tokens_per_s_ratio']:.2f}x tokens/s "
        f"({spec['spec_on']['tokens_per_step']:.2f} tok/slot-step), "
        f"random {spec['random_tokens_per_s_ratio']:.2f}x, "
        f"{spec['mismatched_streams']} mismatched streams"
    )

    if args.json:
        report = {
            "mode": "decode",
            "config": {
                "slots": args.slots,
                "max_batch": args.max_batch,
                "max_in_flight": args.max_in_flight,
                "max_new_tokens": args.max_new_tokens,
                "sim_step_ms": args.sim_step_ms,
                "decode_requests": args.decode_requests,
                "open_rps": open_rps,
            },
            "parity_ok": parity_ok,
            "grid": {k: v for k, v in grid.items() if k != "cells"},
            "ab": rows,
            "ab_attempts": ab_attempts,
            "spec_attempts": spec_attempts,
            "speedup_tokens_per_s": speedup,
            "ttft_p50_ratio": ttft_ratio,
            "max_phase_divergence": max_div,
            "prefix_cache_ab": prefix,
            "chunked_itl_ab": itl,
            "speculation_ab": spec,
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")

    # Correctness is unconditional; the perf thresholds are the --quick CI
    # gate (the same numbers docs/PERF.md round 11 records from a full run).
    if not parity_ok:
        print("FAIL: real-engine greedy decode diverged from the "
              "full-forward reference", file=sys.stderr)
        return 1
    if mismatched:
        print(f"FAIL: {mismatched} sim token streams misrouted by the "
              "slot-table scheduler", file=sys.stderr)
        return 1
    if prefix["mismatched_streams"]:
        print(f"FAIL: {prefix['mismatched_streams']} cached streams "
              "diverge from the cold-prefill reference — prefix-cache "
              "reuse must be bit-exact", file=sys.stderr)
        return 1
    if itl["mismatched_streams"]:
        print(f"FAIL: {itl['mismatched_streams']} sim token streams "
              "corrupted by chunked-prefill interleaving", file=sys.stderr)
        return 1
    if spec_mismatched:
        print(f"FAIL: {spec_mismatched} speculative streams "
              "diverge from the plain-decode reference — exact-match "
              "acceptance must be bit-exact", file=sys.stderr)
        return 1
    if args.quick:
        if spec["random_tokens_per_s_ratio"] < 0.9:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"FAIL: speculation costs "
                  f"{spec['random_tokens_per_s_ratio']:.2f}x tokens/s on "
                  "an adversarial-random workload (<0.9x; best of "
                  f"{spec_attempts} attempts, loadavg/core {load:.2f}) — "
                  "adaptive backoff is no longer bounding the verify "
                  "overhead", file=sys.stderr)
            return 1
        if prefix["cache_on"]["hit_rate"] <= 0.0:
            print("FAIL: prefix-cache hit rate is 0 on a shared-prefix "
                  "workload — the trie never matched", file=sys.stderr)
            return 1
        chunk_ratio = itl["arms"]["chunked"]["itl_p99_ratio"]
        if chunk_ratio > 2.0:
            print(f"FAIL: chunked-prefill decode ITL p99 during "
                  f"long-prompt admission is {chunk_ratio:.2f}x steady "
                  "state (>2x) — prefill chunks are stalling decode",
                  file=sys.stderr)
            return 1
        if max_div > 0.25:
            print(f"FAIL: phase spans diverge {100 * max_div:.1f}% from "
                  "wall latency (>25%)", file=sys.stderr)
            return 1
        if speedup < 1.5:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"FAIL: continuous batching {speedup:.2f}x flush "
                  f"tokens/s (<1.5x, best of {ab_attempts} attempts, "
                  f"loadavg/core {load:.2f}) — admission is no longer "
                  "filling freed slots mid-flight", file=sys.stderr)
            return 1
        if ttft_ratio > 1.05:
            print(f"FAIL: continuous TTFT p50 {ttft_ratio:.2f}x flush "
                  f"(>1.05x, best of {ab_attempts} attempts) — throughput "
                  "must not come from delaying first tokens",
                  file=sys.stderr)
            return 1
    return 0


# ------------------------------------------------------------ sched mode


class SimSchedEngine(SimStepEngine):
    """Resume-exact twin of :class:`SimStepEngine`: token k is a closed
    form of the CUMULATIVE sum of every token before it (prompt AND
    generated), so a stream parked mid-decode and re-prefilled from
    ``prompt + resume_tokens`` lands on the identical continuation. That
    is the property the preemption A/B checks EVERY stream — parked
    victims included — against; ``SimStepEngine.token(prompt_sum, k)``
    cannot express it because a resumed prefill changes both inputs."""

    layout = "sim-sched"

    @staticmethod
    def next_token(state: int) -> int:
        return (state * 9973 + 12345) % 50 + 5

    def prefill(self, admissions: list[dict]):
        with self._lock:
            toks = []
            for a in admissions:
                s = int(np.sum(a["input_ids"]))
                t = self.next_token(s)
                self._state[a["slot"]] = (s + t, 0)
                toks.append(t)
        return toks

    def decode(self, lengths, active, temps, seeds):
        with self._lock:
            toks = np.zeros(self.slots, np.int64)
            for slot, is_active in enumerate(active):
                if is_active and slot in self._state:
                    s, _ = self._state[slot]
                    t = self.next_token(s)
                    toks[slot] = t
                    self._state[slot] = (s + t, 0)
        return toks


def _sched_expected(payload: dict) -> list[int]:
    s = int(np.sum(payload["input_ids"]))
    out = []
    for _ in range(payload["max_new_tokens"]):
        t = SimSchedEngine.next_token(s)
        out.append(t)
        s += t
    return out


def make_sched_payloads(n_bulk: int, n_urgent: int, *, max_new: int,
                        deadline_ms: float, vocab: int = 512,
                        seed: int = 0) -> tuple[list[dict], list[dict]]:
    """Mixed-priority workload: a heavy-tailed bulk backlog (class 2,
    best-effort, the :func:`make_decode_payloads` length mix) plus a
    trickle of small urgent requests (class 0, a TTFT deadline) that
    arrive WHILE the bulk drain owns every slot — the regime priority
    preemption exists for."""
    bulk = make_decode_payloads(n_bulk, max_new=max_new, vocab=vocab,
                                seed=seed)
    for p in bulk:
        p["priority"] = 2
    rng = np.random.default_rng(seed + 1)
    urgent = []
    for _ in range(n_urgent):
        urgent.append({
            "input_ids": rng.integers(5, vocab, size=int(rng.integers(4, 17))),
            "max_new_tokens": int(rng.integers(3, 7)),
            "priority": 0,
            "deadline_ms": deadline_ms,
        })
    return bulk, urgent


def _run_sched_parity_probe(args) -> dict:
    """Forced preempt -> park -> resume on a REAL tiny engine with the
    whole serving stack stacked on (chunked prefill + prefix cache +
    speculation + int8 weights/KV): two low-priority victims fill both
    slots, then a deadline-bearing class-0 request lands and must evict
    one. Every stream — the preempted victims included — must be
    bit-identical to its uninterrupted solo reference."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=128, max_position=48,
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=2, max_batch=2,
        max_new_tokens=8, prefix_cache_mb=0.05, block_tokens=4,
        prefill_chunk=8, spec_tokens=3, weight_dtype="int8",
        kv_dtype="int8",
    )
    rng = np.random.default_rng(20)
    victims = [
        {"input_ids": rng.integers(5, 64, size=n).tolist(),
         "max_new_tokens": 8, "priority": 2}
        for n in (10, 12)
    ]
    hi = {"input_ids": rng.integers(5, 64, size=6).tolist(),
          "max_new_tokens": 4, "priority": 0, "deadline_ms": 5.0}

    # Solo references first: same engine, FIFO client, one request at a
    # time — this also warms every compiled shape outside the contended
    # run (prefix-cache reuse between the phases is itself bit-exact).
    refs = []
    with Client(engine, BatcherConfig(max_batch=2, max_queue=16)) as client:
        for p in victims + [hi]:
            solo = dict(p)
            solo.pop("priority")
            solo.pop("deadline_ms", None)
            refs.append(client.call(solo, timeout=300)["tokens"])

    # Contended run: margin so large any live deadline is already urgent,
    # so the class-0 arrival preempts the moment both slots are busy.
    with Client(
        engine,
        BatcherConfig(max_batch=2, max_queue=16, sched="edf",
                      preempt=True, preempt_margin_ms=1e6),
    ) as client:
        futs = [client.submit(dict(p)) for p in victims]
        t_poll = time.monotonic() + 60.0
        while time.monotonic() < t_poll:
            if client.batcher.status()["slots_active"] == 2:
                break
            time.sleep(0.002)
        futs.append(client.submit(dict(hi)))
        got = [f.result(timeout=300)["tokens"] for f in futs]
        sched = client.batcher.status()["sched"]
    return {
        "streams": len(refs),
        "diverged": sum(a != b for a, b in zip(got, refs)),
        "parked": sched["preempt_parked"],
        "resumed": sched["preempt_resumed"],
        "aborted": sched["preempt_aborted"],
    }


def _run_sched_point(args, policy: str, bulk: list[dict],
                     urgent: list[dict], deadline_ms: float,
                     spacing_s: float) -> dict:
    """One arm of the scheduling A/B: submit the full bulk backlog at t0,
    then trickle the urgent requests in while the drain owns the slot
    table. Same engine model, same workload, same arrival schedule — the
    arms differ ONLY in ``BatcherConfig(sched=, preempt=)``."""
    from distributed_tensorflow_tpu.serve import BatcherConfig, Client

    eng = SimSchedEngine(
        slots=args.slots, max_batch=args.max_batch,
        max_new_tokens=args.max_new_tokens, step_ms=args.sim_step_ms,
    )
    cfg = BatcherConfig(
        max_batch=args.max_batch,
        max_queue=4 * (len(bulk) + len(urgent)),
        max_in_flight=args.max_in_flight,
        max_delay_ms=args.max_delay_ms,
        sched="edf" if policy == "edf" else "fifo",
        preempt=(policy == "edf"),
        # Treat any live deadline as already-urgent: the arm under test
        # acts the moment an urgent request queues behind a full table.
        preempt_margin_ms=deadline_ms if policy == "edf" else 20.0,
    )
    client = Client(eng, cfg, admission="continuous")
    mismatched = 0
    try:
        client.call({"input_ids": [7, 9, 11], "max_new_tokens": 2},
                    timeout=120)
        t0 = time.monotonic()
        bulk_futs = [client.submit(dict(p)) for p in bulk]
        urgent_futs = []
        for p in urgent:
            time.sleep(spacing_s)
            urgent_futs.append(client.submit(dict(p)))
        bulk_res = [f.result(timeout=600) for f in bulk_futs]
        urgent_res = [f.result(timeout=600) for f in urgent_futs]
        wall = time.monotonic() - t0
        for p, r in zip(bulk + urgent, bulk_res + urgent_res):
            if r["tokens"] != _sched_expected(p):
                mismatched += 1
        # Per-request TTFT from the future's phase sidecar: class-0
        # requests are never parked (victims must be strictly lower
        # priority), so queue_wait + prefill IS their time to first
        # token. The global ttft histogram would mix in the bulk class.
        ttfts = sorted(
            1e3 * (f.phases["queue_wait"] + f.phases["prefill"])
            for f in urgent_futs
        )

        def pct(q: float) -> float:
            return ttfts[min(len(ttfts) - 1,
                             int(q * (len(ttfts) - 1) + 0.5))]

        attained = sum(t <= deadline_ms for t in ttfts) / len(ttfts)
        sched = client.batcher.status()["sched"]
        toks = sum(r["n_tokens"] for r in bulk_res + urgent_res)
    finally:
        client.close()
    return {
        "policy": policy,
        "requests": len(bulk) + len(urgent),
        "tokens": toks,
        "wall_s": wall,
        "tokens_per_s": toks / wall,
        "urgent_ttft_p50_ms": pct(0.5),
        "urgent_ttft_p99_ms": pct(0.99),
        "deadline_attainment": attained,
        "preempt_parked": sched["preempt_parked"],
        "preempt_resumed": sched["preempt_resumed"],
        "preempt_aborted": sched["preempt_aborted"],
        "mismatched_streams": mismatched,
    }


def run_sched(args) -> int:
    print("# priority-preemptive scheduling A/B: FIFO admission vs "
          "deadline-aware EDF + slot preemption")
    print("# parity probe: real tiny engine (chunked prefill + prefix "
          "cache + speculation + int8 weights/KV), forced "
          "preempt -> park -> resume vs uninterrupted references")
    probe = _run_sched_parity_probe(args)
    print(f"#   {probe['streams']} streams: {probe['diverged']} diverged; "
          f"parked {probe['parked']} / resumed {probe['resumed']} / "
          f"aborted {probe['aborted']}")

    deadline_ms = 25.0 * args.sim_step_ms
    n_bulk = args.decode_requests
    n_urgent = max(6, n_bulk // 8)
    bulk, urgent = make_sched_payloads(
        n_bulk, n_urgent, max_new=args.max_new_tokens,
        deadline_ms=deadline_ms, seed=20,
    )
    # Spread the urgent arrivals across the middle of the estimated bulk
    # drain so each one lands on a fully-occupied slot table.
    est_drain_s = (sum(p["max_new_tokens"] for p in bulk) / args.slots
                   ) * (args.sim_step_ms / 1e3)
    spacing_s = 0.6 * est_drain_s / n_urgent

    # Same load-flakiness discipline as the decode gates: wall-clock TTFT
    # on a shared CI box, so --quick takes the best of up to 3 attempts.
    # Stream parity stays unconditional — mismatches accumulate across
    # ALL attempts and any one of them fails the run.
    ab_attempts = 3 if args.quick else 1
    mismatched = 0
    rows, ratio, delta = None, 0.0, 0.0
    for attempt in range(1, ab_attempts + 1):
        cand = {
            pol: _run_sched_point(args, pol, bulk, urgent, deadline_ms,
                                  spacing_s)
            for pol in ("fifo", "edf")
        }
        mismatched += sum(a["mismatched_streams"] for a in cand.values())
        cand_ratio = (cand["edf"]["urgent_ttft_p99_ms"]
                      / max(cand["fifo"]["urgent_ttft_p99_ms"], 1e-9))
        cand_delta = (cand["edf"]["deadline_attainment"]
                      - cand["fifo"]["deadline_attainment"])
        if rows is None or (cand_delta, -cand_ratio) > (delta, -ratio):
            rows, ratio, delta = cand, cand_ratio, cand_delta
        if (delta >= 0.2 and ratio <= 0.7
                and rows["edf"]["preempt_parked"] >= 1):
            break
        if attempt < ab_attempts:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"# sched A/B attempt {attempt}/{ab_attempts}: "
                  f"attainment +{cand_delta:.2f}, urgent ttft p99 "
                  f"{cand_ratio:.2f}x at loadavg/core {load:.2f} — "
                  "retrying")

    hdr = (
        f"{'policy':>8} {'tok/s':>8} {'urgent p50':>11} {'urgent p99':>11} "
        f"{'attained':>9} {'parked':>7} {'resumed':>8} {'aborted':>8}"
    )
    print(hdr)
    print("-" * len(hdr))
    for pol in ("fifo", "edf"):
        a = rows[pol]
        print(
            f"{pol:>8} {a['tokens_per_s']:>8.1f} "
            f"{a['urgent_ttft_p50_ms']:>11.1f} "
            f"{a['urgent_ttft_p99_ms']:>11.1f} "
            f"{a['deadline_attainment']:>9.2f} {a['preempt_parked']:>7d} "
            f"{a['preempt_resumed']:>8d} {a['preempt_aborted']:>8d}"
        )
    print(
        f"\nedf+preempt vs fifo: urgent ttft p99 {ratio:.2f}x, deadline "
        f"attainment {rows['edf']['deadline_attainment']:.2f} vs "
        f"{rows['fifo']['deadline_attainment']:.2f} "
        f"(deadline {deadline_ms:.0f}ms), bulk tokens/s "
        f"{rows['edf']['tokens_per_s'] / rows['fifo']['tokens_per_s']:.2f}x, "
        f"{mismatched} mismatched streams"
    )

    if args.json:
        report = {
            "mode": "sched",
            "config": {
                "slots": args.slots,
                "max_batch": args.max_batch,
                "max_new_tokens": args.max_new_tokens,
                "sim_step_ms": args.sim_step_ms,
                "bulk_requests": n_bulk,
                "urgent_requests": n_urgent,
                "deadline_ms": deadline_ms,
                "urgent_spacing_ms": 1e3 * spacing_s,
            },
            "parity_probe": probe,
            "ab": rows,
            "ab_attempts": ab_attempts,
            "urgent_ttft_p99_ratio": ratio,
            "deadline_attainment_delta": delta,
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")

    # Correctness is unconditional; the perf thresholds are the --quick CI
    # gate (the same numbers docs/PERF.md round 20 records from a full run).
    if probe["diverged"]:
        print(f"FAIL: {probe['diverged']} preempted-then-resumed "
              "real-engine streams diverged from their uninterrupted "
              "references — park/resume must be bit-exact",
              file=sys.stderr)
        return 1
    if probe["parked"] + probe["aborted"] < 1:
        print("FAIL: the parity probe never forced a preemption decision "
              "— a class-0 deadline holder behind a full slot table must "
              "mark a victim", file=sys.stderr)
        return 1
    if mismatched:
        print(f"FAIL: {mismatched} sim token streams diverged from the "
              "resume-exact closed form under preemptive scheduling",
              file=sys.stderr)
        return 1
    if args.quick:
        if rows["edf"]["preempt_parked"] < 1:
            print("FAIL: the EDF+preempt arm never parked a victim — "
                  "urgent arrivals against a full table must preempt",
                  file=sys.stderr)
            return 1
        if ratio > 0.7:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"FAIL: urgent TTFT p99 under EDF+preempt is "
                  f"{ratio:.2f}x FIFO (>0.7x, best of {ab_attempts} "
                  f"attempts, loadavg/core {load:.2f}) — preemption is "
                  "no longer rescuing deadline holders", file=sys.stderr)
            return 1
        if delta < 0.2:
            print(f"FAIL: deadline attainment improves only "
                  f"{delta:+.2f} over FIFO (<+0.2, best of "
                  f"{ab_attempts} attempts) — the deadline-aware arm "
                  "must convert preemptions into met deadlines",
                  file=sys.stderr)
            return 1
    return 0


def _run_recorder_ab(args) -> dict:
    """Flight-recorder overhead A/B + forced-dump round-trip.

    Two identical sim-engine backlog drains, recorder off vs on (the same
    measurement shape as PR 10's windowed-metrics gate): the sim engine's
    fixed per-step sleep dominates, so the recorder's per-event cost shows
    up directly in tokens/s. Best-of-2 per arm irons out sleep jitter.
    Afterwards the ON arm's recorder is force-dumped and the payload is
    serialized through ``json`` and parsed back — the ISSUE acceptance
    check that a dump round-trips with all four sidecar sections.
    """
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.serve import BatcherConfig, Client

    payloads = make_decode_payloads(
        min(args.decode_requests, 48), max_new=args.max_new_tokens,
        vocab=args.vocab,
    )

    def drain(recorder) -> float:
        eng = SimStepEngine(
            slots=args.slots, max_batch=args.max_batch,
            max_new_tokens=args.max_new_tokens, step_ms=args.sim_step_ms,
        )
        client = Client(
            eng,
            BatcherConfig(
                max_batch=args.max_batch, max_queue=args.max_queue,
                max_in_flight=args.max_in_flight,
                max_delay_ms=args.max_delay_ms,
            ),
            recorder=recorder,
        )
        try:
            client.call(payloads[0], timeout=120)  # warm the threads
            t0 = time.monotonic()
            futs = [client.submit(dict(p)) for p in payloads]
            toks = sum(f.result(timeout=600)["n_tokens"] for f in futs)
            wall = time.monotonic() - t0
        finally:
            client.close()
        return toks / wall

    recorder = FlightRecorder(capacity=4096)  # no dump_dir: dump() returns
    base_tps = max(drain(None) for _ in range(2))  # the payload inline
    rec_tps = max(drain(recorder) for _ in range(2))
    overhead = max(0.0, 1.0 - rec_tps / base_tps) if base_tps else 0.0

    # Forced dump -> json round-trip. The Client attached all four sidecar
    # sections in __init__; every key must come back as a real object.
    parsed = json.loads(json.dumps(recorder.dump("bench", force=True),
                                   default=str))
    sections_ok = all(
        isinstance(parsed.get(k), dict)
        for k in ("metrics", "memz", "compilez", "tracer")
    )
    return {
        "baseline_tokens_per_s": base_tps,
        "recorder_tokens_per_s": rec_tps,
        "overhead_frac": overhead,
        "events_recorded": len(parsed.get("events", [])),
        "dropped_events": parsed["recorder"]["dropped_events"],
        "dump_sections_ok": sections_ok,
    }


# ----------------------------------------------------------- disagg mode


def _run_disagg_parity_probe(args) -> dict:
    """Bit-parity probe on REAL tiny engines: the same distinct-prompt
    stream runs (a) colocated — one chunked engine with a prefix cache —
    and (b) disaggregated — a prefill engine publishing page chains that
    transfer over the serialized WIRE format (loopback rehearsal of
    POST /v1/kv_transfer) into a ``kv_transfer=True`` decode engine.
    Prompts are all distinct, so any decode-side prefix hit can ONLY come
    from an adopted chain — the probe proves the transferred pages are
    the ones decode actually reads, and the streams must be
    bit-identical."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
        DisaggServingPair,
        TransferBudget,
    )

    if args.quick:
        geo = dict(hidden=32, layers=2, heads=2, maxpos=48,
                   buckets=(8, 32), chunk=8, bt=4, mb=0.25, n=6)
    else:
        geo = dict(hidden=64, layers=3, heads=4, maxpos=96,
                   buckets=(16, 64), chunk=16, bt=8, mb=1.0, n=16)
    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=geo["hidden"],
        num_layers=geo["layers"], num_heads=geo["heads"],
        intermediate_size=4 * geo["hidden"], max_position=geo["maxpos"],
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    rng = np.random.default_rng(11)
    payloads = [
        {
            "input_ids": rng.integers(5, cfg.vocab_size,
                                      size=int(rng.integers(12, 29))),
            "max_new_tokens": int(rng.integers(2, 7)),
        }
        for _ in range(geo["n"])
    ]
    eng_kw = dict(
        buckets=geo["buckets"], slots=4, max_batch=2, max_new_tokens=8,
        prefix_cache_mb=geo["mb"], block_tokens=geo["bt"],
        prefill_chunk=geo["chunk"],
    )

    # Colocated reference arm.
    ref_engine = CausalLMEngine(model, params, **eng_kw)
    with Client(
        ref_engine, BatcherConfig(max_batch=2, max_queue=64, max_in_flight=2)
    ) as client:
        reference = [
            client.call(dict(p), timeout=300)["tokens"] for p in payloads
        ]

    # Disaggregated arm: prefill role publishes, the wire carries, the
    # decode role adopts. Same params, same page geometry.
    pre_engine = CausalLMEngine(model, params, kv_transfer=True, **eng_kw)
    dec_engine = CausalLMEngine(model, params, kv_transfer=True, **eng_kw)
    pre_client = Client(
        pre_engine, BatcherConfig(max_batch=2, max_queue=64, max_in_flight=2)
    )
    dec_client = Client(
        dec_engine, BatcherConfig(max_batch=2, max_queue=64, max_in_flight=2)
    )
    budget = TransferBudget(64 * 1024 * 1024)
    pair = DisaggServingPair(
        prefill_batcher=pre_client.batcher,
        decode_batcher=dec_client.batcher,
        prefill_engine=pre_engine,
        decode_engine=dec_engine,
        budget=budget,
        transport="wire",
        metrics=dec_client.metrics,
        recorder=dec_client.recorder,
    )
    try:
        t0 = time.monotonic()
        disagg = [pair.generate(dict(p))["tokens"] for p in payloads]
        wall = time.monotonic() - t0
        m = dec_client.metrics
        snap = m.snapshot()
        adopted_hits = m.prefix_hits.value
        tokens_saved = m.prefix_tokens_saved.value
    finally:
        pre_client.close()
        dec_client.close()
    mismatched = sum(a != b for a, b in zip(reference, disagg))
    xfer = snap.get("kv_transfer_bytes", {})
    return {
        "requests": geo["n"],
        "geometry": {k: geo[k] for k in
                     ("hidden", "layers", "chunk", "bt", "mb")},
        "mismatched_streams": mismatched,
        "adopted_chain_hits": adopted_hits,
        "tokens_prefilled_from_transfer": tokens_saved,
        "transfer_bytes": xfer,
        "budget": budget.digest(),
        "wall_s": wall,
    }


def _run_disagg_hol_ab(args) -> dict:
    """Head-of-line A/B (sim): a short-prompt decode backlog holds the
    slot table while long prompts admit. The COLOCATED arm is one
    monolithic engine — each long prefill stalls every in-flight slot
    for the whole prompt (the regime ISSUE 17 disaggregates away). The
    DISAGG arm runs the longs on a separate prefill engine (its own
    simulated device, so its prefill sleeps overlap decode's steps),
    transfers the published chain through :class:`DisaggServingPair`,
    and the decode engine re-prefills only the one-block uncached tail.
    Reported per arm: steady decode ITL p99 vs ITL p99 during long
    admission; same closed-form sim tokens, so parity is unconditional."""
    import threading

    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        Client,
        DisaggServingPair,
        TransferBudget,
    )

    rng = np.random.default_rng(5)
    n_short = 16 if args.quick else 48
    shorts = [
        {
            "input_ids": rng.integers(5, 512, size=int(rng.integers(4, 17))),
            "max_new_tokens": int(rng.integers(6, 13)),
        }
        for _ in range(n_short)
    ]
    longs = [
        {
            "input_ids": rng.integers(5, 512, size=224),
            "max_new_tokens": 4,
        }
        for _ in range(4)
    ]
    token_cost_ms = args.sim_step_ms / 32.0
    bt = 16
    sim_kw = dict(slots=8, max_batch=4, max_new_tokens=16,
                  step_ms=args.sim_step_ms, token_cost_ms=token_cost_ms)
    bcfg = BatcherConfig(max_batch=4, max_queue=1024, max_in_flight=2)

    def measure(decode_client, submit_long) -> tuple[float, float, int]:
        """(steady p99, admission p99, mismatches) on the decode client's
        ITL histogram; longs go through ``submit_long`` in threads (the
        pair blocks through prefill + transfer, a real sender would
        too)."""
        m = decode_client.metrics
        bad = 0
        decode_client.call(dict(shorts[0]), timeout=120)
        m.itl.reset()
        futs = [decode_client.submit(dict(p)) for p in shorts]
        res = [f.result(timeout=600) for f in futs]
        bad += sum(r["tokens"] != _sim_expected(p)
                   for p, r in zip(shorts, res))
        steady = m.snapshot()["itl_ms"]["p99"]
        m.itl.reset()
        futs = [decode_client.submit(dict(p)) for p in shorts]
        long_out: list = [None] * len(longs)

        def one(i: int) -> None:
            long_out[i] = submit_long(dict(longs[i]))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(longs))]
        for t in threads:
            t.start()
        res = [f.result(timeout=600) for f in futs]
        for t in threads:
            t.join(timeout=600)
        bad += sum(r["tokens"] != _sim_expected(p)
                   for p, r in zip(shorts, res))
        bad += sum(r["tokens"] != _sim_expected(p)
                   for p, r in zip(longs, long_out))
        admit = m.snapshot()["itl_ms"]["p99"]
        return steady, admit, bad

    arms = {}
    mismatched = 0

    # Colocated arm: one monolithic engine serves both classes.
    eng = SimChunkedEngine(prefill_chunk=256, **sim_kw)
    client = Client(eng, bcfg)
    try:
        steady, admit, bad = measure(
            client, lambda p: client.call(p, timeout=600)
        )
    finally:
        client.close()
    mismatched += bad
    arms["colocated"] = {
        "steady_itl_p99_ms": steady,
        "admission_itl_p99_ms": admit,
        "itl_p99_ratio": admit / steady if steady else float("inf"),
    }

    # Disagg arm: the longs prefill on their own engine and arrive at
    # decode as adopted chains; decode prefills only the uncached tail.
    pre_eng = SimDisaggEngine(pool_blocks=256, block_tokens=bt,
                              prefill_chunk=256, **sim_kw)
    dec_eng = SimDisaggEngine(pool_blocks=256, block_tokens=bt,
                              prefill_chunk=bt, **sim_kw)
    pre_client = Client(pre_eng, bcfg)
    dec_client = Client(dec_eng, bcfg)
    budget = TransferBudget(64 * 1024 * 1024)
    pair = DisaggServingPair(
        prefill_batcher=pre_client.batcher,
        decode_batcher=dec_client.batcher,
        budget=budget,
        transport="d2d",
        metrics=dec_client.metrics,
        recorder=dec_client.recorder,
    )
    try:
        steady, admit, bad = measure(
            dec_client, lambda p: pair.generate(p)
        )
        snap = dec_client.metrics.snapshot()
    finally:
        pre_client.close()
        dec_client.close()
    mismatched += bad
    arms["disagg"] = {
        "steady_itl_p99_ms": steady,
        "admission_itl_p99_ms": admit,
        "itl_p99_ratio": admit / steady if steady else float("inf"),
        "transfer_bytes": snap.get("kv_transfer_bytes", {}),
        "budget": budget.digest(),
    }
    return {
        "config": {
            "short_requests": n_short,
            "long_prompts": len(longs),
            "long_prompt_tokens": 224,
            "block_tokens": bt,
            "token_cost_ms": token_cost_ms,
        },
        "arms": arms,
        "mismatched_streams": mismatched,
    }


def run_disagg(args) -> int:
    """The disaggregated prefill/decode A/B (--disagg)."""
    print("# disagg parity probe: real tiny engines, prefill role -> wire "
          "format -> kv_transfer decode role, vs colocated reference")
    probe = _run_disagg_parity_probe(args)
    xfer = probe["transfer_bytes"]
    print(
        f"# parity {'ok' if not probe['mismatched_streams'] else 'FAIL'}: "
        f"{probe['requests']} distinct-prompt requests, "
        f"{probe['adopted_chain_hits']} adopted-chain hits, "
        f"{probe['tokens_prefilled_from_transfer']} prompt tokens served "
        f"from transferred pages, "
        f"{xfer.get('decode', 0)} wire bytes adopted, "
        f"{probe['budget']['granted_total']} transfers granted / "
        f"{probe['budget']['shed_total']} shed"
    )

    # The head-of-line gate measures wall clock on a shared box — same
    # best-of-N discipline as run_decode's throughput gates; stream
    # parity accumulates across every attempt and stays unconditional.
    attempts = 3 if args.quick else 1
    mismatched = 0
    hol = None
    for attempt in range(1, attempts + 1):
        cand = _run_disagg_hol_ab(args)
        mismatched += cand["mismatched_streams"]
        if hol is None or (
            cand["arms"]["disagg"]["itl_p99_ratio"]
            < hol["arms"]["disagg"]["itl_p99_ratio"]
        ):
            hol = cand
        d = cand["arms"]["disagg"]["itl_p99_ratio"]
        c = cand["arms"]["colocated"]["itl_p99_ratio"]
        if d <= 1.5 and c > d:
            hol = cand
            break
        if attempt < attempts:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"# HOL A/B attempt {attempt}/{attempts}: disagg "
                  f"{d:.2f}x, colocated {c:.2f}x at loadavg/core "
                  f"{load:.2f} — retrying")

    print("\n# head-of-line A/B: sim engines, long-prompt admission "
          "against a short-prompt decode backlog")
    hdr = (
        f"{'arm':>10} {'steady itl p99':>15} {'admission itl p99':>18} "
        f"{'ratio':>6}"
    )
    print(hdr)
    print("-" * len(hdr))
    for name in ("colocated", "disagg"):
        a = hol["arms"][name]
        print(
            f"{name:>10} {a['steady_itl_p99_ms']:>15.2f} "
            f"{a['admission_itl_p99_ms']:>18.2f} "
            f"{a['itl_p99_ratio']:>6.2f}"
        )
    d_ratio = hol["arms"]["disagg"]["itl_p99_ratio"]
    c_ratio = hol["arms"]["colocated"]["itl_p99_ratio"]
    dig = hol["arms"]["disagg"]["budget"]
    print(
        f"\ncolocated vs disagg: long-prompt admission inflates decode "
        f"ITL p99 {c_ratio:.2f}x on the monolithic engine vs "
        f"{d_ratio:.2f}x disaggregated "
        f"({dig['granted_total']} chain transfers, "
        f"{hol['arms']['disagg']['transfer_bytes'].get('decode', 0)} "
        f"bytes, {dig['shed_total']} shed); "
        f"{mismatched + probe['mismatched_streams']} mismatched streams"
    )

    if args.json:
        report = {
            "mode": "disagg",
            "config": {
                "sim_step_ms": args.sim_step_ms,
                "attempts": attempts,
            },
            "parity_probe": probe,
            "hol_ab": hol,
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")

    # Correctness gates are unconditional; the scheduling gate is the
    # --quick CI shape (the acceptance bar ISSUE 17 records).
    if probe["mismatched_streams"]:
        print(f"FAIL: {probe['mismatched_streams']} disaggregated streams "
              "diverge from the colocated reference — KV-page transfer "
              "must be bit-exact", file=sys.stderr)
        return 1
    if not probe["adopted_chain_hits"]:
        print("FAIL: no decode-side prefix hits on distinct prompts — "
              "transferred chains were never adopted (decode re-prefilled "
              "everything)", file=sys.stderr)
        return 1
    if mismatched:
        print(f"FAIL: {mismatched} sim token streams corrupted by chain "
              "adoption", file=sys.stderr)
        return 1
    if args.quick:
        load = os.getloadavg()[0] / (os.cpu_count() or 1)
        if d_ratio > 1.5:
            print(f"FAIL: disagg decode ITL p99 during long-prompt "
                  f"admission is {d_ratio:.2f}x steady state (>1.5x, best "
                  f"of {attempts} attempts, loadavg/core {load:.2f}) — "
                  "transfers are stalling the decode loop",
                  file=sys.stderr)
            return 1
        if c_ratio <= d_ratio:
            print(f"FAIL: colocated arm no longer head-of-line blocks "
                  f"({c_ratio:.2f}x vs disagg {d_ratio:.2f}x) — the A/B "
                  "lost its baseline", file=sys.stderr)
            return 1
    return 0


# ------------------------------------------------------------ fleet mode


def _fleet_geo(quick: bool) -> dict:
    """Replica geometry, shared by the parent (trace shape) and the
    re-entered replica process (engine shape) so both derive it from the
    one ``--quick`` flag instead of a dozen forwarded knobs.

    The KV pool is deliberately sized to hold roughly ONE hot head per
    replica: that is the regime where prefix affinity IS the fleet-wide
    cache policy — affinity partitions the heads across replicas (every
    replica serves its head from cache), spray rotates all heads through
    every too-small pool (evictions, cold prefills)."""
    if quick:
        return dict(hidden=32, layers=2, heads=2, maxpos=48,
                    buckets=(8, 32), slots=4, max_batch=2,
                    head_len=24, tails=(3, 8), max_new=6, long_new=8,
                    mb=0.25, bt=4, chunk=16, max_new_tokens=10,
                    rps_hi=10.0, rps_lo=3.0)
    return dict(hidden=128, layers=4, heads=4, maxpos=384,
                buckets=(32, 256), slots=4, max_batch=2,
                head_len=192, tails=(3, 16), max_new=6, long_new=10,
                mb=1.0, bt=16, chunk=64, max_new_tokens=12,
                rps_hi=8.0, rps_lo=2.0)


def run_fleet_replica(args) -> int:
    """Re-entered child process: one real replica server of the fleet.

    Same stack a production replica runs — tiny causal-LM engine with
    the prefix cache + chunked prefill on, continuous batcher, the
    serve/server.py HTTP face — so the router is exercised against real
    drain semantics and real (AOT-warmed) readiness, not a stub."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
    )
    from distributed_tensorflow_tpu.serve.server import build_http_server

    geo = _fleet_geo(args.quick)
    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=geo["hidden"],
        num_layers=geo["layers"], num_heads=geo["heads"],
        intermediate_size=4 * geo["hidden"], max_position=geo["maxpos"],
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    engine = CausalLMEngine(
        model, params, buckets=geo["buckets"], slots=geo["slots"],
        max_batch=geo["max_batch"], max_new_tokens=geo["max_new_tokens"],
        prefix_cache_mb=geo["mb"], block_tokens=geo["bt"],
        prefill_chunk=geo["chunk"],
    )
    client = Client(
        engine,
        BatcherConfig(max_batch=geo["max_batch"], max_queue=256,
                      max_in_flight=2),
        tag=args.replica_tag,
    )
    server = build_http_server(client, port=args.replica_serve)
    print(f"READY {server.server_address[1]}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        client.close()
    return 0


def make_fleet_trace(n: int, geo: dict, seed: int):
    """(payloads, arrival_gaps): Zipf shared-head prompts with a
    heavy-tailed output mix (every 8th request gets the long budget) and
    bursty Poisson arrivals alternating a high-rate burst regime with a
    low-rate lull every 12 requests — the diurnal-burst shape a fleet
    door actually absorbs, compressed to bench scale."""
    payloads = make_prefix_payloads(
        n, heads=3, head_len=geo["head_len"], tail_lens=geo["tails"],
        max_new=geo["max_new"], vocab=64, seed=seed,
    )
    for i, p in enumerate(payloads):
        # These go over HTTP, not in-process: plain JSON types only.
        p["input_ids"] = [int(t) for t in p["input_ids"]]
        if i % 8 == 7:
            p["max_new_tokens"] = geo["long_new"]
    rng = np.random.default_rng(seed + 1)
    gaps = [
        float(rng.exponential(
            1.0 / (geo["rps_hi"] if (i // 12) % 2 == 0 else geo["rps_lo"])
        ))
        for i in range(n)
    ]
    return payloads, gaps


def _free_ports(n: int) -> list[int]:
    import socket

    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _spawn_fleet(args, *, affinity_tokens: int, tag: str = "fleet-v1"):
    """Router + N owned replica processes (this script re-entered),
    waited until every replica is routable. Returns (router, ports)."""
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.serve.router import (
        Router,
        RouterConfig,
    )

    me = os.path.abspath(__file__)

    def cmd_for(port: int, t: str) -> list[str]:
        c = [sys.executable, me, "--fleet", "--replica-serve", str(port),
             "--replica-tag", t]
        if args.quick:
            c.append("--quick")
        return c

    ports = _free_ports(args.fleet_replicas)
    specs = [
        (f"fleet-{i}", f"http://127.0.0.1:{p}", cmd_for(p, tag))
        for i, p in enumerate(ports)
    ]
    router = Router(
        specs,
        RouterConfig(
            poll_interval_s=0.2,
            poll_timeout_s=2.0,
            start_grace_s=300.0,
            fail_threshold=2,
            max_restarts=3,
            backoff_base_s=0.5,
            max_retries=2,
            request_timeout_s=120.0,
            affinity_tokens=affinity_tokens,
            affinity_max_imbalance=8.0,
            max_in_flight_per_replica=64,
            ready_timeout_s=300.0,
            drain_timeout_s=60.0,
            seed=args.fleet_seed,
        ),
        recorder=FlightRecorder(capacity=2048),
    )
    router.start()
    if not router.wait_ready(timeout=300.0):
        router.close()
        raise RuntimeError(
            "fleet did not come up: "
            + ", ".join(f"{r.name}={r.state}" for r in router.replicas)
        )
    return router, ports


def _drive_fleet_trace(router, payloads, gaps, *, kill_at: int = -1,
                       workers: int = 8):
    """Open-loop trace drive: a dispatcher walks the arrival schedule
    (never waiting on replies) and hands each request to a worker pool
    calling ``router.route``. At request index ``kill_at`` (when >= 0)
    the busiest replica is SIGKILLed — between two submits, exactly
    where a real host loss lands. Returns ``(rows, victim_or_None)``."""
    from concurrent.futures import ThreadPoolExecutor

    results: list[dict | None] = [None] * len(payloads)
    victim = None

    def one(i: int, payload: dict) -> None:
        t0 = time.monotonic()
        code, body = router.route("/v1/generate", dict(payload))
        wall_ms = (time.monotonic() - t0) * 1e3
        phases = body.get("phases") or {}
        ttft = phases.get("queue_wait", 0.0) + phases.get("prefill", 0.0)
        results[i] = {
            "code": code,
            "wall_ms": wall_ms,
            "ttft_ms": ttft if phases else None,
            "replica": body.get("replica"),
            "shed": bool(body.get("shed")) or code == 429,
            "ok": code == 200 and isinstance(body.get("tokens"), list),
        }

    with ThreadPoolExecutor(max_workers=workers) as pool:
        futs = []
        for i, (p, gap) in enumerate(zip(payloads, gaps)):
            if i == kill_at:
                victim = _kill_busiest(router)
            futs.append(pool.submit(one, i, p))
            time.sleep(gap)
        for f in futs:
            f.result()
    return [r for r in results if r is not None], victim


def _kill_busiest(router):
    """SIGKILL the replica carrying the most traffic — killing an idle
    replica would not sever a single in-flight request, and the whole
    point of the drill is that admitted work survives a host loss."""
    import signal

    live = [
        r for r in router.replicas
        if r.proc is not None and r.proc.poll() is None
    ]
    victim = max(live, key=lambda r: r.requests)
    print(f"# chaos: SIGKILL {victim.name} (pid {victim.proc.pid}, "
          f"{victim.requests} requests routed, "
          f"{victim.in_flight} in flight)")
    victim.proc.send_signal(signal.SIGKILL)
    return victim


def _fleet_stats(rows: list[dict]) -> dict:
    ok = [r for r in rows if r["ok"]]
    ttfts = sorted(r["ttft_ms"] for r in ok if r["ttft_ms"] is not None)
    walls = sorted(r["wall_ms"] for r in ok)

    def pct(xs, q):
        return xs[min(int(q * len(xs)), len(xs) - 1)] if xs else 0.0

    per_replica: dict[str, int] = {}
    for r in ok:
        per_replica[r["replica"]] = per_replica.get(r["replica"], 0) + 1
    return {
        "requests": len(rows),
        "ok": len(ok),
        "shed": sum(1 for r in rows if r["shed"]),
        "failed": sum(1 for r in rows if not r["ok"] and not r["shed"]),
        "ttft_p50_ms": pct(ttfts, 0.50),
        "ttft_p99_ms": pct(ttfts, 0.99),
        "wall_p50_ms": pct(walls, 0.50),
        "wall_p99_ms": pct(walls, 0.99),
        "per_replica": per_replica,
    }


def _fleet_prefix_counters(router) -> dict:
    """Summed prefix-cache counters across every live replica's
    /metrics snapshot (the replica-side truth the affinity A/B reads)."""
    import urllib.request

    lookups = hits = saved = 0
    for r in router.replicas:
        try:
            with urllib.request.urlopen(
                r.base_url + "/metrics", timeout=5
            ) as resp:
                snap = json.loads(resp.read().decode())
        except OSError:
            continue
        lookups += snap.get("prefix_lookups", 0)
        hits += snap.get("prefix_hits", 0)
        saved += snap.get("prefix_tokens_saved", 0)
    return {
        "prefix_lookups": lookups,
        "prefix_hits": hits,
        "prefix_tokens_saved": saved,
        "hit_rate": hits / lookups if lookups else 0.0,
    }


def _run_fleet_chaos_drill(args, geo) -> dict:
    """One full chaos pass: fresh 3-replica fleet, bursty Zipf trace with
    a seeded mid-trace SIGKILL, restart-within-budget wait, then the
    rolling hot-swap (v1 -> v2) under continuing background traffic.

    Raises RuntimeError on TIMING failures (fleet/restart/drain/ready
    deadlines) so the --quick caller can apply the best-of-3 load-aware
    retry; returns the measured result rows — including any dropped
    requests, which the caller gates UNCONDITIONALLY — otherwise."""
    import threading

    from distributed_tensorflow_tpu.train.faultinject import FaultPlan

    n = args.fleet_requests
    payloads, gaps = make_fleet_trace(n, geo, args.fleet_seed)
    plan = FaultPlan.generate(
        args.fleet_seed, n, {"host_drop": 1}, min_step=max(1, n // 3)
    )
    kill_at = next(
        e.step for e in plan.events if e.kind == "host_drop"
    )

    router, ports = _spawn_fleet(args, affinity_tokens=16)
    try:
        print(f"# fleet up on ports {ports}; fault plan seed "
              f"{args.fleet_seed}: host_drop at request {kill_at}")
        t0 = time.monotonic()
        rows, victim = _drive_fleet_trace(
            router, payloads, gaps, kill_at=kill_at
        )
        trace_wall = time.monotonic() - t0

        # Victim back inside the progress-aware budget (timing gate).
        deadline = time.monotonic() + args.restart_budget_s
        restarted = False
        while time.monotonic() < deadline:
            fz = router.fleetz()
            rep = next(
                r for r in fz["replicas"] if r["name"] == victim.name
            )
            if (rep["state"] == "ready"
                    and rep["supervisor"]["total_restarts"] >= 1):
                restarted = True
                break
            time.sleep(0.25)
        if not restarted:
            raise RuntimeError(
                f"victim {victim.name} not restarted+ready within "
                f"{args.restart_budget_s:g}s "
                f"(state={rep['state']}, "
                f"supervisor={rep['supervisor']})"
            )
        restart_s = time.monotonic() - t0

        # Rolling hot-swap under background traffic: v1 -> v2.
        swap_rows: list[dict] = []
        stop = threading.Event()

        def background():
            i = 0
            while not stop.is_set():
                p = dict(payloads[i % len(payloads)])
                t1 = time.monotonic()
                code, body = router.route("/v1/generate", p)
                swap_rows.append({
                    "code": code,
                    "wall_ms": (time.monotonic() - t1) * 1e3,
                    "ttft_ms": None,
                    "replica": body.get("replica"),
                    "shed": bool(body.get("shed")) or code == 429,
                    "ok": code == 200
                    and isinstance(body.get("tokens"), list),
                })
                i += 1
                stop.wait(0.05)

        me = os.path.abspath(__file__)
        port_of = {
            f"fleet-{i}": p for i, p in enumerate(ports)
        }

        def new_cmd(replica) -> list[str]:
            c = [sys.executable, me, "--fleet", "--replica-serve",
                 str(port_of[replica.name]), "--replica-tag", "fleet-v2"]
            if args.quick:
                c.append("--quick")
            return c

        bg = threading.Thread(target=background, daemon=True)
        bg.start()
        t1 = time.monotonic()
        try:
            swap = router.hot_swap(new_cmd, expected_tag="fleet-v2")
        except RuntimeError:
            raise  # timing gate: drain/ready deadline or tag mismatch
        finally:
            stop.set()
            bg.join(timeout=10)
        swap_wall = time.monotonic() - t1
        tags = sorted({r.tag for r in router.replicas})

        events = router.recorder.events()
        kinds = {}
        for e in events:
            kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
        return {
            "trace": _fleet_stats(rows),
            "trace_wall_s": trace_wall,
            "kill_at": kill_at,
            "victim": victim.name,
            "victim_restart_s": restart_s,
            "hot_swap": {
                **_fleet_stats(swap_rows),
                "swapped": len(swap["swapped"]),
                "tags": tags,
                "wall_s": swap_wall,
            },
            "prefix": _fleet_prefix_counters(router),
            "fleetz": router.fleetz(),
            "event_counts": kinds,
        }
    finally:
        router.close()


def _run_fleet_affinity_ab(args, geo) -> dict:
    """Affinity vs spray: the SAME bursty Zipf trace (no chaos) through
    two fresh fleets — affinity routing on vs pure load-based p2c — with
    the per-replica KV pool sized to ~one hot head. Affinity partitions
    the heads (fleet-wide cache works); spray thrashes every pool."""
    n = args.fleet_requests
    payloads, gaps = make_fleet_trace(n, geo, args.fleet_seed)
    arms = {}
    for name, aff in (("affinity", 16), ("spray", 0)):
        router, ports = _spawn_fleet(args, affinity_tokens=aff)
        try:
            print(f"# {name} arm up on ports {ports}")
            rows, _ = _drive_fleet_trace(router, payloads, gaps)
            arms[name] = {
                **_fleet_stats(rows),
                **_fleet_prefix_counters(router),
            }
        finally:
            router.close()
    on, off = arms["affinity"], arms["spray"]
    return {
        **arms,
        "ttft_p50_ratio": (
            off["ttft_p50_ms"] / on["ttft_p50_ms"]
            if on["ttft_p50_ms"] else 1.0
        ),
    }


def run_fleet(args) -> int:
    """The --fleet drill (round 16): chaos gate (+ affinity A/B on full
    runs). Correctness — zero dropped non-shed requests, every replica
    on the new tag after the swap — accumulates across EVERY attempt;
    only the timing gates (fleet-up, restart-within-budget, drain/ready
    deadlines, p99 bound) earn the best-of-3 load-aware retries."""
    geo = _fleet_geo(args.quick)
    attempts = 3 if args.quick else 1
    dropped = 0
    drill, last_err = None, None
    for attempt in range(1, attempts + 1):
        try:
            cand = _run_fleet_chaos_drill(args, geo)
        except RuntimeError as e:
            last_err = str(e)
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"# fleet attempt {attempt}/{attempts}: {e} at "
                  f"loadavg/core {load:.2f} — retrying", file=sys.stderr)
            continue
        dropped += cand["trace"]["failed"] + cand["hot_swap"]["failed"]
        if drill is None or (
            cand["trace"]["wall_p99_ms"]
            < drill["trace"]["wall_p99_ms"]
        ):
            drill = cand
        if (dropped == 0
                and cand["trace"]["wall_p99_ms"]
                <= args.fleet_slo_p99_ms):
            drill = cand
            break
        if attempt < attempts and dropped == 0:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"# fleet attempt {attempt}/{attempts}: wall p99 "
                  f"{cand['trace']['wall_p99_ms']:.0f} ms at "
                  f"loadavg/core {load:.2f} — retrying")
        elif dropped:
            break  # correctness failure: retries cannot launder it
    if drill is None:
        print(f"FAIL: every fleet attempt timed out: {last_err}",
              file=sys.stderr)
        return 1

    tr, hs = drill["trace"], drill["hot_swap"]
    print(f"\nfleet chaos drill ({args.fleet_replicas} replicas, "
          f"{tr['requests']} requests, SIGKILL {drill['victim']} at "
          f"request {drill['kill_at']}):")
    hdr = (
        f"{'phase':>9} {'ok':>5} {'shed':>5} {'failed':>7} "
        f"{'ttft p50':>9} {'wall p50':>9} {'wall p99':>9} {'wall s':>7}"
    )
    print(hdr)
    print("-" * len(hdr))
    print(
        f"{'trace':>9} {tr['ok']:>5d} {tr['shed']:>5d} "
        f"{tr['failed']:>7d} {tr['ttft_p50_ms']:>9.1f} "
        f"{tr['wall_p50_ms']:>9.1f} {tr['wall_p99_ms']:>9.1f} "
        f"{drill['trace_wall_s']:>7.1f}"
    )
    print(
        f"{'hot-swap':>9} {hs['ok']:>5d} {hs['shed']:>5d} "
        f"{hs['failed']:>7d} {'-':>9} {hs['wall_p50_ms']:>9.1f} "
        f"{hs['wall_p99_ms']:>9.1f} {hs['wall_s']:>7.1f}"
    )
    spread = ", ".join(
        f"{k}:{v}" for k, v in sorted(tr["per_replica"].items())
    )
    ev = drill["event_counts"]
    print(f"# routed {spread}; victim back in "
          f"{drill['victim_restart_s']:.1f}s; swap touched "
          f"{hs['swapped']} replicas, tags now {hs['tags']}")
    print(f"# prefix cache fleet-wide: hit rate "
          f"{drill['prefix']['hit_rate']:.2f}, "
          f"{drill['prefix']['prefix_tokens_saved']} tokens saved")
    print(f"# router events: "
          + ", ".join(f"{k}={ev.get(k, 0)}" for k in (
              "router_spawn", "replica_lost", "replica_restart",
              "hot_swap", "request_reject")))

    ab = None
    if not args.quick:
        print("\n# affinity A/B: same trace, affinity routing vs "
              "load-only spray (KV pool ~ one head per replica)")
        ab = _run_fleet_affinity_ab(args, geo)
        hdr = (
            f"{'arm':>9} {'ok':>5} {'ttft p50':>9} {'ttft p99':>9} "
            f"{'hit rate':>9} {'tok saved':>10}"
        )
        print(hdr)
        print("-" * len(hdr))
        for name in ("affinity", "spray"):
            a = ab[name]
            print(
                f"{name:>9} {a['ok']:>5d} {a['ttft_p50_ms']:>9.1f} "
                f"{a['ttft_p99_ms']:>9.1f} {a['hit_rate']:>9.2f} "
                f"{a['prefix_tokens_saved']:>10d}"
            )
        print(f"affinity vs spray: ttft p50 "
              f"{ab['ttft_p50_ratio']:.2f}x better "
              f"(docs/PERF.md round 16)")

    if args.json:
        report = {
            "mode": "fleet",
            "config": {
                "replicas": args.fleet_replicas,
                "requests": args.fleet_requests,
                "seed": args.fleet_seed,
                "geo": {k: list(v) if isinstance(v, tuple) else v
                        for k, v in geo.items()},
            },
            "drill": {k: v for k, v in drill.items() if k != "fleetz"},
            "affinity_ab": ab,
        }
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")

    # Correctness gates: unconditional, accumulated across every attempt.
    if dropped:
        print(f"FAIL: {dropped} requests dropped (non-shed, "
              "non-retried) across the kill and hot-swap windows — the "
              "door must lose ZERO admitted requests", file=sys.stderr)
        return 1
    if hs["tags"] != ["fleet-v2"]:
        print(f"FAIL: hot-swap left tags {hs['tags']} (want "
              "['fleet-v2']) — a replica silently restarted the old "
              "deployment", file=sys.stderr)
        return 1
    if hs["swapped"] != args.fleet_replicas:
        print(f"FAIL: hot-swap touched {hs['swapped']} of "
              f"{args.fleet_replicas} replicas", file=sys.stderr)
        return 1
    if not (ev.get("replica_lost") and ev.get("replica_restart")
            and ev.get("hot_swap")):
        print(f"FAIL: flight recorder missing fleet events (got {ev}) — "
              "the post-mortem story is incomplete", file=sys.stderr)
        return 1
    if args.quick:
        if tr["wall_p99_ms"] > args.fleet_slo_p99_ms:
            load = os.getloadavg()[0] / (os.cpu_count() or 1)
            print(f"FAIL: fleet wall p99 {tr['wall_p99_ms']:.0f} ms "
                  f"(> {args.fleet_slo_p99_ms:g} ms; best of {attempts} "
                  f"attempts, loadavg/core {load:.2f})", file=sys.stderr)
            return 1
        if tr["shed"] > tr["requests"] // 4:
            print(f"FAIL: {tr['shed']}/{tr['requests']} requests shed — "
                  "one lost replica must not collapse door admission",
                  file=sys.stderr)
            return 1
        if drill["prefix"]["hit_rate"] <= 0.0:
            print("FAIL: fleet-wide prefix hit rate is 0 on a Zipf "
                  "shared-head trace — affinity routing is not landing "
                  "heads on warm replicas", file=sys.stderr)
            return 1
    return 0


# --------------------------------------------------------------------------
# Live decode-stream migration drill (--migrate, ISSUE 18)


def _migrate_geo(quick: bool) -> dict:
    """Shared geometry for the in-process engines and the re-entered
    subprocess replica: long generations (the migration regime) on a
    tiny model, decode paced by ``slow_s`` per step so streams are
    reliably mid-generation when a drill lands."""
    if quick:
        return dict(hidden=32, layers=2, heads=2, maxpos=96,
                    buckets=(8, 16), slots=3, max_batch=2, max_new=24,
                    mb=0.25, bt=4, chunk=8, n=8, slow_s=0.03,
                    pace_steps=4000)
    return dict(hidden=64, layers=3, heads=4, maxpos=192,
                buckets=(16, 32), slots=4, max_batch=2, max_new=40,
                mb=1.0, bt=8, chunk=16, n=12, slow_s=0.03,
                pace_steps=8000)


def _mig_http(url: str, payload: dict | None = None, timeout_s: float = 60.0):
    """Tiny JSON helper: POST when ``payload`` is given, GET otherwise.
    Returns (code, body) and treats HTTP errors (a draining replica's
    503 /healthz) as answers, not exceptions."""
    import urllib.error
    import urllib.request

    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read() or b"{}")
        finally:
            e.close()


def _wait_drained_url(base: str, deadline_s: float) -> float | None:
    """Poll ``base``/healthz until queued + in-flight + active slots hit
    zero (the router's drain criterion); returns the wall seconds it
    took, or None on deadline."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        try:
            _, body = _mig_http(base + "/healthz", timeout_s=5.0)
        except OSError:
            body = {}
        if body and (
            body.get("queue_depth", 0) + body.get("in_flight", 0)
            + body.get("slots_active", 0)
        ) == 0:
            return time.monotonic() - t0
        time.sleep(0.02)
    return None


def run_migrate_replica(args) -> int:
    """Re-entered child: one migration-enabled replica server (the kill
    target / survivor of the --migrate drills). Same params as the
    parent's in-process engines (PRNGKey(0) init is deterministic), the
    stream receiver and /migratez migrator mounted, decode paced by
    ``--replica-fault-plan``."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
        TransferBudget,
    )
    from distributed_tensorflow_tpu.serve.disagg import (
        make_stream_receiver,
        migrate_streams,
    )
    from distributed_tensorflow_tpu.serve.faultinject import (
        FaultInjector,
        FaultPlan,
    )
    from distributed_tensorflow_tpu.serve.server import build_http_server

    geo = _migrate_geo(args.quick)
    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=geo["hidden"],
        num_layers=geo["layers"], num_heads=geo["heads"],
        intermediate_size=4 * geo["hidden"], max_position=geo["maxpos"],
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    engine = CausalLMEngine(
        model, params, buckets=geo["buckets"], slots=geo["slots"],
        max_batch=geo["max_batch"], max_new_tokens=geo["max_new"],
        prefix_cache_mb=geo["mb"], block_tokens=geo["bt"],
        prefill_chunk=geo["chunk"], stream_migrate=True,
    )
    client = Client(
        engine,
        BatcherConfig(max_batch=geo["max_batch"], max_queue=256,
                      max_in_flight=2),
        recorder=FlightRecorder(capacity=2048),
        tag=args.replica_tag,
    )
    if args.replica_fault_plan:
        client.batcher.fault_injector = FaultInjector(
            FaultPlan.parse(
                args.replica_fault_plan, num_steps=geo["pace_steps"]
            ),
            recorder=client.recorder,
        )
    budget = TransferBudget(64 * 1024 * 1024)
    receiver = make_stream_receiver(
        client.batcher, engine, budget=budget,
        metrics=client.metrics, recorder=client.recorder,
    )

    def migrator(targets):
        return migrate_streams(
            client.batcher, engine, targets,
            metrics=client.metrics, recorder=client.recorder,
            fault_injector=client.batcher.fault_injector,
        )

    server = build_http_server(
        client, port=args.replica_serve, stream_receiver=receiver,
        migrator=migrator, transfer_budget=budget,
    )
    print(f"READY {server.server_address[1]}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        client.close()
    return 0


def _run_migrate_drills(args) -> dict:
    """The three --migrate drills over two in-process migration-enabled
    engines (A, B) plus one subprocess replica (V, the kill target),
    all behind an adopt-mode router. Returns the measured result dict;
    correctness counters accumulate across retried rounds."""
    import subprocess
    import threading

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        CausalLMEngine,
        Client,
        TransferBudget,
    )
    from distributed_tensorflow_tpu.serve.disagg import (
        make_stream_receiver,
        migrate_streams,
    )
    from distributed_tensorflow_tpu.serve.faultinject import (
        FaultInjector,
        FaultPlan,
    )
    from distributed_tensorflow_tpu.serve.router import Router, RouterConfig
    from distributed_tensorflow_tpu.serve.server import build_http_server

    geo = _migrate_geo(args.quick)
    pace_spec = (f"seed=5,slow_decode_step={geo['pace_steps']},"
                 f"slow_step_s={geo['slow_s']}")

    # Two processes on the device: this one builds engines A and B, the
    # subprocess replica builds V.
    from distributed_tensorflow_tpu.runtime import require_chip_per_process

    require_chip_per_process(
        2, "serve_bench --migrate (two in-process engines plus one "
        "subprocess replica)"
    )
    # The subprocess replica warms its AOT grid while we build ours.
    me = os.path.abspath(__file__)
    vport = _free_ports(1)[0]
    vcmd = [sys.executable, me, "--migrate", "--replica-serve", str(vport),
            "--replica-tag", "migrate-v1", "--replica-fault-plan", pace_spec]
    if args.quick:
        vcmd.append("--quick")
    vproc = subprocess.Popen(vcmd, stdout=subprocess.DEVNULL)

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=geo["hidden"],
        num_layers=geo["layers"], num_heads=geo["heads"],
        intermediate_size=4 * geo["hidden"], max_position=geo["maxpos"],
    )
    model = CausalLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"]
    eng_kw = dict(
        buckets=geo["buckets"], slots=geo["slots"],
        max_batch=geo["max_batch"], max_new_tokens=geo["max_new"],
        prefix_cache_mb=geo["mb"], block_tokens=geo["bt"],
        prefill_chunk=geo["chunk"], stream_migrate=True,
    )
    clients, servers, threads = {}, {}, []
    for name in ("mig-a", "mig-b"):
        engine = CausalLMEngine(model, params, **eng_kw)
        c = Client(
            engine,
            BatcherConfig(max_batch=geo["max_batch"], max_queue=256,
                          max_in_flight=2),
            recorder=FlightRecorder(capacity=4096),
            tag="migrate-v1",
        )
        budget = TransferBudget(64 * 1024 * 1024)
        receiver = make_stream_receiver(
            c.batcher, engine, budget=budget,
            metrics=c.metrics, recorder=c.recorder,
        )

        def migrator(targets, c=c, engine=engine):
            return migrate_streams(
                c.batcher, engine, targets,
                metrics=c.metrics, recorder=c.recorder,
                fault_injector=c.batcher.fault_injector,
            )

        srv = build_http_server(
            c, port=0, stream_receiver=receiver, migrator=migrator,
            transfer_budget=budget,
        )
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        clients[name], servers[name] = c, srv
        threads.append((srv, t))

    urls = {
        name: f"http://127.0.0.1:{srv.server_address[1]}"
        for name, srv in servers.items()
    }
    urls["mig-v"] = f"http://127.0.0.1:{vport}"
    router = Router(
        [(name, urls[name], None) for name in
         ("mig-a", "mig-b", "mig-v")],
        RouterConfig(
            poll_interval_s=0.1, poll_timeout_s=2.0, start_grace_s=300.0,
            fail_threshold=2, max_retries=3, request_timeout_s=120.0,
            affinity_tokens=0, max_in_flight_per_replica=64,
            ready_timeout_s=300.0, drain_timeout_s=30.0, seed=7,
        ),
        recorder=FlightRecorder(capacity=2048),
    )
    router.start()

    def arm(name: str, seed: int) -> None:
        c = clients[name]
        c.batcher.fault_injector = FaultInjector(
            FaultPlan.generate(
                seed, geo["pace_steps"],
                {"slow_decode_step": geo["pace_steps"]},
                slow_step_s=geo["slow_s"],
            ),
            recorder=c.recorder,
        )

    def disarm(name: str) -> None:
        clients[name].batcher.fault_injector = None

    def busiest(names) -> str:
        fz = {r["name"]: r["in_flight"]
              for r in router.fleetz()["replicas"]}
        return max(names, key=lambda n: fz.get(n, 0))

    def submit_round(payloads):
        """Route every payload concurrently; returns (rows, joiner).
        ``joiner()`` blocks for completion and returns the rows."""
        rows: list[dict | None] = [None] * len(payloads)

        def one(i: int) -> None:
            code, body = router.route("/v1/generate", dict(payloads[i]))
            rows[i] = {"code": code, "tokens": body.get("tokens"),
                       "replica": body.get("replica")}

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(payloads))]
        for t in ts:
            t.start()

        def joiner():
            for t in ts:
                t.join(timeout=300)
            return rows

        return rows, joiner

    rng = np.random.default_rng(11)
    payloads = [
        {
            "input_ids": [int(x) for x in
                          rng.integers(5, 64, size=int(rng.integers(6, 13)))],
            "max_new_tokens": geo["max_new"],
            "seed": i,
        }
        for i in range(geo["n"])
    ]

    parity_failures = 0
    lost = 0

    def score(rows, reference) -> None:
        nonlocal parity_failures, lost
        for i, r in enumerate(rows):
            if r is None or r["code"] != 200:
                lost += 1
            elif r["tokens"] != reference[i]:
                parity_failures += 1

    result: dict = {"geometry": {k: list(v) if isinstance(v, tuple) else v
                                 for k, v in geo.items()}}
    try:
        if not router.wait_ready(timeout=300.0):
            raise RuntimeError(
                "migrate fleet did not come up: "
                + ", ".join(f"{r.name}={r.state}" for r in router.replicas)
            )
        print(f"# migrate fleet up: {urls} (V pid {vproc.pid})")

        # Uninterrupted reference: direct unpaced posts to A (streams are
        # deterministic functions of (seed, prompt) — replica-agnostic).
        reference = []
        for p in payloads:
            code, body = _mig_http(
                urls["mig-a"] + "/v1/generate", dict(p), timeout_s=120.0
            )
            if code != 200:
                raise RuntimeError(f"reference request failed: {code} {body}")
            reference.append(body["tokens"])

        host = "127.0.0.1"

        # ---- drill 1: migrate onto V, SIGKILL V, replay with resume.
        retries_before = router.fleetz()["retries"]
        kill = None
        for attempt in range(3):
            arm("mig-a", 21 + attempt)
            arm("mig-b", 22 + attempt)
            rows, join = submit_round(payloads)
            time.sleep(0.45)
            victim = busiest(("mig-a", "mig-b"))
            code, mig = _mig_http(
                urls[victim] + "/migratez",
                {"targets": [[host, vport]]}, timeout_s=60.0,
            )
            if code == 200 and mig.get("migrated", 0) >= 1:
                vproc.kill()  # before V finishes the adopted streams
                kill = {"victim": victim, "migratez": mig,
                        "rows": join()}
                score(kill["rows"], reference)
                break
            # Vacuous round (victim idle / nothing migrated): let it
            # finish — parity still gates — and try again. V was NOT
            # killed, so another round is possible.
            print(f"# kill drill attempt {attempt + 1}: nothing migrated "
                  f"off {victim} (HTTP {code}); retrying")
            score(join(), reference)
        if kill is None:
            raise RuntimeError(
                "kill drill: no attempt migrated a live stream onto V"
            )
        vproc.wait(timeout=30)
        kill["retries"] = router.fleetz()["retries"] - retries_before
        print(f"# kill drill: {kill['migratez']['migrated']} streams "
              f"migrated onto V, V SIGKILLed, {kill['retries']} router "
              f"retries (stream_wait failures replayed with resume "
              f"prefix)")

        # ---- drill 2: drain-migrate — victim freed by migration while
        # the SAME streams finish on the survivor (the intrinsic
        # drain-and-wait comparison, zero load mismatch).
        drain = None
        for attempt in range(3):
            arm("mig-a", 31 + attempt)
            arm("mig-b", 32 + attempt)
            rows, join = submit_round(payloads)
            time.sleep(0.45)
            victim = busiest(("mig-a", "mig-b"))
            survivor = "mig-b" if victim == "mig-a" else "mig-a"
            fz = {r["name"]: r["in_flight"]
                  for r in router.fleetz()["replicas"]}
            if fz.get(victim, 0) < 1:
                print(f"# drain drill attempt {attempt + 1}: victim "
                      f"{victim} idle; retrying")
                score(join(), reference)
                continue
            t0 = time.monotonic()
            _mig_http(urls[victim] + "/drainz", {}, timeout_s=10.0)
            code, mig = _mig_http(
                urls[victim] + "/migratez",
                {"targets": [[host, servers[survivor].server_address[1]]]},
                timeout_s=60.0,
            )
            if code != 200:
                raise RuntimeError(f"/migratez on {victim} failed: "
                                   f"{code} {mig}")
            wall_migrate = _wait_drained_url(urls[victim], 60.0)
            rows = join()
            wall_complete = time.monotonic() - t0
            score(rows, reference)
            if wall_migrate is None:
                raise RuntimeError(f"{victim} did not drain after "
                                   "migrating its streams")
            drain = {"victim": victim, "survivor": survivor,
                     "migratez": mig, "wall_migrate_s": wall_migrate,
                     "wall_complete_s": wall_complete, "rows": rows}
            break
        if drain is None:
            raise RuntimeError(
                "drain drill: victim was never holding a live stream"
            )
        print(f"# drain-migrate: {drain['migratez']['migrated']} streams "
              f"off {drain['victim']}; victim drained in "
              f"{drain['wall_migrate_s']:.2f}s, longest stream finished "
              f"on {drain['survivor']} at {drain['wall_complete_s']:.2f}s")

        # ---- drill 3: drain-and-wait baseline on the survivor (never
        # drained so far): same paced load, /drainz only, the drain wall
        # IS the longest stream's natural completion.
        waiter = drain["survivor"]
        arm(waiter, 41)
        rows, join = submit_round(payloads)
        time.sleep(0.45)
        t0 = time.monotonic()
        _mig_http(urls[waiter] + "/drainz", {}, timeout_s=10.0)
        wall_wait = _wait_drained_url(urls[waiter], 120.0)
        score(join(), reference)
        if wall_wait is None:
            raise RuntimeError(f"{waiter} never finished its natural drain")
        print(f"# drain-and-wait baseline: {waiter} drained naturally in "
              f"{wall_wait:.2f}s")

        # Observability: the drills must leave the post-mortem trail.
        events: dict[str, int] = {}
        migrations: dict[str, int] = {}
        for c in clients.values():
            for e in c.recorder.events():
                k = e["kind"]
                if k.startswith("stream_"):
                    events[k] = events.get(k, 0) + 1
            for outcome, v in (
                c.metrics.snapshot().get("stream_migrations") or {}
            ).items():
                migrations[outcome] = migrations.get(outcome, 0) + v

        result.update({
            "requests_per_round": geo["n"],
            "reference_tokens": sum(len(t) for t in reference),
            "kill": {k: v for k, v in kill.items() if k != "rows"},
            "drain": {k: v for k, v in drain.items() if k != "rows"},
            "wall_wait_s": wall_wait,
            "parity_failures": parity_failures,
            "lost": lost,
            "stream_events": events,
            "stream_migrations": migrations,
            "router": {k: v for k, v in router.fleetz().items()
                       if k != "replicas"},
        })
        return result
    finally:
        if vproc.poll() is None:
            vproc.kill()
        router.close()
        for srv, t in threads:
            srv.shutdown()
            srv.server_close()
            t.join(timeout=10)
        for c in clients.values():
            c.batcher.fault_injector = None
            c.close()


def run_migrate(args) -> int:
    """The --migrate gate: live-stream migration drills (kill + drain)
    with unconditional bit-parity, plus the hot-swap drain-wall A/B."""
    print("# migrate drill: 2 in-process migration-enabled engines + 1 "
          "subprocess replica behind an adopt-mode router; paced decode")
    res = _run_migrate_drills(args)

    k, d = res["kill"], res["drain"]
    hdr = (f"{'drill':>14} {'migrated':>9} {'readopted':>10} "
           f"{'drain wall s':>13} {'complete s':>11}")
    print("\n" + hdr)
    print("-" * len(hdr))
    print(f"{'kill+replay':>14} {k['migratez']['migrated']:>9d} "
          f"{k['migratez'].get('readopted', 0):>10d} {'-':>13} {'-':>11}")
    print(f"{'drain-migrate':>14} {d['migratez']['migrated']:>9d} "
          f"{d['migratez'].get('readopted', 0):>10d} "
          f"{d['wall_migrate_s']:>13.2f} {d['wall_complete_s']:>11.2f}")
    print(f"{'drain-and-wait':>14} {'-':>9} {'-':>10} "
          f"{res['wall_wait_s']:>13.2f} {res['wall_wait_s']:>11.2f}")
    ratio = (res["wall_wait_s"] / d["wall_migrate_s"]
             if d["wall_migrate_s"] else float("inf"))
    print(f"# victim freed {ratio:.1f}x faster than drain-and-wait; "
          f"{k['retries']} replay retries after the kill")
    print(f"# stream events: {res['stream_events']}; migrations by "
          f"outcome: {res['stream_migrations']}")

    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"mode": "migrate", **res}, fh, indent=2)
        print(f"# wrote {args.json}")

    # Correctness gates: unconditional, accumulated across every round.
    if res["parity_failures"] or res["lost"]:
        print(f"FAIL: {res['parity_failures']} migrated/replayed streams "
              f"diverged from their uninterrupted reference and "
              f"{res['lost']} requests were lost — migration must never "
              "lose or duplicate a token", file=sys.stderr)
        return 1
    if k["retries"] < 1:
        print("FAIL: killing the migration target produced no replay "
              "retry — the resume-with-prefix path never ran",
              file=sys.stderr)
        return 1
    if not (res["stream_events"].get("stream_export")
            and res["stream_events"].get("stream_adopt")):
        print(f"FAIL: flight recorder missing stream migration events "
              f"(got {res['stream_events']})", file=sys.stderr)
        return 1
    if d["wall_migrate_s"] >= d["wall_complete_s"]:
        print(f"FAIL: migrate drain wall {d['wall_migrate_s']:.2f}s did "
              f"not beat the longest stream's completion "
              f"{d['wall_complete_s']:.2f}s — the victim waited anyway",
              file=sys.stderr)
        return 1
    if d["wall_migrate_s"] >= res["wall_wait_s"]:
        print(f"FAIL: migrate drain wall {d['wall_migrate_s']:.2f}s not "
              f"below the drain-and-wait baseline "
              f"{res['wall_wait_s']:.2f}s", file=sys.stderr)
        return 1
    return 0


def _print_grid_summary(grid: dict) -> None:
    """The one-line AOT-grid digest (/compilez over the bench engine) so
    PERF.md rounds can attribute warmup cost."""
    cold = grid.get("coldest_cell")
    cold_s = (
        f", coldest {cold['key']} ({cold['seconds']:.2f}s)" if cold else ""
    )
    print(
        f"# aot grid: {grid['cells_compiled']}/{grid['cells_total']} cells "
        f"compiled ({grid['cells_failed']} failed) in "
        f"{grid['compile_seconds_total']:.2f}s{cold_s}"
    )


def _parse_layout(name: str) -> dict | None:
    """``single``/``dp``/dash-joined ``(tp|pp|ep)N`` tokens -> knob dict."""
    import re

    knobs = {"tp": 1, "pp": 1, "ep": 1}
    if name in ("single", "dp"):
        return knobs
    for tok in name.split("-"):
        m = re.fullmatch(r"(tp|pp|ep)(\d+)", tok)
        if m is None:
            return None
        knobs[m.group(1)] = int(m.group(2))
    return knobs


def _probe_parity(baseline: list[dict], got: list[dict]) -> bool:
    """Fast-path tolerances (tests/test_serve_fastpath.py): pred_ids exact,
    score rtol 1e-4, embedding/nsp rtol 1e-3 atol 1e-4."""
    try:
        for a, b in zip(baseline, got):
            np.testing.assert_array_equal(a["pred_ids"], b["pred_ids"])
            np.testing.assert_allclose(a["score"], b["score"], rtol=1e-4)
            np.testing.assert_allclose(
                a["embedding"], b["embedding"], rtol=1e-3, atol=1e-4
            )
            np.testing.assert_allclose(
                a["nsp_probs"], b["nsp_probs"], rtol=1e-3, atol=1e-4
            )
    except AssertionError as e:
        print(f"# parity mismatch: {str(e).splitlines()[0]}", file=sys.stderr)
        return False
    return True


def run_mesh_compare(args) -> int:
    """Serve the same weights under each requested mesh layout; compare
    numerics against the first layout and throughput across all of them."""
    import jax

    from distributed_tensorflow_tpu.parallel.mesh import build_mesh, data_axes
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        BertInferenceEngine,
        Client,
        plan_serve_mesh,
    )

    n_dev = len(jax.devices())
    layouts = []
    pps = set()
    for name in args.mesh_layouts:
        knobs = _parse_layout(name)
        if knobs is None:
            print(f"# skip {name}: unrecognized (single|dp|tpN[-ppN][-epN])")
            continue
        layouts.append((name, knobs))
        if knobs["pp"] > 1:
            pps.add(knobs["pp"])
    if len(pps) > 1:
        print("FAIL: mesh layouts mix different pp degrees; the stacked "
              "encoder is built once and must match them all",
              file=sys.stderr)
        return 2
    if len(layouts) < 2:
        print("FAIL: --mesh-layouts needs >=2 recognized layouts to compare",
              file=sys.stderr)
        return 2

    pp_model = pps.pop() if pps else 1
    cfg, model, params = _build_model(args, pipeline_parallel=pp_model)
    payloads = make_payloads(cfg.vocab_size, args.buckets)
    probes = payloads[:4]
    load_rps = args.loads[0]

    rows, baseline_out, baseline_rps = [], None, None
    for name, knobs in layouts:
        if name == "single":
            mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
        else:
            spec, fell_back = plan_serve_mesh(
                tp=knobs["tp"], pp=knobs["pp"], ep=knobs["ep"],
                n_devices=n_dev,
            )
            if fell_back:
                print(f"# skip {name}: needs "
                      f"{knobs['tp'] * knobs['pp'] * knobs['ep']} devices, "
                      f"host has {n_dev}")
                continue
            mesh = build_mesh(spec)
        try:
            engine = BertInferenceEngine(
                model, params, mesh,
                buckets=tuple(args.buckets),
                max_batch=args.max_batch,
                batch_tiers=tuple(args.batch_tiers),
            )
        except ValueError as e:  # head/expert/layer divisibility
            print(f"# skip {name}: {e}")
            continue
        probe_out = [engine.run_batch([p])[0] for p in probes]
        parity_ok = True
        if baseline_out is None:
            baseline_out = probe_out
        else:
            parity_ok = _probe_parity(baseline_out, probe_out)
        client = Client(engine, BatcherConfig(
            max_batch=args.max_batch,
            max_delay_ms=args.max_delay_ms,
            max_queue=args.max_queue,
            max_in_flight=args.max_in_flight,
        ))
        metrics = client.metrics
        try:
            for f in [client.submit(payloads[i]) for i in range(8)]:
                f.result(timeout=120)
            single = run_single_stream(
                client, payloads, max(args.single_duration, 0.5)
            )
            metrics.latency.reset()
            padded0 = metrics.padded_rows.value
            load = run_load(client, payloads, load_rps, args.duration)
            snap = metrics.snapshot()
        finally:
            client.close()
        replicas = math.prod(
            mesh.shape[a] for a in data_axes(mesh)
        ) if data_axes(mesh) else 1
        if baseline_rps is None:
            baseline_rps = single["rps"]
        rows.append({
            "layout": engine.layout,
            "requested": name,
            "devices": int(mesh.size),
            "replicas": replicas,
            "parity_ok": parity_ok,
            "single_rps": single["rps"],
            "single_rps_per_replica": single["rps"] / replicas,
            "achieved_rps": load["achieved_rps"],
            "rps_per_replica": load["achieved_rps"] / replicas,
            "p50_ms": snap["latency_ms"]["p50"],
            "p99_ms": snap["latency_ms"]["p99"],
            "padded_rows": snap["padded_rows"] - padded0,
            "layout_tier_hits": snap["layout_tier_hits"],
        })

    hdr = (
        f"{'layout':>14} {'devs':>5} {'reps':>5} {'single rps':>11} "
        f"{'load rps':>9} {'rps/rep':>8} {'p50 ms':>8} {'p99 ms':>8} "
        f"{'padded':>7} {'parity':>7}"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(
            f"{r['layout']:>14} {r['devices']:>5d} {r['replicas']:>5d} "
            f"{r['single_rps']:>11.1f} {r['achieved_rps']:>9.1f} "
            f"{r['rps_per_replica']:>8.1f} {r['p50_ms']:>8.2f} "
            f"{r['p99_ms']:>8.2f} {r['padded_rows']:>7d} "
            f"{'ok' if r['parity_ok'] else 'FAIL':>7}"
        )

    report = {
        "mode": "mesh_compare",
        "config": {
            "n_devices": n_dev,
            "buckets": list(args.buckets),
            "batch_tiers": list(args.batch_tiers),
            "max_batch": args.max_batch,
            "load_rps": load_rps,
        },
        "mesh_layouts": rows,
    }
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")

    bad_parity = [r["layout"] for r in rows if not r["parity_ok"]]
    if bad_parity:
        print(f"FAIL: parity vs {rows[0]['layout']} broken for "
              f"{', '.join(bad_parity)}", file=sys.stderr)
        return 1
    if args.quick and baseline_rps:
        # Regression tripwire, not a speedup assertion: model-parallel on a
        # simulated-CPU mesh is legitimately slower than single-chip, but a
        # >20x collapse means a layout is broken (e.g. re-tracing per call).
        slow = [r["layout"] for r in rows
                if r["single_rps"] < 0.05 * baseline_rps]
        if slow:
            print(f"FAIL: throughput collapse (<5% of "
                  f"{rows[0]['layout']}) for {', '.join(slow)}",
                  file=sys.stderr)
            return 1
    if len(rows) < 2:
        print("FAIL: fewer than 2 layouts actually ran", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--loads", type=float, nargs="+", default=[50.0, 200.0],
                   help="offered loads in requests/second (>=2 for the sweep)")
    p.add_argument("--duration", type=float, default=2.0,
                   help="seconds per offered-load point")
    p.add_argument("--buckets", type=int, nargs="+", default=[32, 64, 128])
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--batch-tiers", type=int, nargs="+", default=[1, 2, 4, 8],
                   help="batch tiers to AOT-compile; pass a single "
                   "max-batch value for the fixed-batch baseline")
    p.add_argument("--max-in-flight", type=int, default=2,
                   help="overlapped dispatch depth (1 = serial host/device)")
    p.add_argument("--bucket-queues", action="store_true",
                   help="per-sequence-bucket request queues")
    p.add_argument("--max-delay-ms", type=float, default=8.0)
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--single-duration", type=float, default=1.0,
                   help="seconds for the closed-loop occupancy-1 pass "
                   "(0 disables it)")
    p.add_argument("--quick", action="store_true",
                   help="CI smoke: tiny model, one short load point")
    p.add_argument("--mesh-layouts", nargs="+", default=[],
                   help="compare serving mesh layouts instead of the load "
                   "sweep: single|dp|tpN[-ppN][-epN] (first is the parity "
                   "baseline)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="MoE expert count for epN layouts (0 = dense FFN)")
    p.add_argument("--decode", action="store_true",
                   help="continuous-batching decode A/B (simulated-step "
                   "engine + real-engine parity probe) instead of the "
                   "load sweep")
    p.add_argument("--quant", action="store_true",
                   help="with --decode: quantized-serving A/B on a real "
                   "tiny engine — fp32 vs int8 weights + int8 KV, "
                   "teacher-forced top-1 agreement, /memz deltas, "
                   "slots-at-fixed-HBM-budget, and KV-wire cross-dtype "
                   "refusal (gates are unconditional; see DEPLOY.md "
                   "\"Quantized serving\")")
    p.add_argument("--sched", action="store_true",
                   help="priority-preemptive scheduling A/B: FIFO vs "
                   "deadline-aware EDF admission + slot preemption on a "
                   "heavy-tailed mixed-priority workload, plus a real-"
                   "engine forced preempt->park->resume parity probe "
                   "(parity gates are unconditional)")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated prefill/decode A/B: real-engine "
                   "wire-format parity probe + sim head-of-line A/B "
                   "(colocated monolithic vs role-split engines at "
                   "matched simulated chip count)")
    p.add_argument("--fleet", action="store_true",
                   help="replicated-router chaos drill: N real replica "
                   "processes behind serve/router.py, a seeded mid-trace "
                   "SIGKILL, and a rolling hot-swap (round 16)")
    p.add_argument("--migrate", action="store_true",
                   help="live decode-stream migration drills (ISSUE 18): "
                   "kill + drain migrations with unconditional bit-parity "
                   "against uninterrupted references, plus the hot-swap "
                   "drain-wall A/B vs drain-and-wait")
    p.add_argument("--replica-fault-plan", default="",
                   help="internal: fault plan armed inside a re-entered "
                   "replica (paces its decode steps)")
    p.add_argument("--fleet-replicas", type=int, default=3,
                   help="replica processes in the fleet")
    p.add_argument("--fleet-requests", type=int, default=60,
                   help="requests in the bursty Zipf traffic trace")
    p.add_argument("--fleet-seed", type=int, default=7,
                   help="seed for the trace AND the FaultPlan placing "
                   "the SIGKILL (same seed, same chaos)")
    p.add_argument("--fleet-slo-p99-ms", type=float, default=20000.0,
                   help="fleet-wide wall p99 bound for the --quick gate")
    p.add_argument("--restart-budget-s", type=float, default=120.0,
                   help="deadline for the SIGKILLed replica to be "
                   "restarted and ready again")
    p.add_argument("--replica-serve", type=int, default=0,
                   help="internal: run as one fleet replica server on "
                   "this port (spawned by --fleet)")
    p.add_argument("--replica-tag", default="fleet-v1",
                   help="internal: deployment tag surfaced on /healthz")
    p.add_argument("--slots", type=int, default=8,
                   help="KV-cache slot table size (decode mode)")
    p.add_argument("--max-new-tokens", type=int, default=64,
                   help="largest per-request output budget (decode mode)")
    p.add_argument("--sim-step-ms", type=float, default=2.0,
                   help="simulated per-step device cost (decode mode)")
    p.add_argument("--decode-requests", type=int, default=96,
                   help="backlog size for the closed-loop decode drain")
    p.add_argument("--slo-p99-ms", type=float, default=50.0,
                   help="latency SLO threshold (ms) for the SLO section "
                   "and the --quick SLO-math consistency gate")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="latency SLO target fraction")
    p.add_argument("--slo-availability", type=float, default=0.0,
                   help="availability SLO target (0 = disabled)")
    p.add_argument("--no-windowed", action="store_true",
                   help="disable the windowed metric families (the A/B "
                   "baseline for the windowed-overhead measurement; "
                   "docs/PERF.md)")
    p.add_argument("--ckpt-dir", default="",
                   help="serve a real checkpoint instead of random init")
    p.add_argument("--trace-dir", default="",
                   help="enable span tracing and write the Chrome "
                   "trace-event JSON (Perfetto-loadable) here")
    p.add_argument("--trace-buffer", type=int, default=16384,
                   help="span ring-buffer size when tracing")
    p.add_argument("--json", default="", help="also write results here")
    args = p.parse_args(argv)

    if args.quick:
        args.loads = [50.0]
        args.duration = 0.5
        args.single_duration = min(args.single_duration, 0.5)
        args.buckets = [16, 32]
        args.layers, args.hidden, args.vocab = 1, 32, 128
        # Large enough that the end-of-run drain (no queue left to refill
        # freed slots) doesn't eat the continuous-admission margin.
        args.decode_requests = min(args.decode_requests, 64)

    if args.fleet and args.replica_serve:
        return run_fleet_replica(args)
    if args.fleet:
        return run_fleet(args)
    if args.migrate and args.replica_serve:
        return run_migrate_replica(args)
    if args.migrate:
        return run_migrate(args)
    if args.decode and args.quant:
        return run_quant(args)
    if args.decode:
        return run_decode(args)
    if args.sched:
        return run_sched(args)
    if args.disagg:
        return run_disagg(args)
    if args.mesh_layouts:
        return run_mesh_compare(args)

    client, vocab = build_client(args)
    _print_grid_summary(client.grid_status())
    payloads = make_payloads(vocab, args.buckets)
    metrics = client.metrics

    # Warmup: fill every executable path + the thread machinery.
    for f in [client.submit(payloads[i]) for i in range(16)]:
        f.result(timeout=120)

    report = {"config": {
        "batch_tiers": list(client.engine.batch_tiers),
        "max_batch": args.max_batch,
        "max_in_flight": args.max_in_flight,
        "bucket_queues": args.bucket_queues,
        "max_delay_ms": args.max_delay_ms,
    }}
    rows = []
    try:
        if args.single_duration > 0:
            single = run_single_stream(
                client, payloads, args.single_duration
            )
            report["single_stream"] = single
            print(
                f"# single-stream (occupancy-1): {single['rps']:.1f} req/s "
                f"over {single['served']} requests"
            )
        threshold_s = args.slo_p99_ms / 1e3
        for rps in args.loads:
            # Per-point metrics: fresh histograms so p99 is per-load;
            # counters diff across the point (they are cumulative).
            metrics.latency.reset()
            metrics.latency_w.reset()
            metrics.batch_occupancy.reset()
            metrics.tier_hits.reset()
            metrics.bucket_hits.reset()
            metrics.phase.reset()
            padded0 = metrics.padded_rows.value
            batches0 = metrics.batches.value
            r = run_load(client, payloads, rps, args.duration)
            exact = r.pop("_exact_latency_s")
            snap = metrics.snapshot()
            # SLO math consistency: windowed-bucketed attainment (threshold
            # inserted as an explicit bound) vs the exact per-request log.
            # window=None = everything since the per-point reset.
            r["slo_threshold_ms"] = args.slo_p99_ms
            r["slo_attainment_exact"] = (
                sum(1 for v in exact if v <= threshold_s) / len(exact)
                if exact else 1.0
            )
            r["slo_attainment_windowed"] = metrics.latency_w.attainment(
                threshold_s, None
            )
            r["slo_attainment_gap"] = abs(
                r["slo_attainment_windowed"] - r["slo_attainment_exact"]
            )
            slo_rep = client.slo.report()
            r["slo_windows"] = {
                s["name"]: {
                    w: {"attainment": row["attainment"],
                        "burn_rate": row["burn_rate"]}
                    for w, row in s["windows"].items()
                }
                for s in slo_rep["slos"]
            }
            r["slo_verdict"] = slo_rep["verdict"]
            r["p50_ms"] = snap["latency_ms"]["p50"]
            r["p99_ms"] = snap["latency_ms"]["p99"]
            r["mean_ms"] = snap["latency_ms"]["mean"]
            r["mean_batch_occupancy"] = snap["batch_occupancy"]["mean"]
            r["batches"] = snap["batches"] - batches0
            r["padded_rows"] = snap["padded_rows"] - padded0
            r["tier_hits"] = snap["tier_hits"]
            r["bucket_hits"] = snap["bucket_hits"]
            r["phase_ms"] = snap["phase_ms"]
            rows.append(r)
    finally:
        client.close()
    report["loads"] = rows

    hdr = (
        f"{'offered rps':>12} {'achieved rps':>13} {'served':>7} "
        f"{'rejected':>9} {'p50 ms':>8} {'p99 ms':>8} {'occupancy':>10} "
        f"{'padded rows':>12}  tier hits"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        tiers = ",".join(f"{k}:{v}" for k, v in r["tier_hits"].items())
        print(
            f"{r['offered_rps']:>12.1f} {r['achieved_rps']:>13.1f} "
            f"{r['served']:>7d} {r['rejected']:>9d} "
            f"{r['p50_ms']:>8.2f} {r['p99_ms']:>8.2f} "
            f"{r['mean_batch_occupancy']:>10.2f} "
            f"{r['padded_rows']:>12d}  {tiers}"
        )
    # ---------------------------------------------- phase attribution
    # Where the end-to-end latency went, per pipeline phase (the spans'
    # histogram view). The phase boundaries are contiguous timestamps, so
    # their means MUST sum to the end-to-end mean — divergence is
    # instrumentation drift, and --quick treats it as a failure (a
    # standing CI tripwire).
    phase_order = [
        "queue_wait", "batch_assemble", "dispatch", "device", "fetch", "run",
    ]
    max_divergence = 0.0
    print("\nphase attribution (per offered load):")
    for r in rows:
        e2e = r["mean_ms"]
        phases = r["phase_ms"]
        phase_sum = sum(p["mean"] for p in phases.values())
        divergence = abs(phase_sum - e2e) / e2e if e2e else 0.0
        max_divergence = max(max_divergence, divergence)
        r["phase_sum_ms"] = phase_sum
        r["phase_divergence"] = divergence
        print(
            f"  offered {r['offered_rps']:.0f} rps — e2e mean "
            f"{e2e:.2f} ms, phase sum {phase_sum:.2f} ms "
            f"(divergence {100 * divergence:.1f}%)"
        )
        hdr = f"    {'phase':>15} {'mean ms':>9} {'p99 ms':>9} {'of e2e':>7}"
        print(hdr)
        for name in phase_order:
            if name not in phases:
                continue
            ph = phases[name]
            frac = ph["mean"] / e2e if e2e else 0.0
            print(
                f"    {name:>15} {ph['mean']:>9.2f} {ph['p99']:>9.2f} "
                f"{100 * frac:>6.1f}%"
            )
    report["max_phase_divergence"] = max_divergence

    # ---------------------------------------------------- SLO section
    # Attainment against the declared latency SLO per load point, from the
    # windowed bucketed histogram, CHECKED against the exact per-request
    # log (the two must agree: the threshold is an explicit bucket bound).
    # Burn rates are the multi-window error-budget view a pager would see.
    max_slo_gap = 0.0
    print(
        f"\nSLO section (latency threshold {args.slo_p99_ms:g} ms, "
        f"target {args.slo_target:g}):"
    )
    for r in rows:
        max_slo_gap = max(max_slo_gap, r["slo_attainment_gap"])
        print(
            f"  offered {r['offered_rps']:.0f} rps — attainment "
            f"{100 * r['slo_attainment_windowed']:.2f}% windowed / "
            f"{100 * r['slo_attainment_exact']:.2f}% exact "
            f"(gap {r['slo_attainment_gap']:.4f}), verdict {r['slo_verdict']}"
        )
        for name, windows in r["slo_windows"].items():
            burns = " ".join(
                f"{w}={row['burn_rate']:.2f}" for w, row in windows.items()
            )
            print(f"    {name} burn rate: {burns}")
    report["max_slo_attainment_gap"] = max_slo_gap

    # ---------------------------------------------- flight recorder
    # Overhead A/B (sim engine, recorder off vs on) + forced-dump JSON
    # round-trip — the obs-quick gates for obs/flightrec.py.
    rec = _run_recorder_ab(args)
    report["flight_recorder"] = rec
    print(
        f"\nflight recorder: {rec['recorder_tokens_per_s']:.0f} tok/s on / "
        f"{rec['baseline_tokens_per_s']:.0f} off "
        f"({100 * rec['overhead_frac']:.2f}% overhead), "
        f"{rec['events_recorded']} events buffered "
        f"({rec['dropped_events']} dropped), dump sections "
        f"{'ok' if rec['dump_sections_ok'] else 'MISSING'}"
    )

    if args.trace_dir:
        trace_path = os.path.join(args.trace_dir, "serve_bench_trace.json")
        client.tracer.export(trace_path)
        n_events = len(client.tracer.chrome_events())
        print(f"# wrote {n_events} trace events to {trace_path}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
        print(f"# wrote {args.json}")
    if args.quick and max_divergence > 0.25:
        print(
            f"FAIL: traced phase sum diverges {100 * max_divergence:.1f}% "
            "from measured wall latency (>25%) — span instrumentation has "
            "drifted from the enqueue->reply timestamps",
            file=sys.stderr,
        )
        return 1
    if args.quick and not args.no_windowed and max_slo_gap > 0.02:
        print(
            f"FAIL: windowed-histogram SLO attainment diverges "
            f"{max_slo_gap:.4f} (>0.02) from the exact per-request log — "
            "the SLO math has drifted (threshold no longer an exact bucket "
            "bound, or the windowed observe path lost samples)",
            file=sys.stderr,
        )
        return 1
    if not rec["dump_sections_ok"] or not rec["events_recorded"]:
        print(
            "FAIL: forced flight-recorder dump did not round-trip through "
            "JSON with events + all four sidecar sections "
            "(metrics/memz/compilez/tracer)",
            file=sys.stderr,
        )
        return 1
    if args.quick and rec["overhead_frac"] > 0.02:
        print(
            f"FAIL: flight-recorder overhead "
            f"{100 * rec['overhead_frac']:.2f}% (>2%) — recording is no "
            "longer a cheap ring append on the hot path",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
