"""Continuous-batching decode tests: numerics and scheduling.

Numerics run against a real (tiny) :class:`CausalLMEngine` — greedy decode
through the prefill/decode AOT grid must match a one-shot full-forward
reference token for token, on one chip AND on a TP-sharded mesh, with
requests joining mid-flight (the determinism contract: a request's token
stream is a function of the request, never of its batchmates). Scheduling
runs against a pure-python stub engine whose token stream is a closed-form
function of (prompt, position) — slot reuse, flush-vs-continuous admission,
drain semantics, and the race-sanitizer soak all pin the slot-table
machinery without paying XLA compiles.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.obs.sanitizer import sanitize_races
from distributed_tensorflow_tpu.serve import batcher as batcher_mod
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    Client,
    ContinuousBatcher,
    build_http_server,
)

# ---------------------------------------------------------------- fixtures


def _tiny_causal_lm():
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )

    cfg = CausalLMConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position=48,
    )
    model = CausalLM(cfg)
    L = cfg.max_position
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
    )
    return model, variables["params"]


@pytest.fixture(scope="module")
def tiny_lm(devices8):
    return _tiny_causal_lm()


@pytest.fixture(scope="module")
def decode_engine(tiny_lm):
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    return CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8,
    )


@pytest.fixture(scope="module")
def tp_decode_engine(tiny_lm):
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.serve import (
        CausalLMEngine,
        plan_serve_mesh,
    )

    model, params = tiny_lm
    spec, fell_back = plan_serve_mesh(tp=2, n_devices=8)
    assert not fell_back
    return CausalLMEngine(
        model, params, build_mesh(spec), buckets=(8, 16), slots=3,
        max_batch=2, max_new_tokens=8,
    )


def _ref_greedy(model, params, prompt, n):
    """One-shot reference: n greedy tokens by re-running the FULL causal
    forward after each appended token — no cache, no batchmates."""
    import jax.numpy as jnp

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


# ------------------------------------------------- numerics: greedy parity


def _run_mixed_batch(engine, model, params):
    """More requests than slots, mixed prompt lengths and budgets: every
    admission after the first joins an in-flight decode batch, and every
    request's tokens must equal the solo full-forward reference."""
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(7):
        plen = int(rng.integers(3, 14))
        reqs.append({
            "input_ids": rng.integers(5, 64, size=plen),
            "max_new_tokens": int(rng.integers(2, 9)),
        })
    refs = [
        _ref_greedy(model, params, r["input_ids"], r["max_new_tokens"])
        for r in reqs
    ]
    m = ServeMetrics()
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2, max_queue=32), metrics=m
    ) as b:
        futs = [b.submit(r) for r in reqs]
        results = [f.result(timeout=120) for f in futs]
    for r, ref, req in zip(results, refs, reqs):
        assert r["tokens"] == ref
        assert r["n_tokens"] == req["max_new_tokens"]
        assert r["prompt_len"] == len(req["input_ids"])
        assert r["bucket"] == engine.bucket_for(len(req["input_ids"]))
        # Contiguous phases sum EXACTLY to wall latency by construction.
        for f in futs:
            assert abs(sum(f.phases.values()) - f.latency_s) < 1e-9
    return m, sum(r["max_new_tokens"] for r in reqs)


def test_greedy_parity_mid_flight_single_chip(decode_engine, tiny_lm):
    model, params = tiny_lm
    m, total = _run_mixed_batch(decode_engine, model, params)
    snap = m.snapshot()
    # 7 first tokens via prefill + the rest via decode steps.
    assert snap["tokens"] == total
    assert snap["ttft_ms"]["count"] == 7
    assert snap["decode_steps"] > 0
    assert snap["itl_ms"]["count"] == total - 7
    assert snap["slots_active"] == 0  # table empty after drain


def test_greedy_parity_mid_flight_tp_mesh(tp_decode_engine, tiny_lm):
    """Acceptance: identical token streams when the engine shards params
    and cache heads over a model axis (dp4-tp2 on 8 simulated devices)."""
    model, params = tiny_lm
    assert tp_decode_engine.layout != ""
    _run_mixed_batch(tp_decode_engine, model, params)


def test_seeded_sampling_is_deterministic(decode_engine):
    """temperature > 0: same (payload, seed) -> same tokens, run to run;
    the stream is keyed on (seed, absolute position) only."""
    req = {
        "input_ids": np.arange(5, 12), "max_new_tokens": 6,
        "temperature": 0.8, "seed": 123,
    }
    runs = []
    for _ in range(2):
        with ContinuousBatcher(decode_engine, BatcherConfig()) as b:
            runs.append(b.submit(dict(req)).result(timeout=60)["tokens"])
    assert runs[0] == runs[1]
    assert len(runs[0]) == 6


# --------------------------------------- the decode loop's scoped spans

#: where the decode loop's host time goes (serve/batcher.py, PERF.md §3)
_LOOP_SPANS = ("batcher.idle", "batcher.plan", "batcher.sem_wait",
               "engine.prefill_dispatch", "engine.decode_dispatch")
_FETCH_SPANS = ("engine.fetch", "batcher.deliver")


class _LiveLaneSpy:
    """The engine, plus a note of the live lanes of every decode step."""

    def __init__(self, engine):
        self._engine = engine
        self.live = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def decode(self, lengths, active, temps, seeds):
        self.live.append(int(np.sum(active)))
        return self._engine.decode(lengths, active, temps, seeds)


@pytest.fixture(scope="module")
def traced_run(decode_engine):
    """Seven mixed requests through three slots with an enabled tracer:
    ``(spans by name, spy engine, futures, metrics snapshot)``."""
    from distributed_tensorflow_tpu.obs.trace import Tracer

    rng = np.random.default_rng(1)
    spy, tracer, m = _LiveLaneSpy(decode_engine), Tracer(1 << 14), ServeMetrics()
    with ContinuousBatcher(
        spy, BatcherConfig(max_batch=2, max_queue=32), metrics=m,
        tracer=tracer,
    ) as b:
        futs = [
            b.submit({
                "input_ids": rng.integers(5, 64, size=int(rng.integers(3, 14))),
                "max_new_tokens": int(rng.integers(2, 9)),
            })
            for _ in range(7)
        ]
        for f in futs:
            f.result(timeout=120)
    spans = {}
    for sp in tracer.drain():
        spans.setdefault(sp.name, []).append(sp)
    return spans, spy, futs, m.snapshot()


def test_every_decode_step_has_its_scoped_spans(traced_run):
    spans, spy, _futs, snap = traced_run
    steps = snap["decode_steps"]
    assert steps == len(spy.live) > 0
    assert len(spans["engine.decode_dispatch"]) == steps
    assert sum(s.args["kind"] == "decode" for s in spans["engine.fetch"]) == steps
    # one semaphore wait a dispatch, one delivery a fetch, and a planning
    # pass that returned the step's rows before every dispatch
    n_prefill = len(spans["engine.prefill_dispatch"])
    assert len(spans["batcher.sem_wait"]) == steps + n_prefill
    assert len(spans["batcher.deliver"]) == len(spans["engine.fetch"])
    planned = [s for s in spans["batcher.plan"] if s.args and s.args["rows"]]
    assert len(planned) == steps
    assert sum(s.args["tokens"] for s in spans["batcher.deliver"]) == snap["tokens"]
    assert sum(s.args["finished"] for s in spans["batcher.deliver"]) == 7
    assert sum(s.args["admitted"] for s in spans["batcher.plan"] if s.args) == 7
    assert {s.args["bucket"] for s in spans["engine.prefill_dispatch"]} <= {8, 16}
    # the after-the-fact intervals they replace are gone
    assert "decode_step" not in spans


def test_scoped_spans_of_one_thread_never_overlap(traced_run):
    spans, *_ = traced_run
    for names in (_LOOP_SPANS, _FETCH_SPANS):
        mine = sorted(
            (s for n in names for s in spans.get(n, ())), key=lambda s: s.t0
        )
        assert len({s.tid for s in mine}) == 1  # one thread does this work
        for a, b in zip(mine, mine[1:]):
            assert a.t1 <= b.t0, (a.name, b.name)


def test_decode_dispatch_rows_are_the_live_lanes(traced_run):
    spans, spy, *_ = traced_run
    dispatched = sorted(spans["engine.decode_dispatch"], key=lambda s: s.t0)
    assert [s.args["rows"] for s in dispatched] == spy.live
    assert {s.args["slots"] for s in dispatched} == {3}
    planned = sorted(
        (s for s in spans["batcher.plan"] if s.args and s.args["rows"]),
        key=lambda s: s.t0,
    )
    assert [s.args["rows"] for s in planned] == spy.live


def test_decode_dispatch_counts_the_cache_rows_it_writes(traced_run):
    """``kv_rows_written``: a row per layer and cache leaf (K and V) for
    every live lane; an idle lane's sentinel position writes nothing."""
    spans, spy, *_ = traced_run
    per_lane = 2 * spy.model.cfg.num_layers
    dispatched = sorted(spans["engine.decode_dispatch"], key=lambda s: s.t0)
    assert [s.args["kv_rows_written"] for s in dispatched] == [
        n * per_lane for n in spy.live
    ]


# ------------------------- the decode step's inputs stay on the device


_PROMPT_A, _PROMPT_B = [5, 9, 2, 44, 17], [7, 3, 30, 11]
#: the steps of _drive_plan that change its plan: the first, an admission,
#: a finish, a new temperature, a new seed
_PLANNED_UPLOADS = [1, 0, 0, 1, 0, 0, 1, 0, 1, 0, 1, 0]


def _drive_plan(engine, stale: bool):
    """Drive ``engine`` by hand through a plan the batcher could make:
    request A alone for three steps, B admitted beside it for three, A
    finished for two, then B's temperature and, after two steps, its seed
    changed. Returns each step's ``inputs_uploaded`` and every slot's
    tokens (the prefill's first, then one a step). ``stale`` forgets what
    the device holds before every step, so each step uploads its plan."""
    S = engine.slots
    live = {}  # slot -> [length, temperature, seed], as the batcher keeps them
    tokens = {0: [], 1: []}
    uploads = []

    def admit(slot, prompt):
        tok = engine.fetch_step(engine.prefill(
            [{"slot": slot, "input_ids": np.asarray(prompt)}]
        ))
        tokens[slot].append(int(tok[0]))
        live[slot] = [len(prompt), 0.0, 0]

    def step(n=1):
        for _ in range(n):
            lengths, active, temps, seeds = [0] * S, [False] * S, [0.0] * S, [0] * S
            for i, (length, temp, seed) in live.items():
                lengths[i], active[i], temps[i], seeds[i] = length, True, temp, seed
                live[i][0] += 1  # advances at dispatch
            if stale:
                engine._step_mirror = None
            handle = engine.decode(lengths, active, temps, seeds)
            uploads.append(handle.moved["inputs_uploaded"])
            tok = engine.fetch_step(handle)
            for i in live:
                tokens[i].append(int(tok[i]))

    admit(0, _PROMPT_A)
    step(3)
    admit(1, _PROMPT_B)
    step(3)
    del live[0]
    step(2)
    live[1][1] = 0.7
    step(2)
    live[1][2] = 123
    step(2)
    return uploads, tokens


@pytest.fixture(scope="module")
def planned_drives(tiny_lm):
    """One fresh engine driven through ``_drive_plan`` twice: as planned,
    then with every step's operand forgotten."""
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8,
    )
    return _drive_plan(engine, stale=False), _drive_plan(engine, stale=True)


def test_decode_uploads_its_inputs_only_where_the_plan_changed(planned_drives):
    """The engine keeps the step's lengths, live lanes, temperatures and
    seeds on the device, advanced by the step: steps whose plan held upload
    nothing, and the first step, an admission, a finish, a new temperature
    and a new seed each upload once. Forgetting the device's copy makes
    every step upload."""
    (planned, _), (stale, _) = planned_drives
    assert planned == _PLANNED_UPLOADS
    assert stale == [1] * len(_PLANNED_UPLOADS)


def test_reused_step_inputs_sample_the_tokens_of_fresh_uploads(
    planned_drives, tiny_lm
):
    """A step that ran on the resident operand sampled what the same step
    fed its plan afresh samples, greedy and seeded alike, and the greedy
    stream is the full-forward reference's."""
    (_, planned), (_, stale) = planned_drives
    assert planned == stale
    assert len(planned[0]) == 7 and len(planned[1]) == 10
    model, params = tiny_lm
    assert planned[0] == _ref_greedy(model, params, _PROMPT_A, 7)
    # B stays greedy until its temperature changes, four steps before the end
    assert planned[1][:6] == _ref_greedy(model, params, _PROMPT_B, 6)


def test_resident_step_inputs_serve_the_reference_two_steps_in_flight(
    decode_engine, tiny_lm
):
    """Through ``Client`` with two steps in flight — a step dispatched on an
    operand the step before it is still computing — every stream is the
    plain reference's, and the dispatch spans count steps that reused the
    resident inputs beside steps that uploaded theirs."""
    from distributed_tensorflow_tpu.obs.trace import Tracer

    model, params = tiny_lm
    rng = np.random.default_rng(2)
    reqs = [
        {
            "input_ids": rng.integers(5, 64, size=int(rng.integers(3, 14))),
            "max_new_tokens": int(rng.integers(4, 9)),
        }
        for _ in range(6)
    ]
    refs = [
        _ref_greedy(model, params, r["input_ids"], r["max_new_tokens"])
        for r in reqs
    ]
    tracer = Tracer(1 << 14)
    client = Client(
        decode_engine,
        BatcherConfig(max_batch=2, max_queue=32, max_in_flight=2),
        tracer=tracer,
    )
    try:
        futs = [client.submit(r) for r in reqs]
        assert [f.result(timeout=120)["tokens"] for f in futs] == refs
    finally:
        client.close()
    uploaded = [
        s.args["inputs_uploaded"] for s in tracer.drain()
        if s.name == "engine.decode_dispatch"
    ]
    assert set(uploaded) == {0, 1}, uploaded


def test_request_phases_keep_their_three_keys(traced_run):
    spans, _spy, futs, _snap = traced_run
    for f in futs:
        assert set(f.phases) == {"queue_wait", "prefill", "decode"}
        assert abs(sum(f.phases.values()) - f.latency_s) < 1e-9
    for name in ("request", "queue_wait", "prefill", "decode"):
        assert len(spans[name]) == 7  # the per-request record()s stand


@pytest.mark.parametrize("cell", ["decode", "prefill"])
def test_grid_cells_carry_the_scope_names_the_metrics_select(decode_engine, cell):
    """What benchmarks/layer_metrics/engine.*.json select survives the
    compiler: the module's name and the scopes in its ops' op_name."""
    if cell == "decode":
        text = decode_engine._decode_compiled.as_text()
        scopes = ("kv_write", "cached_attention", "lm_head", "sample")
    else:
        text = next(iter(decode_engine._prefill_compiled.values())).as_text()
        scopes = ("kv_write", "lm_head", "sample")
    assert text.startswith(f"HloModule jit_{cell}_fn")
    for scope in scopes:
        assert f"/{scope}/" in text, scope


def test_status_shows_what_the_decode_program_reserves(decode_engine):
    """Scratch and in-place bytes of the decode executable, read once when
    it is built: on the engine, and in the batcher digest /statusz shows.
    The slot table is donated and written in place, so at least its bytes
    are aliased wherever the backend reports a memory analysis."""
    eng = decode_engine
    ma = eng._decode_compiled.memory_analysis()
    assert eng.decode_scratch_bytes == ma.temp_size_in_bytes
    assert eng.decode_aliased_bytes == ma.alias_size_in_bytes
    assert eng.decode_aliased_bytes >= eng.slot_page_bytes * eng.slots
    with ContinuousBatcher(eng, BatcherConfig()) as b:
        st = b.status()
    assert st["decode_scratch_bytes"] == eng.decode_scratch_bytes
    assert st["decode_aliased_bytes"] == eng.decode_aliased_bytes
    with ContinuousBatcher(_StubDecodeEngine(), BatcherConfig()) as b:
        assert b.status()["decode_scratch_bytes"] is None


def test_engine_validate_rejects_oversized(decode_engine):
    from distributed_tensorflow_tpu.serve import RequestError

    eng = decode_engine
    with pytest.raises(RequestError, match="bucket"):
        eng.validate({"input_ids": np.arange(5, 30)})  # > largest bucket
    with pytest.raises(RequestError, match="max_new_tokens"):
        eng.validate({"input_ids": np.arange(5, 10), "max_new_tokens": 0})
    with pytest.raises(RequestError, match="cache"):
        eng.validate(
            {"input_ids": np.arange(5, 21), "max_new_tokens": 1000}
        )


# ------------------------------------------------- scheduling (stub engine)


class _StubDecodeEngine:
    """Closed-form decode engine: token k of a request is a pure function
    of (prompt, k), so any scheduling (solo, joined mid-flight, after slot
    reuse) must deliver the same stream — misrouted or stale-gen tokens
    show up as wrong values immediately."""

    def __init__(self, slots=3, max_batch=2, max_new_tokens=8,
                 step_delay_s=0.0):
        self.slots = slots
        self.max_batch = max_batch
        self.max_new_tokens = max_new_tokens
        self.step_delay_s = step_delay_s
        self.lock = threading.Lock()
        # slot -> (prompt_sum, steps_taken); written only by the decode-loop
        # thread (the single-dispatcher contract), read by the fetch thread.
        # Never cleared on finish — the real engine's pages aren't either,
        # they're overwritten by the slot's next occupant.
        self._state = {}
        self.prefills = []  # admitted slot ids, in dispatch order
        # ("prefill", slot_ids) / ("decode", active_slot_ids) in dispatch
        # order — admission/step interleaving assertions read this.
        self.events = []

    @staticmethod
    def token(prompt_sum, k):
        return (prompt_sum + 7 * k) % 50 + 5

    def validate(self, payload):
        pass

    def bucket_for(self, n):
        return 8 if n <= 8 else 16

    def prefill(self, admissions):
        with self.lock:
            toks = {}
            for a in admissions:
                psum = int(np.sum(a["input_ids"]))
                self._state[a["slot"]] = (psum, 1)
                self.prefills.append(a["slot"])
                toks[a["slot"]] = self.token(psum, 0)
            self.events.append(
                ("prefill", tuple(a["slot"] for a in admissions))
            )
        return ("prefill", [toks[a["slot"]] for a in admissions])

    def decode(self, lengths, active, temps, seeds):
        with self.lock:
            toks = np.zeros(self.slots, np.int64)
            live = []
            for slot, is_active in enumerate(active):
                if not is_active or slot not in self._state:
                    continue
                psum, k = self._state[slot]
                toks[slot] = self.token(psum, k)
                self._state[slot] = (psum, k + 1)
                live.append(slot)
            self.events.append(("decode", tuple(live)))
        return ("decode", toks)

    def fetch_step(self, handle):
        if self.step_delay_s:
            time.sleep(self.step_delay_s)
        kind, toks = handle
        return np.asarray(toks)


def _expected(prompt, n):
    psum = int(np.sum(prompt))
    return [_StubDecodeEngine.token(psum, k) for k in range(n)]


def _drain_state(b, eng, done_requests):
    """The stub never clears its per-slot state (the real engine's pages
    are overwritten by the next occupant) — nothing to assert here beyond
    the batcher-side table emptying."""
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if b.status()["slots_active"] == 0:
            return
        time.sleep(0.005)
    raise AssertionError("slot table did not drain")


def test_slot_free_and_reuse():
    """5 requests through 2 slots: every stream correct, occupancy never
    exceeds the table, and freed slots get reused."""
    eng = _StubDecodeEngine(slots=2, max_batch=2)
    reqs = [
        {"input_ids": np.arange(1, 4 + i), "max_new_tokens": 3 + (i % 3)}
        for i in range(5)
    ]
    with ContinuousBatcher(eng, BatcherConfig(max_batch=2)) as b:
        futs = [b.submit(dict(r)) for r in reqs]
        results = [f.result(timeout=30) for f in futs]
        _drain_state(b, eng, results)
        st = b.status()
        assert st["slots"] == 2 and st["slots_active"] == 0
    for r, req in zip(results, reqs):
        assert r["tokens"] == _expected(
            req["input_ids"], req["max_new_tokens"]
        )
    # Occupancy never exceeded the table...
    assert max(
        (len(s) for kind, s in eng.events if kind == "decode"), default=0
    ) <= 2
    assert len(eng.prefills) == 5
    assert max(eng.prefills) <= 1  # only the 2-slot table
    # ...and at least one slot admitted more than once: free -> reuse.
    assert max(eng.prefills.count(s) for s in set(eng.prefills)) >= 2


def test_eos_frees_slot_early():
    eng = _StubDecodeEngine(slots=1, max_batch=1)
    prompt = np.arange(1, 5)
    toks = _expected(prompt, 8)
    eos = toks[2]  # finish after 3 tokens, far before max_new
    with ContinuousBatcher(eng, BatcherConfig(max_batch=1)) as b:
        r = b.submit({
            "input_ids": prompt, "max_new_tokens": 8, "eos_id": eos,
        }).result(timeout=30)
    assert r["tokens"] == toks[:3]
    assert r["n_tokens"] == 3


def test_continuous_admission_joins_occupied_table():
    """Continuous mode: a request arriving while the table is busy joins
    the in-flight batch (some prefill sees occupied slots)."""
    eng = _StubDecodeEngine(slots=2, max_batch=1, step_delay_s=0.01)
    with ContinuousBatcher(
        eng, BatcherConfig(max_batch=1, max_in_flight=1)
    ) as b:
        f1 = b.submit({"input_ids": np.arange(1, 5), "max_new_tokens": 8})
        deadline = time.monotonic() + 5
        while not eng.prefills and time.monotonic() < deadline:
            time.sleep(0.002)
        f2 = b.submit({"input_ids": np.arange(2, 6), "max_new_tokens": 4})
        r1, r2 = f1.result(timeout=30), f2.result(timeout=30)
    assert r1["tokens"] == _expected(np.arange(1, 5), 8)
    assert r2["tokens"] == _expected(np.arange(2, 6), 4)
    # Mid-flight join: some decode step carried BOTH sequences at once.
    assert any(
        kind == "decode" and len(s) == 2 for kind, s in eng.events
    )


def test_flush_admission_waits_for_empty_table():
    """Flush mode: every admission happens against an EMPTY table — the
    static-batching baseline the serve_bench A/B measures against."""
    eng = _StubDecodeEngine(slots=2, max_batch=2, step_delay_s=0.01)
    with ContinuousBatcher(
        eng, BatcherConfig(max_batch=2, max_in_flight=1),
        admission="flush",
    ) as b:
        assert b.status()["mode"] == "flush"
        f1 = b.submit({"input_ids": np.arange(1, 5), "max_new_tokens": 6})
        deadline = time.monotonic() + 5
        while not eng.prefills and time.monotonic() < deadline:
            time.sleep(0.002)
        f2 = b.submit({"input_ids": np.arange(2, 6), "max_new_tokens": 2})
        r1, r2 = f1.result(timeout=30), f2.result(timeout=30)
    assert r1["tokens"] == _expected(np.arange(1, 5), 6)
    assert r2["tokens"] == _expected(np.arange(2, 6), 2)
    # The joiner was NOT admitted mid-flight: no decode step ever carried
    # both sequences — each ran solo, static-batch style.
    assert len(eng.prefills) == 2
    assert all(
        len(s) <= 1 for kind, s in eng.events if kind == "decode"
    )


def test_close_nodrain_finishes_in_flight_fails_queued():
    eng = _StubDecodeEngine(slots=1, max_batch=1, step_delay_s=0.01)
    b = ContinuousBatcher(eng, BatcherConfig(max_batch=1))
    try:
        live = b.submit({"input_ids": np.arange(1, 5), "max_new_tokens": 10})
        deadline = time.monotonic() + 5
        while b.status()["slots_active"] == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        queued = b.submit({"input_ids": np.arange(2, 6)})
    finally:
        b.close(drain=False)
    # The in-flight sequence ran to completion; the queued one failed.
    assert live.result(timeout=5)["tokens"] == _expected(np.arange(1, 5), 10)
    with pytest.raises(RuntimeError, match="closed"):
        queued.result(timeout=5)
    with pytest.raises(RuntimeError, match="closed"):
        b.submit({"input_ids": np.arange(3)})


def test_decode_dispatch_failure_fails_occupants_only():
    class Exploding(_StubDecodeEngine):
        def __init__(self):
            super().__init__(slots=1, max_batch=1)
            self.fail = False

        def decode(self, *a):
            if self.fail:
                raise RuntimeError("decode exploded")
            return super().decode(*a)

    eng = Exploding()
    m = ServeMetrics()
    with ContinuousBatcher(eng, BatcherConfig(max_batch=1), metrics=m) as b:
        eng.fail = True
        bad = b.submit({"input_ids": np.arange(1, 5), "max_new_tokens": 4})
        with pytest.raises(RuntimeError, match="decode exploded"):
            bad.result(timeout=10)
        eng.fail = False
        ok = b.submit({"input_ids": np.arange(2, 6), "max_new_tokens": 3})
        assert ok.result(timeout=10)["tokens"] == _expected(
            np.arange(2, 6), 3
        )
    assert m.rejected_by_cause.snapshot().get("engine_failure") == 1


# ------------------------------------------------- sanitizer soak


def test_continuous_batching_race_soak():
    """Concurrent submitters over the slot table under the race sanitizer:
    every access to the batcher's declared shared state must be
    happens-before ordered, and the lock graph must stay acyclic. The
    batcher is BUILT inside the context so its threads are tracked."""
    with sanitize_races(modules=[batcher_mod]) as san:
        eng = _StubDecodeEngine(slots=3, max_batch=2)
        b = ContinuousBatcher(
            eng, BatcherConfig(max_batch=2, max_queue=256, max_in_flight=2)
        )
        results = {}
        errs = []

        def worker(base):
            rng = np.random.default_rng(base)
            try:
                futs = []
                for i in range(10):
                    prompt = rng.integers(1, 40, size=int(rng.integers(2, 9)))
                    n = int(rng.integers(1, 7))
                    futs.append((prompt, n, b.submit({
                        "input_ids": prompt, "max_new_tokens": n,
                    })))
                for prompt, n, f in futs:
                    results[(base, tuple(prompt))] = (
                        f.result(timeout=30)["tokens"], _expected(prompt, n)
                    )
            except Exception as e:  # pragma: no cover - surfaced via errs
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(base,))
            for base in (1, 2, 3, 4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        b.close()
        assert not errs
        assert len(results) == 40
        for got, want in results.values():
            assert got == want
        assert san.acquisitions > 0
        assert san.accesses > 0
        san.assert_clean()


# ------------------------------- prefix cache + chunked prefill (tentpole)


@pytest.fixture(scope="module")
def prefix_engine(tiny_lm):
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    return CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8, prefix_cache_mb=0.05, block_tokens=4,
        prefill_chunk=8,
    )


@pytest.fixture(scope="module")
def tp_prefix_engine(tiny_lm):
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.serve import (
        CausalLMEngine,
        plan_serve_mesh,
    )

    model, params = tiny_lm
    spec, fell_back = plan_serve_mesh(tp=2, n_devices=8)
    assert not fell_back
    return CausalLMEngine(
        model, params, build_mesh(spec), buckets=(8, 16), slots=3,
        max_batch=2, max_new_tokens=8, prefix_cache_mb=0.05,
        block_tokens=4, prefill_chunk=8,
    )


def _shared_prefix_reqs(seed, head_len=12, n_tails=3):
    rng = np.random.default_rng(seed)
    head = rng.integers(5, 64, size=head_len)
    return [
        {
            "input_ids": np.concatenate(
                [head, rng.integers(5, 64, size=int(rng.integers(1, 4)))]
            ),
            "max_new_tokens": int(rng.integers(2, 7)),
        }
        for _ in range(n_tails)
    ]


def _run_cached_vs_cold(engine, model, params, seed):
    """Warm one request with a shared head, then replay the whole stream:
    later admissions gather the head's pages from the pool, and EVERY
    stream must still equal the cache-free full-forward reference."""
    reqs = _shared_prefix_reqs(seed)
    refs = [
        _ref_greedy(model, params, r["input_ids"], r["max_new_tokens"])
        for r in reqs
    ]
    m = ServeMetrics()
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2), metrics=m
    ) as b:
        # Sequential warm: the head's pages publish before anyone matches.
        assert b.submit(dict(reqs[0])).result(timeout=120)["tokens"] == refs[0]
        futs = [b.submit(dict(r)) for r in reqs]
        results = [f.result(timeout=120) for f in futs]
        st = b.status()
    for r, ref in zip(results, refs):
        assert r["tokens"] == ref
    return m, st


def test_prefix_cache_greedy_parity_single_chip(prefix_engine, tiny_lm):
    """Acceptance: greedy decode with prefix-cache reuse is bit-identical
    to the cold path, with real hits happening (head = 3 pool blocks)."""
    model, params = tiny_lm
    m, st = _run_cached_vs_cold(prefix_engine, model, params, seed=11)
    assert m.prefix_hits.value >= 3  # every replayed request hit the head
    assert m.prefix_tokens_saved.value >= 3 * 12
    pc = st["prefix_cache"]
    assert pc["hit_rate"] > 0
    assert pc["blocks_used"] > 0
    assert pc["bytes_used"] == pc["blocks_used"] * 2048  # 2*2L*4t*32h*f32


def test_prefix_cache_greedy_parity_tp_mesh(tp_prefix_engine, tiny_lm):
    """Acceptance: same bit-parity when pool pages + slot cache shard
    heads over the model axis (dp4-tp2 on 8 simulated devices)."""
    model, params = tiny_lm
    assert tp_prefix_engine.layout != ""
    m, _ = _run_cached_vs_cold(tp_prefix_engine, model, params, seed=13)
    assert m.prefix_hits.value >= 3


def test_prefix_cache_cow_isolation(prefix_engine, tiny_lm):
    """Copy-on-read isolation: a request that matches a shared head and
    then diverges must not corrupt the published pages — replaying the
    ORIGINAL prompt afterwards still matches its cache-free reference."""
    model, params = tiny_lm
    rng = np.random.default_rng(17)
    head = rng.integers(5, 64, size=12)
    a = {"input_ids": np.concatenate([head, [7, 9]]), "max_new_tokens": 6}
    b_req = {"input_ids": np.concatenate([head, [33]]), "max_new_tokens": 6}
    ref_a = _ref_greedy(model, params, a["input_ids"], 6)
    ref_b = _ref_greedy(model, params, b_req["input_ids"], 6)
    with ContinuousBatcher(prefix_engine, BatcherConfig(max_batch=2)) as bt:
        assert bt.submit(dict(a)).result(timeout=120)["tokens"] == ref_a
        # B hits A's head pages, diverges, generates into ITS OWN pages.
        assert bt.submit(dict(b_req)).result(timeout=120)["tokens"] == ref_b
        # A replay (hits again) proves B's divergence wrote nothing shared.
        assert bt.submit(dict(a)).result(timeout=120)["tokens"] == ref_a


def test_prefix_cache_eviction_under_pressure(tiny_lm):
    """A pool of only 4 blocks under 6 distinct 3-block prompts: chains
    evict LRU-leaf-first, streams stay bit-exact, and correctness never
    depends on whether a given prompt is still cached."""
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=2, max_batch=2,
        max_new_tokens=6, prefix_cache_mb=0.0079, block_tokens=4,
        prefill_chunk=8,
    )
    assert engine.prefix_cache.n_blocks == 4
    rng = np.random.default_rng(23)
    reqs = [
        {
            "input_ids": rng.integers(5, 64, size=13),
            "max_new_tokens": 3,
        }
        for _ in range(6)
    ]
    refs = [_ref_greedy(model, params, r["input_ids"], 3) for r in reqs]
    with ContinuousBatcher(engine, BatcherConfig(max_batch=2)) as b:
        for _ in range(2):  # second pass re-prefills whatever was evicted
            futs = [b.submit(dict(r)) for r in reqs]
            for f, ref in zip(futs, refs):
                assert f.result(timeout=120)["tokens"] == ref
    st = engine.prefix_cache.stats()
    assert st["evictions"] > 0
    assert st["blocks_used"] <= 4


def test_chunked_prefill_parity_without_cache(tiny_lm):
    """--prefill-chunk alone (no prefix pool): prompts prefill in bounded
    absolute-position chunks and every stream still matches the one-shot
    full-forward reference — the bit-exactness the chunk grid promises."""
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8, prefill_chunk=4,
    )
    assert engine.prefix_cache is None
    _run_mixed_batch(engine, model, params)


class _StubChunkedEngine(_StubDecodeEngine):
    """Chunked twin of the scheduling stub: exposes prefill_chunks + a
    real KVBlockPool so the batcher walks the trie/pin/insert path without
    any device work. Handles stay ("chunk"/"decode", toks) 2-tuples so the
    base fetch_step works unchanged."""

    def __init__(self, pool, chunk=4, **kw):
        super().__init__(**kw)
        self.prefill_chunk_size = chunk
        self.prefix_cache = pool
        self.inserted = []

    def prefill_chunks(self, rows):
        with self.lock:
            toks = []
            for r in rows:
                if int(r["start"]) + int(r["n_tokens"]) >= int(r["length"]):
                    psum = int(np.sum(r["input_ids"]))
                    self._state[int(r["slot"])] = (psum, 1)
                    toks.append(self.token(psum, 0))
                else:
                    toks.append(0)  # mid-prompt lane: nobody reads it
            self.events.append(
                ("chunk", tuple(int(r["slot"]) for r in rows))
            )
        return ("chunk", toks)

    def insert_prefix(self, slot, new_blocks):
        self.inserted.append((slot, tuple(new_blocks)))


def test_chunked_prefill_interleaves_with_decode():
    """The ITL contract at the scheduling level: while a long prompt
    chunk-prefills, the in-flight request keeps taking decode steps
    BETWEEN its chunks — admission never stalls the table for the whole
    prompt."""
    from distributed_tensorflow_tpu.serve.kvpool import KVBlockPool

    eng = _StubChunkedEngine(
        KVBlockPool(8, 4), chunk=4, slots=2, max_batch=2,
        step_delay_s=0.005,
    )
    with ContinuousBatcher(
        eng, BatcherConfig(max_batch=2, max_in_flight=1)
    ) as b:
        f1 = b.submit({"input_ids": np.arange(1, 5), "max_new_tokens": 10})
        deadline = time.monotonic() + 5
        while not any(k == "decode" for k, _ in eng.events):
            assert time.monotonic() < deadline
            time.sleep(0.002)
        f2 = b.submit({"input_ids": np.arange(2, 14), "max_new_tokens": 3})
        r1, r2 = f1.result(timeout=30), f2.result(timeout=30)
    assert r1["tokens"] == _expected(np.arange(1, 5), 10)
    assert r2["tokens"] == _expected(np.arange(2, 14), 3)
    # The 12-token prompt took 3 chunks; find the span of the LONG
    # request's chunk events and require a decode step strictly inside it.
    long_chunks = [
        i for i, (k, s) in enumerate(eng.events) if k == "chunk"
    ][-3:]
    assert len(long_chunks) == 3
    assert any(
        eng.events[i][0] == "decode"
        for i in range(long_chunks[0] + 1, long_chunks[-1])
    )


def test_prefix_pool_race_soak():
    """Concurrent shared-prefix submitters through the chunked stub with a
    REAL (tiny, eviction-prone) KVBlockPool under the race sanitizer: the
    batcher's _cv -> pool lock order and the pool's own lock must keep
    every declared attribute happens-before ordered, streams exact, and
    hits nonzero."""
    from distributed_tensorflow_tpu.serve import kvpool as kvpool_mod

    with sanitize_races(modules=[batcher_mod, kvpool_mod]) as san:
        pool = kvpool_mod.KVBlockPool(6, 4)
        eng = _StubChunkedEngine(pool, chunk=4, slots=3, max_batch=2)
        b = ContinuousBatcher(
            eng, BatcherConfig(max_batch=2, max_queue=256, max_in_flight=2)
        )
        heads = [np.arange(10 * h + 1, 10 * h + 9) for h in range(3)]
        results = {}
        errs = []

        def worker(base):
            rng = np.random.default_rng(base)
            try:
                futs = []
                for i in range(10):
                    prompt = np.concatenate([
                        heads[int(rng.integers(0, 3))],
                        rng.integers(1, 40, size=int(rng.integers(1, 5))),
                    ])
                    n = int(rng.integers(1, 7))
                    futs.append((prompt, n, b.submit({
                        "input_ids": prompt, "max_new_tokens": n,
                    })))
                for j, (prompt, n, f) in enumerate(futs):
                    results[(base, j)] = (
                        f.result(timeout=30)["tokens"], _expected(prompt, n)
                    )
            except Exception as e:  # pragma: no cover - surfaced via errs
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(base,))
            for base in (1, 2, 3, 4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        st = b.status()
        b.close()
        assert not errs
        assert len(results) == 40
        for got, want in results.values():
            assert got == want
        assert st["prefix_cache"]["hits"] > 0
        assert san.acquisitions > 0
        san.assert_clean()


# ------------------------------------------------- HTTP front end


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.status, resp.read()


def test_http_generate_and_drain(decode_engine):
    """POST /v1/generate end to end: tokens + batching mode/occupancy in
    the body, slot table in /statusz, per-token families in the prom text,
    and /drainz flipping /healthz before close."""
    client = Client(decode_engine, BatcherConfig(max_batch=2))
    server = build_http_server(client, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    base = "http://%s:%d" % server.server_address
    try:
        code, body = _post(base + "/v1/generate", {
            "input_ids": list(range(5, 12)), "max_new_tokens": 4,
        })
        assert code == 200
        assert len(body["tokens"]) == body["n_tokens"] == 4
        assert body["batching"]["mode"] == "continuous"
        assert body["batching"]["slots"] == decode_engine.slots
        assert set(body["phases"]) == {"queue_wait", "prefill", "decode"}

        code, raw = _get(base + "/statusz")
        st = json.loads(raw)
        assert st["batcher"]["mode"] == "continuous"
        assert st["batcher"]["slots"] == decode_engine.slots

        code, raw = _get(base + "/metrics?format=prom")
        text = raw.decode()
        assert "serve_tokens_total" in text
        assert "serve_decode_steps_total" in text
        assert 'phase="decode_step"' in text

        code, _ = _post(base + "/drainz", {})
        assert code == 200
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _get(base + "/healthz")
        assert exc_info.value.code == 503
    finally:
        server.shutdown()
        server.server_close()
        client.close()
