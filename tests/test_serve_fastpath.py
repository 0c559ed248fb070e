"""Serving fast-path tests: tiered AOT grid, bucket-aware queues, and
overlapped (max_in_flight) dispatch.

The batcher-level tests run against pure-python stub engines — they pin the
NEW queueing semantics (per-bucket flush grouping, pipelined dispatch/fetch
ordering and bounding, short-result failure, visible close timeout). The
engine-level tests pin the tier grid: a lone request runs the 1-row
executable and answers NUMERICALLY the same as the full-tier path, for both
the BERT and the image engine.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.obs.sanitizer import sanitize_locks, sanitize_races
from distributed_tensorflow_tpu.serve import batcher as batcher_mod
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    Client,
    DynamicBatcher,
)

# ------------------------------------------------------------- stub engines


def _echo(payloads):
    return [{"v": p} for p in payloads]


class _PipelinedStub:
    """Stub with the split hot path: dispatch is instant, fetch blocks on
    an optional gate, and both record enough to assert overlap."""

    max_batch = 4

    def __init__(self, fetch_gate: threading.Event | None = None):
        self.fetch_gate = fetch_gate
        self.lock = threading.Lock()
        self.dispatched = 0
        self.max_overlap = 0
        self._open = 0

    def validate(self, payload):
        pass

    def dispatch(self, payloads):
        with self.lock:
            self.dispatched += 1
            self._open += 1
            self.max_overlap = max(self.max_overlap, self._open)
        return list(payloads)  # the "device refs"

    def fetch(self, handle):
        if self.fetch_gate is not None:
            assert self.fetch_gate.wait(timeout=10)
        with self.lock:
            self._open -= 1
        return _echo(handle)

    def run_batch(self, payloads):
        return self.fetch(self.dispatch(payloads))


# ------------------------------------------------- satellite: short results


def test_short_result_fails_futures_explicitly():
    """An engine answering fewer results than requests must FAIL the excess
    futures loudly, not leave them pending forever (the zip-drop bug)."""
    def short(payloads):
        return _echo(payloads[:-1])  # one result missing

    m = ServeMetrics()
    with DynamicBatcher(
        short, BatcherConfig(max_batch=2, max_delay_ms=5.0), m
    ) as b:
        futs = [b.submit(i) for i in range(2)]
        for f in futs:
            with pytest.raises(RuntimeError, match="1 results for a batch of 2"):
                f.result(timeout=5)
    assert m.errors.value == 1


def test_short_result_fails_futures_pipelined():
    class Short(_PipelinedStub):
        def fetch(self, handle):
            return super().fetch(handle)[:-1]

    eng = Short()
    with DynamicBatcher(
        eng.run_batch,
        BatcherConfig(max_batch=2, max_delay_ms=5.0),
        dispatch=eng.dispatch,
        fetch=eng.fetch,
    ) as b:
        futs = [b.submit(i) for i in range(2)]
        for f in futs:
            with pytest.raises(RuntimeError, match="results for a batch"):
                f.result(timeout=5)


# ------------------------------------------------- bucket-aware batching


def test_bucket_queues_group_flushes_by_bucket():
    """Mixed-bucket submissions flush as single-bucket batches."""
    batches = []

    def run(payloads):
        batches.append(list(payloads))
        return _echo(payloads)

    cfg = BatcherConfig(max_batch=2, max_delay_ms=10_000.0, bucket_queues=True)
    with DynamicBatcher(
        run, cfg, bucket_for=lambda p: p["bucket"]
    ) as b:
        futs = [
            b.submit({"bucket": k, "i": i})
            for i, k in enumerate(["a", "b", "a", "b"])
        ]
        results = [f.result(timeout=5) for f in futs]
    assert [r["v"]["i"] for r in results] == [0, 1, 2, 3]
    assert len(batches) == 2
    for batch in batches:
        assert len({p["bucket"] for p in batch}) == 1  # never mixed


def test_bucket_queue_deadline_is_global():
    """A lone request in a cold bucket still flushes within max_delay —
    bucket queues must not starve partial buckets."""
    cfg = BatcherConfig(max_batch=8, max_delay_ms=30.0, bucket_queues=True)
    with DynamicBatcher(
        _echo, cfg, bucket_for=lambda p: p % 3
    ) as b:
        t0 = time.monotonic()
        futs = [b.submit(i) for i in range(3)]  # three different buckets
        results = [f.result(timeout=5) for f in futs]
        elapsed = time.monotonic() - t0
    assert [r["v"] for r in results] == [0, 1, 2]
    assert elapsed < 3.0  # deadline-flushed, not stuck waiting for size


def test_bucket_queue_backpressure_counts_all_buckets():
    from distributed_tensorflow_tpu.serve import Backpressure

    release = threading.Event()

    def slow(payloads):
        release.wait(timeout=10)
        return _echo(payloads)

    cfg = BatcherConfig(
        max_batch=1, max_delay_ms=0.0, max_queue=2, bucket_queues=True
    )
    b = DynamicBatcher(slow, cfg, bucket_for=lambda p: p % 2)
    try:
        first = b.submit(0)
        time.sleep(0.05)  # flusher takes it off the queue
        queued = [b.submit(i) for i in (1, 2)]  # two DIFFERENT buckets
        with pytest.raises(Backpressure):
            b.submit(3)  # global bound, though bucket 1 has one entry
        release.set()
        assert first.result(timeout=5) == {"v": 0}
        assert [f.result(timeout=5)["v"] for f in queued] == [1, 2]
    finally:
        release.set()
        b.close()


# ------------------------------------------------- overlapped dispatch


def test_max_in_flight_overlaps_dispatch():
    """With max_in_flight=2 the flusher dispatches batch k+1 while batch k
    is still unfetched; with 1 it never does. The whole exercise runs under
    the race sanitizer: every batcher/metrics lock is tracked, the
    acquisition graph must stay acyclic, AND every access to the batcher's
    declared shared state (_RACETRACE_ATTRS) must be happens-before
    ordered."""
    with sanitize_races(modules=[batcher_mod]) as san:
        for depth, want_overlap in ((2, 2), (1, 1)):
            gate = threading.Event()
            eng = _PipelinedStub(fetch_gate=gate)
            m = ServeMetrics()
            cfg = BatcherConfig(
                max_batch=1, max_delay_ms=0.0, max_in_flight=depth
            )
            b = DynamicBatcher(
                eng.run_batch, cfg, m, dispatch=eng.dispatch, fetch=eng.fetch
            )
            try:
                futs = [b.submit(i) for i in range(4)]
                deadline = time.monotonic() + 5
                while eng.dispatched < want_overlap and time.monotonic() < deadline:
                    time.sleep(0.005)
                # The gate is still closed: nothing fetched yet, so dispatched
                # == in-flight. Depth 2 pipelines; depth 1 stays serial.
                assert eng.dispatched == want_overlap
                gate.set()
                assert [f.result(timeout=5)["v"] for f in futs] == [0, 1, 2, 3]
                assert eng.max_overlap == want_overlap
            finally:
                gate.set()
                b.close()
        assert san.acquisitions > 0
        assert san.accesses > 0
        san.assert_clean()


def test_pipelined_results_ordered_under_concurrent_submits():
    with sanitize_races(modules=[batcher_mod]) as san:
        eng = _PipelinedStub()
        cfg = BatcherConfig(
            max_batch=3, max_delay_ms=1.0, max_in_flight=2, max_queue=256
        )
        b = DynamicBatcher(
            eng.run_batch, cfg, dispatch=eng.dispatch, fetch=eng.fetch
        )
        results = {}
        errs = []

        def worker(base):
            try:
                futs = [(base + i, b.submit(base + i)) for i in range(20)]
                for v, f in futs:
                    results[v] = f.result(timeout=10)["v"]
            except Exception as e:  # pragma: no cover - surfaced via errs
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(base,))
            for base in (0, 100, 200, 300)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        b.close()
        assert not errs
        # Every request got ITS OWN result back, across interleaved batches.
        assert results == {v: v for v in results}
        assert len(results) == 80
        # 4 submitters x 20 requests through flusher + completion threads:
        # the recorded acquisition order over the batcher's cv / queue /
        # semaphore / metrics locks must be cycle-free, and every watched
        # shared-state access must be ordered by a happens-before edge.
        assert san.acquisitions > 0
        assert san.accesses > 0
        san.assert_clean()


# Watched accesses and lock acquisitions the sanitizer records per request of
# the workload below. Measured (PR 32): 9.1-9.5 / 25.3-25.8 when the 120
# requests are submitted back to back and ride batches of four; 30.5-31.0 /
# 48.0-48.1 when the submitter is slowed until every request is its own batch
# (3-20 ms between submits), where both level off — time spent idle adds
# nothing. A loaded host can only move a run from the first pair towards the
# second, so the budget is the plateau plus a third.
_RACETRACE_ACCESSES_PER_REQUEST = 40
_RACETRACE_ACQUISITIONS_PER_REQUEST = 64


def test_racetrace_overhead_within_ten_percent():
    """What the race sanitizer costs the pipelined hot path, counted in the
    work it does per request: watched accesses and tracked acquisitions
    within a pinned budget, and a clean report. The workload is sleep-paced
    (as real serving is device-paced).

    The name is from when this compared two wall clocks (traced <= 1.10 x
    plain + 20 ms, best of five each): a ratio that the five other xdist
    workers of the tier-1 run decided, red four runs in five. A change that
    makes the sanitizer watch more of the hot path — what the bound was
    for — moves these counts whatever the host is doing."""

    class _SleepyStub(_PipelinedStub):
        def fetch(self, handle):
            time.sleep(0.002)  # stands in for device time
            return super().fetch(handle)

    n = 120
    with sanitize_races(modules=[batcher_mod]) as san:
        eng = _SleepyStub()
        cfg = BatcherConfig(
            max_batch=4, max_delay_ms=0.5, max_in_flight=2, max_queue=256
        )
        b = DynamicBatcher(
            eng.run_batch, cfg, dispatch=eng.dispatch, fetch=eng.fetch
        )
        futs = [b.submit(i) for i in range(n)]
        assert [f.result(timeout=30)["v"] for f in futs] == list(range(n))
        b.close()
        san.assert_clean()
    assert 0 < san.accesses <= n * _RACETRACE_ACCESSES_PER_REQUEST, (
        f"{san.accesses / n:.1f} watched accesses a request"
    )
    assert 0 < san.acquisitions <= n * _RACETRACE_ACQUISITIONS_PER_REQUEST, (
        f"{san.acquisitions / n:.1f} tracked acquisitions a request"
    )


def test_pipelined_dispatch_failure_is_isolated():
    class Exploding(_PipelinedStub):
        def __init__(self):
            super().__init__()
            self.fail = True

        def dispatch(self, payloads):
            if self.fail:
                raise RuntimeError("dispatch exploded")
            return super().dispatch(payloads)

    eng = Exploding()
    m = ServeMetrics()
    cfg = BatcherConfig(max_batch=2, max_delay_ms=2.0, max_in_flight=2)
    with DynamicBatcher(
        eng.run_batch, cfg, m, dispatch=eng.dispatch, fetch=eng.fetch
    ) as b:
        bad = [b.submit(i) for i in range(2)]
        for f in bad:
            with pytest.raises(RuntimeError, match="dispatch exploded"):
                f.result(timeout=5)
        eng.fail = False
        ok = [b.submit(i) for i in range(2)]
        assert [f.result(timeout=5)["v"] for f in ok] == [0, 1]
    assert m.errors.value == 1


# ------------------------------------------------- satellite: close timeout


def test_close_raises_when_flusher_is_wedged():
    """A wedged engine must make close() fail loudly, not silently leak the
    flusher thread."""
    release = threading.Event()

    def wedged(payloads):
        release.wait(timeout=30)
        return _echo(payloads)

    b = DynamicBatcher(wedged, BatcherConfig(max_batch=1, max_delay_ms=0.0))
    try:
        f = b.submit("stuck")
        time.sleep(0.05)  # flusher picks it up and wedges
        with pytest.raises(RuntimeError, match="close timeout"):
            b.close(join_timeout_s=0.2)
    finally:
        release.set()  # unwedge so the daemon thread exits
        f.result(timeout=5)


def test_batcher_config_validates_new_knobs():
    with pytest.raises(ValueError, match="max_in_flight"):
        BatcherConfig(max_in_flight=0)


# ------------------------------------------------- engine tier grid (JAX)


@pytest.fixture(scope="module")
def tiered_bert_engine(devices8):
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.bert import (
        BertConfig,
        BertForPreTraining,
    )
    from distributed_tensorflow_tpu.serve import BertInferenceEngine

    cfg = BertConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        intermediate_size=64,
        max_position=32,
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
    return BertInferenceEngine(
        model, variables["params"], buckets=(16, 32), max_batch=4
    )


def test_tier_ladder_normalization(tiered_bert_engine):
    eng = tiered_bert_engine
    assert eng.batch_tiers == (1, 2, 4)  # default 1/2/4/8 clamped to 4
    assert eng.tier_for(1) == 1
    assert eng.tier_for(2) == 2
    assert eng.tier_for(3) == 4
    with pytest.raises(ValueError, match="exceeds max_batch"):
        eng.tier_for(5)
    # One executable per (tier, bucket) cell.
    assert set(eng._compiled) == {
        (t, b) for t in (1, 2, 4) for b in (16, 32)
    }


def test_lone_request_runs_small_tier_and_matches_full(tiered_bert_engine):
    """The acceptance numeric check: the same request served through the
    1-row executable answers the same as through the full 4-row one."""
    eng = tiered_bert_engine
    eng.metrics = ServeMetrics()
    rng = np.random.default_rng(0)
    ids = rng.integers(5, 64, size=12)
    req = {"input_ids": ids, "mlm_targets": ids}

    solo = eng.run_batch([req])[0]
    assert eng.metrics.tier_hits.snapshot() == {"1": 1}
    assert eng.metrics.padded_rows.value == 0

    pad = [{"input_ids": rng.integers(5, 64, size=9)} for _ in range(3)]
    full = eng.run_batch([req] + pad)[0]
    assert eng.metrics.tier_hits.snapshot() == {"1": 1, "4": 1}

    np.testing.assert_array_equal(solo["pred_ids"], full["pred_ids"])
    np.testing.assert_allclose(solo["score"], full["score"], rtol=1e-4)
    np.testing.assert_allclose(
        solo["embedding"], full["embedding"], rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        solo["nsp_probs"], full["nsp_probs"], rtol=1e-3, atol=1e-4
    )
    eng.metrics = None  # module-scoped fixture: leave no instruments behind


def test_every_tier_matches_reference(tiered_bert_engine):
    """The same request through EVERY tier answers within float tolerance
    of the largest-tier reference (different XLA fusions may round
    differently; the answers must agree)."""
    eng = tiered_bert_engine
    rng = np.random.default_rng(1)
    ids = rng.integers(5, 64, size=20)  # bucket 32
    req = {"input_ids": ids, "mlm_targets": ids}
    mate = {"input_ids": rng.integers(5, 64, size=18)}

    ref = eng.run_batch([req, mate, mate, mate])[0]  # tier 4
    for occupancy in (1, 2):  # tiers 1 and 2
        got = eng.run_batch([req, mate][:occupancy])[0]
        np.testing.assert_array_equal(got["pred_ids"], ref["pred_ids"])
        np.testing.assert_allclose(got["score"], ref["score"], rtol=1e-4)
        np.testing.assert_allclose(
            got["embedding"], ref["embedding"], rtol=1e-3, atol=1e-4
        )
        assert got["bucket"] == ref["bucket"] == 32


def test_engine_dispatch_fetch_pipeline(tiered_bert_engine):
    """Two batches can be in flight at once and fetch in dispatch order;
    staging buffers recycle through the pool."""
    eng = tiered_bert_engine
    rng = np.random.default_rng(2)
    a = {"input_ids": rng.integers(5, 64, size=8)}
    b = {"input_ids": rng.integers(5, 64, size=8)}

    ref_a = eng.run_batch([a])[0]
    ref_b = eng.run_batch([b])[0]
    ha = eng.dispatch([a])
    hb = eng.dispatch([b])
    got_a = eng.fetch(ha)[0]
    got_b = eng.fetch(hb)[0]
    np.testing.assert_array_equal(got_a["pred_ids"], ref_a["pred_ids"])
    np.testing.assert_array_equal(got_b["pred_ids"], ref_b["pred_ids"])
    # Both in-flight sets came back to the pool for the (1, 16) cell.
    assert len(eng._buf_pool[(1, 16)]) >= 2
    # ...and a fresh dispatch reuses one instead of allocating.
    before = len(eng._buf_pool[(1, 16)])
    eng.fetch(eng.dispatch([a]))
    assert len(eng._buf_pool[(1, 16)]) == before


def test_client_bucket_queues_end_to_end(tiered_bert_engine):
    eng = tiered_bert_engine
    m = ServeMetrics()
    rng = np.random.default_rng(3)
    reqs = [
        {"input_ids": rng.integers(5, 64, size=int(l))}
        for l in rng.integers(4, 30, size=12)
    ]
    refs = [eng.run_batch([r])[0] for r in reqs]
    with Client(
        eng,
        BatcherConfig(
            max_batch=4, max_delay_ms=2.0, bucket_queues=True, max_in_flight=2
        ),
        metrics=m,
    ) as client:
        futs = [client.submit(r) for r in reqs]
        results = [f.result(timeout=60) for f in futs]
    for r, ref, req in zip(results, refs, reqs):
        # Bucket queues: every request is served at ITS OWN bucket, never
        # a long batchmate's.
        assert r["bucket"] == eng.bucket_for(len(req["input_ids"]))
        np.testing.assert_array_equal(r["pred_ids"], ref["pred_ids"])
    snap = m.snapshot()
    assert snap["requests"] == 12 and snap["errors"] == 0
    assert snap["tier_hits"]  # engine instruments were wired by the Client
    eng.metrics = None


@pytest.fixture(scope="module")
def tiny_image_engine(devices8):
    import jax
    import flax.linen as nn

    from distributed_tensorflow_tpu.serve import ImageClassifierEngine

    class TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(10)(x.reshape((x.shape[0], -1)))

    model = TinyNet()
    shape = (8, 8, 1)
    params = model.init(jax.random.key(0), np.zeros((1, *shape), np.float32))[
        "params"
    ]
    return ImageClassifierEngine(
        model, params, image_shape=shape, max_batch=4, top_k=3
    )


def test_image_engine_tier_grid_matches(tiny_image_engine):
    eng = tiny_image_engine
    assert eng.batch_tiers == (1, 2, 4)
    rng = np.random.default_rng(4)
    img = {"image": rng.standard_normal((8, 8, 1)).astype(np.float32)}
    other = {"image": rng.standard_normal((8, 8, 1)).astype(np.float32)}
    solo = eng.run_batch([img])[0]                      # tier 1
    full = eng.run_batch([img, other, other, other])[0]  # tier 4
    np.testing.assert_array_equal(solo["top_ids"], full["top_ids"])
    np.testing.assert_allclose(
        solo["top_probs"], full["top_probs"], rtol=1e-5, atol=1e-6
    )


# ------------------------------------------------- serve_bench smoke/sweep


def _import_serve_bench():
    scripts = str(Path(__file__).resolve().parents[1] / "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import serve_bench

    return serve_bench


def test_serve_bench_quick_smoke(tmp_path, devices8):
    """The --quick CI mode runs end to end and reports the new columns.

    Its exit code also folds in two gates on clock readings — the span sum
    against wall latency, the recorder-on/off throughput ratio — which a
    host shared by six xdist workers cannot hold; ``make obs-quick`` runs
    them alone. Here every gate that is not a timing must hold, and a
    nonzero exit must be one of those two."""
    serve_bench = _import_serve_bench()
    out = tmp_path / "bench.json"
    rc = serve_bench.main(
        ["--quick", "--single-duration", "0.2", "--json", str(out)]
    )
    report = json.loads(out.read_text())
    assert report["single_stream"]["served"] >= 1
    (point,) = report["loads"]
    assert point["served"] > 0
    assert "padded_rows" in point and "tier_hits" in point
    assert report["max_slo_attainment_gap"] <= 0.02
    rec = report["flight_recorder"]
    assert rec["dump_sections_ok"] and rec["events_recorded"]
    if rc != 0:
        assert (
            report["max_phase_divergence"] > 0.25
            or rec["overhead_frac"] > 0.02
        ), "--quick failed on a gate that is not a timing"


@pytest.mark.slow
def test_serve_bench_sweep(tmp_path, devices8):
    """Multi-second sweep: tiered grid must waste no more padded rows than
    offered rows, and the single-stream pass must beat zero."""
    serve_bench = _import_serve_bench()
    out = tmp_path / "sweep.json"
    rc = serve_bench.main(
        [
            "--loads", "25", "100",
            "--duration", "1.0",
            "--single-duration", "1.0",
            "--buckets", "16", "32",
            "--layers", "1", "--hidden", "32", "--vocab", "128",
            "--bucket-queues",
            "--json", str(out),
        ]
    )
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["single_stream"]["rps"] > 0
    for point in report["loads"]:
        assert point["served"] > 0
        # Tiered dispatch: wasted rows bounded by what a fixed-batch path
        # would have wasted.
        assert point["padded_rows"] < point["served"] * 8
