"""Tracing tests: span nesting/correlation, ring-buffer bounding, Chrome
JSON export, per-request phase breakdown against wall latency, the live
observability endpoints (/statusz /tracez /profilez), and the disabled
tracer's no-op contract."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from typing import NamedTuple

import numpy as np
import pytest

from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.obs.trace import (
    NULL_SPAN,
    NULL_TRACER,
    Tracer,
)
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    Client,
    DynamicBatcher,
    RequestError,
    build_http_server,
)

# ------------------------------------------------------------ tracer core


def test_span_nesting_and_correlation():
    t = Tracer(buffer_size=64)
    with t.span("request", "serve", request_id="r-7", step=3):
        with t.span("inner", "serve"):
            pass
    spans = {s.name: s for s in t.drain()}
    assert set(spans) == {"request", "inner"}
    outer, inner = spans["request"], spans["inner"]
    # The child records its parent and inherits the correlation keys.
    assert inner.parent_id == outer.span_id
    assert inner.request_id == "r-7" and inner.step == 3
    assert outer.parent_id is None
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_span_set_attaches_args():
    t = Tracer(buffer_size=8)
    with t.span("dispatch", "serve") as sp:
        sp.set(tier=4, rows=3)
    (s,) = t.drain()
    assert s.args == {"tier": 4, "rows": 3}


def test_ring_buffer_bounds_memory():
    t = Tracer(buffer_size=16)
    for i in range(100):
        t.record(f"s{i}", 0.0, 1.0)
    assert len(t) == 16
    st = t.status()
    assert st["buffered_spans"] == 16 and st["dropped_spans"] == 84
    spans = t.drain()
    # Oldest-first within the kept window: the last 16 recorded survive.
    assert [s.name for s in spans] == [f"s{i}" for i in range(84, 100)]
    assert len(t) == 0  # drain empties the ring


def test_drain_keeps_newest_n():
    t = Tracer(buffer_size=32)
    for i in range(10):
        t.record(f"s{i}", 0.0, 1.0)
    spans = t.drain(max_spans=3)
    assert [s.name for s in spans] == ["s7", "s8", "s9"]


def test_chrome_export_validates(tmp_path):
    t = Tracer(buffer_size=64)
    with t.span("outer", "serve", request_id="r-1"):
        time.sleep(0.001)
    t.record("device", t0=time.monotonic() - 0.01, t1=time.monotonic(),
             cat="serve", request_id="r-1")
    t.instant("checkpoint", "train", step=5)
    path = t.export(tmp_path / "trace.json")
    doc = json.loads(path.read_text())  # must be valid JSON
    events = doc["traceEvents"]
    assert len(events) == 3
    for ev in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(ev)
    by_name = {ev["name"]: ev for ev in events}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["dur"] >= 1_000  # >= 1 ms in microseconds
    assert by_name["outer"]["args"]["request_id"] == "r-1"
    assert by_name["checkpoint"]["ph"] == "i"
    assert by_name["checkpoint"]["args"]["step"] == 5


def test_summary_aggregates_without_drain():
    t = Tracer(buffer_size=64)
    t.record("device", 0.0, 0.010)
    t.record("device", 0.0, 0.030)
    summ = t.summary()
    assert summ["device"]["count"] == 2
    assert summ["device"]["mean_ms"] == pytest.approx(20.0)
    assert summ["device"]["max_ms"] == pytest.approx(30.0)
    assert len(t) == 2  # summary() does not drain


def test_disabled_tracer_is_noop():
    t = Tracer(buffer_size=0)
    assert not t.enabled
    assert t.span("x") is NULL_SPAN  # shared singleton, no allocation
    with t.span("x") as sp:
        sp.set(a=1)  # must not raise
    t.record("x", 0.0, 1.0)
    t.instant("x")
    assert len(t) == 0 and t.drain() == []
    assert NULL_TRACER.span("y") is NULL_SPAN


def test_disabled_tracer_overhead_smoke():
    """Branch-cheap contract: 50k disabled span entries finish fast."""
    t = Tracer(buffer_size=4096, enabled=False)
    t0 = time.perf_counter()
    for _ in range(50_000):
        with t.span("hot", "serve", step=1):
            pass
    assert time.perf_counter() - t0 < 2.0
    assert len(t) == 0


# ------------------------------------------ spans on the profiler's clock


def _host_events(logdir):
    """``{event name: [stats dict, ...]}`` over the host planes of the
    newest capture under ``logdir``."""
    import glob

    import jax

    files = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))
    assert files, "the profiler wrote no capture"
    out = {}
    for plane in jax.profiler.ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def _capture(logdir, body):
    """Run ``body`` inside a profiler session opened the way the benchmark
    opens it (benchmarks/trace.py::start: Python tracer off)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(logdir)


@pytest.mark.parametrize("enabled", [True, False], ids=["enabled", "disabled"])
def test_scoped_span_is_a_profiler_annotation(tmp_path, enabled):
    tracer = Tracer(buffer_size=64, enabled=enabled)

    def body():
        with tracer.span("pr27.outer", "serve", step=7, rows=3, share=0.5,
                         kind="decode") as sp:
            sp.set(tokens=11)
            time.sleep(0.002)
        tracer.record("pr27.recorded", 0.0, 1.0)
        tracer.instant("pr27.instant")
        with tracer.step("pr27.step", 5):
            pass

    events = _capture(tmp_path, body)
    if not enabled:
        assert not any(name.startswith("pr27.") for name in events)
        assert tracer.step("pr27.step", 5) is NULL_SPAN
        return
    assert [int(s["step_num"]) for s in events["pr27.step"]] == [5]
    (stats,) = events["pr27.outer"]
    # ints and strings ride the annotation (as the profiler's strings);
    # the float stays in the ring only
    assert {k: str(v) for k, v in stats.items()} == {
        "step": "7", "rows": "3", "kind": "decode", "tokens": "11"}
    # stamps taken elsewhere stay ring-only
    assert "pr27.recorded" not in events and "pr27.instant" not in events
    ring = {s.name: s for s in tracer.drain()}
    assert ring["pr27.outer"].args == {
        "rows": 3, "share": 0.5, "kind": "decode", "tokens": 11}
    assert ring["pr27.outer"].duration_s >= 0.002


def test_fit_spans_and_step_annotation_reach_the_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.train import fit

    class State(NamedTuple):
        step: jax.Array
        total: jax.Array

    @jax.jit
    def step(state, batch, rng):
        total = state.total + batch.sum()
        return State(state.step + 1, total), {"loss": total}

    tracer = Tracer(buffer_size=256)

    def body():
        fit(State(jnp.zeros((), jnp.int32), jnp.zeros(())), step,
            iter([jnp.ones(2)] * 6), num_steps=4, log_every=2, tracer=tracer)

    events = _capture(tmp_path, body)
    assert [int(s["step"]) for s in events["dispatch"]] == [0, 1, 2, 3]
    assert [int(s["step_num"]) for s in events["train"]] == [0, 1, 2, 3]
    assert len(events["host_wait"]) == 4 and len(events["device"]) == 2
    # the ring holds the same spans, under the names the metric files read
    assert tracer.summary()["dispatch"]["count"] == 4


def test_obs_trace_needs_no_jax():
    """obs/trace.py binds the annotation class from sys.modules and never
    imports jax itself: loaded by path in a process where importing jax
    fails, an enabled tracer still records its spans in the ring."""
    import subprocess
    import sys

    from distributed_tensorflow_tpu.obs import trace

    code = (
        "import importlib.util, sys\n"
        "sys.modules['jax'] = None  # any 'import jax' now raises\n"
        f"spec = importlib.util.spec_from_file_location('t', {trace.__file__!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "t = mod.Tracer(buffer_size=8)\n"
        "with t.span('x', step=1) as sp:\n"
        "    sp.set(rows=2)\n"
        "assert [s.name for s in t.drain()] == ['x']\n"
        "assert mod._profiler_class('TraceAnnotation') is None\n"
        "assert t.step('train', 3) is mod.NULL_SPAN\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_trace_scopes_script_reads_scopes_and_names_the_gaps(tmp_path):
    """scripts/trace_scopes.py on a hand-built xplane: device time by the
    scope in an op's *metadata* stats (where the TPU profiler keeps
    op_name), and the host spans that lie in the gaps between two
    executions of a module. One child process: tensorflow ships the
    proto and takes seconds to import."""
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).resolve().parents[1] / "scripts" / "trace_scopes.py"
    out = tmp_path / "t.xplane.pb"
    code = f"""
import runpy, sys
try:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
except ImportError:
    print("SKIP"); sys.exit(0)
space = xplane_pb2.XSpace()
dev = space.planes.add(name="/device:TPU:0")
dev.stat_metadata[1].name = "tf_op"
def meta(plane, i, name, op_name=None):
    m = plane.event_metadata[i]; m.id = i; m.name = name
    if op_name:
        st = m.stats.add(); st.metadata_id = 1; st.str_value = op_name
meta(dev, 1, "jit_decode_fn(7)")
meta(dev, 2, "%copy.1 = bf16[1,8]", "jit(decode_fn)/CausalLM.decode_step/kv_write/broadcast_in_dim")
meta(dev, 3, "%fusion.2 = f32[8]", "jit(decode_fn)/layer_0.decode/attention.decode/cached_attention/dot_general")
meta(dev, 4, "%fusion.3 = f32[8]")
mods = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
for start_us in (0, 50):  # two steps of 40 us, a 10 us gap between them
    mods.events.add(metadata_id=1, offset_ps=start_us * 10**6, duration_ps=40 * 10**6)
    for mid, off, dur in ((2, 0, 10), (3, 10, 20), (4, 30, 10)):
        ops.events.add(metadata_id=mid, offset_ps=(start_us + off) * 10**6, duration_ps=dur * 10**6)
host = space.planes.add(name="/host:CPU")
meta(host, 1, "batcher.deliver"); meta(host, 2, "engine.decode_dispatch"); meta(host, 3, "other")
line = host.lines.add(name="python3", timestamp_ns=1000)
line.events.add(metadata_id=1, offset_ps=38 * 10**6, duration_ps=6 * 10**6)   # 4 us in the gap
host.stat_metadata[5].name = "full_blocks_read"; host.stat_metadata[6].name = "full_blocks_total"
for off, dur, read in ((45, 4, 7), (90, 1, 9)):  # the first all in the gap; each carries its counters
    ev = line.events.add(metadata_id=2, offset_ps=off * 10**6, duration_ps=dur * 10**6)
    for key, value in ((5, read), (6, 48)):
        st = ev.stats.add(); st.metadata_id = key; st.int64_value = value
line.events.add(metadata_id=3, offset_ps=41 * 10**6, duration_ps=1 * 10**6)
open({str(out)!r}, "wb").write(space.SerializeToString())
sys.argv = ["trace_scopes.py", {str(out)!r}, "--per", "^jit_decode_fn", "--host", "^(batcher|engine)[.]"]
runpy.run_path({str(script)!r}, run_name="__main__")
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    text = done.stdout
    if text.strip() == "SKIP":
        pytest.skip("no xplane proto in this installation")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = {ln.split()[0] if not ln.startswith("  host") else ln.split()[1]: ln
             for ln in text.splitlines() if ln.startswith("  ")}
    assert "0.010 ms a step" in lines["kv_write"] and "2 ops" in lines["kv_write"]
    assert "0.020 ms a step" in lines["cached_attention"]
    assert "lm_head" not in lines
    assert "in the gaps   0.004 ms a gap" in lines["batcher.deliver"]
    assert "in the gaps   0.004 ms a gap" in lines["engine.decode_dispatch"]
    assert "1 gaps between executions" in text and "mean 0.010 ms" in text
    # a span's integer keys, summed over the capture
    assert "summed keys: full_blocks_read=16, full_blocks_total=96" in text


# ------------------------------------------- request phases through serving


class _SlowStub:
    """Pipelined stub whose fetch sleeps: gives requests a real, known
    latency so the phase breakdown has something to attribute."""

    max_batch = 4

    def validate(self, payload):
        if "v" not in payload:
            raise RequestError("v required")

    def dispatch(self, payloads):
        return list(payloads)

    def fetch(self, handle):
        time.sleep(0.05)
        return [{"v": p["v"]} for p in handle]

    def run_batch(self, payloads):
        return self.fetch(self.dispatch(payloads))


def test_phase_breakdown_sums_to_wall_latency():
    tracer = Tracer(buffer_size=1024)
    with Client(
        _SlowStub(),
        BatcherConfig(max_batch=4, max_delay_ms=2.0, max_in_flight=2),
        tracer=tracer,
    ) as client:
        t0 = time.monotonic()
        fut = client.submit({"v": 1})
        assert fut.result(timeout=10) == {"v": 1}
        wall = time.monotonic() - t0
        phases = fut.phases
    assert set(phases) == {
        "queue_wait", "batch_assemble", "dispatch", "device", "fetch"
    }
    assert all(v >= 0.0 for v in phases.values())
    # The phases partition enqueue->delivery: they sum to the latency the
    # future reports, hold the stub's 50 ms fetch, and fit inside the wall
    # measured around submit and result (how much the wall exceeds them is
    # the host's scheduling, which a loaded machine stretches at will).
    assert sum(phases.values()) == pytest.approx(fut.latency_s, abs=1e-6)
    assert 0.05 <= sum(phases.values()) <= wall
    assert fut.request_id.startswith("r-")
    # The tracer saw the same request decomposed into phase spans.
    names = {s.name for s in tracer.drain() if s.request_id == fut.request_id}
    assert {"request", "queue_wait"} <= names


def test_serial_path_phases_and_metrics():
    m = ServeMetrics()
    with DynamicBatcher(
        lambda ps: [{"v": p} for p in ps],
        BatcherConfig(max_batch=2, max_delay_ms=2.0),
        m,
    ) as b:
        fut = b.submit(1, request_id="my-id")
        fut.result(timeout=5)
    assert fut.request_id == "my-id"
    assert set(fut.phases) == {"queue_wait", "run"}
    snap = m.snapshot()
    assert snap["phase_ms"]["queue_wait"]["count"] == 1
    assert snap["phase_ms"]["run"]["count"] == 1


def test_engine_failure_counts_cause_and_keeps_request_id():
    def boom(payloads):
        raise ValueError("device on fire")

    m = ServeMetrics()
    with DynamicBatcher(
        boom, BatcherConfig(max_batch=2, max_delay_ms=2.0), m
    ) as b:
        futs = [b.submit(i) for i in range(2)]
        for f in futs:
            with pytest.raises(ValueError, match="device on fire"):
                f.result(timeout=5)
    snap = m.snapshot()
    assert snap["rejected_by_cause"] == {"engine_failure": 2}


def test_backpressure_counts_cause():
    release = threading.Event()

    def blocked(payloads):
        release.wait(timeout=10)
        return [{"v": p} for p in payloads]

    m = ServeMetrics()
    b = DynamicBatcher(
        blocked, BatcherConfig(max_batch=1, max_delay_ms=0.0, max_queue=1), m
    )
    try:
        inflight = b.submit(1)  # flusher takes it
        time.sleep(0.05)
        queued = b.submit(2)  # fills max_queue=1
        with pytest.raises(Exception) as ei:
            b.submit(3)
        assert getattr(ei.value, "request_id", None)  # shed load is tagged
        release.set()
        inflight.result(timeout=5)
        queued.result(timeout=5)
    finally:
        release.set()
        b.close()
    assert m.snapshot()["rejected_by_cause"] == {"backpressure": 1}


# --------------------------------------------------------- live endpoints


class _HttpStub:
    max_batch = 4

    def validate(self, payload):
        if "input_ids" not in payload:
            raise RequestError("input_ids required")

    def run_batch(self, payloads):
        return [
            {"pred_ids": np.asarray(p["input_ids"], np.int32), "score": 0.0}
            for p in payloads
        ]


@pytest.fixture()
def traced_server(tmp_path):
    client = Client(
        _HttpStub(),
        BatcherConfig(max_batch=4, max_delay_ms=2.0),
        tracer=Tracer(buffer_size=1024),
    )
    server = build_http_server(client, port=0, trace_dir=str(tmp_path))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address
    yield f"http://{host}:{port}", client
    server.shutdown()
    server.server_close()
    client.close()
    thread.join(timeout=5)


def _post(url, body: dict, headers: dict | None = None):
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read())


def test_http_response_carries_request_id_and_phases(traced_server):
    base, _ = traced_server
    status, body = _post(
        base + "/v1/mlm", {"input_ids": [1, 2]},
        headers={"X-Request-Id": "abc-123"},
    )
    assert status == 200
    assert body["request_id"] == "abc-123"
    assert body["pred_ids"] == [1, 2]
    assert body["phases"]["queue_wait"] >= 0.0  # milliseconds
    assert sum(body["phases"].values()) > 0.0


def test_http_error_bodies_carry_request_id(traced_server):
    base, client = traced_server
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(base + "/v1/mlm", {"wrong": 1},
              headers={"X-Request-Id": "bad-1"})
    assert ei.value.code == 400
    assert json.loads(ei.value.read())["request_id"] == "bad-1"
    snap = client.metrics.snapshot()
    assert snap["rejected_by_cause"].get("validation") == 1


def test_statusz_roundtrip(traced_server):
    base, _ = traced_server
    _post(base + "/v1/mlm", {"input_ids": [1]})
    status, body = _get(base + "/statusz")
    assert status == 200
    assert body["requests"] == 1
    assert body["tracer"]["enabled"] is True
    assert body["tracer"]["buffered_spans"] > 0
    assert "queue_wait" in body["phase_ms"]
    assert "request" in body["recent_spans"]


def test_tracez_roundtrip(traced_server):
    base, _ = traced_server
    _post(base + "/v1/mlm", {"input_ids": [1]})
    status, doc = _get(base + "/tracez?spans=50")
    assert status == 200
    events = doc["traceEvents"]
    assert events
    for ev in events:
        assert {"ph", "ts", "pid"} <= set(ev)
    assert {"request", "queue_wait"} <= {ev["name"] for ev in events}
    # tracez drains: a second pull starts empty.
    _, doc2 = _get(base + "/tracez")
    assert doc2["traceEvents"] == []


def test_profilez_roundtrip(traced_server, tmp_path):
    base, _ = traced_server
    status, body = _post(base + "/profilez?ms=30", {})
    assert status == 200
    assert body["wall_ms"] >= 30.0
    assert list(tmp_path.rglob("*"))  # profiler dropped a capture


def test_profilez_503_without_trace_dir():
    client = Client(_HttpStub(), BatcherConfig(max_batch=4, max_delay_ms=2.0))
    server = build_http_server(client, port=0)  # no trace_dir
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = "http://{}:{}".format(*server.server_address)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base + "/profilez", {})
        assert ei.value.code == 503
    finally:
        server.shutdown()
        server.server_close()
        client.close()
        thread.join(timeout=5)
