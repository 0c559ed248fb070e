"""The metric files of PR 27 on hand-built traces whose event texts are
copied from chiprun_out/pr27/desc_<cell>.txt (a v5e capture): each selects
only its own events, and the three flash parts add up to what
kernel.flash_ms selects. No device, no jax."""

import json
from pathlib import Path

import pytest

from benchmarks import layers
from benchmarks.trace import DeviceTrace, Event

ROOT = Path(__file__).resolve().parents[1]
STATS = " device_offset_ps=173825012500 device_duration_ps=1156872500 Time Scale Multiplier=1.0"
QKV = "bf16[64,512,768]{2,1,0:T(8,128)(2,1)}"
# The three kernels as the profiler names them: the whole HLO instruction
# ("#" stands for the instruction's number).
FWD = ("%attention.# = (bf16[64,512,768]{2,1,0:T(8,128)(2,1)S(1)}, f32[64,12,512]{2,1,0:T(8,128)}) "
       f"custom-call({QKV} %copy.484, {QKV} %copy.485, {QKV} %copy.486, pred[64,1,512]{{2,1,0}} %bitcast.7), "
       'custom_call_target="tpu_custom_call"')
DQ = (f"%attention.# = {QKV} custom-call({QKV} %copy.476, {QKV} %copy.477, {QKV} %copy.478), "
      'custom_call_target="tpu_custom_call"')
DKV = (f"%attention.# = ({QKV}, {QKV}) custom-call({QKV} %copy.476, {QKV} %copy.477), "
       'custom_call_target="tpu_custom_call"')
HEAD = ("%fusion.3455 = (bf16[64,512]{1,0:T(8,128)(2,1)S(1)}, bf16[64,512,30522]{1,2,0:T(8,128)(2,1)}) "
        "fusion(bf16[30522]{0:T(1024)(128)(2,1)S(1)} %copy-done.530), kind=kOutput")
TABLE_OUT = ("%copy.# = bf16[1,128,384,12,64]{4,3,2,1,0:T(8,128)(2,1)} "
             "copy(bf16[1,128,384,12,64]{2,4,3,1,0:T(8,128)(2,1)} %get-tuple-element.93)")
TABLE_IN = ("%copy.# = bf16[1,128,384,12,64]{2,4,3,1,0:T(8,128)(2,1)} "
            "copy(bf16[1,128,384,12,64]{4,3,2,1,0:T(8,128)(2,1)} %bitcast.4)")
SLICE = ("%fusion.893 = (bf16[1,128,384,12,64]{2,4,3,1,0:T(8,128)(2,1)}, bf16[1,128,384,12,64]{2,4,3,1,0:T(8,128)(2,1)}) "
         "fusion(bf16[12,128,384,12,64]{2,4,3,1,0:T(8,128)(2,1)} %param.1), kind=kLoop")
SMALL_COPY = "%copy.12 = s32[128]{0:T(128)} copy(s32[128]{0:T(128)} %param.5)"


def _spec(name):
    return json.loads((ROOT / "layer_metrics" / f"{name}.json").read_text())


def _op(text, start, ms):
    name = text.split(" ", 1)[0].lstrip("%")
    return Event(0, name, start, start + ms * 1e-3, text + STATS)


def _evaluate(names, trace, spans=None):
    got = layers.evaluate(
        {n: _spec(n) for n in names}, spans=spans or {}, trace=trace,
        config={}, job={}, peaks=None,
    )
    return {k: v["value"] for k, v in got.items()}


def _train_trace():
    """Two steps, two layers each: forward 1.059 ms, dQ 0.806, dK/dV 1.157
    a layer (desc_bert_base.pretrain_L512.txt), the head's logits fusion and
    a copy that no flash metric may take."""
    ops, mods, n = [], [], 36
    for step in (0.0, 0.3):
        mods.append(Event(0, "jit_per_device_step(5000782745026625272)", step, step + 0.2107, ""))
        t = step
        for text, ms in ((FWD, 1.059), (FWD, 1.059), (DKV, 1.157), (DQ, 0.806),
                         (DKV, 1.157), (DQ, 0.806)):
            n += 1
            ops.append(_op(text.replace("#", str(n)), t, ms))
            t += 0.002
        ops.append(_op(HEAD, t, 8.35))
        ops.append(_op(TABLE_OUT.replace("#", "476").replace("1,128,384,12,64", "64,512,768"), t + 0.01, 0.2))
    return DeviceTrace(ops, mods)


def test_the_three_flash_parts_partition_what_flash_ms_selects():
    parts = ["kernel.flash_fwd_ms", "kernel.flash_dq_ms", "kernel.flash_dkv_ms"]
    got = _evaluate(parts + ["kernel.flash_ms"], _train_trace())
    assert got["kernel.flash_fwd_ms"] == pytest.approx(2 * 1.059)
    assert got["kernel.flash_dq_ms"] == pytest.approx(2 * 0.806)
    assert got["kernel.flash_dkv_ms"] == pytest.approx(2 * 1.157)
    assert sum(got[p] for p in parts) == pytest.approx(got["kernel.flash_ms"])


@pytest.mark.parametrize("metric,own", [
    ("kernel.flash_fwd_ms", FWD), ("kernel.flash_dq_ms", DQ), ("kernel.flash_dkv_ms", DKV),
])
def test_a_flash_metric_selects_its_kernel_only(metric, own):
    trace = _train_trace()
    spec = _spec(metric)
    picked = trace.select("ops", spec["select"], chip=0, field="meta")
    assert len(picked) == 4
    assert {e.meta for e in picked} <= {own.replace("#", str(n)) + STATS for n in range(36, 60)}


def _serve_trace():
    """Two decode steps with four one-layer table copies each (0.446 ms out
    of the scatter, 0.41 into it), three prefills between them."""
    ops, mods, n = [], [], 238
    for step in (0.0, 0.05):
        mods.append(Event(0, "jit_decode_fn(13836616247132452225)", step, step + 0.0395, ""))
        ops.append(_op(SLICE, step, 2.74))
        for k, (text, ms) in enumerate(((TABLE_IN, 0.41), (TABLE_OUT, 0.446)) * 2):
            n += 1
            ops.append(_op(text.replace("#", str(n)), step + 0.003 + k * 0.001, ms))
        ops.append(_op(SMALL_COPY, step + 0.02, 0.001))
    for k, ms in enumerate((0.5, 0.7, 2.0)):
        start = 0.04 + k * 0.003
        mods.append(Event(0, f"jit_prefill_fn({k})", start, start + ms * 1e-3, ""))
        ops.append(_op(SMALL_COPY, start, 0.001))
    return DeviceTrace(ops, mods)


def test_serving_metrics_select_table_copies_and_prefill_modules():
    got = _evaluate(["engine.decode_kv_write_ms", "engine.prefill_device_ms",
                     "engine.decode_device_ms"], _serve_trace())
    assert got["engine.decode_kv_write_ms"] == pytest.approx(2 * (0.41 + 0.446))
    assert got["engine.prefill_device_ms"] == pytest.approx(0.7)
    assert got["engine.decode_device_ms"] == pytest.approx(39.5)  # PR 26's, untouched


def test_device_wait_reads_the_loops_one_blocking_span():
    spans = {"device": [1.3, 1.5], "dispatch": [0.008] * 20, "host_wait": [0.05] * 20}
    got = _evaluate(["loop.device_wait_ms", "loop.dispatch_ms"], None, spans)
    assert got["loop.device_wait_ms"] == pytest.approx(1400.0)
    assert got["loop.dispatch_ms"] == pytest.approx(8.0)


def test_a_program_without_these_events_leaves_the_metrics_out():
    """The driver lays these files over the parent too; where a reader
    finds nothing the metric is left out and nothing raises."""
    trace = DeviceTrace(
        [_op(HEAD, 0.0, 8.35)], [Event(0, "jit_per_device_step(1)", 0.0, 0.2, "")]
    )
    new = [p.stem for p in (ROOT / "layer_metrics").glob("*.json")
           if "cells" in json.loads(p.read_text())]
    assert len(new) == 6
    assert _evaluate(new, trace) == {}
