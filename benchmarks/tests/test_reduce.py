"""The reducers on small hand-built traces: no device, no jax."""

import pytest

from benchmarks import layers
from benchmarks import reduce as R
from benchmarks.trace import DeviceTrace, Event


def test_percentile_interpolates_like_numpy_default():
    vals = [10, 20, 30, 40, 50]
    assert R.percentile(vals, 0) == 10
    assert R.percentile(vals, 50) == 30
    assert R.percentile(vals, 95) == pytest.approx(48.0)
    assert R.percentile(vals, 100) == 50
    assert R.percentile([7], 95) == 7
    assert R.percentile([], 95) is None


def test_union_busy_and_idle_share():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.5), (10.0, 10.0)]
    assert R.union(ivs) == [(0.0, 2.0), (3.0, 4.5)]
    assert R.busy_seconds(ivs) == pytest.approx(3.5)
    # window defaults to first start .. last end = 4.5 s
    assert R.idle_share(ivs) == pytest.approx(1 - 3.5 / 4.5)
    assert R.idle_share(ivs, window=(0.0, 7.0)) == pytest.approx(0.5)
    assert R.idle_share([]) is None


def test_gaps_and_consecutive_gaps():
    ivs = [(0.0, 1.0), (1.5, 2.0), (2.0, 3.0), (5.0, 6.0)]
    assert R.gaps(ivs) == [(1.0, 1.5), (3.0, 5.0)]
    assert R.consecutive_gaps(ivs) == pytest.approx([0.5, 0.0, 2.0])
    assert R.median(R.consecutive_gaps(ivs)) == pytest.approx(0.5)


def test_busy_inside_each_module_execution():
    modules = [(0.0, 10.0), (20.0, 30.0)]
    ops = [(0.0, 4.0), (3.0, 6.0), (9.0, 12.0), (21.0, 22.0)]
    assert R.busy_inside(modules, ops) == pytest.approx([7.0, 1.0])


def _trace():
    """Two steps of 'jit_step' on chip 0 (and a mirror on chip 1): each step
    has a 3 ms matmul, a 1 ms kernel, a 2 ms all-reduce half hidden by a
    1 ms fusion; 4 ms idle between the steps."""
    ops, mods = [], []
    for chip in (0, 1):
        for k, t in enumerate((0.000, 0.010)):
            mods.append(Event(chip, "jit_step(1)", t, t + 0.006, ""))
            ops += [
                Event(chip, "fusion.1", t, t + 0.003, "hlo_category=convolution"),
                Event(chip, "custom-call.7", t + 0.003, t + 0.004, "tf_op=pallas_call"),
                Event(chip, "all-reduce.2", t + 0.004, t + 0.006, ""),
                Event(chip, "fusion.9", t + 0.004, t + 0.005, ""),
            ]
    return DeviceTrace(ops, mods)


def test_device_trace_busy_window_and_breakdown():
    tr = _trace()
    busy, window = tr.busy_and_window()
    assert busy == pytest.approx(0.012)
    assert window == pytest.approx(0.016)
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]
    assert bd["idle_gaps"][0][0] == "jit_step(1) -> jit_step(1)"
    assert bd["idle_gaps"][0][1] == pytest.approx(0.004)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_layer_metric_files_reduce_the_hand_built_trace():
    tr = _trace()
    specs = {
        "flash_ms": {"source": "trace", "select": "custom-call", "unit": "ms",
                     "reduce": "sum_per_step_ms", "args": {"per": "^jit_step"}},
        "flash_by_meta": {"source": "trace", "select": "pallas", "unit": "ms",
                          "reduce": "sum_per_step_ms",
                          "args": {"on": "ops_meta", "per": "^jit_step"}},
        "allreduce_ms": {"source": "trace", "select": "all-reduce", "unit": "ms",
                         "reduce": "sum_per_step_ms", "args": {"per": "^jit_step"}},
        "step_ms": {"source": "trace", "select": "^jit_step", "unit": "ms",
                    "reduce": "module_busy_median_ms", "args": {"on": "modules"}},
        "gap_ms": {"source": "trace", "select": "^jit_step", "unit": "ms",
                   "reduce": "median_gap_ms", "args": {"on": "modules"}},
        "module_ms": {"source": "trace", "select": "^jit_step", "unit": "ms",
                      "reduce": "median_ms", "args": {"on": "modules"}},
        "idle": {"source": "trace", "select": ".", "unit": "%",
                 "reduce": "idle_share_pct", "args": {}},
        "roofline": {"source": "trace", "select": "custom-call", "unit": "%",
                     "reduce": "roofline_pct",
                     "args": {"per": "^jit_step", "kernel": "flash_attention_train"}},
        "wait_ms": {"source": "span", "select": "^host_wait$", "unit": "ms",
                    "reduce": "mean_ms", "args": {}},
        "p95_ms": {"source": "span", "select": "^queue_wait$", "unit": "ms",
                   "reduce": "p95_ms", "args": {}},
        "nothing": {"source": "trace", "select": "no-such-op", "unit": "ms",
                    "reduce": "sum_per_step_ms", "args": {"per": "^jit_step"}},
    }
    cfg = {"hidden_size": 8, "num_hidden_layers": 1}
    out = layers.evaluate(
        specs, spans={"host_wait": [0.001, 0.003], "queue_wait": [0.01] * 20},
        trace=tr, config=cfg, job={"seq_len": 16, "per_chip_batch": 2},
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
    )
    v = {k: m["value"] for k, m in out.items()}
    assert "nothing" not in v  # a reader that finds nothing returns nothing
    assert v["flash_ms"] == pytest.approx(1.0)
    assert v["flash_by_meta"] == pytest.approx(1.0)
    assert v["allreduce_ms"] == pytest.approx(2.0)
    assert v["step_ms"] == pytest.approx(6.0)
    assert v["gap_ms"] == pytest.approx(4.0)
    assert v["module_ms"] == pytest.approx(6.0)
    assert v["idle"] == pytest.approx(100 * 4 / 16)
    assert v["wait_ms"] == pytest.approx(2.0)
    assert v["p95_ms"] == pytest.approx(10.0)
    # 18 * 16^2 * 8 * 2 rows * 1 layer = 73728 FLOPs -> 73.728 us at 1 GFLOP/s
    # (bytes: 15 * 16 * 8 * 2 * 2 = 7680 B -> 7.7 ns), over 1 ms of kernel time
    assert v["roofline"] == pytest.approx(100 * 73728e-9 / 1e-3)
    assert out["idle"]["unit"] == "%"


def test_traced_metrics_without_a_trace_are_left_out():
    specs = {"idle": {"source": "trace", "select": ".", "unit": "%",
                      "reduce": "idle_share_pct", "args": {}}}
    assert layers.evaluate(specs, spans={}, trace=None, config={}, job={}, peaks=None) == {}


def test_logit_error_is_the_worst_position_and_a_coarser_type_reads_larger():
    import numpy as np

    from benchmarks.runners.train import logit_error

    rng = np.random.default_rng(0)
    want = rng.normal(0.0, 1.0, (2, 4, 64)).astype(np.float32)
    valid = np.ones((2, 4), bool)
    assert [float(x) for x in logit_error(want, want, valid)] == [0.0, 0.0]
    # one position off by its whole scale: the worst says so, the mean hides it
    got = want.copy()
    got[1, 2] = 0.0
    worst, mean = map(float, logit_error(got, want, valid))
    assert worst == pytest.approx(
        np.linalg.norm(want[1, 2]) / np.linalg.norm(want[1, 2] - want[1, 2].mean()))
    assert worst > 0.9 and mean == pytest.approx(worst / 8)
    valid[1, 2] = False  # a padded position is not read
    assert float(logit_error(got, want, valid)[0]) == 0.0
    # rounding to 3 bits of mantissa (float8) reads ~32 times 8 bits (bfloat16)
    def rounded(x, bits):
        m, e = np.frexp(x)
        return np.ldexp(np.round(m * 2**bits) / 2**bits, e).astype(np.float32)
    valid[:] = True
    b16 = float(logit_error(rounded(want, 8), want, valid)[0])
    f8 = float(logit_error(rounded(want, 3), want, valid)[0])
    assert 0.0 < b16 < 0.003 and 20 * b16 < f8
