"""The cell ``phi4_mini_flash.reason_steady`` and its runner ``serve_sambay``:
what test_consistency.py asserts of a cell and its runner (its ``MEASURES``
table knows ``train`` and ``serve`` only), and the runner itself end to end
on the CPU at a toy size — traffic, engine, batcher, and the comparison with
benchmarks/references/phi4_mini_flash.py that decides ``correct``."""

import json
import time
from pathlib import Path

import pytest

from benchmarks import common

ROOT = Path(__file__).resolve().parents[1]
CELL = "phi4_mini_flash.reason_steady"
WORKLOAD = json.loads((ROOT / "workloads" / f"{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "configs" / "phi4_mini_flash.json").read_text())
BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
#: what runners/serve_sambay.py measures (its "end_to_end")
MEASURES = {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_cell_names_a_config_a_runner_and_metrics_that_exist():
    w = WORKLOAD
    assert (w["config"], w["runner"], w["chips"]) == ("phi4_mini_flash", "serve_sambay", 1)
    assert (ROOT / "runners" / "serve_sambay.py").is_file()
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert "setup_s" in w["end_to_end"] and len(w["end_to_end"]) >= 2
    assert set(w["end_to_end"]) <= MEASURES
    assert len(set(w["layer_metrics"])) == len(w["layer_metrics"]) >= 1
    for metric in w["layer_metrics"]:
        spec = json.loads((ROOT / "layer_metrics" / f"{metric}.json").read_text())
        assert spec["moves"] in w["end_to_end"], metric
    entry = next(x for x in BENCH["workloads"] if x["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        "phi4_mini_flash", "reason_steady", 1, w["why"])
    for m in BENCH["per_layer"]:
        assert (CELL in m.get("workloads", ())) == (m["name"] in w["layer_metrics"]), m["name"]
    for m in BENCH["end_to_end"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in w["end_to_end"]), m["name"]


def test_traffic_is_the_issue_s():
    t = WORKLOAD["traffic"]
    assert t["prompt_len"] == {"median": 160, "sigma": 0.6, "min": 32, "max": 512}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "min": 256, "max": 1024}
    assert t["token_ids"] == {"low": 5} and t["shape_seed"] == 20261003
    assert WORKLOAD["slots"] == 128 and t["rate_rps"] > 0
    serving = CONFIG["serving"]
    assert (serving["buckets"], serving["max_new_tokens"], serving["max_batch"]) == (
        [128, 256, 512], 1024, 4)
    assert t["prompt_len"]["max"] <= serving["buckets"][-1]
    assert t["output_len"]["max"] <= serving["max_new_tokens"]


def test_configuration_holds_the_catalog_s_keys_uncut():
    entry = next(x for x in BENCH["configs"] if x["name"] == "phi4_mini_flash")
    assert entry["reduced"] == CONFIG["reduced"] == []
    assert entry["source"] == CONFIG["source"]
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value and CONFIG["published"][key] == value, key


def test_reference_shares_nothing_with_the_model():
    body = (ROOT / "references" / "phi4_mini_flash.py").read_text().split('"""', 2)[2]
    assert "import jax" in body
    assert "distributed_tensorflow_tpu" not in body and "flax" not in body


TOY_CONFIG = {
    "name": "phi4_mini_flash",  # the reference's file
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "sliding_window": 8, "mb_per_layer": 2, "max_position_embeddings": 4096,
    "layer_norm_eps": 1e-5, "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 4,
    "run": {"compute_dtype": "float32", "weight_dtype": "float32",
            "state_dtype": "float32"},
    "serving": {"buckets": [8, 16], "max_new_tokens": 24, "max_batch": 2},
}
TOY_WORKLOAD = {
    "config": "phi4_mini_flash", "runner": "serve_sambay", "chips": 1,
    "rehearsal": True, "slots": 4,
    "end_to_end": WORKLOAD["end_to_end"], "layer_metrics": [],
    "traffic": {"rate_rps": 6,
                "prompt_len": {"median": 9, "sigma": 0.5, "min": 2, "max": 16},
                "output_len": {"median": 14, "sigma": 0.4, "min": 9, "max": 24},
                "token_ids": {"low": 5}, "shape_seed": 7},
    "check": {"requests": 3, "positions": 8, "logit_tolerance": 1e-4},
}


def _toy_run(config, seed=1234567891):
    return common.Run(
        name="rehearsal.serve_sambay", workload=TOY_WORKLOAD, config=config,
        seed=seed, seconds=2.0, trace=False, t_start=time.monotonic(),
        trace_dir="",
    )


@pytest.fixture(scope="module")
def toy_result():
    from benchmarks.runners import serve_sambay

    return serve_sambay, serve_sambay.run(_toy_run(TOY_CONFIG))


def test_runner_serves_the_toy_and_agrees_with_the_reference(toy_result):
    _runner, result = toy_result
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == 12
    assert set(result["end_to_end"]) == MEASURES
    assert set(result["spans"]) >= {"queue_wait", "prefill", "decode", "ttft"}


def _serve_one(runner, window: int):
    """One prompt of 12 through an engine of the toy at ``window``: the
    weights, the prompt and 21 greedy tokens."""
    import jax
    import numpy as np

    from distributed_tensorflow_tpu.models.sambay import SambaY, sambay_init_params
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model = SambaY(runner.model_config(TOY_CONFIG, sliding_window=window))
    params = sambay_init_params(model, jax.random.PRNGKey(3))
    engine = CausalLMEngine(model, params, buckets=(8, 16), slots=2, max_batch=1,
                            max_new_tokens=24)
    prompt = np.random.default_rng(0).integers(5, 128, 12).astype(np.int32)
    tok = [int(engine.fetch_step(engine.prefill([{"slot": 0, "input_ids": prompt}]))[0])]
    lengths, active = np.asarray([12, 0], np.int32), np.asarray([True, False])
    zeros = np.zeros(2, np.float32)
    for _ in range(20):
        out = engine.fetch_step(engine.decode(lengths, active, zeros, zeros.astype(np.int32)))
        tok.append(int(out[0]))
        lengths = lengths + active
    return params, prompt, tok


def test_a_wrong_window_fails_the_comparison(toy_result):
    """The comparison is of the served path with the reference at the
    CONFIGURATION's sizes: serve a window of 7 where it says 8, and tokens
    chosen past the window no longer top the reference's logits."""
    runner, _ = toy_result
    params, prompt, tok = _serve_one(runner, 7)
    gaps = runner.reference_gaps(TOY_CONFIG, params, [(prompt, tok)], 8)
    assert runner.score_gaps(gaps)["mean_logit_gap"] > TOY_WORKLOAD["check"]["logit_tolerance"]


def test_the_larger_half_s_mean_is_what_is_compared(toy_result):
    """A fault of the prompt's state shows in the first half only and a wrong
    window in the last only: one mean over both would halve either."""
    import numpy as np

    runner, _ = toy_result
    gaps = np.array([[0.0, 0.0, 0.3, 0.5], [0.0, 0.2, 0.3, 0.5]], np.float32)
    score = runner.score_gaps(gaps)
    assert score["mean_first_half"] == pytest.approx(0.05)
    assert score["mean_logit_gap"] == score["mean_last_half"] == pytest.approx(0.4)
    assert runner.score_gaps(gaps[:, ::-1])["mean_logit_gap"] == pytest.approx(0.4)
    assert (score["positions_scored"], score["worst_logit_gap"]) == (8, 0.5)


def test_the_first_and_the_last_positions_are_scored_and_no_others(toy_result):
    """``[streams, positions]`` whatever the lengths: the first half follows
    the prompt, the last half ends the answer. Another token in the middle
    moves the reference at every later position; another LAST token moves
    only its own score."""
    runner, _ = toy_result
    params, prompt, tok = _serve_one(runner, 8)
    gaps = runner.reference_gaps(TOY_CONFIG, params, [(prompt, tok), (prompt, tok[:17])], 8)
    assert gaps.shape == (2, 8) and gaps.max() <= 1e-4
    other = tok[:-1] + [(tok[-1] + 1) % 128]
    moved = runner.reference_gaps(TOY_CONFIG, params, [(prompt, other)], 8)
    assert moved[0, :7].max() <= 1e-4 < moved[0, 7]


def test_too_few_long_requests_is_not_correct(toy_result):
    """The sample is never silently smaller: a run that did not finish
    ``check.requests`` requests reaching a window past their scored tail
    says so and is not ``correct``."""
    from benchmarks import traffic

    runner, _ = toy_result
    short = [{"req": traffic.Request(i, 0.0, [5, 6], 9), "refused": None,
              "result": {"tokens": list(range(9))}} for i in range(12)]
    verdict = runner._check(_toy_run(TOY_CONFIG), None, None, short)
    assert verdict["ok"] is False and "3 are scored" in verdict["reason"]


# ---- the cell's four metric files on a hand-built trace whose event texts are
# ---- copied from chiprun_out/pr35/desc_phi4.txt (a v5e capture of the cell)

STATS = " device_offset_ps=78226675000 device_duration_ps=733760000 Time Scale Multiplier=1.0"
FULL_SCORES = ("%fusion.187 = f32[128,40,1536]{2,1,0:T(8,128)S(1)} fusion(bf16[128,1280,40]{1,2,0:T(8,128)(2,1)S(1)} %copy.300, "
               "bf16[128,1536,1280]{2,1,0:T(8,128)(2,1)} %bitcast.156, pred[128,1536]{1,0} %compare.7), kind=kOutput")
FULL_CONTEXT = ("%fusion.268 = f32[128,20,1280]{2,1,0:T(8,128)} fusion(bf16[128,1536,1280]{2,1,0:T(8,128)(2,1)} %bitcast.158, "
                "f32[128,20,1,1536]{3,2,1,0:T(1,128)} %get-tuple-element.401), kind=kOutput")
RING_SCORES = ("%fusion.251 = f32[128,40,512]{2,1,0:T(8,128)S(1)} fusion(bf16[128,1280,40]{1,2,0:T(8,128)(2,1)S(1)} %copy.288, "
               "bf16[8,128,512,1280]{3,2,1,0:T(8,128)(2,1)} %cache__window____k__.1), kind=kOutput")
RING_CONTEXT = ("%fusion.37 = f32[128,20,1280]{2,1,0:T(8,128)} fusion(bf16[8,128,512,1280]{3,2,1,0:T(8,128)(2,1)} %cache__window____v__.1, "
                "pred[128,512]{1,0:T(8,128)(4,1)S(1)} %fusion.12), kind=kOutput")
STATE = ("%select_dynamic-update-slice_fusion.7 = f32[9,128,16,5120]{3,2,1,0:T(8,128)} fusion(f32[9,128,16,5120]{3,2,1,0:T(8,128)} "
         "%select_dynamic-update-slice_fusion.8, f32[128,5120]{1,0:T(8,128)} %copy.218), kind=kLoop")
CONV_TAIL = ("%select_dynamic-update-slice_fusion.16 = bf16[9,128,15360]{1,2,0:T(8,128)(2,1)} fusion(bf16[9,128,15360]{1,2,0:T(8,128)(2,1)} "
             "%select_dynamic-update-slice_fusion.17, bf16[128,15360]{1,0} %bitcast.726), kind=kLoop")
# what none of them may take: the head, the rings' row scatter, an MLP
HEAD = ("%fusion.1136 = bf16[128,200064]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[200064,2560]{1,0:T(8,128)(2,1)} "
        "%params__embed____embedding__.1, bf16[128,2560]{1,0:T(8,128)(2,1)} %fusion.99), kind=kOutput")
SCATTER = ("%fusion.3 = bf16[524288,1280]{1,0:T(8,128)(2,1)} fusion(bf16[524288,1280]{1,0:T(8,128)(2,1)} %bitcast.129, "
           "s32[1024]{0:T(1024)S(1)} %bitcast.794, bf16[1024,1280]{1,0} %bitcast.130), kind=kCustom")
MLP = ("%fusion.1178 = bf16[128,20480]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[2560,20480]{1,0:T(8,128)(2,1)} "
       "%params__layer_24____gate_up____kernel__.1, bf16[128,2560]{1,0:T(8,128)(2,1)} %fusion.77), kind=kOutput")


def test_the_four_metric_files_select_their_own_ops():
    from benchmarks import layers
    from benchmarks.trace import DeviceTrace, Event

    def op(text, start, ms):
        return Event(0, text.split(" ", 1)[0].lstrip("%"), start, start + ms * 1e-3, text + STATS)

    ops, mods, t = [], [], 0.0
    for step in range(2):
        t0 = t
        for text, ms in ((FULL_SCORES, 0.678), (FULL_CONTEXT, 0.734), (RING_SCORES, 0.224),
                         (RING_CONTEXT, 0.252), (STATE, 0.25), (CONV_TAIL, 0.01),
                         (HEAD, 1.356), (SCATTER, 0.171), (MLP, 0.142)):
            ops.append(op(text, t, ms))
            t += ms * 1e-3
        mods.append(Event(0, "jit_decode_fn(17148971010122191918)", t0, t, ""))
        mods.append(Event(0, "jit_prefill_fn(13001559438205412649)", t, t + 0.0167, ""))
        t += 0.0167
    names = [m for m in WORKLOAD["layer_metrics"] if m.startswith("engine.") and m != "engine.decode_device_ms"]
    specs = {n: json.loads((ROOT / "layer_metrics" / f"{n}.json").read_text()) for n in names}
    got = layers.evaluate(specs, spans={}, trace=DeviceTrace(ops, mods), config={}, job={}, peaks=None)
    got = {k: v["value"] for k, v in got.items()}
    assert got == pytest.approx({
        "engine.full_attention_ms": 0.678 + 0.734,
        "engine.window_attention_ms": 0.224 + 0.252,
        "engine.ssm_state_ms": 0.25 + 0.01,
        "engine.prefill_per_decode_step_ms": 16.7,
    })
    # a program without these groups has nothing to read: the metric is left out
    none = layers.evaluate(specs, spans={}, trace=DeviceTrace([op(MLP, 0.0, 1.0)], mods[:1]),
                           config={}, job={}, peaks=None)
    assert none == {}
