"""The cell ``olmo_hybrid_7b.longdoc_steady`` and its runner
``serve_olmo_hybrid``: what test_consistency.py asserts of a cell and its
runner (its ``MEASURES`` table knows ``train`` and ``serve`` only), and the
runner itself end to end on the CPU at a toy size — traffic, engine with
chunked prefill, batcher, and the comparison with
benchmarks/references/olmo_hybrid_7b.py that decides ``correct``, which every
wrong computation of scripts/olmo_hybrid_sabotage.py must fail."""

import importlib.util
import json
import time
from pathlib import Path

import pytest

from benchmarks import common

ROOT = Path(__file__).resolve().parents[1]
CELL = "olmo_hybrid_7b.longdoc_steady"
WORKLOAD = json.loads((ROOT / "workloads" / f"{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "configs" / "olmo_hybrid_7b.json").read_text())
BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = ("engine.delta_state_ms", "engine.kv_tables_ms",
               "engine.chunk_per_decode_step_ms", "engine.delta_chunk_ms")


def _sabotage():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_sabotage", ROOT.parent / "scripts/olmo_hybrid_sabotage.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_cell_names_a_config_a_runner_and_metrics_that_exist():
    from benchmarks.runners import serve_olmo_hybrid as runner

    w = WORKLOAD
    assert (w["config"], w["runner"], w["chips"]) == ("olmo_hybrid_7b", "serve_olmo_hybrid", 1)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert "setup_s" in w["end_to_end"] and len(w["end_to_end"]) >= 2
    assert set(w["end_to_end"]) <= runner.MEASURES
    assert len(set(w["layer_metrics"])) == len(w["layer_metrics"]) >= 1
    assert set(NEW_METRICS) <= set(w["layer_metrics"])
    for metric in w["layer_metrics"]:
        spec = json.loads((ROOT / "layer_metrics" / f"{metric}.json").read_text())
        assert spec["moves"] in w["end_to_end"], metric
    entry = next(x for x in BENCH["workloads"] if x["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        "olmo_hybrid_7b", "longdoc_steady", 1, w["why"])
    assert BENCH["workloads"][-1] == entry and BENCH["configs"][-1]["name"] == "olmo_hybrid_7b"
    for m in BENCH["per_layer"]:
        assert (CELL in m.get("workloads", ())) == (m["name"] in w["layer_metrics"]), m["name"]
    for m in BENCH["end_to_end"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in w["end_to_end"]), m["name"]
    for name in NEW_METRICS:
        m = next(x for x in BENCH["per_layer"] if x["name"] == name)
        assert (m["workloads"], m["moves"]) == ([CELL], "tpot_p95_ms")


def test_traffic_is_the_issue_s():
    t = WORKLOAD["traffic"]
    assert t["prompt_len"] == {"median": 2048, "sigma": 0.5, "min": 512, "max": 4096}
    assert t["output_len"] == {"median": 256, "sigma": 0.4, "min": 128, "max": 512}
    assert t["token_ids"] == {"low": 5} and isinstance(t["shape_seed"], int)
    assert WORKLOAD["slots"] == 16 and t["rate_rps"] > 0
    serving = CONFIG["serving"]
    assert (serving["buckets"], serving["prefill_chunk"], serving["max_new_tokens"]) == (
        [4096], 512, 512)
    assert t["prompt_len"]["max"] <= serving["buckets"][-1]
    assert t["output_len"]["max"] <= serving["max_new_tokens"]
    check = WORKLOAD["check"]
    assert (check["requests"], check["positions"], check["reach"]) == (8, 256, 2048)


def test_every_run_has_the_requests_the_check_scores():
    """Every seed offers one set of sizes, so how many requests are long
    enough to be scored is the same in every run."""
    from benchmarks.runners import serve_olmo_hybrid as runner

    check = WORKLOAD["check"]
    requests = runner.requests(WORKLOAD["traffic"], 2147483999, 40.0,
                               CONFIG["vocab_size"])
    long_enough = [
        r for r in requests
        if r.max_new_tokens >= check["positions"]
        and len(r.prompt) + r.max_new_tokens >= check["reach"]
    ]
    assert len(long_enough) >= 2 * check["requests"]
    serving = CONFIG["serving"]
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= \
        serving["buckets"][-1] + serving["max_new_tokens"]


def test_every_seed_offers_the_same_requests_at_the_same_moments():
    """The order of arrivals and sizes is ``shape_seed``'s (it alone moved
    ``tpot_p95_ms`` by 5-9%, PERF.md section 6); ``--seed`` draws the ids."""
    import numpy as np

    from benchmarks import traffic
    from benchmarks.runners import serve_olmo_hybrid as runner

    spec, vocab = WORKLOAD["traffic"], CONFIG["vocab_size"]
    a, b, again = (runner.requests(spec, seed, 40.0, vocab)
                   for seed in (2147483999, 3000000001, 2147483999))
    plan = traffic.generate(spec, spec["shape_seed"], 40.0, vocab)
    shape = lambda rs: [(r.index, r.due_s, len(r.prompt), r.max_new_tokens)  # noqa: E731
                        for r in rs]
    assert shape(a) == shape(b) == shape(plan) and len(a) == round(40.0 * spec["rate_rps"])
    assert traffic.offered(a) == traffic.offered(plan)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(r.prompt.dtype == np.int32 and spec["token_ids"]["low"] <= r.prompt.min()
               and r.prompt.max() < vocab for r in a)


def test_configuration_holds_the_catalog_s_keys_and_cuts_only_the_depth():
    entry = next(x for x in BENCH["configs"] if x["name"] == "olmo_hybrid_7b")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["num_hidden_layers"] == 16 == len(CONFIG["layer_types"])
    assert CONFIG["layer_types"] == CONFIG["published"]["layer_types"][:16]
    assert CONFIG["deployment"].startswith("layers 0-15 of 32 on this chip")
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_reference_shares_nothing_with_the_model():
    body = (ROOT / "references" / "olmo_hybrid_7b.py").read_text().split('"""', 2)[2]
    assert "import jax" in body
    assert "distributed_tensorflow_tpu" not in body and "flax" not in body


TOY_CONFIG = {
    "name": "olmo_hybrid_7b",  # the reference's file
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "num_attention_heads": 2, "num_key_value_heads": 2,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "run": {"compute_dtype": "float32", "weight_dtype": "float32",
            "state_dtype": "float32"},
    "serving": {"buckets": [64], "prefill_chunk": 16, "max_new_tokens": 16,
                "max_batch": 1},
}
TOY_WORKLOAD = {
    "config": "olmo_hybrid_7b", "runner": "serve_olmo_hybrid", "chips": 1,
    "rehearsal": True, "slots": 4,
    "end_to_end": WORKLOAD["end_to_end"], "layer_metrics": [],
    "traffic": {"rate_rps": 5,
                "prompt_len": {"median": 40, "sigma": 0.3, "min": 20, "max": 64},
                "output_len": {"median": 12, "sigma": 0.3, "min": 8, "max": 16},
                "token_ids": {"low": 5}, "shape_seed": 7},
    "check": {"requests": 3, "positions": 8, "reach": 28, "logit_tolerance": 1e-4},
}


def _toy_run(seed=1234567891):
    return common.Run(
        name="rehearsal.serve_olmo_hybrid", workload=TOY_WORKLOAD,
        config=TOY_CONFIG, seed=seed, seconds=2.0, trace=False,
        t_start=time.monotonic(), trace_dir="",
    )


def test_runner_serves_the_toy_in_chunks_and_agrees_with_the_reference():
    from benchmarks.runners import serve_olmo_hybrid as runner

    result = runner.run(_toy_run())
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == 10
    assert set(result["end_to_end"]) == runner.MEASURES
    assert set(result["spans"]) >= {"queue_wait", "prefill", "decode", "ttft"}


@pytest.mark.parametrize("variant", [
    "beta_without_2", "alpha_dropped", "qk_unnormalised", "state_not_carried",
    "conv_tail_at_padded_end", "k_norm_left_out", "mxu_fp8",
])
def test_a_wrong_computation_is_not_correct(variant):
    """The runner as the cell runs it, the model computing one thing wrong:
    every request completes, and the comparison with the reference says no."""
    from benchmarks.runners import serve_olmo_hybrid as runner

    with _sabotage().sabotaged(variant):
        result = runner.run(_toy_run())
    assert result["failed"] == 0 and not result["correct"]


def test_too_few_long_requests_is_not_correct():
    """The sample is never silently smaller: a run that did not finish
    ``check.requests`` requests of ``check.reach`` positions says so."""
    from benchmarks import traffic
    from benchmarks.runners import serve_olmo_hybrid as runner

    short = [{"req": traffic.Request(i, 0.0, [5, 6], 9), "refused": None,
              "result": {"tokens": list(range(9))}} for i in range(12)]
    picked, why = runner._probe(_toy_run(), None, short)
    verdict = runner._check(_toy_run(), None, picked, why)
    assert verdict["ok"] is False and "3 are scored" in verdict["reason"]
