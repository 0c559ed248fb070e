"""The cell ``deepseek_v2_lite.docqa_steady`` and its runner
``serve_deepseek_v2``: what test_consistency.py asserts of a cell and its
runner (its ``MEASURES`` table knows ``train`` and ``serve`` only), and the
runner itself end to end on the CPU at a toy size — traffic, engine with
chunked prefill, batcher, and the comparison with
benchmarks/references/deepseek_v2_lite.py that decides ``correct``, which the
wrong computations of scripts/deepseek_v2_sabotage.py must fail."""

import importlib.util
import json
import os
import time
from pathlib import Path

import pytest

from benchmarks import common

ROOT = Path(__file__).resolve().parents[1]
CELL = "deepseek_v2_lite.docqa_steady"
WORKLOAD = json.loads((ROOT / "workloads" / f"{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "configs" / "deepseek_v2_lite.json").read_text())
BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
# A JSON-lines catalog of published architectures, one row per model with its
# ``name``, ``source_url`` and ``config``; the catalog check runs only where it is given.
CATALOG = Path(os.environ.get("MODEL_CATALOG", ""))
NEW_METRICS = ("engine.moe_experts_ms", "engine.latent_attention_ms")
SHARED_METRICS = ("batcher.decode_gap_ms", "engine.decode_device_ms",
                  "device.idle_share.serve", "engine.chunk_per_decode_step_ms")


def _sabotage():
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_sabotage", ROOT.parent / "scripts/deepseek_v2_sabotage.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_cell_names_a_config_a_runner_and_metrics_that_exist():
    from benchmarks.runners import serve_deepseek_v2 as runner

    w = WORKLOAD
    assert (w["config"], w["runner"], w["chips"]) == ("deepseek_v2_lite", "serve_deepseek_v2", 1)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert w["end_to_end"] == ["tpot_p95_ms", "setup_s"]
    assert set(w["end_to_end"]) <= runner.MEASURES
    assert set(w["layer_metrics"]) == set(NEW_METRICS) | set(SHARED_METRICS)
    for metric in w["layer_metrics"]:
        spec = json.loads((ROOT / "layer_metrics" / f"{metric}.json").read_text())
        assert spec["moves"] == "tpot_p95_ms", metric
    entry = next(x for x in BENCH["workloads"] if x["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        "deepseek_v2_lite", "docqa_steady", 1, w["why"])
    assert BENCH["workloads"][-1] == entry and BENCH["configs"][-1]["name"] == "deepseek_v2_lite"
    for m in BENCH["per_layer"]:
        assert (CELL in m.get("workloads", ())) == (m["name"] in w["layer_metrics"]), m["name"]
    for m in BENCH["end_to_end"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in w["end_to_end"]), m["name"]
    for name in NEW_METRICS:
        m = next(x for x in BENCH["per_layer"] if x["name"] == name)
        assert (m["workloads"], m["moves"], m["unit"]) == ([CELL], "tpot_p95_ms", "ms")


def test_traffic_is_the_cell_s():
    t = WORKLOAD["traffic"]
    assert t["prompt_len"] == {"median": 2048, "sigma": 0.5, "min": 512, "max": 4096}
    assert t["output_len"] == {"median": 256, "sigma": 0.5, "min": 64, "max": 512}
    assert t["token_ids"] == {"low": 5} and isinstance(t["shape_seed"], int)
    assert WORKLOAD["slots"] == 128 and t["rate_rps"] > 0
    serving = CONFIG["serving"]
    assert (serving["buckets"], serving["prefill_chunk"], serving["max_new_tokens"]) == (
        [4096], 512, 512)
    assert t["prompt_len"]["max"] <= serving["buckets"][-1]
    assert t["output_len"]["max"] <= serving["max_new_tokens"]
    check = WORKLOAD["check"]
    assert (check["requests"], check["positions"], check["reach"]) == (8, 256, 2048)


def test_every_seed_offers_the_same_requests_at_the_same_moments():
    """The order of arrivals and sizes is ``shape_seed``'s, as in the
    long-document cell; ``--seed`` draws the ids. Enough of them are long
    enough to be scored in every run."""
    import numpy as np

    from benchmarks import traffic
    from benchmarks.runners import serve_deepseek_v2 as runner

    spec, vocab = WORKLOAD["traffic"], CONFIG["vocab_size"]
    a, b, again = (runner.requests(spec, seed, 40.0, vocab)
                   for seed in (2147483999, 3000000001, 2147483999))
    plan = traffic.generate(spec, spec["shape_seed"], 40.0, vocab)
    shape = lambda rs: [(r.index, r.due_s, len(r.prompt), r.max_new_tokens)  # noqa: E731
                        for r in rs]
    assert shape(a) == shape(b) == shape(plan) and len(a) == round(40.0 * spec["rate_rps"])
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    check = WORKLOAD["check"]
    long_enough = [r for r in a if r.max_new_tokens >= check["positions"]
                   and len(r.prompt) + r.max_new_tokens >= check["reach"]]
    assert len(long_enough) >= 2 * check["requests"]


def test_configuration_holds_the_catalog_s_keys_and_cuts_only_the_depth():
    entry = next(x for x in BENCH["configs"] if x["name"] == "deepseek_v2_lite")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"] == list(CONFIG.values())[1]
    assert (CONFIG["num_hidden_layers"], CONFIG["published"]["num_hidden_layers"]) == (7, 27)
    assert CONFIG["deployment"].startswith("layers 0-6")
    # the file's own record of the published model: every key but the cut
    # one is served as published
    for key, value in CONFIG["published"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    if not CATALOG.is_file():
        pytest.skip("MODEL_CATALOG names no catalog file")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "DeepSeek-V2-Lite")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_reference_shares_nothing_with_the_model():
    body = (ROOT / "references" / "deepseek_v2_lite.py").read_text().split('"""', 2)[2]
    assert "import jax" in body
    assert "distributed_tensorflow_tpu" not in body and "flax" not in body


TOY_CONFIG = {
    "name": "deepseek_v2_lite",  # the reference's file
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "scoring_func": "softmax", "topk_method": "greedy",
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "max_position_embeddings": 4096,
    "rope_scaling": CONFIG["rope_scaling"],
    "run": {"compute_dtype": "float32", "weight_dtype": "float32"},
    "serving": {"buckets": [64], "prefill_chunk": 16, "max_new_tokens": 16,
                "max_batch": 1},
}
TOY_WORKLOAD = {
    "config": "deepseek_v2_lite", "runner": "serve_deepseek_v2", "chips": 1,
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
        name="rehearsal.serve_deepseek_v2", workload=TOY_WORKLOAD,
        config=TOY_CONFIG, seed=seed, seconds=2.0, trace=False,
        t_start=time.monotonic(), trace_dir="",
    )


def test_runner_serves_the_toy_in_chunks_and_agrees_with_the_reference(capsys):
    from benchmarks.runners import serve_deepseek_v2 as runner

    result = runner.run(_toy_run())
    assert result["correct"] and result["failed"] == 0 and result["attempted"] == 10
    assert set(result["end_to_end"]) == runner.MEASURES
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    routing = info["check"]["routing"]
    # 3 requests x 8 positions x 3 choices in each of the 2 MoE layers
    assert routing["choices_compared"] == 2 * 3 * 8 * 3
    assert sum(routing["rows_an_expert_mean"]) == pytest.approx(2 * 3 * 8 * 3 / 8)
    assert info["decode_step_must_move"]["bytes"]["latent_table"] == 4 * 80 * 3 * 128 * 4


@pytest.mark.parametrize("variant", [
    "kv_a_norm_left_out", "topk_renormalised", "shared_experts_left_out",
    "capacity_dropping",
])
def test_a_wrong_computation_is_not_correct(variant):
    """The runner as the cell runs it, the model computing one thing wrong:
    every request completes, and the comparison with the reference says no.
    The wrong attentions (the scale, the rotary, the chunk's positions, the
    table's type) are read on the chip only: at this toy's width of 64 and
    weights of 0.02 every score is near 0 and the attention near uniform,
    so no logit moves past the next."""
    from benchmarks.runners import serve_deepseek_v2 as runner

    with _sabotage().sabotaged(variant):
        result = runner.run(_toy_run())
    assert result["failed"] == 0 and not result["correct"]
