"""models/deepseek_v2.py against its plain reference,
benchmarks/references/deepseek_v2_lite.py, and served: ``CausalLMEngine`` + the
continuous batcher, prompts in chunks (decompressed latent attention), then
decode steps in the absorbed form over one latent row a position.

Small on the CPU: 3 layers (the dense one, then two MoE layers), width 64, 4
heads with nope / rope / value dims of 16, a latent rank of 32, 8 routed
experts of 32 top-3 beside two shared, vocabulary 128, float32 (exact on the
CPU's matmuls). The reference shares no code with the model (no flax, no
kvcache, no moe: the decompressed form, every expert over every token), so
agreement here is agreement of two implementations of the layer equations.
What is compared of the served path is what the benchmark cell compares on
the chip (benchmarks/runners/serve_deepseek_v2.py): every emitted token's
logit against that position's maximum in the reference's logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import deepseek_v2_lite as reference
from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.deepseek_v2 import (
    DeepseekV2,
    DeepseekV2Config,
    deepseek_v2_init_params,
    softmax_scale,
    yarn_frequencies,
    yarn_range,
)
from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.ops import decode_attention
from distributed_tensorflow_tpu.obs.trace import Tracer
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    CausalLMEngine,
    ContinuousBatcher,
)

_YARN = dict(rope_theta=10000.0, rope_factor=40.0, original_max_position=4096,
             beta_fast=32.0, beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
_CFG = DeepseekV2Config(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_layers=3, num_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2, **_YARN,
)
# the same sizes under the configuration file's keys, as the reference reads
REF_CFG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_experts_per_tok": 3, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 40.0,
                     "original_max_position_embeddings": 4096, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
}
_SLOTS, _MAX_NEW, _CHUNK, _BUCKET = 3, 10, 8, 40
_ENGINE = dict(buckets=(_BUCKET,), slots=_SLOTS, max_batch=2,
               max_new_tokens=_MAX_NEW, prefill_chunk=_CHUNK)


@pytest.fixture(scope="module")
def tiny():
    model = DeepseekV2(_CFG)
    return model, deepseek_v2_init_params(model, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def served(devices8, tiny):
    model, params = tiny
    return model, params, CausalLMEngine(model, params, **_ENGINE)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(5, _CFG.vocab_size, n).astype(np.int32)


_forward = jax.jit(lambda params, ids, mask: reference.forward(REF_CFG, params, ids, mask))


def _worst_gap(params, prompt, tokens) -> float:
    """How far below the reference's maximum each emitted token's logit lies,
    at worst: 0 when the served path chose what the reference would. Every
    sequence padded to the cache's length, beside an empty row: one program
    for all of them and the forward test's."""
    n = len(prompt) + len(tokens)
    seq = np.zeros((2, _BUCKET + _MAX_NEW), np.int32)
    seq[0, :n] = np.concatenate([prompt, tokens])
    mask = np.arange(seq.shape[1])[None] < np.array([[n], [0]])
    logits = np.asarray(_forward(params, seq, mask))[0]
    rows = logits[len(prompt) - 1: n - 1]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


def test_yarn_follows_the_published_formulas():
    """At DeepSeek-V2-Lite's sizes: the ramp from 10 to 23, the
    frequencies, and the attention's scale ``192^-0.5 m^2``."""
    cfg = DeepseekV2Config()
    assert yarn_range(cfg) == (10, 23)
    j = np.arange(32)
    extra = 10000.0 ** (-2 * j / 64)
    ramp = np.clip((j - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(yarn_frequencies(cfg), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert round(m, 5) == 1.26080
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert round(softmax_scale(cfg), 6) == 0.114721
    # the reference derives the same from the configuration file's keys
    freq, scale = reference.yarn({
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_theta": 10000,
        "rope_scaling": REF_CFG["rope_scaling"],
    })
    np.testing.assert_allclose(np.asarray(freq), want, rtol=1e-6)
    assert scale == pytest.approx(softmax_scale(cfg))


def test_published_size_is_15_71_billion_and_the_cut_4_01():
    """Shapes only (``eval_shape``): the published parameter counts,
    what a position caches, and what a live lane routes."""
    def count(**kw):
        model = DeepseekV2(DeepseekV2Config(dtype=jnp.bfloat16, **kw))
        shapes = jax.eval_shape(
            lambda: deepseek_v2_init_params(model, jax.random.PRNGKey(0))
        )
        return model, sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    _, whole = count()
    model, cut = count(num_layers=7)
    assert round(whole / 1e9, 2) == 15.71 and round(cut / 1e6, 1) == 4009.5
    layout = model.cache_layout("bfloat16")
    # 7 layers x (512 latent + 64 rotated key, held as 640 lanes) x 2 B
    assert model.cfg.latent_width == 576 and model.cfg.row_width == 640
    assert kvcache.bytes_per_token(layout) == 7 * 640 * 2 == 8960
    assert kvcache.step_writes(layout, 1) == {"latent_rows_written": 7}
    assert model.decode_counters() == {"routed_rows": 6 * 6}
    assert kvcache.whole_reads(layout, 4608) == {"latent": 4608}
    assert kvcache.step_positions({"latent": 4608}, np.array([3, 0, 7])) == {
        "latent_positions_live": 10, "latent_positions_total": 3 * 4608}
    # K/V tables read whole (split over ``model``, or int8) count no positions
    from distributed_tensorflow_tpu.models.causal_lm import CausalLMConfig

    split = CausalLMConfig(dtype=jnp.bfloat16, model_axis="model", model_parallel=2)
    for kv in ("bfloat16", "int8"):
        assert kvcache.whole_reads(kvcache.cache_layout(split, kv), 4608) == {}


@pytest.mark.parametrize("lengths", [(13, 21), (4, 1)])
def test_the_model_s_forward_is_the_reference_s(tiny, lengths):
    """Rows of unequal length padded to one width (one program each side)."""
    model, params = tiny
    ids = np.random.default_rng(max(lengths)).integers(
        5, _CFG.vocab_size, (2, 24)).astype(np.int32)
    mask = np.arange(24)[None] < np.asarray(lengths)[:, None]
    got = np.asarray(jax.jit(model.apply)({"params": params}, ids, mask))
    want = np.asarray(_forward(params, ids, mask))
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-5)


def test_absorbed_decode_is_the_decompressed_form(tiny):
    """One query a slot over cached rows: ``W_UK`` folded into the query and
    ``W_UV`` after the latent context give what the expanded keys and values
    give, at every head."""
    model, params = tiny
    rng = np.random.default_rng(7)
    s, length, h = 3, 12, _CFG.num_heads
    q_nope = jnp.asarray(rng.normal(size=(s, h, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(s, h, 16)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(s, length, 128)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(s, 128)), jnp.float32)
    position = jnp.asarray([5, 0, 11], jnp.int32)

    def both(m):
        attn = m.layers[1].attn
        absorbed = attn.absorbed(q_nope, q_pe, table, position, row)
        # the decompressed form reads the table with the row at its position
        written = kvcache.select_rows(table, row, position, slot_axis=0)
        expanded = attn.expanded(
            q_nope[:, None], q_pe[:, None], written, position[:, None]
        )[:, 0]
        return absorbed, expanded

    absorbed, expanded = model.apply({"params": params}, method=both)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)


def test_a_step_counts_the_latent_blocks_it_reads():
    """At the cell's size (7 layers, rows of 640 lanes, 4,608 positions) a
    step's read moves each live lane's blocks below its position and the
    block that holds it, once a layer (one table is both sides); a table of
    part blocks takes the mask form, every position of every slot."""
    block = decode_attention.LATENT_BLOCK
    layout = DeepseekV2(DeepseekV2Config(num_layers=7)).cache_layout(
        "bfloat16"
    )
    assert kvcache.prefix_reads(layout, 4608) == {
        "latent": (block, 7, 4608 // block)}
    assert kvcache.prefix_reads(layout, 4600) == {"latent": (4600, 7, 1)}
    seen = np.array([3, 0, block + 1, 4608])  # position + 1; 0 is idle
    assert kvcache.step_reads({"latent": (block, 7, 4608 // block)}, seen) == {
        "latent_blocks_read": 7 * (1 + 0 + 2 + 4608 // block),
        "latent_blocks_total": 7 * 4 * (4608 // block),
    }
    # the positions counters stay beside them
    assert kvcache.whole_reads(layout, 4608) == {"latent": 4608}


@pytest.mark.parametrize(
    "case", ["kernel", "part_blocks", "part_tiles", "one_layer"]
)
def test_latent_attention_takes_the_kernel_where_the_table_admits_it(
    case, monkeypatch
):
    """``kvcache.latent_attention`` given the layer of the stacked table:
    the kernel for whole blocks of rows of whole lane tiles, equal to the
    mask form; anything else the mask form, bit for bit."""
    cache_len = 100 if case == "part_blocks" else 2 * decode_attention.LATENT_BLOCK
    lanes = 48 if case == "part_tiles" else 128
    slots, heads, layer, scale = 3, 4, 1, 0.125
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(slots, heads, lanes)), jnp.float32)
    table = jnp.asarray(
        rng.normal(size=(2, slots, cache_len, lanes)), jnp.float32
    )
    row = jnp.asarray(rng.normal(size=(slots, lanes)), jnp.float32)
    position = jnp.asarray([5, cache_len, cache_len - 1])
    want = kvcache.latent_attention(q, table[layer], position, row, scale)
    calls = []
    kernel = decode_attention.latent_row_attention
    monkeypatch.setattr(
        decode_attention, "latent_row_attention",
        lambda *a, **kw: calls.append(kw) or kernel(*a, **kw),
    )
    if case == "one_layer":
        got = kvcache.latent_attention(q, table[layer], position, row, scale)
    else:
        got = kvcache.latent_attention(
            q, table, position, row, scale, layer=layer
        )
    assert got.shape == q.shape and got.dtype == jnp.float32
    if case == "kernel":
        assert calls == [{"layer": layer, "scale": scale}]
        live = np.asarray(position) < cache_len
        np.testing.assert_allclose(
            np.asarray(got)[live], np.asarray(want)[live], atol=2e-6
        )
        assert not np.asarray(got)[~live].any()  # an idle lane: zeros
    else:
        assert not calls
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_served_through_the_latent_kernel(devices8, tiny, monkeypatch):
    """The toy model's rows are one lane tile, so a cache of whole blocks
    sends its absorbed reads through the kernel (interpreted): a prompt in
    chunks, then decode steps whose tokens top the reference's as the mask
    form's do, and whose dispatch spans count the blocks read — one a live
    lane a layer at these lengths, against two a slot."""
    model, params = tiny
    calls = []
    kernel = decode_attention.latent_row_attention
    monkeypatch.setattr(
        decode_attention, "latent_row_attention",
        lambda *a, **kw: calls.append(kw["layer"]) or kernel(*a, **kw),
    )
    cache_len = 2 * decode_attention.LATENT_BLOCK
    engine = CausalLMEngine(
        model, params, **{**_ENGINE, "buckets": (cache_len - _MAX_NEW,)}
    )
    assert engine.cache_len == cache_len
    assert sorted(set(calls)) == [0, 1, 2]  # the decode step's three reads
    assert engine._prefix_reads == {"latent": (cache_len // 2, 3, 2)}
    prompt = _prompt(19, seed=19)
    tracer = Tracer(1 << 12)
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2), metrics=ServeMetrics(), tracer=tracer,
    ) as batcher:
        result = batcher.submit(
            {"input_ids": prompt, "max_new_tokens": _MAX_NEW}
        ).result(timeout=300)
    assert result["n_tokens"] == _MAX_NEW
    assert _worst_gap(params, prompt, np.asarray(result["tokens"])) <= 2e-5
    steps = [sp for sp in tracer.drain() if sp.name == "engine.decode_dispatch"]
    assert steps
    for sp in steps:
        assert sp.args["latent_blocks_total"] == 3 * _SLOTS * 2
        assert sp.args["latent_blocks_read"] == 3 * sp.args["rows"]


@pytest.mark.parametrize("length,chunks", [(11, 2), (8, 1), (30, 4)])
def test_chunks_then_absorbed_decode_match_the_reference(served, length, chunks):
    """A prompt in chunks of 8 (the last one partial, or whole), then
    ``max_new_tokens`` absorbed decode steps through the batcher: every
    emitted token tops the reference's full forward at its position. The
    tolerance, 2e-5, is float32's rounding through three layers on the CPU,
    whose matmuls are exact float32: two forms of one function agree to it,
    and a wrong YaRN or scale misses by orders of magnitude."""
    _model, params, engine = served
    prompt = _prompt(length, seed=length)
    tracer = Tracer(1 << 12)
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2), metrics=ServeMetrics(), tracer=tracer,
    ) as batcher:
        result = batcher.submit(
            {"input_ids": prompt, "max_new_tokens": _MAX_NEW}
        ).result(timeout=300)
    assert result["n_tokens"] == _MAX_NEW
    assert _worst_gap(params, prompt, np.asarray(result["tokens"])) <= 2e-5
    spans = tracer.drain()
    sent = [sp for sp in spans if sp.name == "engine.chunk_dispatch"]
    assert len(sent) == chunks
    assert sum(sp.args["real_tokens"] for sp in sent) == length
    for sp in (sp for sp in spans if sp.name == "engine.decode_dispatch"):
        assert sp.args["latent_rows_written"] == 3 * sp.args["rows"]
        assert sp.args["routed_rows"] == 2 * 3 * sp.args["rows"]
        assert sp.args["latent_positions_total"] == _SLOTS * engine.cache_len
        assert sp.args["rows"] <= sp.args["latent_positions_live"] \
            <= sp.args["rows"] * engine.cache_len
        # a table of part blocks takes the mask form: a block is the slot
        assert sp.args["latent_blocks_total"] == 3 * _SLOTS
        assert sp.args["latent_blocks_read"] == 3 * sp.args["rows"]


def test_streams_through_the_batcher_are_the_solo_streams(served):
    """Five requests over three slots joining mid-flight, their chunks
    between other streams' decode steps, an idle lane riding each step."""
    _model, params, engine = served
    rng = np.random.default_rng(5)
    payloads = [
        {"input_ids": _prompt(int(rng.integers(2, _BUCKET)), seed=40 + i),
         "max_new_tokens": int(rng.integers(4, _MAX_NEW + 1))}
        for i in range(5)
    ]
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2, max_queue=32), metrics=ServeMetrics(),
    ) as batcher:
        results = [f.result(timeout=300)
                   for f in [batcher.submit(p) for p in payloads]]
    for payload, result in zip(payloads, results):
        assert result["n_tokens"] == payload["max_new_tokens"]
        assert _worst_gap(params, payload["input_ids"],
                          np.asarray(result["tokens"])) <= 2e-5


def test_an_idle_lane_writes_nothing(tiny):
    model, params = tiny
    cache_len = 24
    layout = model.cache_layout("float32")
    rng = np.random.default_rng(3)
    cache = jax.tree.map(
        lambda leaf: jnp.asarray(rng.normal(size=(
            *leaf.lead((_SLOTS, cache_len)), *leaf.shape)), leaf.dtype),
        layout,
    )
    token = jnp.asarray([7, 9, 11], jnp.int32)
    position = jnp.asarray([5, cache_len, 0], jnp.int32)  # lane 1 is idle
    _, new = model.apply({"params": params}, token, position, cache,
                         method="decode_step")
    old, fresh = np.asarray(cache["latent"]["row"]), np.asarray(new["latent"]["row"])
    changed = (old != fresh).any(axis=-1)  # [layers, slots, positions]
    assert not changed[:, 1].any()
    assert changed[:, 0].sum(axis=1).tolist() == [1] * 3 and changed[:, 0, 5].all()
    assert changed[:, 2].sum(axis=1).tolist() == [1] * 3 and changed[:, 2, 0].all()


_REFUSED = {
    "prefix_cache": dict(prefix_cache_mb=1.0, block_tokens=4),
    "speculative_verify": dict(spec_tokens=2),
    "kv_transfer": dict(kv_transfer=True),
    "stream_migrate": dict(stream_migrate=True),
    "int8_kv": dict(kv_dtype="int8"),
    "model_sharding": dict(),
}


@pytest.mark.parametrize("mode", sorted(_REFUSED))
def test_page_moving_modes_refuse_naming_the_group(served, mode):
    """A latent row is one row for every head, no K/V page: every mode that
    moves a cached position about as a page refuses at construction."""
    model, params, _engine = served
    mesh = None
    if mode == "model_sharding":
        mesh = build_mesh({"model": 2}, devices=jax.devices()[:2])
    with pytest.raises(
        ValueError,
        match=r"transferable page; cache group 'latent' is latent rows, not K/V pages",
    ):
        CausalLMEngine(model, params, mesh, **_ENGINE, **_REFUSED[mode])
    kvcache.require_carry(model.cache_layout("float32"))  # chunks are admitted
