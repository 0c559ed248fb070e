"""models/olmo_hybrid.py against its plain reference,
benchmarks/references/olmo_hybrid_7b.py, and served: ``CausalLMEngine`` + the
continuous batcher with chunked prefill over a matrix state carried from
chunk to chunk beside two K/V tables.

Small on the CPU: 8 layers = two periods of three gated delta-rule layers and
one full-attention layer; width 64, 2 heads of 32 (full) and 2 of 8 keys x 16
values (linear), vocabulary 128, float32 (exact on the CPU's matmuls). The
reference shares no code with the model (no flax, no kvcache, no chunks: a
position at a time through the recurrence), so agreement here is agreement
of two implementations of the layer equations. What is compared of the
served path is what the benchmark cell compares on the chip
(benchmarks/runners/serve_olmo_hybrid.py): every emitted token's logit
against that position's maximum in the reference's logits, prompt and
emitted tokens teacher-forced through its full forward.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import olmo_hybrid_7b as reference
from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.olmo_hybrid import (
    FULL,
    LINEAR,
    OlmoHybrid,
    OlmoHybridConfig,
    delta_chunks,
    layer_kinds,
    olmo_hybrid_init_params,
)
from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.obs.trace import Tracer
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    CausalLMEngine,
    ContinuousBatcher,
)

_CFG = OlmoHybridConfig(
    vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=8,
    num_heads=2, linear_num_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, delta_chunk=4,
)
# the same sizes under the configuration file's keys, as the reference reads
REF_CFG = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 2,
    "layer_types": list(layer_kinds(_CFG)), "linear_num_key_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6,
}
_SLOTS, _MAX_NEW, _CHUNK = 3, 12, 16
_ENGINE = dict(buckets=(80,), slots=_SLOTS, max_batch=2,
               max_new_tokens=_MAX_NEW, prefill_chunk=_CHUNK)
CONFIG_FILE = Path(__file__).resolve().parents[1] \
    / "benchmarks/configs/olmo_hybrid_7b.json"


@pytest.fixture(scope="module")
def tiny():
    model = OlmoHybrid(_CFG)
    return model, olmo_hybrid_init_params(model, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def served(devices8, tiny):
    model, params = tiny
    return model, params, CausalLMEngine(model, params, **_ENGINE)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(5, _CFG.vocab_size, n).astype(np.int32)


def test_layers_follow_the_published_list():
    assert layer_kinds(_CFG) == (LINEAR, LINEAR, LINEAR, FULL) * 2
    kinds = layer_kinds(OlmoHybridConfig())
    assert (kinds.count(LINEAR), kinds.count(FULL)) == (24, 8)
    assert [l for l, k in enumerate(kinds) if k == FULL] == list(range(3, 32, 4))
    with pytest.raises(ValueError, match="layer_types must name 4 layers"):
        OlmoHybridConfig(num_layers=4, layer_types=(LINEAR, FULL))


def test_the_cut_config_keeps_the_first_16_of_the_published_list():
    from benchmarks.runners import serve_olmo_hybrid as runner

    config = json.loads(CONFIG_FILE.read_text())
    cfg = runner.model_config(config)
    assert cfg.num_layers == 16
    assert list(layer_kinds(cfg)) == config["published"]["layer_types"][:16]
    assert layer_kinds(cfg) == (LINEAR, LINEAR, LINEAR, FULL) * 4


def test_published_size_is_7_43_billion_and_the_cut_4_10():
    """Shapes only (``eval_shape``): the parameter counts ISSUE 37 derives,
    and what a slot caches at the benchmark cell's 4,608 positions."""
    def count(**kw):
        model = OlmoHybrid(OlmoHybridConfig(dtype=jnp.bfloat16, **kw))
        shapes = jax.eval_shape(
            lambda: olmo_hybrid_init_params(model, jax.random.PRNGKey(0))
        )
        return model, sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    _, whole = count()
    model, cut = count(num_layers=16)
    assert round(whole / 1e9, 2) == 7.43 and round(cut / 1e9, 2) == 4.10
    layout = model.cache_layout("bfloat16")
    groups = kvcache.components(layout, (16, 4608))
    # a slot: 12 x (30 x 96 x 192 x 4 B of state + 3 x 11,520 x 2 B of tails)
    # whatever its length, and 4 x 2 x 3,840 x 2 B a position
    assert groups["cache.state"][0] == 16 * 12 * (30 * 96 * 192 * 4 + 69_120)
    assert kvcache.bytes_per_token(layout) == 61_440
    assert groups["cache.full"][0] == 16 * 4608 * 61_440
    assert kvcache.step_writes(layout, 1) == {
        "state_bytes_written": 12 * (2_211_840 + 69_120),
        "full_rows_written": 8,
    }
    # four layers read K and V below each lane's position through the
    # new-row kernel, 36 blocks of 128 a slot; a table of part blocks takes
    # the mask form, a pass over the whole slot
    assert kvcache.prefix_reads(layout, 4608) == {"full": (128, 4 * 2, 36)}
    assert kvcache.prefix_reads(layout, 4600) == {"full": (4600, 4 * 2, 1)}


@pytest.mark.parametrize("lengths", [(13, 7), (4, 1), (21, 16)])
def test_the_model_s_forward_is_the_reference_s(tiny, lengths):
    """Every position of rows of unequal length, the prompt's recurrence in
    chunks of 4 that the lengths do not divide."""
    model, params = tiny
    width = max(lengths)
    ids = np.random.default_rng(width).integers(
        5, _CFG.vocab_size, (len(lengths), width)).astype(np.int32)
    mask = np.arange(width)[None] < np.asarray(lengths)[:, None]
    got = np.asarray(model.apply({"params": params}, ids, mask))
    want = np.asarray(reference.forward(REF_CFG, params, ids, mask))
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-5)


def _recurrence(q, k, v, g, beta, state):
    """The definition, a position at a time, in numpy's float64."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, state))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        alpha = np.exp(g[:, t])[..., None, None]
        s = alpha * s
        err = v[:, t] - np.einsum("bhkv,bhk->bhv", s, k[:, t])
        s = s + k[:, t][..., None] * (beta[:, t][..., None] * err)[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", s, q[:, t])
    return out, s


@pytest.mark.parametrize("lengths", [(1, 64), (63, 100), (130, 65), (128, 192)])
def test_the_chunked_delta_rule_is_the_recurrence(lengths):
    """Chunks of 64 over lengths that 64 does not divide, two rows of unequal
    length in one batch, from a state that is not zero: a pad (``beta = 0``,
    ``alpha = 1``) leaves the state as the row's last real position left it,
    and decays strong enough to underflow a chunk's product do no harm."""
    rng = np.random.default_rng(sum(lengths))
    b, h, d_k, d_v = len(lengths), 3, 8, 16
    width = -(-max(lengths) // 64) * 64
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.normal(size=(b, width, h, d_k))) * d_k ** -0.5
    k = unit(rng.normal(size=(b, width, h, d_k)))
    v = rng.normal(size=(b, width, h, d_v))
    g = -np.exp(rng.normal(size=(b, width, h)) * 2.0 - 1.0)  # to exp(-20) a step
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(b, width, h))))
    real = (np.arange(width)[None] < np.asarray(lengths)[:, None])[..., None]
    g, beta = g * real, beta * real
    state = rng.normal(size=(b, h, d_k, d_v))
    f32 = lambda *xs: (jnp.asarray(x, jnp.float32) for x in xs)  # noqa: E731
    got, left = delta_chunks(*f32(q, k, v, g, beta, state), 64)
    for row, n in enumerate(lengths):
        want, end = _recurrence(*(a[row:row + 1, :n] for a in (q, k, v, g, beta)),
                                state[row:row + 1])
        np.testing.assert_allclose(np.asarray(got)[row, :n], want[0], atol=2e-5)
        np.testing.assert_allclose(np.asarray(left)[row], end[0], atol=2e-5)


def test_an_idle_lane_writes_nothing_in_either_group(tiny):
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
    for old, fresh in zip(jax.tree.leaves(cache), jax.tree.leaves(new)):
        old, fresh = np.asarray(old), np.asarray(fresh)
        assert np.array_equal(old[:, 1], fresh[:, 1])
        assert not np.array_equal(old[:, 0], fresh[:, 0])
        assert not np.array_equal(old[:, 2], fresh[:, 2])
    # a live lane's table changed at its position and nowhere else
    for side in ("k", "v"):
        old, fresh = np.asarray(cache["full"][side]), np.asarray(new["full"][side])
        changed = (old != fresh).any(axis=-1)  # [layers, slots, positions]
        assert changed[:, 0].sum(axis=1).tolist() == [1, 1] and changed[:, 0, 5].all()


_forward = jax.jit(lambda params, ids, mask: reference.forward(REF_CFG, params, ids, mask))


def _worst_gap(params, prompt, tokens) -> float:
    """How far below the reference's maximum each emitted token's logit lies,
    at worst: 0 when the served path chose what the reference would. Every
    sequence padded to the cache's length: one program for all of them."""
    n = len(prompt) + len(tokens)
    seq = np.zeros((1, 80 + _MAX_NEW), np.int32)
    seq[0, :n] = np.concatenate([prompt, tokens])
    logits = np.asarray(_forward(params, seq, np.arange(seq.shape[1])[None] < n))[0]
    rows = logits[len(prompt) - 1: n - 1]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


@pytest.mark.parametrize("length,chunks", [(11, 1), (27, 2), (70, 5), (16, 1), (32, 2)])
def test_a_prompt_in_chunks_then_decoded_matches_the_reference(
    served, length, chunks
):
    """A prompt that enters as 1, 2 and 5 chunks of 16 (the last one partial,
    or whole), then ``max_new_tokens`` decode steps through the batcher: every
    emitted token tops the reference's full forward at its position."""
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
    sent = [sp for sp in tracer.drain() if sp.name == "engine.chunk_dispatch"]
    assert len(sent) == chunks
    assert sum(sp.args["real_tokens"] for sp in sent) == length
    assert [sp.args["first_chunks"] for sp in sent] == [1] + [0] * (chunks - 1)


def _run(engine, prompts: dict, steps: int) -> dict:
    """Each prompt a chunk at a time into its slot (a tier at a time), then
    ``steps`` decode steps; the other lanes ride along idle."""
    slots = sorted(prompts)
    out = {s: [] for s in slots}
    size = engine.prefill_chunk_size
    for i in range(0, len(slots), engine.max_batch):
        tier = slots[i:i + engine.max_batch]
        for start in range(0, max(len(prompts[s]) for s in tier), size):
            rows = [s for s in tier if start < len(prompts[s])]
            tok = engine.fetch_step(engine.prefill_chunks([
                {"slot": s, "input_ids": prompts[s], "start": start,
                 "n_tokens": min(size, len(prompts[s]) - start),
                 "length": len(prompts[s])}
                for s in rows
            ]))
            for row, s in enumerate(rows):
                if start + size >= len(prompts[s]):
                    out[s].append(int(tok[row]))
    lengths = np.zeros(engine.slots, np.int32)
    active = np.zeros(engine.slots, bool)
    for s in slots:
        lengths[s], active[s] = len(prompts[s]), True
    zeros = np.zeros(engine.slots, np.float32)
    for _ in range(steps):
        tok = engine.fetch_step(
            engine.decode(lengths, active, zeros, zeros.astype(np.int32))
        )
        for s in slots:
            out[s].append(int(tok[s]))
        lengths = lengths + active
    return out


@pytest.mark.parametrize("slot", [0, 2])
def test_a_slot_reused_after_a_longer_occupant_gives_the_solo_stream(
    served, slot
):
    """What the longer occupant left in a slot — its matrix state, its conv
    tails, table positions past the newcomer's length — is never read: a
    row's first chunk starts from zero state."""
    model, params, engine = served
    newcomers = {0: _prompt(5, seed=21), 2: _prompt(19, seed=22)}
    solo = CausalLMEngine(model, params, **_ENGINE)
    want = _run(solo, {slot: newcomers[slot]}, steps=8)[slot]
    _run(engine, {0: _prompt(40, seed=23), 2: _prompt(33, seed=24)}, steps=10)
    assert _run(engine, newcomers, steps=8)[slot] == want


def test_streams_through_the_batcher_are_the_solo_streams(served):
    """Seven requests over three slots, joining mid-flight, their prompts'
    chunks between other streams' decode steps (an idle lane's state must not
    move while its prompt is half in): each stream is its request's. The
    spans carry what a step wrote by group and what a chunk batch held."""
    _model, params, engine = served
    rng = np.random.default_rng(5)
    payloads = [
        {"input_ids": _prompt(int(rng.integers(2, 60)), seed=40 + i),
         "max_new_tokens": int(rng.integers(5, _MAX_NEW + 1))}
        for i in range(7)
    ]
    tracer = Tracer(1 << 14)
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2, max_queue=32),
        metrics=ServeMetrics(), tracer=tracer,
    ) as batcher:
        futures = [batcher.submit(p) for p in payloads]
        results = [f.result(timeout=300) for f in futures]
        status = batcher.status()
    for payload, result in zip(payloads, results):
        assert result["n_tokens"] == payload["max_new_tokens"]
        assert _worst_gap(params, payload["input_ids"],
                          np.asarray(result["tokens"])) <= 2e-5
    assert status["kv_active_bytes"] == 0 and status["slots"] == _SLOTS
    spans = {}
    for sp in tracer.drain():
        spans.setdefault(sp.name, []).append(sp)
    per_lane = engine._writes_per_lane
    assert sorted(per_lane) == ["full_rows_written", "state_bytes_written"]
    # two layers read K and V; at these toy heads the mask form passes over
    # a slot whole, so a live lane reads its slot and an idle one nothing
    assert engine._prefix_reads == {"full": (engine.cache_len, 2 * 2, 1)}
    for sp in spans["engine.decode_dispatch"]:
        for counter, one in per_lane.items():
            assert sp.args[counter] == sp.args["rows"] * one
        assert sp.args["full_blocks_read"] == sp.args["rows"] * 4
        assert sp.args["full_blocks_read"] <= sp.args["full_blocks_total"]
        assert sp.args["full_blocks_total"] == _SLOTS * 4
    sent = spans["engine.chunk_dispatch"]
    assert sum(sp.args["real_tokens"] for sp in sent) == sum(
        len(p["input_ids"]) for p in payloads)
    assert sum(sp.args["first_chunks"] for sp in sent) == len(payloads)
    assert all(sp.args["real_tokens"] <= sp.args["rows"] * _CHUNK for sp in sent)


def test_memory_registry_lists_the_two_groups(served):
    _model, _params, engine = served
    components = engine.memory.snapshot()["components"]
    want = kvcache.components(engine._layout, (_SLOTS, engine.cache_len))
    assert sorted(want) == ["cache.full", "cache.state"]
    for name, (nbytes, _dtype) in want.items():
        assert components[name] == nbytes > 0
    assert engine.cache_groups == want
    assert engine.cache_len == 80 + _MAX_NEW
    assert engine.prefill_chunk_size == _CHUNK and engine.prefix_cache is None
    assert engine.kv_bytes_per_token() == 2 * 2 * 64 * 4  # two layers' K, V


# -- which layouts take which mode (kvcache.require_pages / require_carry) --

_REFUSED = {
    "prefix_cache": dict(prefix_cache_mb=1.0, block_tokens=4),
    "speculative_verify": dict(spec_tokens=2),
    "kv_transfer": dict(kv_transfer=True),
    "stream_migrate": dict(stream_migrate=True),
    "int8_kv": dict(kv_dtype="int8"),
    "model_sharding": dict(),
}


@pytest.mark.parametrize("mode", sorted(_REFUSED))
def test_modes_that_need_pages_still_refuse_naming_the_group(served, mode):
    """Chunked prefill is what a carried state admits; the six modes that
    move a cached position about as a page of its own refuse as before."""
    model, params, _engine = served
    mesh = None
    if mode == "model_sharding":
        mesh = build_mesh({"model": 2}, devices=jax.devices()[:2])
    with pytest.raises(
        ValueError, match=r"transferable page; cache group 'state' is positionless"
    ):
        CausalLMEngine(model, params, mesh, **_ENGINE, **_REFUSED[mode])


def test_chunked_prefill_takes_positions_and_state_and_refuses_a_ring(tiny):
    from distributed_tensorflow_tpu.models.sambay import SambaY, SambaYConfig

    model, _params = tiny
    kvcache.require_carry(model.cache_layout("float32"))  # admitted
    kvcache.require_carry(kvcache.cache_layout(
        type("Cfg", (), dict(
            hidden_size=8, num_heads=2, model_axis=None, num_layers=1,
        )),
        "float32",
    ))
    with pytest.raises(ValueError, match=r"positionless"):
        kvcache.require_pages(model.cache_layout("float32"), "chunked prefill")
    rings = SambaY(SambaYConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=8,
        num_heads=4, num_kv_heads=2, sliding_window=8,
    )).cache_layout("float32")
    with pytest.raises(
        ValueError,
        match=r"chunked prefill needs .* cache group 'window' is ring \(after=8\)",
    ):
        kvcache.require_carry(rings)


def test_a_head_of_whole_lane_tiles_attends_as_the_merged_row_does():
    """``chunk_attention`` splits the row into heads where a head is whole
    lane tiles (30 x 128 at the published size) and contracts over the merged
    row elsewhere (12 x 64): the same attention."""
    rng = np.random.default_rng(0)
    b, c, l, h, d = 2, 5, 12, 2, 128
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    cache = {name: jnp.asarray(rng.normal(size=(b, l, h * d)), jnp.float32)
             for name in ("k", "v")}
    position = jnp.asarray(rng.integers(0, l, (b, c)), jnp.int32)
    got = kvcache.chunk_attention(q, cache, position)
    want = kvcache._attend(q, cache, position, "bqch,blc->bhql", "bhql,blc->bhqc")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
