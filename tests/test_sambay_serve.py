"""SambaY served: ``CausalLMEngine`` + the continuous batcher over three
cache groups side by side (Mamba state, window rings, one full K/V table).

The sizes of tests/test_sambay.py. What is compared is what the benchmark
cell compares on the chip (benchmarks/runners/serve_sambay.py): every
emitted token's logit against that position's maximum in the plain
reference's logits, prompt and emitted tokens teacher-forced through its
full forward — no cache, no engine.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import phi4_mini_flash as reference
from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.sambay import SambaY, sambay_init_params
from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.obs.trace import Tracer
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.serve import (
    BatcherConfig,
    CausalLMEngine,
    ContinuousBatcher,
)
from tests.test_sambay import _CFG, REF_CFG

_SLOTS, _MAX_NEW = 3, 32
_ENGINE = dict(buckets=(8, 16), slots=_SLOTS, max_batch=2, max_new_tokens=_MAX_NEW)


@pytest.fixture(scope="module")
def served(devices8):
    model = SambaY(_CFG)
    params = sambay_init_params(model, jax.random.PRNGKey(1))
    return model, params, CausalLMEngine(model, params, **_ENGINE)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(5, _CFG.vocab_size, n).astype(np.int32)


def _run(engine, prompts: dict, steps: int) -> dict:
    """Prefill ``{slot: prompt}`` (a tier at a time), then ``steps`` decode
    steps over the slot table; the other lanes ride along idle. Returns the
    emitted tokens by slot."""
    slots = sorted(prompts)
    out = {s: [] for s in slots}
    for i in range(0, len(slots), engine.max_batch):
        tier = slots[i:i + engine.max_batch]
        first = engine.fetch_step(engine.prefill(
            [{"slot": s, "input_ids": prompts[s]} for s in tier]
        ))
        for row, s in enumerate(tier):
            out[s].append(int(first[row]))
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


def _worst_gap(params, prompt, tokens) -> float:
    """How far below the reference's maximum each emitted token's logit lies,
    at worst: 0 when the served path chose what the reference would."""
    seq = np.concatenate([prompt, tokens])[None]
    logits = np.asarray(
        reference.forward(REF_CFG, params, seq, np.ones_like(seq, bool))
    )[0]
    rows = logits[len(prompt) - 1: len(prompt) - 1 + len(tokens)]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


@pytest.mark.parametrize("length", [3, 11, 16])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
    served, length
):
    """``length`` prompt tokens and 28 decode steps: past the window of 8
    three times over, the rings wrap, the table grows."""
    _model, params, engine = served
    prompt = _prompt(length, seed=length)
    tokens = _run(engine, {1: prompt}, steps=28)[1]
    assert len(tokens) == 29
    assert _worst_gap(params, prompt, tokens) <= 1e-5


@pytest.mark.parametrize("slot", [0, 2])
def test_a_slot_reused_after_a_longer_occupant_gives_the_solo_stream(
    served, slot
):
    """What the longer occupant left in a slot — scan state, ring rows past
    the newcomer's length, table positions past it — is never read."""
    _model, _params, engine = served
    newcomers = {0: _prompt(4, seed=21), 2: _prompt(6, seed=22)}
    solo = CausalLMEngine(_model, _params, **_ENGINE)
    want = _run(solo, {slot: newcomers[slot]}, steps=20)[slot]
    _run(engine, {0: _prompt(16, seed=23), 2: _prompt(15, seed=24)}, steps=30)
    assert _run(engine, newcomers, steps=20)[slot] == want


def test_streams_through_the_batcher_are_the_solo_streams(served):
    """Seven requests over three slots, joining mid-flight: each stream is
    its request's, whoever rode beside it; the spans carry what the step
    wrote by group, and prefill its real tokens beside rows and bucket."""
    _model, params, engine = served
    rng = np.random.default_rng(5)
    payloads = [
        {"input_ids": _prompt(int(rng.integers(2, 16)), seed=40 + i),
         "max_new_tokens": int(rng.integers(9, 30))}
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
                          np.asarray(result["tokens"])) <= 1e-5
    assert status["kv_active_bytes"] == 0 and status["slots"] == _SLOTS
    spans = {}
    for sp in tracer.drain():
        spans.setdefault(sp.name, []).append(sp)
    per_lane = engine._writes_per_lane
    assert sorted(per_lane) == [
        "full_rows_written", "state_bytes_written", "window_rows_written",
    ]
    # two layers read the one table's K and V up to each lane's length; at
    # these toy heads the mask form passes over a slot whole, so a live lane
    # reads its slot and an idle one nothing
    assert engine._prefix_reads == {"full": (engine.cache_len, 2 * 2, 1)}
    for sp in spans["engine.decode_dispatch"]:
        for counter, one in per_lane.items():
            assert sp.args[counter] == sp.args["rows"] * one
        assert sp.args["full_blocks_read"] == sp.args["rows"] * 4
        assert sp.args["full_blocks_total"] == _SLOTS * 4
    admitted = spans["engine.prefill_dispatch"]
    assert sum(sp.args["real_tokens"] for sp in admitted) == sum(
        len(p["input_ids"]) for p in payloads
    )
    assert all(sp.args["real_tokens"] <= sp.args["rows"] * sp.args["bucket"]
               for sp in admitted)


def test_memory_registry_lists_the_three_groups(served):
    _model, _params, engine = served
    components = engine.memory.snapshot()["components"]
    want = kvcache.components(engine._layout, (_SLOTS, engine.cache_len))
    assert sorted(want) == ["cache.full", "cache.state", "cache.window"]
    for name, (nbytes, _dtype) in want.items():
        assert components[name] == nbytes > 0
    assert engine.cache_groups == want  # and no `kv_slot_cache` of its own
    assert engine.slot_page_bytes * _SLOTS == sum(n for n, _ in want.values())
    # no position table: the cache is the largest bucket plus the answer
    assert engine.cache_len == 16 + _MAX_NEW
    assert engine.kv_bytes_per_token() == 2 * 2 * 16 * 4  # one layer's K, V


_REFUSED = {
    "prefix_cache": dict(prefix_cache_mb=1.0, block_tokens=4),
    "chunked_prefill": dict(prefill_chunk=8),
    "speculative_verify": dict(spec_tokens=2),
    "kv_transfer": dict(kv_transfer=True),
    "stream_migrate": dict(stream_migrate=True),
    "int8_kv": dict(kv_dtype="int8"),
    "model_sharding": dict(),
}


@pytest.mark.parametrize("mode", sorted(_REFUSED))
def test_modes_that_need_pages_refuse_at_construction_naming_the_group(
    served, mode
):
    model, params, _engine = served
    mesh = None
    if mode == "model_sharding":
        mesh = build_mesh({"model": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=r"cache group '(state|window)'"):
        CausalLMEngine(model, params, mesh, **_ENGINE, **_REFUSED[mode])


# The lowered decode step of lm_base (tests/test_chip_compile.py's builder at
# a small size), as the parent of PR 35 lowered it: the cache became groups of
# leaves and lm_base, the one-group case, must not have noticed. Since PR 38
# a layer's table is sliced out inside kvcache.cached_attention, after the
# projections and not before them: the same instructions (the text sorted,
# value names aside, is the parent's) in another order, so other digests.
# The step's per-lane inputs are now one packed operand that the step hands
# back advanced (engine._make_causal_decode): the digests of that program.
_LM_BASE_DECODE = {"bfloat16": "35827c7b9ddca85a", "int8": "adafd8bd05be4496"}


_GRIDS = {
    "hybrid": {},
    "lm_monolithic": {},
    "lm_chunked_prefix": dict(prefix_cache_mb=1, block_tokens=4, prefill_chunk=8),
    "lm_speculative": dict(spec_tokens=3),
}


@pytest.mark.parametrize("grid", sorted(_GRIDS))
def test_construction_runs_every_cell_once_and_writes_no_slot(devices8, grid):
    """A program's first execution loads it onto the device; the engine pays
    that for the whole grid before it serves (``_load_grid``), over padding
    rows and idle lanes: every cell has run (its staging buffers are back in
    the pool), and no group of any slot, nor a slot's last token, changed."""
    from distributed_tensorflow_tpu.models.causal_lm import CausalLM, CausalLMConfig

    if grid == "hybrid":
        model = SambaY(_CFG)
        params = sambay_init_params(model, jax.random.PRNGKey(1))
    else:
        model = CausalLM(CausalLMConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_position=48,
        ))
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 48), jnp.int32),
            jnp.ones((1, 48), bool),
        )["params"]
    engine = CausalLMEngine(model, params, **_ENGINE, **_GRIDS[grid])
    ran = set(engine._buf_pool)
    kind = "chunk" if engine._chunk_compiled else "prefill"
    cells = set(engine._chunk_compiled or engine._prefill_compiled)
    assert cells and {(kind, *cell) for cell in cells} <= ran
    assert ("decode",) in ran and (("verify",) in ran) == (grid == "lm_speculative")
    for leaf in jax.tree.leaves(engine._cache):
        assert not np.asarray(leaf, np.float32).any()
    assert not np.asarray(engine._last_token).any()


@pytest.mark.parametrize("kv", sorted(_LM_BASE_DECODE))
def test_lm_base_decode_lowers_to_the_program_it_was(kv):
    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )
    from distributed_tensorflow_tpu.serve.engine import _make_causal_decode

    cfg = CausalLMConfig(
        vocab_size=128, hidden_size=48, num_layers=2, num_heads=4,
        intermediate_size=96, max_position=32, dtype=jnp.bfloat16,
    )
    model, slots, cache_len = CausalLM(cfg), 4, 16
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
        jnp.ones((1, 8), bool),
    )["params"])
    table = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            (cfg.num_layers, slots, cache_len, *leaf.shape), leaf.dtype
        ),
        kvcache.cache_layout(cfg, kv),
    )
    lowered = jax.jit(
        _make_causal_decode(model, cache_len), donate_argnums=(1, 2, 3)
    ).lower(
        params, table, jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((4, slots), jnp.int32),
    )
    digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    assert digest == _LM_BASE_DECODE[kv]
