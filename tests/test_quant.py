"""Quantized serving tests: int8 weights + int8 KV cache (ROADMAP item 4).

Numerics pin the two independent int8 modes (models/quant.py) and their
composition with every serving feature that moves KV bytes: decode on one
chip and on a TP mesh, speculative decoding (bit-parity spec-on vs
spec-off is the contract quantization must not break), the prefix pool
(cached-vs-cold), and the disagg wire format (v3/v4 round-trip plus the
fail-closed fp32<->int8 cross-refusal in BOTH directions). Accounting
pins the /memz ledger: per-component dtypes, ``bytes_saved_vs_fp32``,
and the restore-time ``weight_quantization`` release.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from distributed_tensorflow_tpu.obs.metrics import ServeMetrics
from distributed_tensorflow_tpu.serve import BatcherConfig, ContinuousBatcher

# ---------------------------------------------------------------- fixtures


def _tiny_causal_lm():
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )

    cfg = CausalLMConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position=48,
    )
    model = CausalLM(cfg)
    L = cfg.max_position
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
    )
    return model, variables["params"]


@pytest.fixture(scope="module")
def tiny_lm(devices8):
    return _tiny_causal_lm()


@pytest.fixture(scope="module")
def plain_int8_engine(tiny_lm):
    """Minimal int8-weights + int8-KV engine: the quantized reference arm
    (no prefix cache, no speculation)."""
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    return CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8, weight_dtype="int8", kv_dtype="int8",
    )


def _transfer_engine(tiny_lm, *, weight_dtype, kv_dtype, memory):
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    return CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8, prefix_cache_mb=0.05, block_tokens=4,
        prefill_chunk=8, kv_transfer=True,
        weight_dtype=weight_dtype, kv_dtype=kv_dtype, memory=memory,
    )


@pytest.fixture(scope="module")
def int8_transfer(tiny_lm):
    """(engine, registry): int8 arm with prefix pool + wire transfer."""
    from distributed_tensorflow_tpu.obs.memory import MemoryRegistry

    registry = MemoryRegistry()
    return (
        _transfer_engine(
            tiny_lm, weight_dtype="int8", kv_dtype="int8", memory=registry
        ),
        registry,
    )


@pytest.fixture(scope="module")
def fp32_transfer(tiny_lm):
    """(engine, registry): the fp32 arm with identical serving knobs."""
    from distributed_tensorflow_tpu.obs.memory import MemoryRegistry

    registry = MemoryRegistry()
    return (
        _transfer_engine(
            tiny_lm, weight_dtype=None, kv_dtype=None, memory=registry
        ),
        registry,
    )


def _ref_greedy(model, params, prompt, n):
    """One-shot fp32 reference: n greedy tokens by re-running the FULL
    causal forward after each appended token — no cache, no quant."""
    import jax.numpy as jnp

    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        x = jnp.asarray([toks], jnp.int32)
        logits = model.apply(
            {"params": params}, x, jnp.ones((1, len(toks)), bool)
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ------------------------------------------------ round-trip error bounds


def test_weight_quant_roundtrip_bounds(tiny_lm):
    """Per-channel absmax: every dequantized kernel entry is within half a
    quantization step of the original, per OUTPUT channel."""
    import jax

    from distributed_tensorflow_tpu.models.quant import (
        dequantize_params,
        is_quantized_leaf,
        quantize_params,
    )

    _, params = tiny_lm
    qtree = quantize_params(params)
    flat = {
        tuple(str(p) for p in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            qtree, is_leaf=is_quantized_leaf
        )[0]
    }
    n_packed = sum(1 for v in flat.values() if is_quantized_leaf(v))
    assert n_packed > 0
    dq = dequantize_params(qtree)
    orig = {
        tuple(str(p) for p in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    checked = 0
    for path, leaf in flat.items():
        if not is_quantized_leaf(leaf):
            continue
        w = np.asarray(orig[path], np.float32)
        s = np.asarray(leaf["_q8_scale"], np.float32)
        q = np.asarray(leaf["_q8"])
        assert q.dtype == np.int8
        err = np.abs(w - q.astype(np.float32) * s)
        # round() puts every value within s/2 of its grid point (absmax
        # scaling means nothing clips).
        assert (err <= s / 2 + 1e-7).all()
        checked += 1
    assert checked == n_packed
    # Dequantizing the full tree reproduces every UNtouched leaf exactly.
    dq_emb = np.asarray(
        jax.tree_util.tree_flatten_with_path(dq)[0][0][1]
    )
    assert dq_emb.dtype == np.float32


def test_weight_quant_idempotent_and_shares_leaves(tiny_lm):
    import jax

    from distributed_tensorflow_tpu.models.quant import (
        is_quantized_leaf,
        is_quantized_tree,
        quantize_params,
    )

    _, params = tiny_lm
    q1 = quantize_params(params)
    assert is_quantized_tree(q1) and not is_quantized_tree(params)
    q2 = quantize_params(q1)
    # Idempotent: the packed dicts pass through BY IDENTITY.
    l1 = jax.tree.leaves(q1, is_leaf=is_quantized_leaf)
    l2 = jax.tree.leaves(q2, is_leaf=is_quantized_leaf)
    assert all(a is b for a, b in zip(l1, l2))
    # Non-kernel leaves (embeddings, biases, norms) are the ORIGINAL
    # arrays, shared not copied.
    shared = [
        a is b
        for a, b in zip(jax.tree.leaves(params), l1)
        if not is_quantized_leaf(b)
    ]
    assert shared  # embeddings/biases exist...
    # (identity can't be zipped structurally here — the packed dicts shift
    # alignment — so assert via the quantize contract instead: any leaf
    # that is NOT packed must appear in the original tree by identity)
    orig_ids = {id(x) for x in jax.tree.leaves(params)}
    for leaf in l1:
        if not is_quantized_leaf(leaf):
            assert id(leaf) in orig_ids


def test_kv_quant_roundtrip_bounds():
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.quant import (
        dequantize_kv,
        quantize_kv,
    )

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5)
    err = np.abs(np.asarray(x) - np.asarray(dequantize_kv(q, s)))
    # One absmax scale per position: error <= s/2 across its merged
    # heads * head_dim row.
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()
    # All-zero positions must stay finite (epsilon-floored scale, q == 0).
    q0, s0 = quantize_kv(jnp.zeros((1, 3, 8), jnp.float32))
    assert int(jnp.abs(q0).max()) == 0 and float(s0.min()) > 0


def test_normalize_quant_dtype_contract():
    from distributed_tensorflow_tpu.models.quant import normalize_quant_dtype

    assert normalize_quant_dtype(None) is None
    assert normalize_quant_dtype("bf16") == "bfloat16"
    assert normalize_quant_dtype("fp32") == "float32"
    with pytest.raises(ValueError, match="fp8"):
        normalize_quant_dtype("fp8", "weight_dtype")


# ------------------------------------- decode agreement vs fp32 reference


def _teacher_forced_agreement(engine, model, params, n_prompts=3, n_steps=6):
    """Per-step top-1 agreement of the QUANTIZED engine against the fp32
    full-forward reference. Teacher-forced: every probe re-submits the
    reference prefix with ``max_new_tokens=2``, so token 1 checks the
    prefill forward (int8 weights) and token 2 a decode step read from
    the int8 KV the prefill scatter quantized. Free-running agreement
    would cascade after one flip and measure luck, not error."""
    rng = np.random.default_rng(23)
    agree = total = 0
    with ContinuousBatcher(engine, BatcherConfig(max_batch=2)) as b:
        for _ in range(n_prompts):
            p = rng.integers(5, 64, size=int(rng.integers(5, 10)))
            ref = _ref_greedy(model, params, p, n_steps)
            for t in range(len(ref) - 1):
                forced = np.concatenate([p, np.asarray(ref[:t], np.int64)])
                out = b.submit(
                    {"input_ids": forced, "max_new_tokens": 2}
                ).result(timeout=120)["tokens"]
                agree += (out[0] == ref[t]) + (out[1] == ref[t + 1])
                total += 2
    return agree / total, total


def test_int8_agreement_single_chip(plain_int8_engine, tiny_lm):
    model, params = tiny_lm
    assert plain_int8_engine.weight_dtype == "int8"
    assert plain_int8_engine.kv_dtype == "int8"
    ratio, total = _teacher_forced_agreement(
        plain_int8_engine, model, params
    )
    assert total >= 30
    assert ratio >= 0.95  # measured 1.0 on this model (docs/PERF.md r19)


def test_int8_agreement_tp_mesh(tiny_lm):
    """Same agreement bar when int8 params and {q, s} cache leaves shard
    heads over a model axis (dp4-tp2 on 8 simulated devices)."""
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.serve import (
        CausalLMEngine,
        plan_serve_mesh,
    )

    model, params = tiny_lm
    spec, fell_back = plan_serve_mesh(tp=2, n_devices=8)
    assert not fell_back
    engine = CausalLMEngine(
        model, params, build_mesh(spec), buckets=(8, 16), slots=3,
        max_batch=2, max_new_tokens=8, weight_dtype="int8",
        kv_dtype="int8",
    )
    assert engine.layout != ""
    ratio, _ = _teacher_forced_agreement(engine, model, params)
    assert ratio >= 0.95


# ------------------------------------------------ spec parity under quant


def test_spec_parity_under_quant(plain_int8_engine, tiny_lm):
    """Speculative decoding stays BIT-IDENTICAL to the plain path when
    weights and KV are int8 — verify reads the same quantized pages the
    decode step would have written, so accept/reject is exact. Prompts
    embed the QUANTIZED model's own continuation (fp32-built prompts
    would measure weight error, not spec error) so the drafter genuinely
    engages."""
    from distributed_tensorflow_tpu.models.quant import (
        dequantize_params,
        quantize_params,
    )
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    dq = dequantize_params(quantize_params(params))
    rng = np.random.default_rng(31)
    reqs = []
    for seed in (3, 5):
        # Fixed-point predictive prompt against the DEQUANTIZED weights:
        # embed the model's own greedy continuation after marker t and end
        # with t — the n-gram drafter then proposes the exact upcoming
        # tokens (same construction as test_serve_spec.py).
        prng = np.random.default_rng(seed)
        t = int(prng.integers(5, 64))
        c = _ref_greedy(model, dq, prng.integers(5, 64, size=12), 5)
        for _ in range(6):
            p = [int(prng.integers(5, 64)), t] + c + [
                int(x) for x in prng.integers(5, 64, size=12 - 3 - len(c))
            ] + [t]
            c2 = _ref_greedy(model, dq, p, 5)
            if c2 == c:
                break
            c = c2
        reqs.append({"input_ids": np.array(p, np.int32),
                     "max_new_tokens": 5})
    reqs.append({
        "input_ids": rng.integers(5, 64, size=9), "max_new_tokens": 6,
    })

    spec_engine = CausalLMEngine(
        model, params, buckets=(8, 16), slots=3, max_batch=2,
        max_new_tokens=8, spec_tokens=3, weight_dtype="int8",
        kv_dtype="int8",
    )
    m = ServeMetrics()
    with ContinuousBatcher(
        spec_engine, BatcherConfig(max_batch=2), metrics=m
    ) as b:
        spec_out = [
            b.submit(dict(r)).result(timeout=120)["tokens"] for r in reqs
        ]
    with ContinuousBatcher(plain_int8_engine, BatcherConfig(max_batch=2)) as b:
        plain_out = [
            b.submit(dict(r)).result(timeout=120)["tokens"] for r in reqs
        ]
    assert spec_out == plain_out
    assert m.snapshot()["accepted_tokens"] > 0  # speculation really ran


# -------------------------------------------- prefix cache cached-vs-cold


def test_prefix_cache_cached_vs_cold_int8(
    int8_transfer, plain_int8_engine, tiny_lm
):
    """Pool pages quantize once at publish and gathers move {q, s}
    bit-exactly, so cache-hit streams equal the cache-free quantized
    engine's streams token for token — with real hits happening."""
    engine, _ = int8_transfer
    rng = np.random.default_rng(41)
    head = rng.integers(5, 64, size=12)
    reqs = [
        {
            "input_ids": np.concatenate(
                [head, rng.integers(5, 64, size=int(rng.integers(1, 4)))]
            ),
            "max_new_tokens": int(rng.integers(2, 7)),
        }
        for _ in range(3)
    ]
    with ContinuousBatcher(plain_int8_engine, BatcherConfig(max_batch=2)) as b:
        cold = [
            b.submit(dict(r)).result(timeout=120)["tokens"] for r in reqs
        ]
    m = ServeMetrics()
    with ContinuousBatcher(
        engine, BatcherConfig(max_batch=2), metrics=m
    ) as b:
        # Sequential warm: the head's pages publish before anyone matches.
        assert b.submit(dict(reqs[0])).result(timeout=120)["tokens"] == cold[0]
        futs = [b.submit(dict(r)) for r in reqs]
        cached = [f.result(timeout=120)["tokens"] for f in futs]
    assert cached == cold
    assert m.prefix_hits.value >= 3


# ----------------------------------------------------- wire format v3/v4


def test_wire_int8_chain_roundtrip_and_version_tamper():
    from distributed_tensorflow_tpu.serve.disagg import (
        _PREFIX,
        WIRE_VERSION,
        WIRE_VERSION_QUANT,
        WireError,
        deserialize_chain,
        serialize_chain,
    )

    meta = {"num_layers": 2, "block_tokens": 4, "heads": 2, "head_dim": 3,
            "dtype": "int8"}
    rng = np.random.default_rng(5)
    shape = (2, 3, 4, 2, 3)  # 3 blocks

    def side():
        return {
            "q": rng.integers(-127, 128, shape, dtype=np.int8),
            "s": rng.random(shape[:3], dtype=np.float32),
        }

    pk, pv = side(), side()
    ids = [int(x) for x in rng.integers(5, 64, size=12)]
    buf = serialize_chain(ids, pk, pv, meta)
    assert _PREFIX.unpack_from(buf)[1] == WIRE_VERSION_QUANT
    tids, k2, v2, header = deserialize_chain(buf)
    assert tids == ids and header["page_meta"]["dtype"] == "int8"
    for got, sent in ((k2, pk), (v2, pv)):
        assert got["q"].tobytes() == sent["q"].tobytes()
        assert got["s"].tobytes() == sent["s"].tobytes()
    # A bare int8 array without its scale tree must refuse at serialize.
    with pytest.raises(ValueError, match="scale tree"):
        serialize_chain(ids, pk["q"], pv["q"], meta)
    # Version<->dtype consistency is load-bearing: re-tagging the int8
    # buffer as v1 (what an old peer would claim) must refuse, and an
    # fp32 buffer re-tagged v3 must refuse — fail closed both ways.
    magic, _, hlen = _PREFIX.unpack_from(buf)
    with pytest.raises(WireError):
        deserialize_chain(
            _PREFIX.pack(magic, WIRE_VERSION, hlen) + buf[_PREFIX.size:]
        )
    fbuf = serialize_chain(
        ids,
        rng.random(shape, dtype=np.float32),
        rng.random(shape, dtype=np.float32),
        {**meta, "dtype": "float32"},
    )
    magic, fver, fhlen = _PREFIX.unpack_from(fbuf)
    assert fver == WIRE_VERSION
    with pytest.raises(WireError):
        deserialize_chain(
            _PREFIX.pack(magic, WIRE_VERSION_QUANT, fhlen)
            + fbuf[_PREFIX.size:]
        )
    # Scale bytes are covered by the CRC: flip one scale byte -> refuse.
    corrupt = bytearray(buf)
    corrupt[-3] ^= 0xFF
    with pytest.raises(WireError, match="CRC"):
        deserialize_chain(bytes(corrupt))


def test_wire_int8_stream_roundtrip():
    from distributed_tensorflow_tpu.serve.batcher import StreamState
    from distributed_tensorflow_tpu.serve.disagg import (
        _PREFIX,
        WIRE_VERSION_STREAM,
        WIRE_VERSION_STREAM_QUANT,
        WireError,
        deserialize_stream,
        serialize_stream,
    )

    meta = {"num_layers": 2, "cache_len": 24, "heads": 2, "head_dim": 3,
            "dtype": "int8"}
    rng = np.random.default_rng(9)
    st = StreamState(
        request_id="q-mig-1",
        input_ids=[int(t) for t in rng.integers(5, 60, size=8)],
        tokens=[int(t) for t in rng.integers(5, 60, size=4)],
        max_new_tokens=8, length=11,
    )
    shape = (2, 24, 2, 3)

    def stage():
        return {
            "q": rng.integers(-127, 128, shape, dtype=np.int8),
            "s": rng.random(shape[:2], dtype=np.float32),
        }

    pk, pv = stage(), stage()
    buf = serialize_stream(st, pk, pv, meta)
    assert _PREFIX.unpack_from(buf)[1] == WIRE_VERSION_STREAM_QUANT
    sd, k2, v2, header = deserialize_stream(buf)
    assert sd == st.to_dict() and header["n_tokens"] == 11
    # Exactly the settled positions round-trip, q and s together.
    for got, sent in ((k2, pk), (v2, pv)):
        assert got["q"].tobytes() == np.ascontiguousarray(
            sent["q"][:, :11]
        ).tobytes()
        assert got["s"].tobytes() == np.ascontiguousarray(
            sent["s"][:, :11]
        ).tobytes()
    # Page-less streams are ALWAYS v2 (nothing quantized to describe);
    # a v4 tag with no pages must refuse.
    pl = serialize_stream(st)
    magic, ver, hlen = _PREFIX.unpack_from(pl)
    assert ver == WIRE_VERSION_STREAM
    with pytest.raises(WireError, match="carry pages"):
        deserialize_stream(
            _PREFIX.pack(magic, WIRE_VERSION_STREAM_QUANT, hlen)
            + pl[_PREFIX.size:]
        )


def _chain_buf(engine, seed):
    """A 1-block chain serialized against ``engine``'s page geometry."""
    from distributed_tensorflow_tpu.serve.disagg import serialize_chain

    meta = engine.page_meta()
    rng = np.random.default_rng(seed)
    bt = meta["block_tokens"]
    shape = (meta["num_layers"], 1, bt, meta["heads"], meta["head_dim"])

    def side():
        if meta["dtype"] == "int8":
            return {
                "q": rng.integers(-127, 128, shape, dtype=np.int8),
                "s": rng.random(shape[:3], dtype=np.float32),
            }
        return rng.random(shape, dtype=np.float32)

    ids = [int(x) for x in rng.integers(5, 64, size=bt)]
    return serialize_chain(
        ids, side(), side(),
        {k: v for k, v in meta.items() if k != "max_chain"},
    )


def test_wire_cross_dtype_refusal_both_directions(
    int8_transfer, fp32_transfer
):
    """A REAL receiver (batcher + engine, the /v1/kv_transfer handler)
    must refuse a chain from the other arm's geometry in both directions
    and still adopt its own dtype — cross-dtype KV adoption fails closed,
    never reinterprets bytes."""
    from distributed_tensorflow_tpu.serve.disagg import (
        WireError,
        make_kv_receiver,
    )

    engines = {"int8": int8_transfer[0], "fp32": fp32_transfer[0]}
    batchers = {
        name: ContinuousBatcher(e, BatcherConfig(max_batch=2))
        for name, e in engines.items()
    }
    try:
        receivers = {
            name: make_kv_receiver(batchers[name], engines[name])
            for name in engines
        }
        for src, dst in (("int8", "fp32"), ("fp32", "int8")):
            with pytest.raises(WireError, match="dtype"):
                receivers[dst](_chain_buf(engines[src], seed=5))
        for name in engines:
            out = receivers[name](_chain_buf(engines[name], seed=6))
            assert out["adopted_blocks"] >= 1
    finally:
        for b in batchers.values():
            b.close()


# ------------------------------------------------------- /memz accounting


def test_memz_accounting_int8_vs_fp32(int8_transfer, fp32_transfer):
    """The registry must show WHERE int8 bytes went: per-component dtype
    labels, positive ``bytes_saved_vs_fp32`` for params + both KV pools,
    and the slots-at-fixed-budget arithmetic behind the r19 headline."""
    int8_engine, int8_reg = int8_transfer
    fp32_engine, fp32_reg = fp32_transfer
    snap8, snap32 = int8_reg.snapshot(), fp32_reg.snapshot()

    assert snap8["component_dtypes"]["lm_params"] == "int8"
    assert snap8["component_dtypes"]["kv_slot_cache"] == "int8"
    assert snap8["component_dtypes"]["kv_prefix_pool"] == "int8"
    assert snap32["component_dtypes"]["kv_slot_cache"] == "float32"
    for comp in ("lm_params", "kv_slot_cache", "kv_prefix_pool"):
        assert snap8["bytes_saved_vs_fp32"].get(comp, 0) > 0
    # Shape-sized components shrink; the BUDGET-sized prefix pool instead
    # packs more blocks into the same MB (the whole point of int8 KV).
    for comp in ("lm_params", "kv_slot_cache"):
        assert snap8["components"][comp] < snap32["components"][comp]
    assert int8_engine._pool_blocks > fp32_engine._pool_blocks
    assert snap8["bytes_saved_vs_fp32_total"] == sum(
        snap8["bytes_saved_vs_fp32"].values()
    )

    # Byte-per-token accounting: 2*nl*hidden*4 fp32 vs 2*nl*(hidden+4)
    # int8 (+4 = the per-position f32 scale) on the tiny config.
    assert fp32_engine.kv_bytes_per_token() == 512
    assert int8_engine.kv_bytes_per_token() == 144
    # The fixed-HBM-budget headline is deterministic arithmetic: the fp32
    # arm's slot-cache bytes re-divided by int8 per-slot bytes must admit
    # >= 1.7x the configured slots (ISSUE r19 acceptance).
    budget = snap32["components"]["kv_slot_cache"]
    assert budget // int8_engine.slot_page_bytes >= math.ceil(1.7 * 3)


def test_restore_quantizes_and_releases(tiny_lm, tmp_path):
    """restore_serving_state(weight_dtype="int8"): checkpoints stay fp32
    on disk, the restored tree comes back packed, the freed fp32 kernels
    land in the released ledger, and an engine built from the restored
    tree auto-detects quantization and still serves."""
    import optax

    from distributed_tensorflow_tpu.ckpt import (
        Checkpointer,
        restore_serving_state,
    )
    from distributed_tensorflow_tpu.models.quant import is_quantized_tree
    from distributed_tensorflow_tpu.obs.memory import MemoryRegistry
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.serve import CausalLMEngine
    from distributed_tensorflow_tpu.train import create_train_state
    from distributed_tensorflow_tpu.train.step import place_state

    model, params = tiny_lm
    mesh = build_mesh({"data": -1})
    tx = optax.sgd(0.05, momentum=0.9)
    state = place_state(create_train_state(params, tx), mesh)
    with Checkpointer(tmp_path / "ck", use_async=False) as ckpt:
        ckpt.save(1, state)
        ckpt.wait()
    registry = MemoryRegistry()
    template = place_state(create_train_state(params, tx), mesh)
    rparams, _, step = restore_serving_state(
        tmp_path / "ck", template, weight_dtype="int8", memory=registry,
    )
    assert step == 1 and is_quantized_tree(rparams)
    released = registry.snapshot()["released"]
    assert released.get("weight_quantization", 0) > 0
    assert released.get("opt_state", 0) > 0

    engine = CausalLMEngine(
        model, rparams, buckets=(8,), slots=2, max_batch=1,
        max_new_tokens=4,
    )
    assert engine.weight_dtype == "int8"  # auto-detected from the tree
    with ContinuousBatcher(engine, BatcherConfig(max_batch=1)) as b:
        out = b.submit(
            {"input_ids": np.arange(5, 10), "max_new_tokens": 3}
        ).result(timeout=120)
    assert len(out["tokens"]) == 3
