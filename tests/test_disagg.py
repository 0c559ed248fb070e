"""Disaggregated prefill/decode serving (serve/disagg.py, ISSUE 17):
wire-format round-trip bit-exactness and refusal paths, the transfer
budget's queue/shed behavior, role planning in parallel.mesh, and the
real-engine transfer path — export from a prefill role's pool, adopt on
a ``kv_transfer=True`` decode role, streams bit-identical to the
full-forward greedy reference.
"""

from __future__ import annotations

import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_tensorflow_tpu.parallel.mesh import (
    DisaggPlan,
    plan_disagg_mesh,
)
from distributed_tensorflow_tpu.serve.batcher import Backpressure
from distributed_tensorflow_tpu.serve.disagg import (
    WIRE_VERSION,
    TransferBudget,
    WireError,
    deserialize_chain,
    make_kv_receiver,
    serialize_chain,
)
from distributed_tensorflow_tpu.serve.kvpool import KVBlockPool

META = {"num_layers": 2, "block_tokens": 4, "heads": 2, "head_dim": 3,
        "dtype": "float32", "max_chain": 8}


def _chain(n_blocks: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    shape = (META["num_layers"], n_blocks, META["block_tokens"],
             META["heads"], META["head_dim"])
    pk = rng.standard_normal(shape).astype(np.float32)
    pv = rng.standard_normal(shape).astype(np.float32)
    ids = list(rng.integers(5, 60, size=n_blocks * META["block_tokens"]))
    return ids, pk, pv


# ------------------------------------------------------------- wire format


def test_wire_round_trip_bit_exact():
    ids, pk, pv, = _chain(3)
    buf = serialize_chain(ids, pk, pv, META)
    ids2, k2, v2, header = deserialize_chain(buf)
    assert ids2 == [int(t) for t in ids]
    # Bit-exactness, not closeness: the decode role must read the very
    # bytes the prefill role computed.
    assert k2.tobytes() == pk.tobytes()
    assert v2.tobytes() == pv.tobytes()
    assert header["n_blocks"] == 3
    assert header["page_meta"]["dtype"] == "float32"


def test_wire_refuses_truncation():
    ids, pk, pv = _chain(2)
    buf = serialize_chain(ids, pk, pv, META)
    with pytest.raises(WireError, match="prefix"):
        deserialize_chain(buf[:6])
    with pytest.raises(WireError, match="truncated header"):
        deserialize_chain(buf[:12])
    with pytest.raises(WireError, match="payload"):
        deserialize_chain(buf[:-50])


def test_wire_refuses_bad_magic_and_corrupt_header():
    ids, pk, pv = _chain(2)
    buf = serialize_chain(ids, pk, pv, META)
    with pytest.raises(WireError, match="magic"):
        deserialize_chain(b"NOPE" + buf[4:])
    # Flip a byte inside the JSON header: parse must fail closed.
    corrupt = bytearray(buf)
    corrupt[12] = 0xFF
    with pytest.raises(WireError):
        deserialize_chain(bytes(corrupt))


def test_wire_refuses_version_from_the_future():
    ids, pk, pv = _chain(1)
    buf = serialize_chain(ids, pk, pv, META)
    future = buf[:4] + (WIRE_VERSION + 1).to_bytes(2, "big") + buf[6:]
    with pytest.raises(WireError, match="version"):
        deserialize_chain(future)


def test_wire_refuses_corrupt_payload_crc():
    ids, pk, pv = _chain(2)
    buf = bytearray(serialize_chain(ids, pk, pv, META))
    buf[-1] ^= 0x01  # one bit flip in the last v-page byte
    with pytest.raises(WireError, match="CRC"):
        deserialize_chain(bytes(buf))


def test_wire_refuses_token_key_coverage_mismatch():
    ids, pk, pv = _chain(2)
    # Token keys for 3 blocks but only 2 pages carried: a receiving pool
    # would index a block whose pages never arrived.
    with pytest.raises(ValueError, match="cover"):
        serialize_chain(ids + [1, 2, 3, 4], pk, pv, META)
    with pytest.raises(ValueError, match="page_meta"):
        serialize_chain(ids, pk, pv, {**META, "heads": 7})


# --------------------------------------------------------- transfer budget


def test_budget_grants_and_releases():
    b = TransferBudget(1000)
    b.acquire(600)
    b.acquire(400)
    d = b.digest()
    assert d["in_flight_bytes"] == 1000 and d["granted_total"] == 2
    b.release(600)
    b.acquire(500)
    b.release(900)
    assert b.digest()["in_flight_bytes"] == 0


def test_budget_sheds_oversized_immediately():
    b = TransferBudget(100, timeout_s=5.0)
    t0 = time.monotonic()
    with pytest.raises(Backpressure):
        b.acquire(101)  # can never fit: no point waiting
    assert time.monotonic() - t0 < 1.0
    assert b.digest()["shed_total"] == 1


def test_budget_sheds_on_timeout_and_full_queue():
    b = TransferBudget(100, max_queued=1, timeout_s=0.05)
    b.acquire(80)
    with pytest.raises(Backpressure):
        b.acquire(40)  # queues, then times out
    # Saturate the waiter queue from a thread, then the next acquire
    # must shed immediately instead of queueing behind it.
    b2 = TransferBudget(100, max_queued=1, timeout_s=1.0)
    b2.acquire(100)
    started = threading.Event()

    def waiter():
        started.set()
        try:
            b2.acquire(50)
        except Backpressure:
            pass

    t = threading.Thread(target=waiter)
    t.start()
    started.wait()
    time.sleep(0.05)  # let the waiter enter the queue
    with pytest.raises(Backpressure):
        b2.acquire(50)
    b2.release(100)  # unblocks the queued waiter
    t.join(timeout=5)
    assert b2.digest()["queued"] == 0


def test_budget_waiter_unblocks_on_release():
    b = TransferBudget(100, timeout_s=5.0)
    b.acquire(100)
    got = threading.Event()

    def waiter():
        b.acquire(60)
        got.set()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.02)
    assert not got.is_set()
    b.release(100)
    t.join(timeout=5)
    assert got.is_set()
    assert b.digest()["in_flight_bytes"] == 60


def test_budget_validates_cap():
    with pytest.raises(ValueError, match="cap_bytes"):
        TransferBudget(0)


# ------------------------------------------------------------ role planning


def test_plan_disagg_mesh_splits_devices():
    p = plan_disagg_mesh(8, prefill_tp=2, decode_tp=4)
    assert isinstance(p, DisaggPlan) and not p.fell_back
    assert p.prefill_device_ids == (0, 1, 2, 3)
    assert p.decode_device_ids == (4, 5, 6, 7)
    assert p.prefill_axes == {"data": 2, "model": 2}
    assert p.decode_axes == {"data": 1, "model": 4}


def test_plan_disagg_mesh_explicit_split_and_shrink():
    p = plan_disagg_mesh(8, prefill_devices=2)
    assert p.prefill_device_ids == (0, 1)
    assert len(p.decode_device_ids) == 6
    # Asking for every device as prefill leaves decode nothing: shrink
    # with a note rather than refuse.
    p = plan_disagg_mesh(4, prefill_devices=4)
    assert p.prefill_device_ids == (0, 1, 2)
    assert p.decode_device_ids == (3,)
    assert p.notes


def test_plan_disagg_mesh_tp_falls_back_to_divisor():
    p = plan_disagg_mesh(8, prefill_devices=3, prefill_tp=2)
    # tp=2 does not divide the 3 prefill chips: largest divisor wins.
    assert p.prefill_axes["model" if "model" in p.prefill_axes else "data"]
    assert np.prod(list(p.prefill_axes.values())) == 3
    assert p.notes


def test_plan_disagg_mesh_single_device_colocates():
    p = plan_disagg_mesh(1)
    assert p.fell_back
    assert p.prefill_device_ids == p.decode_device_ids == (0,)


def test_plan_disagg_mesh_rejects_nonsense():
    with pytest.raises(ValueError):
        plan_disagg_mesh(0)
    with pytest.raises(ValueError):
        plan_disagg_mesh(8, prefill_devices=0)
    with pytest.raises(ValueError):
        plan_disagg_mesh(8, prefill_tp=0)


# ------------------------------------------------------- kvpool peek


def test_kvpool_cached_len_peeks_without_pinning():
    pool = KVBlockPool(8, 4)
    prompt = list(range(1, 13))
    pool.insert(prompt)
    # Same one-token-suffix cap as match, but no pin: release not needed.
    assert pool.cached_len(prompt) == 8
    # One extra token lifts the cap past the last inserted block.
    assert pool.cached_len(prompt + [99]) == 12
    assert pool.cached_len([7, 7, 7, 7]) == 0
    m = pool.match(prompt)  # still fully matchable: nothing was pinned
    assert m.cached_len == 8
    pool.release(m)


# ------------------------------------------- real engines: transfer + parity


def _tiny_causal_lm():
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.causal_lm import (
        CausalLM,
        CausalLMConfig,
    )

    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=48,
    )
    model = CausalLM(cfg)
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.max_position), jnp.int32),
        jnp.ones((1, cfg.max_position), bool),
    )
    return model, variables["params"]


@pytest.fixture(scope="module")
def tiny_lm(devices8):
    return _tiny_causal_lm()


def _role_engine(tiny_lm):
    from distributed_tensorflow_tpu.serve import CausalLMEngine

    model, params = tiny_lm
    return CausalLMEngine(
        model, params, buckets=(8, 32), slots=3, max_batch=2,
        max_new_tokens=8, prefix_cache_mb=0.25, block_tokens=4,
        prefill_chunk=8, kv_transfer=True,
    )


@pytest.fixture(scope="module")
def role_pair(tiny_lm):
    """A prefill-role and decode-role client pair over shared params,
    wired through DisaggServingPair on the WIRE transport (the loopback
    rehearsal of POST /v1/kv_transfer)."""
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.serve import (
        BatcherConfig,
        Client,
        DisaggServingPair,
    )

    pre_c = Client(_role_engine(tiny_lm),
                   BatcherConfig(max_batch=2, max_queue=32, max_in_flight=2))
    dec_c = Client(_role_engine(tiny_lm),
                   BatcherConfig(max_batch=2, max_queue=32, max_in_flight=2),
                   recorder=FlightRecorder(512))
    budget = TransferBudget(16 * 1024 * 1024)
    pair = DisaggServingPair(
        prefill_batcher=pre_c.batcher,
        decode_batcher=dec_c.batcher,
        prefill_engine=pre_c.engine,
        decode_engine=dec_c.engine,
        budget=budget,
        transport="wire",
        metrics=dec_c.metrics,
        recorder=dec_c.recorder,
    )
    yield pair, pre_c, dec_c, budget
    pre_c.close()
    dec_c.close()


def _ref_greedy(model, params, prompt, n):
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


def test_disagg_streams_match_full_forward_reference(role_pair, tiny_lm):
    pair, _, dec_c, _ = role_pair
    model, params = tiny_lm
    rng = np.random.default_rng(3)
    hits0 = dec_c.metrics.prefix_hits.value
    for i in range(3):
        prompt = rng.integers(5, 64, size=int(rng.integers(12, 25)))
        n = int(rng.integers(2, 5))
        got = pair.generate(
            {"input_ids": prompt, "max_new_tokens": n}
        )["tokens"]
        assert got == _ref_greedy(model, params, prompt, n), f"request {i}"
    # Distinct prompts: any decode-side hit can ONLY be an adopted chain.
    assert dec_c.metrics.prefix_hits.value > hits0


def test_transfer_records_events_and_metrics(role_pair):
    pair, _, dec_c, budget = role_pair
    rng = np.random.default_rng(9)
    prompt = rng.integers(5, 64, size=20)
    pair.generate({"input_ids": prompt, "max_new_tokens": 2})
    kinds = [e["kind"] for e in dec_c.recorder.events()]
    assert "kv_transfer_start" in kinds and "kv_transfer_done" in kinds
    snap = dec_c.metrics.snapshot()
    assert snap["kv_transfer_bytes"]["decode"] > 0
    assert snap["kv_transfer_seconds"]["decode"]["count"] >= 1
    assert budget.digest()["granted_total"] >= 1
    assert budget.digest()["in_flight_bytes"] == 0


def test_export_import_round_trip_pages_bit_exact(role_pair):
    """The transferred pages ARE the prefill role's pool bytes: export a
    published chain, wire round-trip, and compare against a direct host
    read of the source pool."""
    import jax

    pair, pre_c, _, _ = role_pair
    eng = pre_c.engine
    rng = np.random.default_rng(17)
    prompt = rng.integers(5, 64, size=16)
    pre_c.call({"input_ids": prompt, "max_new_tokens": 1}, timeout=300)
    pool = eng.prefix_cache
    m = pool.match(list(prompt) + [1])  # +1 token: match every block
    try:
        assert m.blocks, "prefill publish must index the prompt"
        pk, pv = eng.export_prefix_pages(m.blocks)
        n = len(m.blocks)
        host_k = np.asarray(jax.device_get(pk))[:, :n]
        # A publish on the batcher thread donates the pool buffer and
        # rebinds the ref; _pool_lock is what orders a read against it.
        with eng._pool_lock:
            src = np.asarray(jax.device_get(eng._pool["k"]))[:, m.blocks]
        assert host_k.tobytes() == src.tobytes()
        ids = [int(t) for t in prompt[: n * pool.block_tokens]]
        buf = serialize_chain(ids, host_k,
                              np.asarray(jax.device_get(pv))[:, :n],
                              eng.page_meta())
        ids2, k2, _, _ = deserialize_chain(buf)
        assert k2.tobytes() == host_k.tobytes() and ids2 == ids
    finally:
        pool.release(m)


def test_receiver_refuses_geometry_mismatch_and_garbage(role_pair):
    pair, _, dec_c, _ = role_pair
    receive = make_kv_receiver(dec_c.batcher, dec_c.engine,
                               recorder=dec_c.recorder)
    with pytest.raises(WireError):
        receive(b"garbage bytes, not a chain")
    ids, pk, pv = _chain(2)
    # META geometry differs from the tiny engine's: refuse, don't adopt.
    buf = serialize_chain(ids, pk, pv, META)
    with pytest.raises(WireError, match="geometry"):
        receive(buf)
    causes = [e.get("cause") for e in dec_c.recorder.events()
              if e["kind"] == "kv_transfer_reject"]
    assert "wire" in causes and "geometry" in causes


def test_receiver_budget_shed_raises_backpressure(role_pair):
    pair, pre_c, dec_c, _ = role_pair
    eng = pre_c.engine
    rng = np.random.default_rng(23)
    prompt = rng.integers(5, 64, size=16)
    pre_c.call({"input_ids": prompt, "max_new_tokens": 1}, timeout=300)
    pool = eng.prefix_cache
    m = pool.match(list(prompt) + [1])
    try:
        import jax

        n = len(m.blocks)
        pk, pv = eng.export_prefix_pages(m.blocks)
        buf = serialize_chain(
            [int(t) for t in prompt[: n * pool.block_tokens]],
            np.asarray(jax.device_get(pk))[:, :n],
            np.asarray(jax.device_get(pv))[:, :n],
            eng.page_meta(),
        )
    finally:
        pool.release(m)
    tiny = TransferBudget(1, max_queued=1, timeout_s=0.05)
    receive = make_kv_receiver(dec_c.batcher, dec_c.engine, budget=tiny)
    with pytest.raises(Backpressure):
        receive(buf)
    assert tiny.digest()["shed_total"] == 1
    # Under a roomy budget the same buffer adopts cleanly.
    receive_ok = make_kv_receiver(dec_c.batcher, dec_c.engine,
                                  budget=TransferBudget(1 << 24))
    out = receive_ok(buf)
    assert out["bytes"] == len(buf)


def test_adopt_chain_fails_cleanly_on_closed_batcher(tiny_lm):
    from distributed_tensorflow_tpu.serve import BatcherConfig, Client

    c = Client(_role_engine(tiny_lm),
               BatcherConfig(max_batch=2, max_queue=8, max_in_flight=1))
    c.close()
    with pytest.raises(RuntimeError):
        c.batcher.adopt_chain([1, 2, 3, 4])


def test_kv_transfer_http_route(role_pair):
    """POST /v1/kv_transfer end to end: garbage -> 400, a well-formed
    chain -> 200 + adoption digest, and /statusz carries the budget."""
    import json

    from distributed_tensorflow_tpu.serve import build_http_server

    pair, pre_c, dec_c, _ = role_pair
    budget = TransferBudget(1 << 24)
    receiver = make_kv_receiver(dec_c.batcher, dec_c.engine, budget=budget)
    server = build_http_server(dec_c, port=0, kv_receiver=receiver,
                               transfer_budget=budget)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = "http://%s:%d" % server.server_address
    try:
        req = urllib.request.Request(
            base + "/v1/kv_transfer", data=b"not a chain", method="POST",
            headers={"Content-Type": "application/octet-stream"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("garbage must not adopt")
        except urllib.error.HTTPError as e:
            assert e.code == 400

        import jax

        eng = pre_c.engine
        rng = np.random.default_rng(29)
        prompt = rng.integers(5, 64, size=16)
        pre_c.call({"input_ids": prompt, "max_new_tokens": 1}, timeout=300)
        pool = eng.prefix_cache
        m = pool.match(list(prompt) + [1])
        try:
            n = len(m.blocks)
            pk, pv = eng.export_prefix_pages(m.blocks)
            buf = serialize_chain(
                [int(t) for t in prompt[: n * pool.block_tokens]],
                np.asarray(jax.device_get(pk))[:, :n],
                np.asarray(jax.device_get(pv))[:, :n],
                eng.page_meta(),
            )
        finally:
            pool.release(m)
        req = urllib.request.Request(
            base + "/v1/kv_transfer", data=buf, method="POST",
            headers={"Content-Type": "application/octet-stream"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["bytes"] == len(buf)

        with urllib.request.urlopen(base + "/statusz", timeout=10) as r:
            status = json.loads(r.read())
        assert status["kv_transfer"]["granted_total"] >= 1
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
