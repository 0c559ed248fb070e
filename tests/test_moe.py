"""Expert parallelism: switch-routed MoE sharded over the "expert" axis.

Invariant: expert sharding is an execution layout, not a different model —
routing, capacity drops, outputs, and training trajectories must match the
single-shard expert stack exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_tpu.data import (
    device_batches,
    synthetic_image_classification,
)
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.parallel.moe import (
    expert_param_specs,
    moe_apply,
    stack_expert_params,
    switch_route,
)
from distributed_tensorflow_tpu.train import create_train_state, make_train_step
from distributed_tensorflow_tpu.train.step import make_state_specs, place_state

H, E, CLASSES = 16, 8, 10


def _expert_fn(p, tokens):
    return jnp.tanh(tokens @ p["w1"]) @ p["w2"]


def _init_params(key):
    keys = jax.random.split(key, E + 2)
    experts = [
        {
            "w1": jax.random.normal(keys[i], (H, 2 * H)) * 0.3,
            "w2": jax.random.normal(keys[i], (2 * H, H)) * 0.3,
        }
        for i in range(E)
    ]
    return jax.device_get(
        {
            "router": jax.random.normal(keys[-2], (H, E)) * 0.3,
            "experts": stack_expert_params(experts),
            "head": jax.random.normal(keys[-1], (H, CLASSES)) * 0.3,
        }
    )


def test_switch_route_capacity_and_slots():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(64, E)), jnp.float32)
    cap = 5
    assign, gate, slot, kept, aux = switch_route(logits, cap)
    assign, slot, kept = np.asarray(assign), np.asarray(slot), np.asarray(kept)
    for e in range(E):
        mine = kept & (assign == e)
        # No expert over capacity; slots within an expert are unique.
        assert mine.sum() <= cap
        slots = slot[mine]
        assert len(set(slots.tolist())) == len(slots)
        assert (slots < cap).all()
    assert float(aux) > 0
    assert (np.asarray(gate) > 1.0 / E - 1e-6).all()


def test_switch_route_pads_consume_no_capacity():
    """PAD tokens (valid=False) must not occupy capacity slots, displace
    real tokens, or bias the load-balance aux (ADVICE r2: pads routed like
    real tokens displaced real tokens into the dropped-overflow path)."""
    rng = np.random.default_rng(3)
    n, cap = 48, 3
    logits_real = jnp.asarray(rng.normal(size=(n, E)), jnp.float32)
    # Interleave pad rows between the real rows; pads get huge logits toward
    # expert 0 so the bug (pads consuming expert-0 capacity) would show.
    pad_logits = jnp.full((n, E), -1.0).at[:, 0].set(10.0)
    interleaved = jnp.stack([logits_real, pad_logits], 1).reshape(2 * n, E)
    valid = jnp.stack(
        [jnp.ones(n, bool), jnp.zeros(n, bool)], 1
    ).reshape(2 * n)

    a_ref, g_ref, s_ref, k_ref, aux_ref = switch_route(logits_real, cap)
    a, g, s, k, aux = switch_route(interleaved, cap, valid)

    # No pad is ever kept; real tokens keep exactly the slots they'd get
    # with no pads present; aux statistics match the pad-free batch.
    assert not bool(np.asarray(k)[1::2].any())
    np.testing.assert_array_equal(np.asarray(k)[0::2], np.asarray(k_ref))
    np.testing.assert_array_equal(
        np.asarray(s)[0::2][np.asarray(k_ref)],
        np.asarray(s_ref)[np.asarray(k_ref)],
    )
    assert np.isclose(float(aux), float(aux_ref), atol=1e-6)


def test_moe_apply_pads_emit_zero():
    """moe_apply with a valid mask returns 0 for invalid tokens (they ride
    the residual unchanged) and real-token outputs match a pad-free call:
    pads change nothing about real-token routing or outputs."""
    params = _init_params(jax.random.key(4))
    rng = np.random.default_rng(5)
    n_real, n_pad = 24, 8
    x_real = jnp.asarray(rng.normal(size=(n_real, H)), jnp.float32)
    x = jnp.concatenate([x_real, jnp.zeros((n_pad, H), jnp.float32)])
    valid = jnp.concatenate([jnp.ones(n_real, bool), jnp.zeros(n_pad, bool)])
    logits = x @ params["router"]

    y, aux = moe_apply(
        _expert_fn, params["experts"], logits, x, axis_name=None, valid=valid
    )
    assert np.abs(np.asarray(y)[n_real:]).max() == 0.0

    # True reference: the real tokens alone, with capacity_factor scaled so
    # capacity = ceil(cf * (n_real+n_pad) / E) matches the padded call
    # (capacity depends on N; the semantics under test don't).
    cf_ref = 1.25 * (n_real + n_pad) / n_real
    y_ref, aux_ref = moe_apply(
        _expert_fn,
        params["experts"],
        logits[:n_real],
        x_real,
        axis_name=None,
        capacity_factor=cf_ref,
    )
    np.testing.assert_allclose(
        np.asarray(y)[:n_real], np.asarray(y_ref), atol=1e-6
    )
    assert np.isclose(float(aux), float(aux_ref), atol=1e-6)


def test_moe_apply_matches_single_shard(devices8):
    params = _init_params(jax.random.key(0))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(32, H)), jnp.float32)
    logits = x @ params["router"]

    y_ref, aux_ref = moe_apply(
        _expert_fn, params["experts"], logits, x, axis_name=None
    )

    mesh = build_mesh({"expert": 8})
    specs = expert_param_specs(params["experts"])
    run = jax.jit(
        jax.shard_map(
            lambda p, lg, x: moe_apply(_expert_fn, p, lg, x, axis_name="expert"),
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    y_ep, aux_ep = run(params["experts"], logits, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep), atol=1e-5)
    assert np.isclose(float(aux_ref), float(aux_ep))


def _make_loss(ep: bool):
    axis = "expert" if ep else None

    def loss_fn(params, model_state, batch, rng):
        x = batch["image"].reshape(batch["image"].shape[0], -1)
        logits_r = x @ params["router"]
        y, aux = moe_apply(
            _expert_fn, params["experts"], logits_r, x, axis_name=axis
        )
        h = x + y  # residual: dropped tokens pass through
        logits = h @ params["head"]
        labels = batch["label"]
        loss = (
            optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
            + 0.01 * aux
        )
        acc = (jnp.argmax(logits, -1) == labels).mean()
        return loss, (model_state, {"accuracy": acc, "aux": aux})

    return loss_fn


def test_moe_training_matches_single_shard(devices8):
    params = _init_params(jax.random.key(2))
    ds = synthetic_image_classification(256, (4, 4, 1), CLASSES, seed=0)
    tx = optax.sgd(0.1, momentum=0.9)

    mesh_ref = build_mesh({"data": 2}, devices=jax.devices()[:2])
    state_ref = place_state(create_train_state(params, tx), mesh_ref)
    step_ref = make_train_step(_make_loss(False), tx, mesh_ref)
    batches_ref = device_batches(ds, mesh_ref, 32, seed=7)

    mesh_ep = build_mesh({"data": 2, "expert": 4})
    host_state = create_train_state(params, tx)
    pspecs = {
        "router": P(),
        "experts": expert_param_specs(params["experts"]),
        "head": P(),
    }
    specs = make_state_specs(host_state, tx, pspecs)
    state_ep = place_state(host_state, mesh_ep, specs)
    step_ep = make_train_step(_make_loss(True), tx, mesh_ep, state_specs=specs)
    batches_ep = device_batches(ds, mesh_ep, 32, seed=7)

    rng = jax.random.key(0)
    for _ in range(3):
        state_ref, m_ref = step_ref(state_ref, next(batches_ref), rng)
        state_ep, m_ep = step_ep(state_ep, next(batches_ep), rng)

    assert np.isclose(float(m_ref["loss"]), float(m_ep["loss"]), atol=1e-5)
    assert np.isclose(
        float(m_ref["grad_norm"]), float(m_ep["grad_norm"]), rtol=1e-4
    )
    flat_ref = jax.tree_util.tree_leaves_with_path(jax.device_get(state_ref.params))
    flat_ep = dict(jax.tree_util.tree_leaves_with_path(jax.device_get(state_ep.params)))
    for path, leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(leaf),
            np.asarray(flat_ep[path]),
            atol=1e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_switch_route_topk_semantics():
    """Top-2 routing (r5): renormalized gates, first-choice queue priority,
    per-expert capacity unchanged."""
    from distributed_tensorflow_tpu.parallel.moe import switch_route_topk

    logits = jnp.array(
        [
            [4.0, 3.0, 0.0],   # t0: e0 then e1
            [4.0, 3.0, 0.0],   # t1: e0 then e1
            [3.0, 4.0, 0.0],   # t2: e1 then e0
            [0.0, 0.0, 5.0],   # t3: e2 then (e0 or e1, tie -> lower idx e0)
        ]
    )
    assign, gate, slot, kept, aux = switch_route_topk(logits, capacity=2, k=2)
    assert assign.shape == (4, 2)
    np.testing.assert_array_equal(np.asarray(assign[:, 0]), [0, 0, 1, 2])
    # Gates renormalize over the chosen pair: sum to 1 per token.
    np.testing.assert_allclose(np.asarray(gate.sum(-1)), np.ones(4), rtol=1e-6)
    # First choices fill queues before ANY second choice: e0's queue is
    # [t0#1, t1#1] (capacity 2) -> t2's and t3's SECOND choices of e0 are
    # dropped; t0/t1's second choices land in e1's queue behind t2's first.
    kept = np.asarray(kept)
    assert kept[0, 0] and kept[1, 0] and kept[2, 0] and kept[3, 0]
    assert not kept[2, 1] and not kept[3, 1]  # e0 full from first choices
    assert kept[0, 1] and not kept[1, 1]      # e1: t2#1, then t0#2; t1#2 over
    assert float(aux) > 0


def test_moe_apply_topk2_matches_single_shard(devices8):
    """Top-2 dispatch equality across layouts (mirrors the top-1 set):
    sharded-expert moe_apply == single-shard reference, and the a2a layout
    matches too when capacity is ample (grouped quotas never bind)."""
    from distributed_tensorflow_tpu.parallel.moe import moe_apply_a2a

    params = _init_params(jax.random.key(0))
    stacked = params["experts"]
    rng = np.random.default_rng(1)
    n = 64
    x = jnp.asarray(rng.normal(size=(n, H)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(n, E)), jnp.float32)

    y_ref, aux_ref = moe_apply(
        _expert_fn, stacked, logits, x, axis_name=None,
        capacity_factor=16.0, topk=2,
    )
    assert float(jnp.abs(y_ref).sum()) > 0

    mesh = build_mesh({"expert": 8})
    specs = expert_param_specs(stacked)

    def run(fn, **kw):
        f = jax.jit(
            jax.shard_map(
                lambda s, l, xx: fn(
                    _expert_fn, s, l, xx, axis_name="expert",
                    capacity_factor=16.0, topk=2, **kw,
                ),
                mesh=mesh,
                in_specs=(specs, P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )
        return f(stacked, logits, x)

    y_ep, aux_ep = run(moe_apply)
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(y_ref), atol=1e-5
    )
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-6)

    y_a2a, _ = run(moe_apply_a2a, stats_axes=("expert",))
    np.testing.assert_allclose(
        np.asarray(y_a2a), np.asarray(y_ref), atol=1e-5
    )


# -- moe_dropless: top-k with no capacity, the held experts' part ----------

F = 24  # an expert's FFN width in the dropless cases


def _gated_experts(key, e=E):
    k1, k2 = jax.random.split(key)
    return {
        "gate_up": jax.random.normal(k1, (e, H, 2 * F)) * 0.3,
        "down": jax.random.normal(k2, (e, F, H)) * 0.3,
    }


def _dense_topk(x, logits, experts, k, renormalize=False):
    """One token at a time: its k largest softmax probabilities, each
    chosen expert's SiLU-gated FFN weighted by its probability."""
    x, experts = np.asarray(x, np.float64), jax.tree.map(np.asarray, experts)
    z = np.exp(logits - logits.max(-1, keepdims=True))
    probs = z / z.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-probs[t], kind="stable")[:k]
        gates = probs[t, chosen]
        if renormalize:
            gates = gates / gates.sum()
        for e, g in zip(chosen, gates):
            h = x[t] @ experts["gate_up"][e]
            a, b = h[:F], h[F:]
            out[t] += g * ((a / (1 + np.exp(-a)) * b) @ experts["down"][e])
    return out


def test_moe_dropless_is_a_dense_per_token_top_k():
    from distributed_tensorflow_tpu.parallel.moe import moe_dropless

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(40, H)), jnp.float32)
    logits = rng.normal(size=(40, E)).astype(np.float32)
    experts = _gated_experts(jax.random.key(3))
    y, choice = moe_dropless(x, jnp.asarray(logits), experts, 3)
    np.testing.assert_allclose(np.asarray(y), _dense_topk(x, logits, experts, 3),
                               atol=1e-5)
    assert choice.shape == (40, 3)


def test_moe_dropless_keeps_rows_over_any_would_be_capacity():
    """Every token's first choice is expert 5: 48 rows of one expert where a
    capacity of 1.25 x 48 / 8 = 8 would keep eight. All are computed."""
    from distributed_tensorflow_tpu.parallel.moe import moe_dropless

    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(48, H)), jnp.float32)
    logits = rng.normal(size=(48, E)).astype(np.float32)
    logits[:, 5] += 8.0
    experts = _gated_experts(jax.random.key(4))
    y, choice = moe_dropless(x, jnp.asarray(logits), experts, 2)
    assert (np.asarray(choice)[:, 0] == 5).all()
    np.testing.assert_allclose(np.asarray(y), _dense_topk(x, logits, experts, 2),
                               atol=1e-5)
    y_capped, _ = moe_apply(
        lambda p, t: _gated_ffn(p, t), experts, jnp.asarray(logits), x,
        axis_name=None, capacity_factor=1.25, topk=2,
    )
    assert not np.allclose(np.asarray(y_capped),
                           _dense_topk(x, logits, experts, 2, renormalize=True),
                           atol=1e-3)


def _gated_ffn(p, t):
    g, u = jnp.split(t @ p["gate_up"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ p["down"]


def test_moe_dropless_does_not_renormalise_the_gates():
    """The chosen probabilities as they are (DeepSeek-V2's
    ``norm_topk_prob: false``), times the scaling factor; renormalised only
    when asked."""
    from distributed_tensorflow_tpu.parallel.moe import moe_dropless

    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(16, H)), jnp.float32)
    logits = rng.normal(size=(16, E)).astype(np.float32)
    experts = _gated_experts(jax.random.key(5))
    plain, _ = moe_dropless(x, jnp.asarray(logits), experts, 3)
    scaled, _ = moe_dropless(x, jnp.asarray(logits), experts, 3, scale=2.5)
    norm, _ = moe_dropless(x, jnp.asarray(logits), experts, 3, renormalize=True)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(plain),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(norm), _dense_topk(x, logits, experts, 3, renormalize=True),
        atol=1e-5)
    assert not np.allclose(np.asarray(norm), np.asarray(plain), atol=1e-3)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_moe_dropless_shares_sum_to_the_uncut_layer(held):
    """The share test of expert-sharded MoE: the partial results of the
    held ranges (``E / held`` chips of ``held`` experts each), with the
    shared expert that every chip computes alike counted once, add up to
    the uncut layer."""
    from distributed_tensorflow_tpu.parallel.moe import moe_dropless

    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(32, H)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(32, E)), jnp.float32)
    experts = _gated_experts(jax.random.key(6))
    shared = jax.tree.map(lambda a: a[0], _gated_experts(jax.random.key(7), 1))
    whole, _ = moe_dropless(x, logits, experts, 3)
    whole = whole + _gated_ffn(shared, x)
    parts = [
        moe_dropless(x, logits, jax.tree.map(lambda a: a[lo:lo + held], experts),
                     3, first=lo)[0]
        for lo in range(0, E, held)
    ]
    np.testing.assert_allclose(np.asarray(sum(parts) + _gated_ffn(shared, x)),
                               np.asarray(whole), atol=1e-5)
    assert all(float(jnp.abs(p).sum()) > 0 for p in parts)
