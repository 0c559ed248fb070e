"""BERT tests: param count, pretraining convergence, seq-parallel equivalence.

The seq-parallel equivalence test is the central long-context invariant:
ring-attention BERT over a 4-way "seq" axis must produce the same loss and
the same parameter updates as the dense single-shard model (SURVEY.md §5
long-context row + train/step.py seq-grad contract).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.data.text import (
    SyntheticMLM,
    SyntheticMLMConfig,
    bert_batch_specs,
    mlm_device_batches,
)
from distributed_tensorflow_tpu.models.bert import (
    BertConfig,
    BertForPreTraining,
    make_bert_eval_metrics,
    make_bert_pretraining_loss,
    mlm_gather_rows,
)
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.train import create_train_state, make_train_step
from distributed_tensorflow_tpu.train.step import place_state


def _tiny_cfg(**kw):
    return BertConfig(
        vocab_size=100,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position=64,
        dropout_rate=0.0,
        **kw,
    )


def _init(cfg, key=0, b=2, l=16):
    model = BertForPreTraining(cfg)
    variables = model.init(
        jax.random.key(key),
        jnp.zeros((b, l), jnp.int32),
        jnp.ones((b, l), bool),
        jnp.zeros((b, l), jnp.int32),
        train=False,
    )
    return model, variables["params"]


def _param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


@pytest.mark.slow
def test_bert_base_param_count():
    cfg = BertConfig()  # full base config
    model, params = _init(cfg, l=8)
    # bert-base-uncased encoder+embeddings+pooler: 109,482,240. Our extra
    # heads: MLM transform 768x768+768=590,592, LN 1,536, tied decoder bias
    # 30,522, NSP 768x2+2=1,538 → +624,188.
    encoder = _param_count(params["bert"])
    assert encoder == 109_482_240, encoder
    total = _param_count(params)
    assert total == 109_482_240 + 624_188, total


def test_bert_shapes_and_tied_decoder():
    cfg = _tiny_cfg()
    model, params = _init(cfg)
    mlm, nsp = model.apply(
        {"params": params},
        jnp.zeros((2, 16), jnp.int32),
        jnp.ones((2, 16), bool),
        jnp.zeros((2, 16), jnp.int32),
        train=False,
    )
    assert mlm.shape == (2, 16, 100) and nsp.shape == (2, 2)
    # Tied decoder: no separate [H, V] kernel — only the embedding table
    # itself and the decoder bias touch the vocab dim.
    big = sorted(
        p.shape for p in jax.tree.leaves(params) if 100 in p.shape
    )
    assert big == [(100,), (100, 32)], big


def test_bert_pretraining_converges(devices8):
    """Sync-DP BERT pretraining on the Markov-chain corpus: losses fall."""
    mesh = build_mesh({"data": -1})
    cfg = _tiny_cfg()
    model, params = _init(cfg, l=32)
    tx = optax.adam(3e-3)
    state = place_state(create_train_state(params, tx), mesh)
    step = make_train_step(make_bert_pretraining_loss(model), tx, mesh)
    data = SyntheticMLM(SyntheticMLMConfig(vocab_size=100, seq_len=32, seed=1))
    batches = mlm_device_batches(data, mesh, global_batch=64, seed=0)
    rng = jax.random.key(0)
    losses = []
    for _ in range(100):
        state, metrics = step(state, next(batches), rng)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses[:5] + losses[-5:]
    assert float(metrics["mlm_accuracy"]) > 0.05


def test_bert_seq_parallel_equals_dense(devices8):
    """4-way ring-attention BERT ≡ dense BERT: same loss, same updates."""
    results = {}
    for name, spec, seq_axis, seq_sharded in [
        ("dense", {"data": 2}, None, False),
        ("ring", {"data": 2, "seq": 4}, "seq", True),
    ]:
        devices = jax.devices()[: 2 if name == "dense" else 8]
        mesh = build_mesh(spec, devices=devices)
        # Init without the seq axis bound (init runs outside shard_map; the
        # param shapes are identical), then apply with the seq-parallel cfg.
        _, params = _init(_tiny_cfg(), key=7, l=32)
        model = BertForPreTraining(_tiny_cfg(seq_axis=seq_axis))
        tx = optax.sgd(0.1)
        state = place_state(create_train_state(params, tx), mesh)
        step = make_train_step(
            make_bert_pretraining_loss(model),
            tx,
            mesh,
            batch_spec=bert_batch_specs(mesh, seq_sharded=seq_sharded),
        )
        data = SyntheticMLM(SyntheticMLMConfig(vocab_size=100, seq_len=32, seed=2))
        batches = mlm_device_batches(
            data, mesh, global_batch=8, seq_sharded=seq_sharded, seed=0
        )
        rng = jax.random.key(3)
        ls = []
        for _ in range(3):
            state, metrics = step(state, next(batches), rng)
            ls.append(float(metrics["loss"]))
        results[name] = (
            ls,
            jax.tree.map(np.asarray, jax.device_get(state.params)),
        )

    np.testing.assert_allclose(results["ring"][0], results["dense"][0], rtol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        results["ring"][1],
        results["dense"][1],
    )


# ------------------------------------------- the MLM head's row gather

# Shapes at which the gather engages on the CPU: every shard (or micro-slice)
# holds 1,024 or 2,048 rows, of which the head runs over 256 or 512.
# name -> (mesh, seq-parallel, grad_accum, global batch, L, all content masked)
GATHER_CASES = {
    "single": ({"data": 1}, False, 1, 16, 128, False),
    "data8": ({"data": 8}, False, 1, 64, 128, False),
    "seq4": ({"data": 2, "seq": 4}, True, 1, 32, 256, False),
    "grad_accum2": ({"data": 2}, False, 2, 32, 128, False),
    "overflow": ({"data": 1}, False, 1, 16, 128, True),
}


def _gather_cfg(**kw):
    return BertConfig(
        vocab_size=1000, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position=256, dropout_rate=0.0, **kw,
    )


def _one_step(mesh, model, params, batch, *, mask_prob, seq, grad_accum):
    """One engine step whose optimizer keeps the reduced gradient as its
    state (a momentum trace with no decay) and moves nothing."""
    tx = optax.chain(optax.trace(decay=0.0), optax.scale(0.0))
    state = place_state(create_train_state(params, tx), mesh)
    step = make_train_step(
        make_bert_pretraining_loss(model, mask_prob=mask_prob), tx, mesh,
        batch_spec=bert_batch_specs(mesh, seq_sharded=seq),
        grad_accum=grad_accum, donate=False,
    )
    state, metrics = step(state, batch, jax.random.key(3))
    grads = jax.device_get(state.opt_state[0].trace)
    return {k: float(v) for k, v in metrics.items()}, grads


@pytest.mark.parametrize("case", sorted(GATHER_CASES))
def test_gathered_mlm_head_equals_dense(devices8, case):
    """The head over the masked rows alone gives the loss, the metrics and
    every gradient of the head over all rows, to float32 rounding: on one
    device, per ``data`` shard, per ``seq`` shard (each gathers its own
    rows, then the psum) and per micro-slice of gradient accumulation. A
    shard with more masked rows than the gather holds takes the dense
    branch and says so in ``mlm_head_share``."""
    spec, seq, grad_accum, gb, L, overflow = GATHER_CASES[case]
    n_dev = int(np.prod(list(spec.values())))
    mesh = build_mesh(spec, devices=jax.devices()[:n_dev])
    _, params = _init(_gather_cfg(), key=5, l=L)
    model = BertForPreTraining(_gather_cfg(seq_axis="seq" if seq else None))
    data = SyntheticMLM(SyntheticMLMConfig(vocab_size=1000, seq_len=L, seed=4))
    host = data.batch(gb, seed=11)
    if overflow:
        host["mlm_targets"] = np.where(
            host["input_ids"] >= 4, host["input_ids"], -1
        ).astype(np.int32)
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        bert_batch_specs(mesh, seq_sharded=seq),
    )
    batch = {k: jax.device_put(v, shardings[k]) for k, v in host.items()}
    local_rows = gb * L // n_dev // grad_accum
    k_rows = mlm_gather_rows(local_rows, 0.15)
    assert k_rows is not None and mlm_gather_rows(local_rows, 0.5) is None

    run = functools.partial(
        _one_step, mesh, model, params, batch, seq=seq, grad_accum=grad_accum
    )
    got, got_grads = run(mask_prob=0.15)
    want, want_grads = run(mask_prob=0.5)  # no gather at this rate: dense
    assert want.pop("mlm_head_share") == 1.0
    share = got.pop("mlm_head_share")
    assert share == (1.0 if overflow else pytest.approx(k_rows / local_rows))
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, err_msg=k)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    # the key biases' gradient is zero but for rounding (a softmax does not
    # see them): the floor is in units of the largest gradient
    floor = 1e-9 * max(np.abs(w).max() for w in flat_want.values())
    for path, g in jax.tree_util.tree_leaves_with_path(got_grads):
        w = flat_want[path]
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-6 * np.abs(w).max() + floor,
            err_msg=jax.tree_util.keystr(path),
        )


def test_gathered_mlm_head_in_eval_metrics_equals_dense():
    """Eval goes through the same helper: same sums, no gradients taken."""
    model, params = _init(_gather_cfg(), key=5, l=128)
    data = SyntheticMLM(SyntheticMLMConfig(vocab_size=1000, seq_len=128, seed=4))
    batch = {k: jnp.asarray(v) for k, v in data.batch(16, seed=11).items()}
    got = jax.jit(make_bert_eval_metrics(model))(params, {}, batch)
    want = jax.jit(make_bert_eval_metrics(model, mask_prob=0.5))(params, {}, batch)
    masked = batch["mlm_targets"] >= 0
    assert got["mlm_loss"][1] == want["mlm_loss"][1] == masked.sum()
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), got, want)
    # and both are the cross-entropy of the logits the model serves
    logits, _ = model.apply(
        {"params": params}, batch["input_ids"], batch["attention_mask"],
        batch["token_type_ids"], train=False,
    )
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.maximum(batch["mlm_targets"], 0)
    )
    np.testing.assert_allclose(got["mlm_loss"][0], (ce * masked).sum(), rtol=1e-5)


@pytest.mark.parametrize("rows,branch", [((2, 16), False), ((16, 128), True)])
def test_mlm_head_branches_only_where_the_gather_engages(rows, branch):
    """At tiny shapes the gather would hold half the rows or more: the head
    is dense statically, the program of before, with no conditional in it."""
    b, l = rows
    model, params = _init(_gather_cfg(), l=l)
    ids = jnp.zeros((b, l), jnp.int32)
    batch = {
        "input_ids": ids, "attention_mask": jnp.ones((b, l), bool),
        "token_type_ids": ids, "mlm_targets": ids,
        "nsp_label": jnp.zeros((b,), jnp.int32),
    }
    grad = jax.jit(jax.grad(make_bert_pretraining_loss(model), has_aux=True))
    text = grad.lower(params, {}, batch, jax.random.key(0)).as_text()
    assert ("stablehlo.case" in text or "stablehlo.if" in text) == branch


def test_bert_stale_mode(devices8):
    """BERT + staleness emulator (the flavors compose freely)."""
    mesh = build_mesh({"data": -1})
    cfg = _tiny_cfg()
    model, params = _init(cfg, l=32)
    tx = optax.adam(1e-3)
    state = place_state(create_train_state(params, tx, staleness=2), mesh)
    step = make_train_step(
        make_bert_pretraining_loss(model), tx, mesh, mode="stale", staleness=2
    )
    data = SyntheticMLM(SyntheticMLMConfig(vocab_size=100, seq_len=32, seed=3))
    batches = mlm_device_batches(data, mesh, global_batch=32, seed=0)
    rng = jax.random.key(0)
    losses = []
    for _ in range(30):
        state, metrics = step(state, next(batches), rng)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


@pytest.mark.slow
def test_bert_seq_parallel_flash_inner_equals_dense(devices8):
    """ring x flash THROUGH the full model: seq-parallel BERT with the
    Pallas kernel as the ring's inner step trains identically to dense."""
    results = {}
    for name, spec, seq_axis, seq_sharded, attn in [
        ("dense", {"data": 2}, None, False, "dense"),
        ("ringflash", {"data": 2, "seq": 4}, "seq", True, "flash"),
    ]:
        devices = jax.devices()[: 2 if name == "dense" else 8]
        mesh = build_mesh(spec, devices=devices)
        _, params = _init(_tiny_cfg(), key=7, l=32)
        model = BertForPreTraining(_tiny_cfg(seq_axis=seq_axis, attn_impl=attn))
        tx = optax.sgd(0.1)
        state = place_state(create_train_state(params, tx), mesh)
        step = make_train_step(
            make_bert_pretraining_loss(model),
            tx,
            mesh,
            batch_spec=bert_batch_specs(mesh, seq_sharded=seq_sharded),
        )
        data = SyntheticMLM(SyntheticMLMConfig(vocab_size=100, seq_len=32, seed=2))
        batches = mlm_device_batches(
            data, mesh, global_batch=8, seq_sharded=seq_sharded, seed=0
        )
        rng = jax.random.key(3)
        ls = []
        for _ in range(2):
            state, metrics = step(state, next(batches), rng)
            ls.append(float(metrics["loss"]))
        results[name] = (
            ls,
            jax.tree.map(np.asarray, jax.device_get(state.params)),
        )

    np.testing.assert_allclose(
        results["ringflash"][0], results["dense"][0], rtol=5e-4
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5),
        results["ringflash"][1],
        results["dense"][1],
    )


@pytest.mark.slow
def test_bert_seq_parallel_ulysses_equals_dense(devices8):
    """Ulysses SP through the full model: all-to-all head re-partitioning
    trains identically to the dense model (mirrors the ring test)."""
    results = {}
    for name, spec, seq_axis, seq_sharded in [
        ("dense", {"data": 2}, None, False),
        # tiny cfg has 2 heads -> 2-way seq (ulysses needs H % S == 0)
        ("ulysses", {"data": 2, "seq": 2}, "seq", True),
    ]:
        devices = jax.devices()[: 2 if name == "dense" else 4]
        mesh = build_mesh(spec, devices=devices)
        _, params = _init(_tiny_cfg(), key=7, l=32)
        model = BertForPreTraining(
            _tiny_cfg(seq_axis=seq_axis, sp_impl="ulysses")
        )
        tx = optax.sgd(0.1)
        state = place_state(create_train_state(params, tx), mesh)
        step = make_train_step(
            make_bert_pretraining_loss(model),
            tx,
            mesh,
            batch_spec=bert_batch_specs(mesh, seq_sharded=seq_sharded),
        )
        data = SyntheticMLM(SyntheticMLMConfig(vocab_size=100, seq_len=32, seed=2))
        batches = mlm_device_batches(
            data, mesh, global_batch=8, seq_sharded=seq_sharded, seed=0
        )
        rng = jax.random.key(3)
        ls = []
        for _ in range(2):
            state, metrics = step(state, next(batches), rng)
            ls.append(float(metrics["loss"]))
        results[name] = (
            ls,
            jax.tree.map(np.asarray, jax.device_get(state.params)),
        )

    np.testing.assert_allclose(
        results["ulysses"][0], results["dense"][0], rtol=1e-4
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        results["ulysses"][1],
        results["dense"][1],
    )
