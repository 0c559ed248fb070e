"""BERT with the Pallas flash-attention kernel ≡ dense BERT."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.bert import BertConfig, BertForPreTraining


def _cfg(**kw):
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


def test_bert_flash_equals_dense():
    ids = jnp.asarray(
        np.random.default_rng(0).integers(4, 100, (2, 32)), jnp.int32
    )
    amask = np.ones((2, 32), bool)
    amask[1, 28:] = False
    amask = jnp.asarray(amask)
    tt = jnp.zeros((2, 32), jnp.int32)
    dense = BertForPreTraining(_cfg())
    flash = BertForPreTraining(_cfg(attn_impl="flash"))
    params = dense.init(jax.random.key(0), ids, amask, tt, train=False)["params"]
    mlm_d, nsp_d = dense.apply({"params": params}, ids, amask, tt, train=False)
    mlm_f, nsp_f = flash.apply({"params": params}, ids, amask, tt, train=False)
    np.testing.assert_allclose(
        np.asarray(mlm_f), np.asarray(mlm_d), atol=1e-4
    )
    np.testing.assert_allclose(np.asarray(nsp_f), np.asarray(nsp_d), atol=1e-4)


def test_train_step_carries_the_scope_names_the_metrics_select():
    """The names benchmarks/layer_metrics/*.json select on the device are
    pinned: each part of the step under its scope, the three flash kernels
    apart, and the jitted step still called per_device_step."""
    import optax

    from distributed_tensorflow_tpu.data.text import bert_batch_specs
    from distributed_tensorflow_tpu.models.bert import make_bert_pretraining_loss
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import (
        create_train_state,
        make_rng,
        make_train_step,
    )

    def zeros_batch(b, l):
        ids = jnp.zeros((b, l), jnp.int32)
        return {
            "input_ids": ids, "attention_mask": jnp.ones((b, l), bool),
            "token_type_ids": ids, "mlm_targets": ids,
            "nsp_label": jnp.zeros((b,), jnp.int32),
        }

    model = BertForPreTraining(_cfg(attn_impl="flash"))
    batch = zeros_batch(8, 32)
    ids = batch["input_ids"]
    params = model.init(
        jax.random.key(0), ids, batch["attention_mask"], ids, train=False
    )["params"]
    tx = optax.adamw(1e-4)
    mesh = build_mesh({"data": -1})
    step = make_train_step(
        make_bert_pretraining_loss(model), tx, mesh,
        batch_spec=bert_batch_specs(mesh), clip_norm=1.0,
    )
    text = step.lower(
        create_train_state(params, tx, {}), batch, make_rng(0)
    ).as_text(debug_info=True)
    assert "@jit_per_device_step" in text
    # as scripts/trace_scopes.py finds a scope: jax writes the outermost one
    # of a differentiated function as jvp(mlm_head)
    for scope in ("flash_fwd", "flash_dq", "flash_dkv", "mlm_head",
                  "grad_reduce", "clip", "optimizer"):
        assert re.search(rf'[/("]{scope}[/)]', text), scope
    # the kernels keep `attention` innermost (the TPU compiler names the
    # custom call after it), and the head's backward carries its scope too
    assert "/flash_dkv/attention/pallas_call" in text
    assert re.search(r"transpose\(jvp\([^\"]*mlm_head[/)]", text)
    assert "stablehlo.case" not in text  # 256 rows: the head is dense here

    # At a shape where the head gathers its masked rows, the scope still
    # covers the index build, both branches with their backward passes (taken
    # inside the branches) and the scaling by the loss's cotangent.
    # 128 x 64: 1,024 rows a device on the eight-way data mesh
    text = step.lower(
        create_train_state(params, tx, {}), zeros_batch(128, 64), make_rng(0)
    ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    head = {n for n in names if re.search(r"[/(]mlm_head[/)]", n)}
    assert any(n.endswith("/cond/branch_1_fun/jit(sort)") for n in head)
    for branch in ("branch_0_fun", "branch_1_fun"):
        for way in ("jvp(", "transpose(jvp("):
            want = f"/cond/{branch}/{way}BertForPreTraining.mlm_logits)"
            assert any(want in n and n.endswith("word.attend/dot_general")
                       for n in head), want
    assert any(re.search(r"transpose\(jvp\([^)]*mlm_head\)+/mul$", n) for n in head)
    parts = ("word.attend/", "mlm_transform/", "mlm_ln/", "/cond/branch_")
    assert all(n in head for n in names if any(p in n for p in parts))
