"""The seam of models/kvcache.py: the engine's half works for ANY layout.

serve/engine.py handles a cache as a pytree whose every leaf is ``[layers,
slots, *after, *trailing]``, addresses the slot axis and asks the layout for
sizes (a group of leaves says how many layers it has and what follows its
slots: positions here, a ring or nothing in tests/test_sambay.py). So its
generic operations — struct / zeros / take and put slots / take and put
blocks / publish / the one ``_wrap`` — must work unchanged over a layout the
repo does not ship: ``latent`` below is one leaf with trailing shape ``(r,)``
and no heads axis. It is a fixture of this test only, not an option.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.causal_lm import CausalLMConfig
from distributed_tensorflow_tpu.models.quant import _EPS
from distributed_tensorflow_tpu.parallel.mesh import build_mesh
from distributed_tensorflow_tpu.serve.engine import (
    CausalLMEngine,
    _make_export,
    _make_pool_import,
    _make_prefix_insert,
    _make_slot_import,
)

_NL, _SLOTS, _CACHE_LEN, _BLOCKS, _BT, _CHAIN = 12, 4, 16, 6, 4, 3


def _lm_base(kv):
    # lm_base's widths (benchmarks/configs/lm_base.json) under tp=2
    cfg = CausalLMConfig(
        vocab_size=40478, dtype=jnp.bfloat16,
        model_axis="model", model_parallel=2,
    )
    return kvcache.cache_layout(cfg, kv)


_LAYOUTS = {
    # name: (layout, leaves as (trailing, dtype, spec of a table), bytes/token)
    "dense_bf16": lambda: (
        _lm_base("bfloat16"),
        [((768,), "bfloat16", P(None, None, None, "model"))] * 2,
        2 * 12 * 768 * 2,
    ),
    "int8": lambda: (
        _lm_base("int8"),
        [
            ((768,), "int8", P(None, None, None, "model")),
            ((), "float32", P(None, None, None)),
        ] * 2,
        2 * 12 * (768 + 4),
    ),
    "latent": lambda: (
        {"c": kvcache.Leaf((40,), jnp.dtype(jnp.bfloat16), (None,), _NL)},
        [((40,), "bfloat16", P(None, None, None, None))],
        12 * 40 * 2,
    ),
}


def _fill(tree, seed):
    """Distinct values in every page: a gather or scatter that lands in the
    wrong place, or drops a leaf, changes a byte."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jax.device_put(
            rng.integers(-100, 100, a.shape).astype(a.dtype), a.sharding
        ),
        tree,
    )


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.parametrize("name", sorted(_LAYOUTS))
def test_engine_side_operations_work_over_any_layout(name):
    layout, want, per_token = _LAYOUTS[name]()
    mesh = build_mesh({"model": 2}, devices=jax.devices()[:2])
    table, pool = (_SLOTS, _CACHE_LEN), (_BLOCKS, _BT)

    # -- one tree.map over the description each
    assert kvcache.bytes_per_token(layout) == per_token
    sharding = kvcache.shardings(layout, mesh)
    spec = kvcache.specs(layout)
    lane_sharding = kvcache.shardings(layout, mesh, lane=True)
    cache = kvcache.zeros(layout, table, sharding)
    structs = kvcache.structs(layout, table, sharding)
    for leaf, st, sp, lane, (trailing, dtype, pspec) in zip(
        jax.tree.leaves(cache), jax.tree.leaves(structs),
        jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, P)),
        jax.tree.leaves(lane_sharding), want, strict=True,
    ):
        assert leaf.shape == st.shape == (_NL, *table, *trailing)
        assert leaf.dtype == st.dtype == jnp.dtype(dtype)
        assert sp == pspec and lane.spec == P(*tuple(pspec)[1:])
        assert leaf.sharding == st.sharding == NamedSharding(mesh, pspec)
        assert not np.asarray(leaf).any()

    # -- the executable bodies, plain and under the one _wrap
    sharded = types.SimpleNamespace(mesh=mesh, _model_sharded=True)
    rep, lane = P(), kvcache.specs(layout, lane=True)

    def both(fn, in_specs, out_specs):
        wrapped = CausalLMEngine._wrap(sharded, fn, in_specs, out_specs)
        assert wrapped is not fn
        plain = types.SimpleNamespace(mesh=mesh, _model_sharded=False)
        assert CausalLMEngine._wrap(plain, fn, in_specs, out_specs) is fn
        return jax.jit(fn), jax.jit(wrapped)

    cache = _fill(cache, 1)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    for take, put in zip(
        both(_make_export(), (spec, rep), lane),
        both(_make_slot_import(), (spec, rep, lane, rep, rep), (spec, rep)),
        strict=True,
    ):
        # take a slot's lane, put it into another slot of an empty table
        stage = take(cache, i32(2))
        for leaf, full in zip(
            jax.tree.leaves(stage), jax.tree.leaves(cache), strict=True
        ):
            assert leaf.shape == full.shape[:1] + full.shape[2:]
        empty = kvcache.zeros(layout, table, sharding)
        moved, last = put(
            empty, jnp.zeros(_SLOTS, jnp.int32), stage, i32(0), i32(7)
        )
        _same(take(moved, i32(0)), stage)
        _same(take(moved, i32(1)), take(empty, i32(1)))
        assert last.tolist() == [7, 0, 0, 0]

    for publish, take, put in zip(
        both(
            _make_prefix_insert(_CACHE_LEN, _BT),
            (spec, spec, rep, rep, rep), (spec, spec),
        ),
        both(_make_export(), (spec, rep), spec),
        both(_make_pool_import(), (spec, spec, rep), spec),
        strict=True,
    ):
        # publish slot 3's blocks 0, 2, 1 as pool blocks 5, 0, 3 (a sentinel
        # id drops), take them back as a chain, put the chain elsewhere
        empty = kvcache.zeros(layout, pool, sharding)
        filled, kept = publish(
            empty, cache, i32(3), i32([5, 0, 3, _BLOCKS]), i32([0, 2, 1, 0])
        )
        _same(kept, cache)
        chain = take(filled, i32([5, 3, 0]))
        for got, full in zip(
            jax.tree.leaves(chain), jax.tree.leaves(cache), strict=True
        ):
            lane3 = np.asarray(full)[:, 3]
            got = np.asarray(got)
            np.testing.assert_array_equal(
                got.reshape(got.shape[:1] + (-1,) + got.shape[3:]),
                lane3[:, : _CHAIN * _BT],
            )
        adopted = put(
            kvcache.zeros(layout, pool, sharding), chain,
            i32([1, _BLOCKS, 4]),
        )
        back = take(adopted, i32([1, 2, 4]))
        for got, sent in zip(
            jax.tree.leaves(back), jax.tree.leaves(chain), strict=True
        ):
            got, sent = np.asarray(got), np.asarray(sent)
            np.testing.assert_array_equal(got[:, 0], sent[:, 0])
            assert not got[:, 1].any()  # the sentinel's page never landed
            np.testing.assert_array_equal(got[:, 2], sent[:, 2])


def _parent_page(x, kv):
    """What the parent commit kept, and put on the wire, for fresh ``x [..,
    heads, head_dim]``: the cast, or int8 under one absmax scale a position
    taken over ``(heads, head_dim)``. Plain numpy."""
    if kv != "int8":
        return np.asarray(jnp.asarray(x).astype(kv))
    scale = np.maximum(
        np.abs(x).max(axis=(-2, -1)) / np.float32(127.0), np.float32(_EPS)
    )
    q = np.clip(np.round(x / scale[..., None, None]), -127, 127)
    return {"q": q.astype(np.int8), "s": scale}


@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
def test_host_boundary_speaks_pages_k_pages_v(kv):
    """What serve/disagg.py and the wire headers are handed is what they were
    before the cache had one home, and before a cached position became one
    merged row: ``(pages_k, pages_v)``, plain arrays or the ``{"q", "s"}``
    pair, ``[.., heads, head_dim]``, the payload's geometry and dtype — and
    for the same tokens the same bytes the parent exported."""
    nl, slots, cache_len, heads, head_dim, prompt = 3, 2, 8, 2, 16, 5
    cfg = CausalLMConfig(
        hidden_size=heads * head_dim, num_heads=heads, num_layers=nl
    )
    layout = kvcache.cache_layout(cfg, kv)
    assert kvcache.page_geometry(cfg, layout) == {
        "num_layers": nl, "heads": heads, "head_dim": head_dim, "dtype": kv,
    }
    mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
    sharding = kvcache.shardings(layout, mesh)
    stage = kvcache.zeros(layout, (slots, 4), sharding)
    pages_k, pages_v = kvcache.split_kv(stage, heads)
    if kv == "int8":
        assert sorted(pages_k) == sorted(pages_v) == ["q", "s"]
        assert pages_k["q"].shape == (nl, slots, 4, heads, head_dim)
        assert pages_k["s"].shape == (nl, slots, 4)
    else:
        assert pages_k.shape == pages_v.shape == (nl, slots, 4, heads, head_dim)
    _same(kvcache.join_kv(pages_k, pages_v), stage)

    # a prompt's per-head K and V, written as prefill writes them and taken
    # out as stream migration takes a slot's lane: the parent's bytes
    rng = np.random.default_rng(5)
    fresh = rng.normal(size=(2, nl, 1, prompt, heads, head_dim)).astype(
        np.float32
    )
    rows = jnp.asarray(fresh).reshape(2, nl, 1, prompt, heads * head_dim)
    cache = kvcache.write_prompt(
        kvcache.zeros(layout, (slots, cache_len), sharding),
        jnp.asarray([1], jnp.int32), dict(zip(("k", "v"), rows)),
    )
    lane = _make_export()(cache, jnp.asarray(1, jnp.int32))
    for got, x in zip(kvcache.split_kv(lane, heads), fresh, strict=True):
        got = jax.tree.map(lambda a: np.asarray(a)[:, :prompt], got)
        _same(got, _parent_page(x[:, 0], kv))
