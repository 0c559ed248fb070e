"""``CausalLM.decode_step`` against write-then-attend, bit for bit.

decode_step attends the slot table as the step found it, with the new row
selected in, and writes all layers' rows once at the end, as rows of the
flat table (models/kvcache.py, "How decode_step writes and reads"). The
reference here is the plain spelling it replaced: per layer, scatter the
token with ``.at[idx, position].set(mode="drop")``, attend the written
table, re-stack. Same operand values, so on the CPU the returned tables and
the logits must be IDENTICAL — for mixed positions, idle lanes at
``position == cache_len``, a position past it, a slot reused after a free,
and the int8 pytree.

The attention both sides share contracts over the merged ``heads *
head_dim`` row; the last test holds it to per-head attention written out in
plain ``jax.numpy``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.causal_lm import (
    CausalLM,
    CausalLMConfig,
)
from distributed_tensorflow_tpu.models.kvcache import (
    cached_attention,
    chunk_attention,
)
from distributed_tensorflow_tpu.models.quant import quantize_kv

_SLOTS, _CACHE_LEN = 5, 24


class _WriteThenAttend(CausalLM):
    """The same parameters under the spelling decode_step had."""

    def decode_step(self, token, position, cache):
        k_cache, v_cache = cache["k"], cache["v"]
        x = self._embed(
            token, jnp.minimum(position, self.cfg.max_position - 1)
        )
        idx = jnp.arange(token.shape[0])
        clamped = jnp.minimum(position, _CACHE_LEN - 1)

        def write(table, row):
            return table.at[idx, position].set(row, mode="drop")

        new_k, new_v = [], []
        for i, layer in enumerate(self.layers):
            att = layer.attention
            q, k, v = att._qkv(x)  # a cached position is one merged row
            if isinstance(k_cache, dict):
                kc = jax.tree.map(
                    write, {n: t[i] for n, t in k_cache.items()},
                    dict(zip(("q", "s"), quantize_kv(k))),
                )
                vc = jax.tree.map(
                    write, {n: t[i] for n, t in v_cache.items()},
                    dict(zip(("q", "s"), quantize_kv(v))),
                )
            else:
                kc = write(k_cache[i], k.astype(k_cache.dtype))
                vc = write(v_cache[i], v.astype(v_cache.dtype))
            ctx = cached_attention(q, {"k": kc, "v": vc}, clamped)
            x = layer._ffn(att._finish(x, ctx))
            new_k.append(kc)
            new_v.append(vc)
        stack = lambda *layers: jnp.stack(layers)  # noqa: E731
        return self._head(x), {
            "k": jax.tree.map(stack, *new_k),
            "v": jax.tree.map(stack, *new_v),
        }


@pytest.fixture(scope="module")
def lm():
    cfg = CausalLMConfig(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=2,
        intermediate_size=64, max_position=48,
    )
    params = CausalLM(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool),
    )["params"]

    def step_of(model):
        return jax.jit(
            lambda tok, pos, cache: model.apply(
                {"params": params}, tok, pos, cache, method="decode_step"
            )
        )

    return cfg, step_of(CausalLM(cfg)), step_of(_WriteThenAttend(cfg))


def _table(cfg, kv, seed):
    """A table every page of which holds something: a write to the wrong
    place, or a missed one, changes a value."""
    rng = np.random.default_rng(seed)
    pages = (cfg.num_layers, _SLOTS, _CACHE_LEN, cfg.hidden_size)
    if kv == "int8":
        return {
            "q": jnp.asarray(rng.integers(-127, 128, pages), jnp.int8),
            "s": jnp.asarray(rng.uniform(0.001, 0.02, pages[:3]), jnp.float32),
        }
    return jnp.asarray(rng.normal(size=pages), kv)


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# positions of successive steps; _CACHE_LEN marks an idle lane
_SCENARIOS = {
    "mixed_positions": [[0, 7, 23, 3, 12]],
    "idle_lanes": [[_CACHE_LEN, 5, _CACHE_LEN, 0, _CACHE_LEN]],
    "all_idle": [[_CACHE_LEN] * _SLOTS],
    # past the sentinel is as idle as the sentinel: nothing matches
    "past_the_cache": [[_CACHE_LEN + 3, 5, 2 * _CACHE_LEN, 0, 1]],
    # slot 1 decodes at 9 and 10, is freed (idle), and its next occupant
    # starts at 2 under pages the first one left behind
    "slot_reused_after_free": [
        [4, 9, 0, 1, 2], [5, 10, 1, 2, 3], [6, _CACHE_LEN, 2, 3, 4],
        [7, 2, 3, 4, 5], [8, 3, 4, 5, 6],
    ],
}


@pytest.mark.parametrize("scenario", sorted(_SCENARIOS))
@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
def test_decode_step_is_write_then_attend_bit_for_bit(lm, kv, scenario):
    cfg, step, reference = lm
    rng = np.random.default_rng(1)
    cache = ref_cache = {"k": _table(cfg, kv, 2), "v": _table(cfg, kv, 3)}
    for positions in _SCENARIOS[scenario]:
        tok = jnp.asarray(rng.integers(5, cfg.vocab_size, _SLOTS), jnp.int32)
        pos = jnp.asarray(positions, jnp.int32)
        before = cache
        logits, cache = step(tok, pos, cache)
        ref_logits, ref_cache = reference(tok, pos, ref_cache)
        _same(cache, ref_cache)
        live = np.asarray(positions) < _CACHE_LEN
        # an idle lane's logits are garbage nobody reads, but the same garbage
        _same(logits, ref_logits)
        # ... and its slot's pages are untouched, as are all but one
        # position of a live slot's
        for new, old in zip(
            jax.tree.leaves(cache), jax.tree.leaves(before), strict=True
        ):
            new, old = np.asarray(new), np.asarray(old)
            np.testing.assert_array_equal(new[:, ~live], old[:, ~live])
            kept = np.ones((_SLOTS, _CACHE_LEN), bool)
            kept[np.flatnonzero(live), np.asarray(positions)[live]] = False
            np.testing.assert_array_equal(new[:, kept], old[:, kept])
            assert (new[:, ~kept] != old[:, ~kept]).any() or not live.any()


def _per_head_attention(q, k, v, position, k_scale=None, v_scale=None):
    """The attention of one layer written out head by head: ``q [R, C, h,
    d]``, ``k, v [R, L, h, d]`` (an int8 side comes with its ``[R, L]``
    scale), query ``[r, c]`` seeing cache positions ``<= position[r, c]``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    s = jnp.einsum("rchd,rlhd->rhcl", f32(q), f32(k))
    if k_scale is not None:
        s = s * k_scale[:, None, None, :]
    s = s / np.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[1]) <= position[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, None, None, :]
    return jnp.einsum("rhcl,rlhd->rchd", p, f32(v))


@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("which", ["cached", "chunk"])
def test_merged_row_attention_is_per_head_attention(which, kv):
    """``_attend`` contracts over the merged ``heads * head_dim`` row with a
    block-diagonal query and keeps each head's own lanes of the context;
    that is attention per head, for one query a slot and for a chunk."""
    rows, length, heads, dim, chunk = 4, 24, 3, 8, 1 if which == "cached" else 5
    rng = np.random.default_rng(11)
    dtype = jnp.float32 if kv == "int8" else jnp.dtype(kv)
    q = jnp.asarray(rng.normal(size=(rows, chunk, heads, dim)), dtype)
    position = jnp.asarray(rng.integers(0, length, (rows, chunk)), jnp.int32)
    sides, plain = {}, {}
    for name in ("k", "v"):
        if kv == "int8":
            page = rng.integers(-127, 128, (rows, length, heads, dim))
            scale = jnp.asarray(rng.uniform(0.001, 0.02, (rows, length)), jnp.float32)
            page = jnp.asarray(page, jnp.int8)
            sides[name] = {"q": page.reshape(rows, length, -1), "s": scale}
            plain[name], plain[name + "_scale"] = page, scale
        else:
            page = jnp.asarray(rng.normal(size=(rows, length, heads, dim)), dtype)
            sides[name] = page.reshape(rows, length, -1)
            plain[name] = page
    if which == "cached":
        got = jax.jit(cached_attention)(q[:, 0], sides, position[:, 0])[:, None]
    else:
        got = jax.jit(chunk_attention)(q, sides, position)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _per_head_attention(q, plain.pop("k"), plain.pop("v"), position, **plain)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=3e-2 if kv == "bfloat16" else 1e-5,
    )
