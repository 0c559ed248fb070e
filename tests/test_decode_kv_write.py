"""``CausalLM.decode_step`` against write-then-attend, bit for bit.

decode_step attends the slot table as the step found it, with the new row
selected in, and writes all layers' rows once at the end by a select over
the stacked table (models/kvcache.py, "Why decode_step writes by
select"). The reference here is the plain spelling it replaced: per layer,
scatter the token with ``.at[idx, position].set(mode="drop")``, attend the
written table, re-stack. Same operand values, so on the CPU the returned
tables and the logits must be IDENTICAL — for mixed positions, idle lanes at
``position == cache_len``, a slot reused after a free, and the int8 pytree.
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
from distributed_tensorflow_tpu.models.kvcache import cached_attention
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
            q, k, v = att.query(x), att.key(x), att.value(x)
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
    pages = (
        cfg.num_layers, _SLOTS, _CACHE_LEN, cfg.num_heads,
        cfg.hidden_size // cfg.num_heads,
    )
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
