"""models/sambay.py against its plain reference, benchmarks/references/phi4_mini_flash.py.

Small on the CPU: 8 layers = Mamba, window, Mamba, window, Mamba + memory,
full, GMU, cross; width 64, 4 query and 2 K/V heads of 16, window 8,
vocabulary 128, float32 (exact on the CPU's matmuls). The reference
shares no code with the model (no flax, no kvcache, a position at a time
through the recurrence), so agreement here is agreement of two
implementations of the published layer equations. The served path — engine,
slots, batcher — is tests/test_sambay_serve.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import phi4_mini_flash as reference
from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.causal_lm import CausalLMConfig
from distributed_tensorflow_tpu.models.sambay import (
    SambaY,
    SambaYConfig,
    _ring_rows,
    layer_kinds,
    sambay_init_params,
)

_WINDOW = 8
_CFG = SambaYConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=8,
    num_heads=4, num_kv_heads=2, sliding_window=_WINDOW,
)
# the same sizes under the configuration file's keys, as the reference reads
REF_CFG = {
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "sliding_window": _WINDOW, "mb_per_layer": 2,
    "layer_norm_eps": 1e-5, "d_state": 16, "d_conv": 4,
}
_GROUPS = ("state", "window", "full")


@pytest.fixture(scope="module")
def tiny():
    model = SambaY(_CFG)
    return model, sambay_init_params(model, jax.random.PRNGKey(1))


def _rows(lengths, width, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(5, _CFG.vocab_size, (len(lengths), width)).astype(np.int32)
    mask = np.arange(width)[None] < np.asarray(lengths)[:, None]
    return ids, mask


def test_layers_follow_the_published_rule():
    assert layer_kinds(_CFG) == (
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross",
    )
    kinds = layer_kinds(SambaYConfig())
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] \
        == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[15] == "window"
    assert kinds[18] == "gmu" and kinds[31] == "cross"
    assert [reference.mixer_kind(REF_CFG, l) for l in range(8)] == list(
        layer_kinds(_CFG)
    )


def test_published_size_is_3_85_billion_and_caches_three_groups():
    """Shapes only (``eval_shape``): the parameter count ISSUE 35 derives,
    and the bytes of each group at the benchmark cell's 128 slots and 1,536
    positions."""
    model = SambaY(SambaYConfig(dtype=jnp.bfloat16))
    shapes = jax.eval_shape(
        lambda: sambay_init_params(model, jax.random.PRNGKey(0))
    )
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 3_852_562_944
    layout = model.cache_layout("bfloat16")
    assert kvcache.components(layout, (128, 1536)) == {
        "cache.state": (9 * 128 * (16 * 5120 * 4 + 3 * 5120 * 2), "float32"),
        "cache.window": (8 * 128 * 512 * 1280 * 2 * 2, "bfloat16"),
        "cache.full": (128 * 1536 * 1280 * 2 * 2, "bfloat16"),
    }
    assert kvcache.bytes_per_token(layout) == 5120  # one layer's K and V
    assert kvcache.step_writes(layout, 3) == {
        "state_bytes_written": 3 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2),
        "window_rows_written": 3 * 8 * 2,
        "full_rows_written": 3 * 2,
    }
    # eight layers read the table's K and V, in blocks of 128 positions where
    # the table is whole blocks; any other table is passed over whole
    assert kvcache.prefix_reads(layout, 1536) == {"full": (128, 8 * 2, 12)}
    assert kvcache.prefix_reads(layout, 1500) == {"full": (1500, 8 * 2, 1)}
    seen = np.asarray([0, 1, 256, 257, 1536])  # position + 1; 0: an idle lane
    assert kvcache.step_reads({"full": (256, 16, 6)}, seen) == {
        "full_blocks_read": (0 + 1 + 1 + 2 + 6) * 16,
        "full_blocks_total": 5 * 6 * 16,
    }
    # lm_base's 12 layers read K and V through the new-row form of the kernel
    # (PR 38); int8 K/V and the tests' toy heads pass over the whole table
    lm_base = CausalLMConfig(vocab_size=64)
    assert kvcache.prefix_reads(
        kvcache.cache_layout(lm_base, "bfloat16"), 384
    ) == {"kv": (128, 12 * 2, 3)}
    assert kvcache.prefix_reads(kvcache.cache_layout(lm_base, "int8"), 384) == {}
    assert kvcache.prefix_reads(kvcache.cache_layout(
        CausalLMConfig(vocab_size=64, hidden_size=48, num_heads=4), "bfloat16"
    ), 384) == {}


@pytest.mark.parametrize(
    "lengths", [(24, 24), (17, 9), (5, 1)],
    ids=["past_the_window", "ragged", "shorter_than_window"],
)
def test_forward_matches_the_reference(tiny, lengths):
    model, params = tiny
    ids, mask = _rows(lengths, 24)
    got = np.asarray(model.apply({"params": params}, ids, mask))
    want = np.asarray(reference.forward(REF_CFG, params, ids, mask))
    assert np.abs(want).max() > 0.3  # the logits are not all alike
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row, :n], atol=2e-6)


@functools.lru_cache(maxsize=None)
def _jitted(model, method):
    """One jitted program a method and shape for the whole file."""
    return jax.jit(
        lambda params, *args: model.apply({"params": params}, *args, method=method)
    )


def _prefill(tiny, ids, mask):
    model, params = tiny
    lengths = jnp.asarray(mask.sum(1), jnp.int32)
    return _jitted(model, "prefill_rows")(params, ids, mask, lengths)


def _readable(fresh, length):
    """What a reader may see of one row's fresh state: all of ``state``, the
    ring's filled rows, the table's real positions."""
    return {
        "state": fresh["state"],
        "window": jax.tree.map(
            lambda a: a[:, : min(length, _WINDOW)], fresh["window"]
        ),
        "full": jax.tree.map(lambda a: a[:, :length], fresh["full"]),
    }


@pytest.mark.parametrize("length", [5, 11, 16])
def test_prefill_scores_the_last_real_position_only_and_exactly(tiny, length):
    """Layers past the full-attention layer run at each row's last position
    only; the logits there are the full forward's."""
    model, params = tiny
    ids, mask = _rows((length,), 16, seed=length)
    logits, _ = _prefill(tiny, ids, mask)
    want = reference.forward(REF_CFG, params, ids, mask)[0, length - 1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("group", _GROUPS)
@pytest.mark.parametrize("length", [5, 13])
def test_one_prompt_padded_to_two_buckets_leaves_the_same_state(
    tiny, group, length
):
    """The state a slot is left with is the state AT THE ROW'S LENGTH: a pad
    has ``Delta = 0``, the conv tail is the last real inputs, the ring holds
    the last real rows. So the bucket a prompt was padded to cannot show."""
    ids, _ = _rows((length,), 32, seed=3)
    fresh = {}
    for bucket in (16, 32):
        mask = np.arange(bucket)[None] < length
        _, out = _prefill(tiny, ids[:, :bucket] * mask, mask)
        fresh[bucket] = jax.tree.map(lambda a: a[:, 0], out)
    a, b = (_readable(fresh[bucket], length)[group] for bucket in (16, 32))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.shape == y.shape and x.size
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


@pytest.mark.parametrize("length", [1, 7, 8, 9, 20, 29])
def test_ring_rows_keep_the_last_position_of_each_residue(length):
    rows = jnp.arange(32, dtype=jnp.float32)[None, :, None] * jnp.ones((1, 1, 3))
    ring = _ring_rows({"k": rows, "v": -rows}, jnp.asarray([length]), _WINDOW)
    for row in range(min(length, _WINDOW)):
        want = max(p for p in range(length) if p % _WINDOW == row)
        assert float(ring["k"][0, row, 0]) == want == -float(ring["v"][0, row, 0])


def _cache_from(tiny, fresh, slots, cache_len, slot):
    model, _ = tiny
    layout = model.cache_layout("float32")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    cache = kvcache.zeros(
        layout, (slots, cache_len), kvcache.shardings(layout, mesh)
    )
    # every page holds something, so a stray write shows
    cache = jax.tree.map(lambda a: a + jnp.asarray(0.5, a.dtype), cache)
    return kvcache.write_prompt(cache, jnp.asarray([slot], jnp.int32), fresh)


def test_prefill_then_cached_steps_match_the_full_forward(tiny):
    """Logits, not tokens: a prompt of 11 and then 26 teacher-forced steps
    through the cache — past the window three times over — against the
    reference's one forward over all 37 positions."""
    model, params = tiny
    prompt, steps, slots, cache_len = 11, 26, 3, 40
    ids, mask = _rows((prompt + steps,), prompt + steps, seed=9)
    _, fresh = _prefill(tiny, ids[:, :16] * (np.arange(16) < prompt),
                        np.arange(16)[None] < prompt)
    cache = _cache_from(tiny, fresh, slots, cache_len, slot=1)
    want = np.asarray(reference.forward(REF_CFG, params, ids, mask))[0]
    step = _jitted(model, "decode_step")
    for at in range(prompt, prompt + steps):
        tok = jnp.asarray([0, ids[0, at], 0], jnp.int32)
        pos = jnp.asarray([cache_len, at, cache_len], jnp.int32)
        logits, cache = step(params, tok, pos, cache)
        np.testing.assert_allclose(np.asarray(logits[1]), want[at], atol=5e-6)


def test_steps_through_the_table_kernel_match_the_full_forward():
    """Heads the kernel admits (8 query / 4 K/V heads of 64: rows of 256
    lanes) and a table of three blocks, so the two readers' read is
    ops/decode_attention.py, interpreted: a prompt of 250, then teacher-forced
    steps over the block's edge at 256, against the reference's one forward;
    the idle lanes beside it (length 0: nothing read) stay finite."""
    cfg = SambaYConfig(
        vocab_size=128, hidden_size=512, intermediate_size=128, num_layers=8,
        num_heads=8, num_kv_heads=4, sliding_window=_WINDOW,
    )
    ref_cfg = {**REF_CFG, "hidden_size": 512, "num_attention_heads": 8,
               "num_key_value_heads": 4}
    model = SambaY(cfg)
    params = sambay_init_params(model, jax.random.PRNGKey(3))
    prompt, steps, slots, cache_len = 250, 9, 3, 384
    layout = model.cache_layout("float32")
    assert kvcache.prefix_reads(layout, cache_len) == {"full": (128, 2 * 2, 3)}
    rng = np.random.default_rng(12)
    ids = rng.integers(5, cfg.vocab_size, (1, prompt + steps)).astype(np.int32)
    mask = np.arange(256)[None] < prompt
    _, fresh = _jitted(model, "prefill_rows")(
        params, ids[:, :256] * mask, mask, jnp.asarray([prompt], jnp.int32)
    )
    cache = _cache_from((model, params), fresh, slots, cache_len, slot=1)
    want = np.asarray(reference.forward(
        ref_cfg, params, ids, np.ones_like(ids, bool)
    ))[0]
    step = _jitted(model, "decode_step")
    assert "pallas_call" in str(jax.make_jaxpr(step)(
        params, jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32), cache,
    ))
    for at in range(prompt, prompt + steps):
        tok = jnp.asarray([0, ids[0, at], 0], jnp.int32)
        pos = jnp.asarray([cache_len, at, cache_len], jnp.int32)
        logits, cache = step(params, tok, pos, cache)
        assert np.isfinite(np.asarray(logits)).all()
        np.testing.assert_allclose(np.asarray(logits[1]), want[at], atol=2e-5)


@pytest.mark.parametrize("group", _GROUPS)
def test_an_idle_lane_changes_no_group(tiny, group):
    model, params = tiny
    ids, _ = _rows((9,), 16, seed=4)
    _, fresh = _prefill(tiny, ids * (np.arange(16) < 9), np.arange(16)[None] < 9)
    before = _cache_from(tiny, fresh, 3, 24, slot=0)
    # slot 0 decodes at position 9; slots 1 and 2 are idle (the sentinel,
    # and a position past it)
    pos = jnp.asarray([9, 24, 31], jnp.int32)
    _, after = _jitted(model, "decode_step")(
        params, jnp.asarray([7, 8, 9], jnp.int32), pos, before
    )
    for new, old in zip(
        jax.tree.leaves(after[group]), jax.tree.leaves(before[group]),
        strict=True,
    ):
        new, old = np.asarray(new), np.asarray(old)
        np.testing.assert_array_equal(new[:, 1:], old[:, 1:])
        assert (new[:, 0] != old[:, 0]).any()  # the live lane did write


def test_paired_attention_is_differential_attention_pair_by_pair():
    """``kvcache.paired_attention`` contracts over the merged K/V row against
    a block-diagonal query; written out pair by pair in numpy it is
    ``(softmax(q1 k1) - lam softmax(q2 k2)) [v1; v2]`` with pair ``p``
    reading K/V pair ``p // 2``."""
    rows, length, n_q, n_kv, d, lam = 3, 10, 8, 4, 16, 0.37
    rng = np.random.default_rng(2)
    q = rng.normal(size=(rows, n_q, d)).astype(np.float32)
    k = rng.normal(size=(rows, length, n_kv, d)).astype(np.float32)
    v = rng.normal(size=(rows, length, n_kv, d)).astype(np.float32)
    valid = np.arange(length)[None] < np.asarray([10, 4, 1])[:, None]
    got = np.asarray(kvcache.paired_attention(
        jnp.asarray(q),
        {"k": jnp.asarray(k.reshape(rows, length, -1)),
         "v": jnp.asarray(v.reshape(rows, length, -1))},
        jnp.asarray(valid), lam,
    ))

    def weights(qh, kh, seen):
        s = kh[seen] @ qh / np.sqrt(d)
        e = np.exp(s - s.max())
        return e / e.sum()

    for r in range(rows):
        for pair in range(n_q // 2):
            g = pair // 2
            w = weights(q[r, 2 * pair], k[r, :, 2 * g], valid[r]) \
                - lam * weights(q[r, 2 * pair + 1], k[r, :, 2 * g + 1], valid[r])
            v12 = np.concatenate([v[r, :, 2 * g], v[r, :, 2 * g + 1]], -1)
            np.testing.assert_allclose(
                got[r, pair], w @ v12[valid[r]], atol=1e-5
            )


@pytest.mark.parametrize("method", ["prefill_chunk", "verify_step"])
def test_forwards_that_need_pages_refuse(tiny, method):
    model, params = tiny
    with pytest.raises(NotImplementedError, match="SambaY has no"):
        model.apply(
            {"params": params}, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 2), jnp.int32), {}, method=method,
        )
