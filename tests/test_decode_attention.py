"""ops/decode_attention.py, interpreted on the CPU, against the plain
statements it replaces: for a prefix ``kvcache.paired_attention`` with the
mask ``arange(L) < lengths``, for plain heads over a table that lacks the
step's own row ``kvcache._attend`` over ``select_rows(table, rows,
position)``, and for one latent row a position the mask form of
``kvcache.latent_attention``. Times and the compile for the chip are
elsewhere (scripts/table_attention_bench.py, tests/test_chip_compile.py)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.ops import decode_attention
from distributed_tensorflow_tpu.ops.decode_attention import (
    latent_row_attention,
    row_attention,
    table_attention,
)

_CACHE_LEN = 64
# (query heads, K/V heads, head size): the reasoning cell's, the least the
# kernel admits (one group of eight heads over one 256-lane window), and a
# query head a K/V head (four pairs a window)
_HEADS = {"cell": (40, 20, 64), "small": (8, 4, 64), "one_to_one": (8, 8, 64)}
_TOLERANCE = {"float32": 2e-6, "bfloat16": 1.5e-2}  # of values up to ~2


@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("dtype", sorted(_TOLERANCE))
@pytest.mark.parametrize("heads", sorted(_HEADS))
def test_table_attention_is_paired_attention_over_each_slots_prefix(
    heads, dtype, block
):
    """Ragged lengths: 0 (an idle lane: zeros), 1, a block's edge and one
    past it, the whole table, and one mid-block. Every row of a wholly dead
    block is NaN in both K and V: a dead block is never read."""
    n_q, n_kv, d = _HEADS[heads]
    lengths = np.asarray([0, 1, block, block + 1, 0, _CACHE_LEN, 37, 0])
    rng = np.random.default_rng(n_q + block)
    shape = (len(lengths), _CACHE_LEN, n_kv * d)
    q = jnp.asarray(rng.normal(size=(len(lengths), n_q, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    lam = jnp.float32(0.37)
    want = np.asarray(kvcache.paired_attention(
        q, {"k": k, "v": v},
        jnp.arange(_CACHE_LEN) < jnp.asarray(lengths)[:, None], lam,
    ))
    dead = np.arange(_CACHE_LEN)[None] // block >= -(-lengths // block)[:, None]
    k, v = (jnp.where(dead[..., None], jnp.nan, a) for a in (k, v))
    got = np.asarray(table_attention(
        q, k, v, jnp.asarray(lengths, jnp.int32), lam, block=block,
    ))
    assert got.shape == (len(lengths), n_q // 2, 2 * d)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=_TOLERANCE[dtype])
    assert not got[lengths == 0].any()


@pytest.mark.parametrize(
    "n_q, d, lanes, window",
    [
        (40, 64, 1280, 256),   # Phi-4-mini-flash: two K/V pairs a group
        (8, 64, 256, 256),
        (16, 128, 1024, 512),  # a pair is two lane tiles
        (4, 16, 32, None),     # the tests' toy model: no whole group
        (8, 32, 128, None),    # a pair is half a lane tile
        (8, 64, 512, 512),     # a query head a K/V head
        (12, 64, 384, None),   # a group and a half
    ],
)
def test_the_kernel_applies_to_whole_groups_over_whole_lane_tiles(
    n_q, d, lanes, window
):
    assert decode_attention.window_lanes(n_q, d, lanes) == window
    assert decode_attention.block_for(n_q, d, lanes) == (
        decode_attention.BLOCK if window else 0
    )


@pytest.mark.parametrize(
    "form", ["paired", "new_row", "latent", "latent_part_tiles"]
)
def test_a_table_of_part_blocks_is_refused_by_the_kernel_itself(form):
    n = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="does not apply"):
        if form == "paired":
            table = jnp.zeros((2, 48, 256))
            table_attention(jnp.zeros((2, 8, 64)), table, table, n, 0.5,
                            block=32)
        elif form == "new_row":
            table, row = jnp.zeros((1, 2, 48, 512)), jnp.zeros((2, 512))
            row_attention(jnp.zeros((2, 8, 64)), table, table, n, row, row,
                          layer=0, block=32)
        else:
            # part blocks, or whole blocks of a row that ends mid-tile
            length, lanes = (48, 640) if form == "latent" else (64, 576)
            table = jnp.zeros((1, 2, length, lanes))
            latent_row_attention(
                jnp.zeros((2, 16, lanes)), table, n, jnp.zeros((2, lanes)),
                layer=0, scale=0.1, block=32,
            )


# ---------------- plain heads, the step's own row an operand of the kernel

_ROW_LEN, _ROW_BLOCK = 256, 128
# (heads, head size): the long-document cell's (three groups of eight and one
# of six), the chat cell's (a head is half a lane tile: a group and a half),
# and one short group alone
_PLAIN = {"cell": (30, 128), "chat": (12, 64), "short": (3, 128)}
_POSITIONS = {
    # a first token, a block's last row and the next block's first, the
    # table's last, the idle sentinel and past it, two mid-block
    "ragged": [0, 127, 128, _ROW_LEN - 1, _ROW_LEN, _ROW_LEN + 7, 200, 129],
    "all_idle": [_ROW_LEN] * 4,
}


def _mask_form(q, table, rows, position):
    read = kvcache.select_rows(table, rows, position, slot_axis=0)
    return kvcache._attend(q, read, position, "sch,slc->shl", "shl,slc->shc")


@pytest.mark.parametrize("positions", sorted(_POSITIONS))
@pytest.mark.parametrize("dtype", sorted(_TOLERANCE))
@pytest.mark.parametrize("heads", sorted(_PLAIN))
def test_row_attention_is_attention_over_the_table_with_the_row_selected_in(
    heads, dtype, positions
):
    """Layer 1 of a stacked table of two. The reference reads a CLEAN table;
    the kernel one whose every row at and past a slot's position holds a
    prior occupant's large values (the stale row too), and whose other layer
    is NaN: neither shows. Idle lanes come back as zeros."""
    n_q, d = _PLAIN[heads]
    position = np.asarray(_POSITIONS[positions])
    slots, lanes = len(position), n_q * d
    rng = np.random.default_rng(n_q)
    q = jnp.asarray(rng.normal(size=(slots, n_q, d)), dtype)
    k, v = (
        jnp.asarray(rng.normal(size=(slots, _ROW_LEN, lanes)), dtype)
        for _ in range(2)
    )
    rows = {
        name: jnp.asarray(rng.normal(size=(slots, lanes)), dtype)
        for name in ("k", "v")
    }
    want = np.asarray(
        _mask_form(q, {"k": k, "v": v}, rows, jnp.asarray(position)),
        np.float32,
    )
    stale = (np.arange(_ROW_LEN) >= position[:, None])[..., None]
    k, v = (
        jnp.stack([jnp.full_like(a, jnp.nan), jnp.where(stale, 3e4, a)])
        for a in (k, v)
    )
    got = np.asarray(row_attention(
        q, k, v, jnp.asarray(position, jnp.int32), rows["k"], rows["v"],
        layer=1, block=_ROW_BLOCK,
    ))
    assert got.shape == (slots, n_q, d) and got.dtype == np.float32
    assert np.isfinite(got).all()
    live = position < _ROW_LEN
    np.testing.assert_allclose(got[live], want[live], atol=_TOLERANCE[dtype])
    assert not got[~live].any()
    if live.any():
        assert np.abs(want[live]).max() > 0.5


@pytest.mark.parametrize(
    "n_q, d, lanes, window",
    [
        (30, 128, 3840, 1024),  # Olmo-Hybrid: the last group is six heads
        (12, 64, 768, 512),     # lm_base: a group and a half
        (3, 128, 384, 1024),    # one short group
        (8, 256, 2048, 2048),   # a head is two lane tiles
        (9, 64, 576, None),     # the last group ends mid-tile
        (12, 32, 384, None),    # a head is a quarter of a tile
        (4, 16, 64, None),      # the tests' toy models
        (40, 64, 1280, None),   # grouped-query: the row is not heads x size
    ],
)
def test_the_new_row_form_applies_to_plain_heads_on_whole_lane_tiles(
    n_q, d, lanes, window
):
    assert decode_attention.head_window_lanes(n_q, d, lanes) == window
    assert decode_attention.block_for(n_q, d, lanes, paired=False) == (
        decode_attention.BLOCK if window else 0
    )


@pytest.mark.parametrize(
    "case", ["kernel", "part_blocks", "sharded", "int8", "one_layer", "toy"]
)
def test_cached_attention_takes_the_kernel_where_the_table_admits_it(
    case, monkeypatch
):
    """``kvcache.cached_attention`` with the step's rows: the kernel for the
    stacked leaf of whole blocks and admitted heads, and for anything else the
    mask form, bit for bit what the callers spelled out before."""
    from distributed_tensorflow_tpu.models.quant import quantize_kv

    n_q, d = (4, 16) if case == "toy" else (3, 128)
    cache_len = 100 if case == "part_blocks" else 128
    slots, lanes, layer = 3, n_q * d, 1
    rng = np.random.default_rng(7)
    position = jnp.asarray([5, cache_len, 77])
    q = jnp.asarray(rng.normal(size=(slots, n_q, d)), jnp.float32)
    table = {
        name: jnp.asarray(
            rng.normal(size=(2, slots, cache_len, lanes)), jnp.float32
        )
        for name in ("k", "v")
    }
    fresh = {
        name: jnp.asarray(rng.normal(size=(slots, lanes)), jnp.float32)
        for name in ("k", "v")
    }
    if case == "int8":
        table = {
            name: dict(zip(("q", "s"), quantize_kv(t)))
            for name, t in table.items()
        }
    rows = kvcache.encode(table, fresh)
    want = _mask_form(q, kvcache.take_layer(table, layer), rows, position)
    calls = []
    kernel = decode_attention.row_attention
    monkeypatch.setattr(
        decode_attention, "row_attention",
        lambda *a, **kw: calls.append(kw) or kernel(*a, **kw),
    )
    if case == "one_layer":
        got = kvcache.cached_attention(
            q, kvcache.take_layer(table, layer), position, rows
        )
    else:
        got = kvcache.cached_attention(
            q, table, position, rows, layer=layer, sharded=case == "sharded"
        )
    assert got.shape == q.shape and got.dtype == q.dtype
    if case == "kernel":
        assert calls == [{"layer": layer}]
        live = np.asarray(position) < cache_len
        np.testing.assert_allclose(
            np.asarray(got)[live], np.asarray(want)[live], atol=2e-6
        )
    else:
        assert not calls
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------- one latent row a position: every head's key and its value

# the DeepSeek-V2-Lite cell's 16 heads over its row of 576 lanes held as 640
_LATENT_HEADS, _LATENT_LANES = 16, 640
_LATENT_POSITIONS = {
    # an idle lane, a first token, both sides of the first block's last row,
    # the next block's first, the table's last, two mid-block
    "ragged": [_ROW_LEN, 0, 126, 127, 128, _ROW_LEN - 1, 200, 61],
    "first_block": [1, 64, 127],
    "all_idle": [_ROW_LEN, _ROW_LEN + 3],
}


@pytest.mark.parametrize(
    "lanes, block",
    [(640, decode_attention.LATENT_BLOCK), (128, decode_attention.LATENT_BLOCK),
     (576, 0), (48, 0), (0, 0)],
)
def test_the_latent_form_applies_to_rows_of_whole_lane_tiles(lanes, block):
    assert decode_attention.latent_block_for(lanes) == block


@pytest.mark.parametrize("positions", sorted(_LATENT_POSITIONS))
@pytest.mark.parametrize("dtype", sorted(_TOLERANCE))
def test_latent_row_attention_is_the_mask_form_of_latent_attention(
    dtype, positions
):
    """Layer 1 of a stacked table of two layers x 256 positions. The mask
    form reads a CLEAN layer; the kernel one whose every row at and past a
    slot's position holds a prior occupant's large values (the stale row
    too), and whose other layer is NaN: neither shows. Idle lanes come back
    as zeros."""
    position = np.asarray(_LATENT_POSITIONS[positions])
    slots, lanes = len(position), _LATENT_LANES
    scale = lanes ** -0.5
    rng = np.random.default_rng(len(position))
    q = jnp.asarray(rng.normal(size=(slots, _LATENT_HEADS, lanes)), dtype)
    table = jnp.asarray(rng.normal(size=(slots, _ROW_LEN, lanes)), dtype)
    row = jnp.asarray(rng.normal(size=(slots, lanes)), dtype)
    want = np.asarray(kvcache.latent_attention(
        q, table, jnp.asarray(position), row, scale
    ))
    stale = (np.arange(_ROW_LEN) >= position[:, None])[..., None]
    stacked = jnp.stack(
        [jnp.full_like(table, jnp.nan), jnp.where(stale, 3e4, table)]
    )
    got = np.asarray(latent_row_attention(
        q, stacked, jnp.asarray(position, jnp.int32), row, layer=1,
        scale=scale, block=_ROW_BLOCK,
    ))
    assert got.shape == (slots, _LATENT_HEADS, lanes)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    live = position < _ROW_LEN
    np.testing.assert_allclose(got[live], want[live], atol=_TOLERANCE[dtype])
    assert not got[~live].any()
    if live.any():
        assert np.abs(want[live]).max() > 0.5
