"""ops/decode_attention.py, interpreted on the CPU, against the plain
statement it replaces for a prefix: ``kvcache.paired_attention`` with the
mask ``arange(L) < lengths``. Times and the compile for the chip are
elsewhere (scripts/table_attention_bench.py, tests/test_chip_compile.py)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.ops import decode_attention
from distributed_tensorflow_tpu.ops.decode_attention import table_attention

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


def test_a_table_of_part_blocks_is_refused_by_the_kernel_itself():
    q = jnp.zeros((2, 8, 64))
    table = jnp.zeros((2, 48, 256))
    with pytest.raises(ValueError, match="does not apply"):
        table_attention(q, table, table, jnp.zeros((2,), jnp.int32), 0.5,
                        block=32)
