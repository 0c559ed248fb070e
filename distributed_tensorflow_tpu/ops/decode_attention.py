"""Decode attention over a slot table that stops at each slot's length
(Pallas TPU): ``models/kvcache.paired_attention`` for a prefix mask, moving
only the blocks of positions a slot has.

One query token a slot, grouped-query differential attention over one
layer's K and V as they lie in HBM — ``[S, L, c]`` merged rows, never
reshaped, sliced per head or copied — given ``lengths [S]``: slot ``s`` sees
positions ``[0, lengths[s])``. The XLA form contracts over the whole ``[S, L,
c]`` rectangle and masks, so an idle slot and a short sequence cost what a
full one does; here a slot moves ``ceil(length / block)`` blocks a side and a
length of 0 moves nothing and returns zeros.

Structure (the paged-attention pattern, without the pages):

- the grid is the slots, one step each; the step's body loops over the
  slot's live blocks, each one ``make_async_copy`` a side into a
  double-buffered VMEM scratch, the next block (or the next live slot's
  first, across the grid step) in flight while this one is computed. A
  ``(slot, block)`` grid whose index map skips dead blocks would still pay
  a grid step for each of them;
- eight consecutive query heads are one group: one float32 sublane tile of
  scores. Their K/V pairs are ``8d / per`` adjacent lanes of the row (``per``
  query heads a K/V head; 256 lanes at 40 / 20 heads of 64): a lane-tile
  slice of the block in VMEM, which costs nothing. The group's query is
  block-diagonal over that window only (built outside, ``[S, groups, 8,
  window]``), so a K/V lane meets the heads that read it and the zeros of a
  tile it is loaded into the MXU with anyway;
- scores, the running maximum and sum and the context stay in VMEM, float32;
  the table's dtype goes into the MXU and float32 comes out, as in
  ``paired_attention``. Both softmaxes are normalised each on its own before
  ``softmax1 - lam * softmax2``; the tail of the last block is masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_tpu.ops.flash_attention import (
    _NEG,
    _use_interpret,
)

_GROUP = 8  # query heads a group: one float32 sublane tile of scores
# Positions a DMA moves. A sequence wastes half a block on average, and
# under 128 a block's turn of the loop takes longer than its DMA: swept on
# the chip at the reasoning cell's geometry (scripts/table_attention_bench.py;
# PERF.md, PR 36: 32 / 64 / 128 / 256 / 512 read 0.93 / 0.58 / 0.49 / 0.53 /
# 0.63 ms a reader at a quarter of the table live, 128 and up alike when full).
BLOCK = 128


def window_lanes(n_q: int, d: int, lanes: int) -> int | None:
    """The lanes of a merged K/V row that one group of eight query heads
    reads, or ``None`` where the kernel does not apply: the groups have to be
    whole, read whole K/V pairs, and both a pair (``2d`` lanes, what a query
    pair's context is) and a group's window start on a lane tile."""
    if d <= 0 or lanes % (2 * d) or n_q % _GROUP:
        return None
    n_kv = lanes // d
    if n_q % n_kv:
        return None
    per = n_q // n_kv  # query heads a K/V head serves
    if _GROUP % (2 * per) or (2 * d) % 128:
        return None
    return _GROUP // (2 * per) * 2 * d


def block_for(n_q: int, d: int, lanes: int) -> int:
    """Positions a read moves at a time for these heads, 0 where the kernel
    does not apply to them (:func:`window_lanes`)."""
    return BLOCK if window_lanes(n_q, d, lanes) else 0


def _grouped_query(q, window: int):
    """``q [S, n_q, d]`` as ``[S, n_q / 8, 8, window]``: head ``h`` of a
    group on the lanes of the K/V head it reads (of the window's K/V pairs
    the one its share of the eight rows falls to, and that pair's head ``h %
    2``), zeros elsewhere."""
    s, n_q, d = q.shape
    head = np.arange(_GROUP)
    rows_a_pair = _GROUP * 2 * d // window
    kv_of = 2 * (head // rows_a_pair) + head % 2  # [8], within the window
    own = np.arange(window)[None, :] // d == kv_of[:, None]  # [8, window]
    tiled = jnp.tile(
        q.reshape(s, n_q // _GROUP, _GROUP, d), (1, 1, 1, window // d)
    )
    return jnp.where(own, tiled, 0)


def _kernel(
    lengths_ref, lam_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, fetched_ref, heads_ref,
    *, block: int, scale: float, pair_lanes: int,
):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    length = lengths_ref[s]
    n_blocks = pl.cdiv(length, block)
    n_groups, _, window = q_ref.shape[1:]
    n_q = n_groups * _GROUP
    pieces = window // pair_lanes  # K/V pairs a group's window holds
    # which of a group's rows read which K/V pair of its window
    row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, pair_lanes), 0)
    piece_of_row = row // (_GROUP // pieces)

    def fetch(slot, i, buf):
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        return (
            pltpu.make_async_copy(
                k_hbm.at[slot, rows], k_buf.at[buf], sems.at[0, buf]
            ),
            pltpu.make_async_copy(
                v_hbm.at[slot, rows], v_buf.at[buf], sems.at[1, buf]
            ),
        )

    @pl.when(s == 0)
    def _():
        fetched_ref[0] = 0  # blocks waited for so far: its parity, the buffer

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        base = fetched_ref[0]

        @pl.when(base == 0)  # the first live slot: nobody fetched for it
        def _():
            for copy in fetch(s, 0, 0):
                copy.start()

        # the next slot that holds anything (idle slots move nothing)
        following = jax.lax.while_loop(
            lambda t: (t < n_slots)
            & (lengths_ref[jnp.minimum(t, n_slots - 1)] == 0),
            lambda t: t + 1, s + 1,
        )

        def body(i, carry):
            m, l, acc = carry
            buf = (base + i) % 2

            @pl.when(i + 1 < n_blocks)
            def _():
                for copy in fetch(s, i + 1, 1 - buf):
                    copy.start()

            @pl.when((i + 1 == n_blocks) & (following < n_slots))
            def _():
                for copy in fetch(following, 0, 1 - buf):
                    copy.start()

            k_copy, v_copy = fetch(s, i, buf)
            k_copy.wait()
            scores = jnp.concatenate(
                [
                    jax.lax.dot_general(
                        q_ref[0, g],
                        k_buf[buf, :, g * window:(g + 1) * window],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    for g in range(n_groups)
                ],
                axis=0,
            ) * scale  # [n_q, block]
            at = i * block + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            scores = jnp.where(at < length, scores, _NEG)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(scores - m_new)  # exactly 0 past the length
            l = alpha * l + jnp.sum(e, axis=1, keepdims=True)
            e = e.astype(v_buf.dtype)
            v_copy.wait()
            fresh = []
            for g in range(n_groups):
                ctx = jnp.dot(
                    e[g * _GROUP:(g + 1) * _GROUP],
                    v_buf[buf, :, g * window:(g + 1) * window],
                    preferred_element_type=jnp.float32,
                )  # [8, window]: each head keeps its own K/V pair's lanes
                mine = ctx[:, :pair_lanes]
                for piece in range(1, pieces):
                    mine = jnp.where(
                        piece_of_row == piece,
                        ctx[:, piece * pair_lanes:(piece + 1) * pair_lanes],
                        mine,
                    )
                fresh.append(mine)
            acc = alpha * acc + jnp.concatenate(fresh, axis=0)
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_blocks, body,
            (
                jnp.full((n_q, 1), _NEG, jnp.float32),
                jnp.zeros((n_q, 1), jnp.float32),
                jnp.zeros((n_q, pair_lanes), jnp.float32),
            ),
        )
        fetched_ref[0] = base + n_blocks
        # each softmax normalised on its own, then the pair's difference:
        # heads 2p and 2p + 1 are rows two apart
        heads_ref[...] = acc / l
        o_ref[0] = heads_ref[0::2] - lam_ref[0] * heads_ref[1::2]


def table_attention(
    q, k, v, lengths, lam, *, block: int = BLOCK,
    interpret: bool | None = None,
):
    """``paired_attention(q, {"k": k, "v": v}, arange(L) < lengths[:, None],
    lam)``: ``q [S, 2P, d]``, ``k`` and ``v`` ``[S, L, 2G * d]`` in one
    dtype, ``lengths [S]`` in ``[0, L]``, ``lam`` a scalar; returns ``[S, P,
    2d]`` float32. ``L`` is a multiple of ``block`` and the heads are those
    :func:`window_lanes` admits. ``interpret=None`` runs the interpreter off
    the TPU."""
    if interpret is None:
        interpret = _use_interpret()
    n_slots, n_q, d = q.shape
    cache_len, lanes = k.shape[1:]
    window = window_lanes(n_q, d, lanes)
    if window is None or cache_len % block:
        raise ValueError(
            f"table_attention does not apply to {n_q} query heads of {d} "
            f"over rows of {lanes} lanes at {cache_len} positions in "
            f"blocks of {block}"
        )
    n_groups = n_q // _GROUP
    out_shape = (n_slots, n_q // 2, 2 * d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, n_groups, _GROUP, window), lambda s, _: (s, 0, 0, 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, *out_shape[1:]), lambda s, _: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, lanes), k.dtype),
            pltpu.VMEM((2, block, lanes), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_q, 2 * d), jnp.float32),
        ],
    )
    # the custom call is named after the innermost scope around it: what
    # benchmarks/layer_metrics/engine.table_attention_kernel_ms selects
    with jax.named_scope("table_attention"):
        return pl.pallas_call(
            functools.partial(
                _kernel, block=block, scale=d ** -0.5, pair_lanes=2 * d
            ),
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
        )(
            lengths.astype(jnp.int32),
            jnp.reshape(lam, (1,)).astype(jnp.float32),
            _grouped_query(q, window).astype(k.dtype),
            k, v,
        )
