"""Decode attention over a slot table that stops at each slot's length
(Pallas TPU): the reads of ``models/kvcache.py`` for one query token a slot,
moving only the blocks of positions a slot has.

One layer's K and V as they lie in HBM — merged rows ``[S, L, c]`` (or one
layer of the stacked ``[layers, S, L, c]``), never reshaped, sliced per head
or copied. The XLA forms contract over the whole ``[S, L, c]`` rectangle and
mask, so an idle slot and a short sequence cost what a full one does; here a
slot moves ``ceil(length / block)`` blocks a side and an idle one nothing, and
zeros come back for it. Three forms over one body (:func:`_over_live_blocks`):

- :func:`table_attention`: ``kvcache.paired_attention`` for a prefix mask —
  grouped-query differential attention over a table that is written before it
  is read, given ``lengths [S]``: slot ``s`` sees positions ``[0,
  lengths[s])``;
- :func:`row_attention`: ``kvcache._attend`` over ``select_rows(table, rows,
  position)`` — plain heads over a table that does NOT hold the step's own
  row yet, given ``position [S]`` and the row: slot ``s`` sees the table's
  ``[0, position[s])`` and the row, which starts the online softmax (its
  score the first maximum, its weight 1, its values the context so far). A
  select on its way into a custom call would copy the table;
- :func:`latent_row_attention`: ``kvcache.latent_attention`` — the new-row
  form over ONE table whose row is every head's key and value (multi-head
  latent attention, absorbed): a block is one copy, and all heads are one
  group that reads the whole row, one dot for the scores and one for the
  context.

Structure (the paged-attention pattern, without the pages):

- the grid is the slots, one step each; the step's body loops over the
  slot's live blocks, each one ``make_async_copy`` a side into a
  double-buffered VMEM scratch, the next block (or the next live slot's
  first, across the grid step) in flight while this one is computed. A
  ``(slot, block)`` grid whose index map skips dead blocks would still pay
  a grid step for each of them;
- eight consecutive query heads are one group: one float32 sublane tile of
  scores. What they read is adjacent lanes of the row — their K/V pairs,
  ``8d / per`` lanes (``per`` query heads a K/V head; 256 lanes at 40 / 20
  heads of 64), or for plain heads their own ``8d`` (a last group may be
  short: 30 heads of 128 are 1,024 lanes three times and 768 once): a
  lane-tile slice of the block in VMEM, which costs nothing. The group's
  query is block-diagonal over that window only (built outside, ``[S, groups,
  8, window]``), so a K/V lane meets the heads that read it and the zeros of
  a tile it is loaded into the MXU with anyway. The latent form's heads all
  read the whole row: one group of every head, one window, no zeros;
- scores, the running maximum and sum and the context stay in VMEM, float32;
  the table's dtype goes into the MXU and float32 comes out, as in the mask
  forms. In the paired form both softmaxes are normalised each on its own
  before ``softmax1 - lam * softmax2``; the tail of the last block is masked.
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
# Positions a latent read moves at a time (:func:`latent_row_attention`). A
# block passes through the MXU twice for 16 heads' rows, so a turn of the
# loop costs about what its copy does, plus a fixed ~0.35 us: swept on a TPU
# v5e at the DeepSeek-V2-Lite cell's geometry (scripts/table_attention_bench.py
# --cell docqa; 128 x 4,608 x 640 lanes; PERF.md's Findings): 128 / 256 / 512
# read 0.353 / 0.268 / 0.233 ms a layer with 24 slots live (the mask form
# 2.155) and 2.421 / 1.611 / 1.236 with every slot full (2.143).
LATENT_BLOCK = 512


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


def head_window_lanes(n_q: int, d: int, lanes: int) -> int | None:
    """:func:`window_lanes` for plain heads, each reading its own ``d`` lanes
    of the row: the lanes a group of eight reads, or ``None`` where
    :func:`row_attention` does not apply. A head is whole lane tiles or half
    of one (then two share the tile both keep), so every window starts on a
    tile; the last group may be short — 30 heads of 128 are three groups of
    eight and one of six — if what is left of the row is whole tiles too."""
    if n_q <= 0 or lanes != n_q * d or not (d == 64 or d % 128 == 0):
        return None
    if (n_q % _GROUP) * d % 128:
        return None
    return _GROUP * d


def block_for(n_q: int, d: int, lanes: int, paired: bool = True) -> int:
    """Positions a read moves at a time for these heads, 0 where the kernel
    does not apply to them: :func:`window_lanes` for differential pairs,
    :func:`head_window_lanes` for plain heads."""
    admit = window_lanes if paired else head_window_lanes
    return BLOCK if admit(n_q, d, lanes) else 0


def latent_block_for(lanes: int) -> int:
    """Positions a latent read of rows of ``lanes`` moves at a time, 0 where
    :func:`latent_row_attention` does not apply: the row has to be whole
    lane tiles."""
    return LATENT_BLOCK if lanes > 0 and lanes % 128 == 0 else 0


def _grouped(q, window: int, kv_of):
    """``q [S, h, d]`` as ``[S, ceil(h / 8), 8, window]``: row ``r`` of a
    group on the lanes of the window's K/V head ``kv_of[r]``, zeros elsewhere
    and in the rows that pad a short last group."""
    s, n_q, d = q.shape
    n_groups = -(-n_q // _GROUP)
    own = np.arange(window)[None, :] // d == kv_of[:, None]  # [8, window]
    if n_q % _GROUP:
        q = jnp.pad(q, ((0, 0), (0, n_groups * _GROUP - n_q), (0, 0)))
    tiled = jnp.tile(
        q.reshape(s, n_groups, _GROUP, d), (1, 1, 1, window // d)
    )
    return jnp.where(own, tiled, 0)


def _grouped_query(q, window: int):
    """Differential pairs, grouped-query: head ``h`` of a group reads, of the
    window's K/V pairs, the one its share of the eight rows falls to, and of
    that pair head ``h % 2``."""
    head = np.arange(_GROUP)
    rows_a_pair = _GROUP * 2 * q.shape[-1] // window
    return _grouped(q, window, 2 * (head // rows_a_pair) + head % 2)


def _piece_of_row(group: int, keep: int, window: int):
    """``[group, keep]``: which ``keep``-lane piece of its group's window a
    row's head keeps — ``group * keep / window`` consecutive rows a piece."""
    row = jax.lax.broadcasted_iota(jnp.int32, (group, keep), 0)
    return row // (group * keep // window)


def _own_piece(of, width: int, piece_of_row):
    """Each row's own piece of a group's window: ``of(lo, hi)`` gives the
    lanes ``[lo, hi)`` of what the group's heads share, ``[group, ..]`` or
    ``[1, ..]``, and a row keeps the piece its head's values lie in."""
    keep = piece_of_row.shape[1]
    mine = of(0, keep)
    for piece in range(1, width // keep):
        mine = jnp.where(
            piece_of_row == piece, of(piece * keep, (piece + 1) * keep), mine
        )
    return jnp.broadcast_to(mine, piece_of_row.shape)


def _over_live_blocks(
    s, lengths_ref, q_ref, k_hbm, v_hbm, k_buf, v_buf, sems, fetched_ref,
    carry, *, block: int, scale: float, window: int, windows, keep: int,
    layer,
):
    """The online softmax ``(m, l, acc)`` of slot ``s`` over the table's
    first ``lengths_ref[s]`` positions, from ``carry`` on: what every kernel
    shares. ``windows`` are the groups' ``(first lane, lanes)`` — ``window``
    lanes each but a short last one — ``keep`` the lanes of a group's context
    a head keeps, ``layer`` the index into a
    stacked ``[layers, S, L, c]`` table or ``None`` for ``[S, L, c]``. A
    group is the query block's rows (``q_ref [1, groups, rows, window]``).
    ``v_hbm`` ``None``: one table is the keys and the values, a copy a block.
    A length of 0 moves nothing and returns ``carry``."""
    n_slots = pl.num_programs(0)
    length = lengths_ref[s]
    n_blocks = pl.cdiv(length, block)
    lead = () if layer is None else (layer,)
    group = q_ref.shape[2]
    piece_of_row = _piece_of_row(group, keep, window)
    sides = ((k_hbm, k_buf),) if v_hbm is None else (
        (k_hbm, k_buf), (v_hbm, v_buf)
    )
    values = k_buf if v_buf is None else v_buf

    def fetch(slot, i, buf):
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[(*lead, slot, rows)], vmem.at[buf], sems.at[side, buf]
            )
            for side, (hbm, vmem) in enumerate(sides)
        )

    base = fetched_ref[0]  # blocks waited for so far: its parity, the buffer

    @pl.when((base == 0) & (n_blocks > 0))  # the first: nobody fetched for it
    def _():
        for copy in fetch(s, 0, 0):
            copy.start()

    # the next slot that has a block to move (the others move nothing)
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

        k_copy, *v_copy = fetch(s, i, buf)
        k_copy.wait()
        scores = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q_ref[0, g, :, :width],
                    k_buf[buf, :, lo:lo + width],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for g, (lo, width) in enumerate(windows)
            ],
            axis=0,
        ) * scale  # [heads, block]
        at = i * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(at < length, scores, _NEG)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(scores - m_new)  # exactly 0 past the length
        l = alpha * l + jnp.sum(e, axis=1, keepdims=True)
        e = e.astype(values.dtype)
        for copy in v_copy:
            copy.wait()
        fresh = []
        for g, (lo, width) in enumerate(windows):
            ctx = jnp.dot(
                e[g * group:(g + 1) * group], values[buf, :, lo:lo + width],
                preferred_element_type=jnp.float32,
            )  # [group, width]: each head keeps its own lanes of it
            fresh.append(_own_piece(
                lambda a, b, ctx=ctx: ctx[:, a:b], width, piece_of_row
            ))
        acc = alpha * acc + jnp.concatenate(fresh, axis=0)
        return m_new, l, acc

    carry = jax.lax.fori_loop(0, n_blocks, body, carry)
    fetched_ref[0] = base + n_blocks
    return carry


def _paired_kernel(
    lengths_ref, lam_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, fetched_ref, heads_ref, **geometry,
):
    s = pl.program_id(0)
    n_q = q_ref.shape[1] * _GROUP

    @pl.when(s == 0)
    def _():
        fetched_ref[0] = 0

    @pl.when(lengths_ref[s] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(lengths_ref[s] > 0)
    def _():
        _, l, acc = _over_live_blocks(
            s, lengths_ref, q_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
            fetched_ref,
            (
                jnp.full((n_q, 1), _NEG, jnp.float32),
                jnp.zeros((n_q, 1), jnp.float32),
                jnp.zeros((n_q, geometry["keep"]), jnp.float32),
            ),
            layer=None, **geometry,
        )
        # each softmax normalised on its own, then the pair's difference:
        # heads 2p and 2p + 1 are rows two apart
        heads_ref[...] = acc / l
        o_ref[0] = heads_ref[0::2] - lam_ref[0] * heads_ref[1::2]


def _row_kernel(
    lengths_ref, live_ref, layer_ref, q_ref, k_new_ref, v_new_ref, k_hbm,
    v_hbm, o_ref, k_buf, v_buf, sems, fetched_ref, **geometry,
):
    s = pl.program_id(0)
    windows = geometry["windows"]

    @pl.when(s == 0)
    def _():
        fetched_ref[0] = 0

    @pl.when(live_ref[s] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[s] != 0)
    def _():
        # the softmax starts at the step's own row, which no table holds yet:
        # its score is the running maximum, its weight 1, its values the
        # context so far
        piece_of_row = _piece_of_row(
            q_ref.shape[2], geometry["keep"], geometry["window"]
        )
        first, values = [], []
        for g, (lo, width) in enumerate(windows):
            first.append(jnp.sum(
                q_ref[0, g, :, :width].astype(jnp.float32)
                * k_new_ref[0, :, lo:lo + width].astype(jnp.float32),
                axis=1, keepdims=True,
            ))
            values.append(_own_piece(
                lambda a, b, lo=lo: v_new_ref[0, :, lo + a:lo + b].astype(
                    jnp.float32
                ),
                width, piece_of_row,
            ))
        m = jnp.concatenate(first, axis=0) * geometry["scale"]
        _, l, acc = _over_live_blocks(
            s, lengths_ref, q_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
            fetched_ref,
            (m, jnp.ones_like(m), jnp.concatenate(values, axis=0)),
            layer=layer_ref[0], **geometry,
        )
        o_ref[0] = acc / l


def _scratch(block: int, *tables):
    """Two blocks a table (the keys and the values, or one latent table),
    their semaphores, and the count of blocks waited for that tells the
    buffers apart across grid steps."""
    return [
        *(pltpu.VMEM((2, block, t.shape[-1]), t.dtype) for t in tables),
        pltpu.SemaphoreType.DMA((len(tables), 2)),
        pltpu.SMEM((1,), jnp.int32),
    ]


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
            *_scratch(block, k, v), pltpu.VMEM((n_q, 2 * d), jnp.float32),
        ],
    )
    # the custom call is named after the innermost scope around it: what
    # benchmarks/layer_metrics/engine.table_attention_kernel_ms selects
    with jax.named_scope("table_attention"):
        return pl.pallas_call(
            functools.partial(
                _paired_kernel, block=block, scale=d ** -0.5, keep=2 * d,
                window=window, windows=tuple(
                    (g * window, window) for g in range(n_groups)
                ),
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


def row_attention(
    q, k, v, position, k_new, v_new, *, layer: int, block: int = BLOCK,
    interpret: bool | None = None,
):
    """Plain per-head attention of one token a slot over a table that does
    not hold the token's own row yet — ``kvcache._attend(q, select_rows(
    table, rows, position), position)`` without the select, which on its way
    into a custom call would copy the table: ``q [S, h, d]``, ``k`` and ``v``
    the STACKED tables ``[layers, S, L, h * d]`` in one dtype of which layer
    ``layer`` is read (slicing it out first copies it too), ``position [S]``
    the index the token sits at (``>= L``: an idle lane, zeros come back),
    ``k_new`` and ``v_new`` ``[S, h * d]`` the step's rows as the table will
    store them. Slot ``s`` attends the table's positions ``[0, position[s])``
    block by block and its own row from the operand; what the table holds at
    ``position[s]`` and past it is masked. Returns ``[S, h, d]`` float32.
    ``L`` is a multiple of ``block`` and the heads are those
    :func:`head_window_lanes` admits."""
    if interpret is None:
        interpret = _use_interpret()
    n_q, d = q.shape[1:]
    cache_len, lanes = k.shape[2:]
    if head_window_lanes(n_q, d, lanes) is None or cache_len % block:
        raise ValueError(
            f"row_attention does not apply to {n_q} heads of {d} over rows "
            f"of {lanes} lanes at {cache_len} positions in blocks of {block}"
        )
    # the layer is a scalar of the call, so a model's layers share one traced
    # and lowered kernel
    return _row_call(
        q, k, v, position, k_new, v_new, jnp.full((1,), layer, jnp.int32),
        block=block, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _row_call(q, k, v, position, k_new, v_new, layer, *, block, interpret):
    n_slots, n_q, d = q.shape
    cache_len, lanes = k.shape[2:]
    window = head_window_lanes(n_q, d, lanes)
    keep = max(d, 128)
    n_groups = -(-n_q // _GROUP)
    padded = n_groups * _GROUP
    live = position < cache_len
    spec = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda s, *_: (s, *(0,) * len(shape))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots,),
        in_specs=[
            spec(n_groups, _GROUP, window), spec(1, lanes), spec(1, lanes),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=spec(padded, keep),
        scratch_shapes=_scratch(block, k, v),
    )
    # named for benchmarks/layer_metrics/engine.kv_tables_kernel_ms
    with jax.named_scope("row_attention"):
        out = pl.pallas_call(
            functools.partial(
                _row_kernel, block=block, scale=d ** -0.5, keep=keep,
                window=window,
                windows=tuple(
                    (lo, min(window, lanes - lo))
                    for lo in range(0, lanes, window)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (n_slots, padded, keep), jnp.float32
            ),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
        )(
            jnp.where(live, position, 0).astype(jnp.int32),
            live.astype(jnp.int32), layer,
            _grouped(q, window, np.arange(_GROUP)).astype(k.dtype),
            k_new[:, None], v_new[:, None], k, v,
        )
    # a head of half a lane tile shares its tile with its neighbour
    out = out[:, :n_q].reshape(n_slots, n_q, keep // d, d)
    head = np.arange(n_q)
    return out[:, head, head % (keep // d)]


def _latent_kernel(
    lengths_ref, live_ref, layer_ref, q_ref, row_ref, table_hbm, o_ref, buf,
    sems, fetched_ref, **geometry,
):
    # the new-row kernel with the row as both the step's key and its value,
    # and the one table as both sides
    _row_kernel(
        lengths_ref, live_ref, layer_ref, q_ref, row_ref, row_ref, table_hbm,
        None, o_ref, buf, None, sems, fetched_ref, **geometry,
    )


def latent_row_attention(
    q, table, position, row, *, layer: int, scale: float,
    block: int = LATENT_BLOCK, interpret: bool | None = None,
):
    """Every head over ONE cached row a position, of one token a slot —
    ``kvcache.latent_attention``'s mask form without its two passes over
    every position: ``q [S, h, r]`` the heads' queries in the row's own
    coordinates, ``table`` the STACKED rows ``[layers, S, L, r]`` of which
    layer ``layer`` is read (slicing it out first copies it), ``position
    [S]`` the index the token sits at (``>= L``: an idle lane, zeros come
    back), ``row [S, r]`` the step's own row as the table will store it.
    Slot ``s`` attends the table's ``[0, position[s])`` block by block and
    its row from the operand, scores ``scale * q . row``; what the table
    holds at ``position[s]`` and past it is masked. The row is each head's
    key and its value: returns the context over the whole row, ``[S, h, r]``
    float32. ``L`` is a multiple of ``block`` and ``r`` whole lane tiles."""
    if interpret is None:
        interpret = _use_interpret()
    lanes = q.shape[-1]
    cache_len = table.shape[2]
    if not latent_block_for(lanes) or table.shape[-1] != lanes \
            or cache_len % block:
        raise ValueError(
            f"latent_row_attention does not apply to rows of {lanes} lanes "
            f"over a table of {table.shape[-1]} at {cache_len} positions in "
            f"blocks of {block}"
        )
    return _latent_call(
        q, table, position, row, jnp.full((1,), layer, jnp.int32),
        scale=scale, block=block, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "block", "interpret")
)
def _latent_call(q, table, position, row, layer, *, scale, block, interpret):
    n_slots, n_q, lanes = q.shape
    live = position < table.shape[2]
    spec = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, *shape), lambda s, *_: (s, *(0,) * len(shape))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots,),
        in_specs=[
            spec(1, n_q, lanes), spec(1, lanes),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=spec(n_q, lanes),
        scratch_shapes=_scratch(block, table),
    )
    # named for benchmarks/layer_metrics/engine.latent_kernel_ms
    with jax.named_scope("latent_row_attention"):
        return pl.pallas_call(
            functools.partial(
                # every head one group, reading one window: the whole row
                _latent_kernel, block=block, scale=scale, keep=lanes,
                window=lanes, windows=((0, lanes),),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (n_slots, n_q, lanes), jnp.float32
            ),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
        )(
            jnp.where(live, position, 0).astype(jnp.int32),
            live.astype(jnp.int32), layer,
            q[:, None].astype(table.dtype), row[:, None].astype(table.dtype),
            table,
        )
