"""What a sequence caches between steps — the one module that knows.

A cache is a pytree of arrays, described leaf by leaf (:class:`Leaf`): every
leaf is ``[layers, slots, *after, *trailing]``. Leaves that share ``layers``
and what comes ``after`` the slot axis are a group, and say so: ``POSITIONS``
(a table of the engine's ``cache_len`` positions: a transferable page per
position), a ring length (a window's last rows, row ``position % ring``), or
``None`` (state that has no positions at all). ``CausalLM`` caches one group,
``kv``; a hybrid model several, side by side. Everything outside this module
(serve/engine.py above all) addresses the slot axis, asks the layout for
sizes and maps over the leaves; which leaves there are, what trails them,
their dtype, how they shard and how a fresh row is encoded into them is
decided here and by the model's ``cache_layout``:

- :func:`cache_layout` describes ``CausalLM``'s group. Dense K/V is ``{"k",
  "v"}``, each one row of ``heads * head_dim`` in the store dtype; int8 K/V
  makes each side the ``{"q", "s"}`` pair of models/quant.py: the int8
  payload and its float32 per-position scale, which has no trailing axes.
  models/sambay.py declares three groups: ``state`` (positionless),
  ``window`` (a ring) and ``full`` (positions); models/deepseek_v2.py one,
  ``latent``: a table of positions whose row serves every head, no K/V page
  (``Leaf.pages`` false), read by :func:`latent_attention`.
- ``specs`` / ``shardings`` / ``structs`` / ``zeros`` / ``bytes_per_token`` /
  ``components`` are one pass over that description each, for any layout.
- :func:`require_pages` is what a mode that moves cached positions about as
  pages (prefix pool, verify, export/import, int8, ``model`` sharding) calls
  at construction: it raises, naming the group, for a layout that has a
  positionless, ring or latent group. Chunked prefill asks
  :func:`require_carry` instead: positionless state is what a prompt's
  chunks hand on, and only a ring refuses.
- The model's reads and writes — ``take_layer``, ``encode``, ``select_rows``,
  ``write_rows``, ``scatter_rows``, ``stack_layers``, ``write_prompt``,
  ``cached_attention``, ``chunk_attention``, ``paired_attention``,
  ``prefix_attention``, ``latent_attention`` — are written once for every
  form.
- ``split_kv`` / ``join_kv`` / ``page_geometry`` convert at the engine's host
  boundary, where serve/disagg.py and the wire format still speak of
  ``pages_k, pages_v`` and of ``[.., heads, head_dim]``: a row-major reshape
  of the same bytes (ROADMAP Design 1, the host half).

How decode_step writes and reads. A cached position of one layer is ONE
contiguous row: the heads are merged, the table is ``[nl, S, L, h * d]``, and
on the TPU its default layout ``{3,2,1,0:T(8,128)(2,1)}`` is the one it lives
in (768 = 6 lane tiles, no padding; trailing ``(h=12, d=64)`` would pad to
(16, 128) tiles, which is why the table of PRs 28-32 lived position
minor-most, admitted no in-place write, and was written by a select over all
of it: 3.6 GB of traffic a step for 4.7 MB of rows). Compiled for a described
v5e at the serving benchmark's geometry (bf16, 128 slots, cache 384, the table
donated; ``memory_analysis().temp_size_in_bytes``; times are in PERF.md, PR
33):

- the write, what is here (:func:`write_rows`): the ``nl x S`` rows scattered
  into the FLAT table ``[nl * S * L, h * d]`` at ``(layer * S + slot) * L +
  position``, ``unique_indices`` and ``mode="drop"`` — 0 B, aliased, a
  ``kCustom`` scatter fusion a side; a ``fori_loop`` of row-sized
  ``dynamic_update_slice`` on the flat table does the same (97 kB);
- the STACKED scatter ``table.at[:, arange(S), position].set(rows)`` still
  copies the table to ``{3,0,2,1}`` and back — 1.21 GB. Do not retry it;
- the read, what is here (:func:`_attend`): both contractions over the merged
  row, scores against a block-diagonal query and the context's own lanes kept
  — two ``kOutput`` fusions a layer that read the parameter, 0 B;
- the read with the lane axis split (``table[i].reshape(S, L, h, d)`` and the
  per-head ``shd,slhd->shl``): the layer is sliced, copied to ``{2,3,1,0}``
  and converted to float32 — 0.227 GB a layer. Do not retry it;
- the read by a prefix length of a table that is written before it is read
  (:func:`prefix_attention`, PR 36; a hybrid's one full table and its eight
  readers): ops/decode_attention.py, a Mosaic custom call whose operand is
  the layer where it lies (``take_layer`` of a one-layer leaf is a bitcast).
  The kernel copies a slot's live blocks HBM -> VMEM itself and splits the
  lanes there, where a lane-tile slice costs nothing — 0 B, no fusion, copy
  or convert of the table, the hybrid step's scratch 129.6 MB with all five
  leaves aliased. Both reads above pass over every position of every slot
  whatever it holds; this one moves ``ceil(length / block)`` blocks a slot;
- the read of a table that does NOT hold the step's row yet
  (:func:`cached_attention` given ``rows``, PR 38; ``CausalLM`` and the four
  full layers of models/olmo_hybrid.py): the same kernel's plain-heads form,
  ``decode_attention.row_attention``. It moves a slot's blocks below
  ``position`` and takes the row as an operand, which starts the online
  softmax (its score the first maximum, its weight 1). Its table operands are
  the STACKED leaves and the layer an index into them (the DMA reads
  ``k_hbm.at[layer, slot, rows]``): 0 B at the long-document cell's ``[4, 16,
  4608, 3840]``, four custom calls in a step that reserves under 64 MB. Two
  spellings of the same read copy, do not retry them: the select on its way
  into the custom call — ``where(position_hit, new_row, table[i])`` cannot
  fuse into a Mosaic call, so the layer is made, both sides: 1,132,655,616 B
  — and ``take_layer`` of a leaf of several layers — a one-layer leaf's
  slice is a bitcast (above), a four-layer leaf's is two ``slice``
  instructions of 566 MB: 1,132,623,360 B. Where the heads, the dtype or the
  ``cache_len`` do not admit the kernel, the mask form below;
- a leaf without trailing axes (the int8 scale, ``[nl, S, L]``, 2.4 MB) has
  the position minor-most again; it keeps the select, a pass over 0.3% of what
  the payload's was.

In the mask form each layer attends ``where(position_hit, new_row,
table[i])`` of the step's INPUT table (the select fuses into the attention's
read; no layer table exists), so the operand values are those of
write-then-attend, bit for bit (tests/test_decode_kv_write.py); in the kernel
form they are the same values from two operands. Either way the rows go in
once, after the last read. The whole step: 26 MB of scratch, both tables
aliased, no fusion, copy, slice or convert of a table
(tests/test_chip_compile.py keeps it so).
``prefill_chunk`` / ``verify_step`` still slice a layer out, scatter into it
and re-stack, and have the copies by construction.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models.quant import quantize_kv
from distributed_tensorflow_tpu.ops import decode_attention

MASK_VALUE = -1e30
POSITIONS = "positions"  # Leaf.after: the engine's cache_len follows the slots


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One array of a cache: ``[layers, slots, *after, *shape]``. ``shape`` is
    what trails (global, before any sharding), ``partition`` one mesh axis
    name or ``None`` per trailing axis. ``layers``, ``after`` and ``group``
    are the group's, alike on all its leaves: how many layers keep this,
    what follows the slot axis — ``POSITIONS``, a ring length, or ``None``
    for state without positions — and the name the group goes by."""

    shape: tuple[int, ...]
    dtype: np.dtype
    partition: tuple[str | None, ...]
    layers: int
    after: int | str | None = POSITIONS
    group: str = "kv"
    # Layers whose decode step reads this leaf up to the slot's length —
    # :func:`prefix_attention`, :func:`cached_attention` given the step's
    # rows, or :func:`latent_attention` given the layer — (0: every reader
    # passes over all of it), and the positions such a read moves at a time
    # where the kernel applies to the model's heads or rows (0: it does
    # not). What :func:`step_reads` counts from.
    prefix_readers: int = 0
    prefix_block: int = 0
    # Whether a position of this group is a K/V page the host half (the
    # prefix pool, the wire, ``split_kv``) can move: ``{"k", "v"}`` rows of
    # heads. A latent row (models/deepseek_v2.py) is one row for every head.
    pages: bool = True

    def lead(self, axes: tuple[int, ...]) -> tuple[int, ...]:
        """The leading shape for ``axes = (*front, positions)``: ``(slots,
        cache_len)`` of a slot table, ``(blocks, block_tokens)`` of a pool,
        ``(cache_len,)`` of one slot's lane. A ring keeps its own length in
        place of ``positions``; state has nothing there."""
        *front, positions = axes
        after = {None: (), POSITIONS: (positions,)}.get(
            self.after, (self.after,)
        )
        return (self.layers, *front, *after)

    @property
    def nbytes(self) -> int:
        """Bytes of one slot's one position (or ring row, or state)."""
        return self.layers * math.prod(self.shape) * self.dtype.itemsize


def cache_layout(cfg, kv_dtype: str):
    """The one group ``CausalLM(cfg)`` caches under ``kv_dtype`` (a concrete
    name from ``CausalLMEngine._plan_quant``). Heads split over
    ``cfg.model_axis``; a scale has no axis to split."""
    row = (cfg.hidden_size,)  # heads x head_dim, merged: one contiguous row
    split = (cfg.model_axis,)
    nl = cfg.num_layers
    if kv_dtype == "int8":
        side = {
            "q": Leaf(row, np.dtype(np.int8), split, nl),
            "s": Leaf((), np.dtype(np.float32), (), nl),
        }
    else:
        # every layer's decode read is cached_attention given the step's
        # rows: up to the slot's length where the kernel applies, which a
        # table split over a mesh axis does not ask for
        block = 0 if cfg.model_axis else decode_attention.block_for(
            cfg.num_heads, cfg.hidden_size // cfg.num_heads, cfg.hidden_size,
            paired=False,
        )
        side = Leaf(
            row, jnp.dtype(kv_dtype), split, nl,
            prefix_readers=nl if block else 0, prefix_block=block,
        )
    return {"k": side, "v": side}


def _by_group(layout) -> dict[str, list[Leaf]]:
    out = {}
    for leaf in jax.tree.leaves(layout):
        out.setdefault(leaf.group, []).append(leaf)
    return out


def _refuse(leaf: Leaf, needs: str) -> ValueError:
    kind = {None: "positionless", POSITIONS: "latent rows, not K/V pages"}.get(
        leaf.after, "ring"
    )
    return ValueError(
        f"{needs}; cache group {leaf.group!r} is {kind} "
        f"(after={leaf.after!r}): not supported for this model"
    )


def require_pages(layout, mode: str) -> None:
    """``mode`` treats a cached position as a page it may copy, share,
    quantize or shard by itself. That holds for a table of K/V positions
    only: a ring forgets, state has no positions, and a latent row is no
    page of heads."""
    for leaf in jax.tree.leaves(layout):
        if leaf.after != POSITIONS or not leaf.pages:
            raise _refuse(
                leaf, f"{mode} needs every cached position to be a "
                "transferable page"
            )


def require_carry(layout) -> None:
    """Chunked prefill needs a carry between a prompt's chunks, not a page: a
    table of positions takes each chunk's rows where they belong, and
    positionless state IS the carry (the chunk program gathers a row's slot,
    hands it to the model's ``prefill_chunk`` and scatters it back). A ring
    refuses: a chunk's later rows would overwrite what its earlier queries
    still have to read."""
    for leaf in jax.tree.leaves(layout):
        if leaf.after not in (POSITIONS, None):
            raise _refuse(
                leaf, "chunked prefill needs every cache group to be a table "
                "of positions or state carried between chunks"
            )


# -- any layout: one tree.map over the description each -------------------


def specs(layout, lane: bool = False):
    """PartitionSpec per leaf of a slot table (or a pool, or a stage of
    either); ``lane`` for one slot's lane, which drops the slot axis. The
    leading axes are never sharded."""
    return jax.tree.map(
        lambda leaf: P(
            *(None,) * (len(leaf.lead((0, 0))) - lane), *leaf.partition
        ),
        layout,
    )


def shardings(layout, mesh, lane: bool = False):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs(layout, lane),
        is_leaf=lambda x: isinstance(x, P),
    )


def structs(layout, axes: tuple[int, ...], sharding):
    """ShapeDtypeStruct per leaf for ``axes`` (:meth:`Leaf.lead`);
    ``sharding`` is the matching tree from :func:`shardings`."""
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            (*leaf.lead(axes), *leaf.shape), leaf.dtype, sharding=s
        ),
        layout, sharding,
    )


def zeros(layout, axes: tuple[int, ...], sharding):
    return jax.tree.map(
        lambda st: jax.device_put(jnp.zeros(st.shape, st.dtype), st.sharding),
        structs(layout, axes, sharding),
    )


def bytes_per_token(layout) -> int:
    """Bytes ONE more cached position occupies across the layers and leaves
    that keep positions (K + V, plus scales at int8). A ring and state cost
    the same whatever the length: :func:`components`."""
    return sum(
        leaf.nbytes for leaf in jax.tree.leaves(layout)
        if leaf.after == POSITIONS
    )


def components(layout, axes: tuple[int, ...]) -> dict[str, tuple[int, str]]:
    """``(bytes at axes, storage dtype)`` of each group, by the name the
    memory registry lists it under: ``cache.<group>``, and ``kv_slot_cache``
    for the K/V table of a model that caches nothing else, the name it always
    had. The dtype is that of the group's largest leaf: the int8 payload, not
    its scale."""
    out = {}
    for group, leaves in _by_group(layout).items():
        biggest = max(leaves, key=lambda leaf: leaf.nbytes)
        out["kv_slot_cache" if group == "kv" else f"cache.{group}"] = (
            sum(
                leaf.nbytes * math.prod(leaf.lead(axes)[1:]) for leaf in leaves
            ),
            str(np.dtype(biggest.dtype).name),
        )
    return out


def step_writes(layout, live: int) -> dict[str, int]:
    """What ONE decode step writes for ``live`` lanes, as the counters the
    ``engine.decode_dispatch`` span carries: ``<group>_rows_written``, a row
    per layer and leaf of a group that has positions or a ring (a scale
    counts as a row), and ``<group>_bytes_written`` of a positionless group,
    which is rewritten whole."""
    out = {}
    for group, leaves in _by_group(layout).items():
        if leaves[0].after is None:
            out[f"{group}_bytes_written"] = live * sum(
                leaf.nbytes for leaf in leaves
            )
        else:
            out[f"{group}_rows_written"] = live * sum(
                leaf.layers for leaf in leaves
            )
    return out


def _kernel_block(block: int, cache_len: int) -> int:
    """``block`` where a prefix read of a table of ``cache_len`` positions
    goes through the kernel — it applies to the heads (``block`` > 0) and
    the table is whole blocks — else 0: the mask form."""
    return block if block and cache_len % block == 0 else 0


def prefix_reads(layout, cache_len: int) -> dict[str, tuple[int, int, int]]:
    """``{group: (positions a read moves at a time, reads a slot: layers x
    leaves, blocks a slot)}`` of the groups whose decode-step readers take a
    prefix length: what :func:`step_reads` needs, fixed at the engine's
    construction."""
    out = {}
    for group, leaves in _by_group(layout).items():
        if leaves[0].prefix_readers:
            # the mask form passes over the whole slot
            block = _kernel_block(leaves[0].prefix_block, cache_len) or cache_len
            out[group] = (
                block, leaves[0].prefix_readers * len(leaves),
                cache_len // block,
            )
    return out


def step_reads(reads, lengths) -> dict[str, int]:
    """What ONE decode step reads of the groups in ``reads``
    (:func:`prefix_reads`), beside :func:`step_writes` on the
    ``engine.decode_dispatch`` span: ``<group>_blocks_read`` for ``lengths
    [S]`` (a lane's position + 1, and 0 for an idle lane) and
    ``<group>_blocks_total``, what a step that stopped nowhere would move.
    Their ratio is the share of the table the step touched. A read that
    takes the step's row as an operand stops one position earlier: where
    that position is a block's first (one step in ``block``) this counts a
    block more than it moved."""
    out = {}
    for group, (block, sides, a_slot) in reads.items():
        out[f"{group}_blocks_read"] = sides * int(np.sum(-(-lengths // block)))
        out[f"{group}_blocks_total"] = sides * len(lengths) * a_slot
    return out


def whole_reads(layout, cache_len: int) -> dict[str, int]:
    """``{group: cache_len}`` of the latent tables (``Leaf.pages`` false):
    what :func:`step_positions` counts from, beside the blocks
    :func:`step_reads` counts of the same tables."""
    return {
        group: cache_len for group, leaves in _by_group(layout).items()
        if leaves[0].after == POSITIONS and not leaves[0].pages
    }


def step_positions(whole, lengths) -> dict[str, int]:
    """Beside :func:`step_reads`, for the groups of :func:`whole_reads`:
    ``<group>_positions_live``, the positions ``lengths [S]`` hold (a lane's
    position + 1, 0 for an idle lane), and ``<group>_positions_total``, the
    positions of every slot. Their ratio is the share of the table the
    step's attention needs; ``<group>_blocks_read`` over ``_blocks_total``
    is the share :func:`latent_attention` moved, which the tail of each
    slot's last block and the mask form (every block) put above it."""
    out = {}
    for group, cache_len in whole.items():
        out[f"{group}_positions_live"] = int(np.sum(lengths))
        out[f"{group}_positions_total"] = len(lengths) * cache_len
    return out


# -- the engine's host boundary -------------------------------------------


def merge_heads(a):
    """``[.., h, d]`` as the row ``[.., h * d]`` a cached position is."""
    return a.reshape(*a.shape[:-2], -1)


def _is_side(x) -> bool:
    return isinstance(x, dict) and set(x) == {"q", "s"}


def _payload(side, fn):
    """``fn`` over the leaf of a side that has the row; a scale rides as is."""
    if _is_side(side):
        return {**side, "q": fn(side["q"])}
    return fn(side)


def split_kv(tree, heads: int):
    """A K/V tree (slot table, lane, pool stage) as the ``(pages_k,
    pages_v)`` the host half takes: plain arrays, or the ``{"q", "s"}`` pair
    each. The wire says ``[.., heads, head_dim]`` where the cache holds one
    merged row: a row-major reshape, the same bytes."""
    split = lambda a: a.reshape(*a.shape[:-1], heads, -1)  # noqa: E731
    return _payload(tree["k"], split), _payload(tree["v"], split)


def join_kv(pages_k, pages_v):
    return {
        "k": _payload(pages_k, merge_heads),
        "v": _payload(pages_v, merge_heads),
    }


def page_geometry(cfg, layout) -> dict:
    """What the wire headers say of a page: the layers that keep one, the
    model's heads, and the payload's dtype — int8 pools report int8, so fp32
    and int8 peers refuse each other's pages."""
    side = layout["k"]
    payload = side["q"] if _is_side(side) else side
    return {
        "num_layers": int(payload.layers),
        "heads": int(cfg.num_heads),
        "head_dim": int(cfg.hidden_size // cfg.num_heads),
        "dtype": str(np.dtype(payload.dtype).name),
    }


# -- the model's reads and writes -----------------------------------------


def cache_len(cache) -> int:
    """Positions of a K/V table ``[nl, rows, positions, ..]``."""
    return jax.tree.leaves(cache)[0].shape[2]


def take_layer(cache, i: int):
    return jax.tree.map(lambda a: a[i], cache)


def put_layer(cache, i: int, layer):
    """``cache`` with layer ``i`` replaced: the write of state that is
    rewritten whole each step, in place when ``layer`` was computed from
    ``take_layer(cache, i)`` of the same value."""
    return jax.tree.map(lambda a, x: a.at[i].set(x), cache, layer)


def _encode(like, fresh):
    if _is_side(like):
        # int8: quantize per position at the write; attention reads the
        # factored per-position scales.
        return dict(zip(("q", "s"), quantize_kv(fresh)))
    return fresh.astype(like.dtype)


def encode(like, fresh):
    """``fresh`` (a tree of arrays by leaf name: ``{"k", "v"}``, each ``[...,
    h * d]``, the projections' heads merged as a cached row is) as rows of
    the form ``like`` (a group's leaves, or one layer of them) stores: a
    cast, or the int8 ``{"q", "s"}`` pair. Every writer encodes here, so a
    page is the same bits whichever path — prompt prefill, chunk, verify,
    decode — wrote it."""
    with jax.named_scope("kv_write"):
        return jax.tree.map(_encode, like, fresh, is_leaf=_is_side)


def select_rows(table, rows, position, slot_axis: int):
    """``table`` with ``rows`` at each slot's ``position``, as a select.
    ``table`` is ``[.., S, L, ..]`` with the slots at ``slot_axis`` and the
    cache positions after them, ``rows`` the same without the position axis,
    ``position: [S]``; the leaves differ only in trailing axes. A position
    of ``L`` or more matches nothing: the slot keeps its pages. Over one
    layer (``slot_axis=0``) it fuses into the attention that reads it; over
    the stacked table it is a pass over the whole leaf, which
    :func:`write_rows` keeps for the leaves that are small.
    """

    def leaf(t, r):
        hit = jnp.arange(t.shape[slot_axis + 1]) == position[:, None]  # [S, L]
        hit = hit.reshape(hit.shape + (1,) * (t.ndim - slot_axis - 2))
        return jnp.where(hit, jnp.expand_dims(r, slot_axis + 1), t)

    return jax.tree.map(leaf, table, rows)


def write_rows(cache, rows, position):
    """The stacked table ``[nl, S, L, ..]`` with the step's ``rows [nl, S,
    ..]`` at each slot's ``position [S]``, in place (module docstring). A
    leaf with trailing axes is written as ``nl x S`` rows of the flat ``[nl
    * S * L, row]`` table, whose traffic is the rows'; a leaf without (a
    scale) has the position minor-most and takes the select. A position of
    ``L`` or more writes nothing: the slot keeps its pages."""
    with jax.named_scope("kv_write"):
        return jax.tree.map(
            lambda t, r: (
                _scatter_flat(t, r, position) if t.ndim > 3
                else select_rows(t, r, position, slot_axis=1)
            ),
            cache, rows,
        )


def _scatter_flat(t, r, position):
    nl, s, l = t.shape[:3]
    lane = jnp.arange(nl * s).reshape(nl, s)  # layer * S + slot
    # an idle lane's index lies past the table, each its own: all dropped,
    # and the indices stay unique as promised
    idx = jnp.where(position < l, lane * l + position, nl * s * l + lane)
    flat = t.reshape(nl * s * l, -1)
    flat = flat.at[idx.reshape(-1)].set(
        r.reshape(nl * s, -1), mode="drop", unique_indices=True
    )
    return flat.reshape(t.shape)


def scatter_rows(table, rows, positions):
    """One layer's per-row tables ``[B, Lc, ..]`` with ``rows [B, C, ..]``
    written at ``positions [B, C]``; the out-of-range sentinel ``Lc`` on a
    padding lane drops its write."""
    b = jnp.arange(positions.shape[0])[:, None]
    return jax.tree.map(
        lambda t, r: t.at[b, positions].set(r, mode="drop"), table, rows
    )


def stack_layers(layers):
    """Per-layer results as one ``[nl, ...]`` tree. Part of ``kv_write``: the
    per-layer tables the scatters of ``prefill_chunk`` produced become the
    new slot table, and the per-layer rows of ``decode_step`` the
    ``[nl, S, ..]`` it writes."""
    with jax.named_scope("kv_write"):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def write_prompt(cache, slots, fresh):
    """The slot table with whole prefilled prompts in it. ``fresh`` mirrors
    the cache, arrays ``[layers, T, ..]`` from the model's ``prefill_rows``:
    positions ``[0, L)`` of a K/V table (contiguous rows each), a ring's rows
    where they live, a row's state whole — each is what follows ``slots [T]``
    in its leaf, so one indexed set writes any of them. A tier's padding rows
    carry slot index == S (one past the pool), so their writes drop and never
    dirty a live slot. Encoded by :func:`encode` like every other write: a
    prefilled page is bit-identical to one the decode path would have
    written."""
    rows = encode(cache, fresh)
    with jax.named_scope("kv_write"):
        return jax.tree.map(
            lambda c, r: c.at[:, slots, : r.shape[2]].set(r, mode="drop"),
            cache, rows,
        )


def _operand(side):
    """(what the einsum reads, per-position scale or None)."""
    if _is_side(side):
        return side["q"].astype(jnp.float32), side["s"]
    return side, None


def _attend(q, cache, position, qk: str, pv: str):
    """Attention of ``q [.., h, d]`` over one layer's cache ``[rows, L, h *
    d]``, each query seeing cache positions ``<= position`` (clamped: an
    idle lane's sentinel reads garbage nobody uses). Both contractions run
    over the merged row ``c = h * d`` as the table holds it — splitting it
    into heads would copy the layer (module docstring): the scores contract
    ``k`` with the block-diagonal ``qb[.., c, h]`` (``q`` where lane ``c`` is
    head ``h``'s, else 0), and of the context ``[rows, h, .., c]`` each head
    keeps its own lanes. The zeros add nothing and the lanes dropped are other
    heads' values, so this is per-head attention, in f32 score/context
    accumulation and with exactly-0 masking as the full forward. An int8
    side is never dequantized: the k-scale multiplies the scores after the
    QK^T product and the v-scale folds into the softmax weights before the
    context product — in that order in both callers, so verify columns stay
    bit-identical to the decode steps they replace. ``h`` and ``d`` are the
    local ones: under ``model`` sharding a shard holds whole heads."""
    k, k_scale = _operand(cache["k"])
    v, v_scale = _operand(cache["v"])
    position = jnp.minimum(position, k.shape[1] - 1)
    h, d = q.shape[-2:]
    own = jnp.arange(h * d)[:, None] // d == jnp.arange(h)  # [c, h]
    qb = jnp.where(own, q.reshape(*q.shape[:-2], h * d, 1), 0)
    s = jnp.einsum(qk, qb, k, preferred_element_type=jnp.float32)
    between = tuple(range(1, s.ndim - 1))  # the axes between rows and L
    if k_scale is not None:
        s = s * jnp.expand_dims(k_scale, between)
    s = s * d ** -0.5
    valid = jnp.arange(k.shape[1]) <= position[..., None]
    valid = jnp.expand_dims(valid, 1)  # heads
    s = jnp.where(valid, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * valid
    if v_scale is not None:
        p = p * jnp.expand_dims(v_scale, between)
    ctx = jnp.einsum(
        pv, p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )  # [rows, h, .., c]
    mine = own.T.reshape(h, *(1,) * (ctx.ndim - 3), h * d)
    out = jnp.sum(jnp.where(mine, ctx, 0), axis=1)  # [rows, .., c]
    return out.reshape(q.shape).astype(q.dtype)


def cached_attention(
    q, cache, position, rows=None, layer: int | None = None,
    sharded: bool = False,
):
    """One token per slot: ``q: [S, h, d]``, ``position: [S]`` the index the
    newest token sits at, over one layer's table ``[S, Lmax, ..]`` — ``cache``
    itself, or with ``layer`` that layer of the stacked ``[nl, S, Lmax,
    ..]``. ``rows [S, ..]`` are the step's own rows, encoded
    (:func:`encode`) and not in the table yet: the read sees them at
    ``position`` (``None``: the table holds them already). Where the table
    admits it — the stacked leaf, plain arrays (not the int8 pair) of whole
    blocks, heads that are whole lane tiles
    (``decode_attention.head_window_lanes``), not ``sharded`` over a mesh
    axis — ops/decode_attention.py reads each slot's blocks below
    ``position`` where they lie and takes the row as an operand; any other
    table takes the mask form, a pass over every position with the rows
    selected in."""
    with jax.named_scope("cached_attention"):
        k = cache["k"]
        if (
            rows is not None and layer is not None and not sharded
            and not _is_side(k)
            and _kernel_block(
                decode_attention.block_for(
                    *q.shape[-2:], k.shape[-1], paired=False
                ),
                k.shape[2],
            )
        ):
            return decode_attention.row_attention(
                q, k, cache["v"], position, rows["k"], rows["v"], layer=layer
            ).astype(q.dtype)
        if layer is not None:
            cache = take_layer(cache, layer)
        if rows is not None:
            cache = select_rows(cache, rows, position, slot_axis=0)
        return _attend(q, cache, position, "sch,slc->shl", "shl,slc->shc")


def latent_attention(
    q, table, position, row, scale: float, layer: int | None = None
):
    """One token per slot, every head over ONE cached row a position (the
    absorbed form of multi-head latent attention, models/deepseek_v2.py):
    ``q [S, h, r]`` the heads' queries in the row's own coordinates, ``table
    [S, L, r]`` one layer's rows as the step found them — or with ``layer``
    that layer of the stacked ``[nl, S, L, r]`` —, ``row [S, r]`` the step's
    own, encoded and not in the table yet, seen at ``position [S]`` (``L``
    or more on an idle lane: nothing is selected in, and what it reads
    nobody uses). Scores ``scale * q . row`` over positions ``<=
    position``, float32 softmax, and the context over the whole row ``[S,
    h, r]`` float32: the caller keeps the lanes that are values. Where the
    stacked table admits it — whole blocks, a row of whole lane tiles
    (``decode_attention.latent_block_for``) — ops/decode_attention.py reads
    each slot's blocks below ``position`` where they lie, once, and takes
    the row as an operand, in the table's dtype; any other table takes the
    mask form, in ``q``'s: the select fuses into both contractions' read, a
    pass over every position of every slot."""
    with jax.named_scope("latent_attention"):
        if layer is not None and _kernel_block(
            decode_attention.latent_block_for(table.shape[-1]),
            table.shape[2],
        ):
            return decode_attention.latent_row_attention(
                q, table, position, row, layer=layer, scale=scale
            )
        if layer is not None:
            table = take_layer(table, layer)
        table = select_rows(
            table.astype(q.dtype), row.astype(q.dtype), position, slot_axis=0
        )
        position = jnp.minimum(position, table.shape[1] - 1)
        s = jnp.einsum(
            "shr,slr->shl", q, table, preferred_element_type=jnp.float32
        ) * scale
        valid = (jnp.arange(table.shape[1]) <= position[:, None])[:, None]
        s = jnp.where(valid, s, MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1) * valid
        return jnp.einsum(
            "shl,slr->shr", p.astype(table.dtype), table,
            preferred_element_type=jnp.float32,
        )


def chunk_attention(q, cache, position):
    """A chunk of queries per row: ``q: [B, C, h, d]``, per-row caches ``[B,
    Lc, ..]``, ``position: [B, C]``. Cache positions beyond a row's written
    length hold zeros or a prior occupant's values — finite either way, with
    softmax weight exactly 0 under the causal mask."""
    # the context comes heads-second, as the product leaves it: the CPU
    # backend has no bf16 dot whose result is transposed ("->bqhc")
    if q.shape[-1] % 128 == 0 and not _is_side(cache["k"]):
        return _attend_tiled_heads(q, cache, position)
    return _attend(q, cache, position, "bqch,blc->bhql", "bhql,blc->bhqc")


_QUERY_BLOCK = 128  # queries whose scores _attend_tiled_heads holds at a time


def _attend_tiled_heads(q, cache, position):
    """:func:`chunk_attention` where a head is whole lane tiles (``head_dim``
    a multiple of 128): the merged row splits into heads on tile boundaries,
    so each head contracts over its own lanes only. :func:`_attend`'s
    block-diagonal query spends ``heads`` times the operations — nothing
    beside a decode step's memory traffic, but for a chunk of hundreds of
    queries over thousands of positions (30 heads of 128: 1.1 TFLOP a layer
    and row) more than the rest of the model. The copy of a row's table that
    the split costs is a chunk's, not a step's. A long chunk goes
    ``_QUERY_BLOCK`` queries at a time, one after another: the float32 scores
    of 512 queries x 30 heads x 4,608 positions are 283 MB a row, and several
    of them live at once. Same masking, scaling and float32 accumulation."""
    k, v = cache["k"], cache["v"]
    b, c, h, d = q.shape
    k, v = (a.reshape(*a.shape[:2], h, d) for a in (k, v))
    position = jnp.minimum(position, k.shape[1] - 1)

    def block(q, position):  # [B, n, h, d], [B, n]
        s = jnp.einsum(
            "bqhd,blhd->bhql", q, k, preferred_element_type=jnp.float32
        ) * d ** -0.5
        valid = (jnp.arange(k.shape[1]) <= position[..., None])[:, None]
        s = jnp.where(valid, s, MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1) * valid
        ctx = jnp.einsum(
            "bhql,blhd->bhqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return jnp.swapaxes(ctx, 1, 2).astype(q.dtype)

    if c <= _QUERY_BLOCK or c % _QUERY_BLOCK:
        return block(q, position)
    n = c // _QUERY_BLOCK
    ctx = jax.lax.map(
        lambda xs: block(*xs),
        (jnp.moveaxis(q.reshape(b, n, _QUERY_BLOCK, h, d), 1, 0),
         jnp.moveaxis(position.reshape(b, n, _QUERY_BLOCK), 1, 0)),
    )
    return jnp.moveaxis(ctx, 0, 1).reshape(q.shape)


def paired_attention(q, cache, valid, lam):
    """Differential attention of one token per row over a layer's K/V rows,
    grouped-query: ``q [S, 2P, d]`` (query pair ``p`` is heads ``2p, 2p +
    1``), ``cache {"k", "v"}`` each ``[S, L, 2G * d]`` (K/V pair ``g`` is the
    lanes ``[2g * d, (2g + 2) * d)``; pair ``p`` reads pair ``p // (P //
    G)``), ``valid [S, L]`` the rows a query may see — table positions ``<=
    position``, or the filled rows of a ring, whose order does not matter
    without positional encoding — and ``lam`` the layer's scalar. Returns
    ``(softmax(q1 k1) - lam * softmax(q2 k2)) [v1; v2]`` as ``[S, P, 2d]``
    float32, before the pair norm. Both contractions run over the merged row
    as the table holds it (module docstring: a lane split copies the layer):
    the scores against a block-diagonal query, and of the context ``[S, P, 2G
    * d]`` each pair keeps its K/V pair's 128-aligned lanes."""
    k, v = cache["k"], cache["v"]
    n_q, d = q.shape[-2:]
    c = k.shape[-1]
    n_kv = c // d
    per = n_q // n_kv  # query heads a K/V head serves, two pairs' worth
    head = jnp.arange(n_q)
    kv_of = 2 * (head // (2 * per)) + head % 2  # [2P]: the K/V head read
    own = (jnp.arange(c)[:, None] // d) == kv_of  # [c, 2P]
    qt = jnp.tile(jnp.swapaxes(q, -1, -2), (1, n_kv, 1))  # [S, c, 2P]
    qb = jnp.where(own, qt, 0)
    s = jnp.einsum("sch,slc->shl", qb, k, preferred_element_type=jnp.float32)
    s = s * d ** -0.5
    seen = valid[:, None, :]
    s = jnp.where(seen, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * seen  # [S, 2P, L]
    p = p.reshape(p.shape[0], n_q // 2, 2, -1)
    w = p[:, :, 0] - lam * p[:, :, 1]  # [S, P, L]
    ctx = jnp.einsum(
        "spl,slc->spc", w.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )  # [S, P, c]
    n_g = n_kv // 2
    ctx = ctx.reshape(ctx.shape[0], n_g, per, n_g, 2 * d)
    mine = jnp.eye(n_g, dtype=bool)[:, None, :, None]
    out = jnp.sum(jnp.where(mine, ctx, 0), axis=3)  # [S, G, per, 2d]
    return out.reshape(out.shape[0], n_q // 2, 2 * d)


def prefix_attention(q, cache, lengths, lam):
    """:func:`paired_attention` where row ``s`` sees the table's first
    ``lengths[s]`` positions (0: nothing, and zeros come back). Where the
    table admits it — rows that are whole lane tiles for these heads, a
    ``cache_len`` of whole blocks — ops/decode_attention.py reads each slot's
    live blocks where they lie and nothing else; any other table takes the
    mask form, a pass over every position. The caller has a length, not a
    mask: the table is written before it is read, so no row is selected in."""
    k = cache["k"]
    block = decode_attention.block_for(*q.shape[-2:], k.shape[-1])
    if _kernel_block(block, k.shape[1]):
        return decode_attention.table_attention(
            q, k, cache["v"], lengths, lam
        )
    valid = jnp.arange(k.shape[1]) < lengths[:, None]
    return paired_attention(q, cache, valid, lam)
