"""What a sequence caches between steps — the one module that knows.

A cache is a pytree whose every leaf is ``[layers, slots, positions,
*trailing]``. Everything outside this module (serve/engine.py above all)
addresses the first three axes and maps over the leaves; which leaves there
are, what trails them, their dtype, how they shard and how a fresh row is
encoded into them is decided here:

- :func:`cache_layout` describes the leaves (:class:`Leaf`). Dense K/V is
  ``{"k", "v"}``, each one row of ``heads * head_dim`` in the store dtype;
  int8 K/V makes each side the ``{"q", "s"}`` pair of models/quant.py: the
  int8 payload and its float32 per-position scale, which has no trailing
  axes.
- ``specs`` / ``shardings`` / ``structs`` / ``zeros`` / ``bytes_per_token``
  are one ``tree.map`` over that description each, for any layout.
- The model's reads and writes — ``take_layer``, ``encode``, ``select_rows``,
  ``write_rows``, ``scatter_rows``, ``stack_layers``, ``write_prompt``,
  ``cached_attention``, ``chunk_attention`` — are written once for every form.
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
- a leaf without trailing axes (the int8 scale, ``[nl, S, L]``, 2.4 MB) has
  the position minor-most again; it keeps the select, a pass over 0.3% of what
  the payload's was.

Each layer attends ``where(position_hit, new_row, table[i])`` of the step's
INPUT table (the select fuses into the attention's read; no layer table
exists), so the operand values are those of write-then-attend, bit for bit
(tests/test_decode_kv_write.py), and the rows go in once, after the last
read. The whole step: 26 MB of scratch, both tables aliased, no fusion, copy,
slice or convert of a table (tests/test_chip_compile.py keeps it so).
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

MASK_VALUE = -1e30
_LEAD = 3  # layers, slots (or pool blocks), positions


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One array of a cache, by what follows ``[layers, slots, positions]``:
    the trailing shape (global, before any sharding), the dtype, and one
    mesh axis name or ``None`` per trailing axis."""

    shape: tuple[int, ...]
    dtype: np.dtype
    partition: tuple[str | None, ...]


def cache_layout(cfg, kv_dtype: str):
    """The leaves ``CausalLM(cfg)`` caches under ``kv_dtype`` (a concrete
    name from ``CausalLMEngine._plan_quant``). Heads split over
    ``cfg.model_axis``; a scale has no axis to split."""
    row = (cfg.hidden_size,)  # heads x head_dim, merged: one contiguous row
    split = (cfg.model_axis,)
    if kv_dtype == "int8":
        side = {
            "q": Leaf(row, np.dtype(np.int8), split),
            "s": Leaf((), np.dtype(np.float32), ()),
        }
    else:
        side = Leaf(row, jnp.dtype(kv_dtype), split)
    return {"k": side, "v": side}


# -- any layout: one tree.map over the description each -------------------


def specs(layout, lead: int = _LEAD):
    """PartitionSpec per leaf; ``lead`` unsharded axes come first (3 for a
    table or a stage of pool pages, 2 for one slot's lane)."""
    return jax.tree.map(
        lambda leaf: P(*(None,) * lead, *leaf.partition), layout
    )


def shardings(layout, mesh, lead: int = _LEAD):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs(layout, lead),
        is_leaf=lambda x: isinstance(x, P),
    )


def structs(layout, lead: tuple[int, ...], sharding):
    """ShapeDtypeStruct per leaf at leading shape ``lead``; ``sharding`` is
    the matching tree from :func:`shardings`."""
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            (*lead, *leaf.shape), leaf.dtype, sharding=s
        ),
        layout, sharding,
    )


def zeros(layout, lead: tuple[int, ...], sharding):
    return jax.tree.map(
        lambda st: jax.device_put(jnp.zeros(st.shape, st.dtype), st.sharding),
        structs(layout, lead, sharding),
    )


def bytes_per_token(layout, num_layers: int) -> int:
    """Bytes ONE cached position occupies across all layers and leaves
    (K + V, plus scales at int8)."""
    return num_layers * sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(layout)
    )


# -- the engine's host boundary -------------------------------------------


def merge_heads(a):
    """``[.., h, d]`` as the row ``[.., h * d]`` a cached position is."""
    return a.reshape(*a.shape[:-2], -1)


def _payload(side, fn):
    """``fn`` over the leaf of a side that has the row; a scale rides as is."""
    if isinstance(side, dict):
        return {**side, "q": fn(side["q"])}
    return fn(side)


def split_kv(tree, heads: int):
    """A cache-shaped tree as the ``(pages_k, pages_v)`` the host half takes:
    plain arrays, or the ``{"q", "s"}`` pair each. The wire says ``[..,
    heads, head_dim]`` where the cache holds one merged row: a row-major
    reshape, the same bytes."""
    split = lambda a: a.reshape(*a.shape[:-1], heads, -1)  # noqa: E731
    return _payload(tree["k"], split), _payload(tree["v"], split)


def join_kv(pages_k, pages_v):
    return {
        "k": _payload(pages_k, merge_heads),
        "v": _payload(pages_v, merge_heads),
    }


def page_geometry(cfg, layout) -> dict:
    """What the wire headers say of a page: the model's heads, and the
    payload's dtype — int8 pools report int8, so fp32 and int8 peers refuse
    each other's pages."""
    side = layout["k"]
    payload = side["q"] if isinstance(side, dict) else side
    return {
        "heads": int(cfg.num_heads),
        "head_dim": int(cfg.hidden_size // cfg.num_heads),
        "dtype": str(np.dtype(payload.dtype).name),
    }


# -- the model's reads and writes -----------------------------------------


def cache_len(cache) -> int:
    return jax.tree.leaves(cache)[0].shape[2]


def take_layer(cache, i: int):
    return jax.tree.map(lambda a: a[i], cache)


def _encode(like, fresh):
    if isinstance(like, dict):
        # int8: quantize per position at the write; attention reads the
        # factored per-position scales.
        return dict(zip(("q", "s"), quantize_kv(fresh)))
    return fresh.astype(like.dtype)


def encode(like, k, v):
    """Fresh ``k, v: [..., h * d]`` (the projections' heads merged, as a
    cached row is) as rows of the form ``like`` (a cache, or one layer of
    it) stores: a cast, or the int8 ``{"q", "s"}`` pair. Every writer
    encodes here, so a page is the same bits whichever path — prompt
    prefill, chunk, verify, decode — wrote it."""
    with jax.named_scope("kv_write"):
        return {"k": _encode(like["k"], k), "v": _encode(like["v"], v)}


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
                _scatter_flat(t, r, position) if t.ndim > _LEAD
                else select_rows(t, r, position, slot_axis=1)
            ),
            cache, rows,
        )


def _scatter_flat(t, r, position):
    nl, s, l = t.shape[:_LEAD]
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


def write_prompt(cache, slots, k, v):
    """The slot table with whole prefilled prompts in it: ``k, v: [nl, T, L,
    h * d]`` fresh from ``CausalLM.prefill`` go to positions ``[0, L)`` of
    ``slots [T]``, contiguous rows each. A tier's padding rows carry slot
    index == S (one past the pool), so their writes drop and never dirty a
    live slot's pages. Encoded by :func:`encode` like every other write: a
    prefilled page is bit-identical to one the decode path would have
    written."""
    rows = encode(cache, k, v)
    with jax.named_scope("kv_write"):
        return jax.tree.map(
            lambda c, r: c.at[:, slots, : r.shape[2]].set(r, mode="drop"),
            cache, rows,
        )


def _operand(side):
    """(what the einsum reads, per-position scale or None)."""
    if isinstance(side, dict):
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


def cached_attention(q, cache, position):
    """One token per slot: ``q: [S, h, d]``, the layer's cache ``[S, Lmax,
    ..]``, ``position: [S]`` the index the newest token sits at."""
    with jax.named_scope("cached_attention"):
        return _attend(q, cache, position, "sch,slc->shl", "shl,slc->shc")


def chunk_attention(q, cache, position):
    """A chunk of queries per row: ``q: [B, C, h, d]``, per-row caches ``[B,
    Lc, ..]``, ``position: [B, C]``. Cache positions beyond a row's written
    length hold zeros or a prior occupant's values — finite either way, with
    softmax weight exactly 0 under the causal mask."""
    # the context comes heads-second, as the product leaves it: the CPU
    # backend has no bf16 dot whose result is transposed ("->bqhc")
    return _attend(q, cache, position, "bqch,blc->bhql", "bhql,blc->bhqc")
