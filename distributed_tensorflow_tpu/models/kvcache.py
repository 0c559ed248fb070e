"""What a sequence caches between steps — the one module that knows.

A cache is a pytree whose every leaf is ``[layers, slots, positions,
*trailing]``. Everything outside this module (serve/engine.py above all)
addresses the first three axes and maps over the leaves; which leaves there
are, what trails them, their dtype, how they shard and how a fresh row is
encoded into them is decided here:

- :func:`cache_layout` describes the leaves (:class:`Leaf`). Dense K/V is
  ``{"k", "v"}``, each ``(heads, head_dim)`` in the store dtype; int8 K/V
  makes each side the ``{"q", "s"}`` pair of models/quant.py: the int8
  payload and its float32 per-position scale, which has no trailing axes.
- ``specs`` / ``shardings`` / ``structs`` / ``zeros`` / ``bytes_per_token``
  are one ``tree.map`` over that description each, for any layout.
- The model's reads and writes — ``take_layer``, ``encode``, ``select_rows``,
  ``scatter_rows``, ``stack_layers``, ``write_prompt``, ``cached_attention``,
  ``chunk_attention`` — are written once for every form.
- ``split_kv`` / ``join_kv`` / ``page_geometry`` convert at the engine's host
  boundary, where serve/disagg.py and the wire format still speak of
  ``pages_k, pages_v`` (ROADMAP Design 1, the host half).

Why decode_step writes by select. On the TPU the slot table ``[nl, S, L, h,
d]`` lives with the cache POSITION minor-most (layout ``{2,4,3,1,0}``:
``d x L`` tiles without padding, ``h x d`` would not), which is the layout
the attention einsums read. The ``scatter`` and ``dynamic-update-slice``
emitters want ``{4,3,..}`` instead, so the compiler brackets every such
write with two copies of whatever table it writes. Compiled for a described
v5e at the serving benchmark's geometry (bf16, 128 slots, cache 384, tables
donated; ``memory_analysis().temp_size_in_bytes``):

- per layer ``table[i].at[idx, position].set(..)``, then re-stack (the
  spelling until PR 28): 48 copies of a layer table a step, 2 slicing
  fusions, 24 scatters, 24 re-stacking updates — 3.55 GB;
- one stacked scatter ``table.at[:, idx, position].set(..)`` at the end: the
  whole table copied there and back — 2.45 GB; the same for a loop of
  per-slot ``dynamic_update_slice``, rolled or unrolled — 2.45 GB;
- the stacked table carried through the layers with ``.at[i, idx,
  position].set(..)``: the whole program flips layout, 24 full-table
  scatters — 7.26 GB;
- what is here: each layer attends ``where(position_hit, new_row,
  table[i])`` (slice and select fuse into the attention loop; no layer
  table exists), and the ``[nl, S, h, d]`` of new rows are written once, by
  one select over the stacked table that aliases its donated operand —
  0.026 GB, no table-sized copy, slice, scatter or update (int8 KV: 0.028).

The select passes over the whole table to write ``nl x S`` rows; that one
pass is what the layout costs, and PERF.md (PR 28) has its time on the chip.
The operand values are those of write-then-attend, bit for bit
(tests/test_decode_kv_write.py); tests/test_chip_compile.py keeps the
compiled program free of the copies. ``prefill_chunk`` / ``verify_step``
still slice, scatter and re-stack, and have the copies by construction.
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
    heads = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
    split = (cfg.model_axis, None)
    if kv_dtype == "int8":
        side = {
            "q": Leaf(heads, np.dtype(np.int8), split),
            "s": Leaf((), np.dtype(np.float32), ()),
        }
    else:
        side = Leaf(heads, jnp.dtype(kv_dtype), split)
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


def split_kv(tree):
    """A cache-shaped tree as the ``(pages_k, pages_v)`` the host half takes:
    plain arrays, or the ``{"q", "s"}`` pair each."""
    return tree["k"], tree["v"]


def join_kv(pages_k, pages_v):
    return {"k": pages_k, "v": pages_v}


def page_geometry(layout) -> dict:
    """What the wire headers say of a page: int8 pools report int8 (the
    payload's dtype), so fp32 and int8 peers refuse each other's pages."""
    side = layout["k"]
    payload = side["q"] if isinstance(side, dict) else side
    heads, head_dim = payload.shape
    return {
        "heads": int(heads),
        "head_dim": int(head_dim),
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
    """Fresh ``k, v: [..., h, d]`` as rows of the form ``like`` (a cache, or
    one layer of it) stores: a cast, or the int8 ``{"q", "s"}`` pair. Every
    writer encodes here, so a page is the same bits whichever path — prompt
    prefill, chunk, verify, decode — wrote it."""
    with jax.named_scope("kv_write"):
        return {"k": _encode(like["k"], k), "v": _encode(like["v"], v)}


def select_rows(table, rows, position, slot_axis: int):
    """``table`` with ``rows`` at each slot's ``position``, as a select —
    never a scatter (module docstring). ``table`` is ``[.., S, L, ..]`` with
    the slots at ``slot_axis`` and the cache positions after them, ``rows``
    the same without the position axis, ``position: [S]``; the leaves differ
    only in trailing axes. A position of ``L`` or more matches nothing: the
    slot keeps its pages.
    """

    def leaf(t, r):
        hit = jnp.arange(t.shape[slot_axis + 1]) == position[:, None]  # [S, L]
        hit = hit.reshape(hit.shape + (1,) * (t.ndim - slot_axis - 2))
        return jnp.where(hit, jnp.expand_dims(r, slot_axis + 1), t)

    return jax.tree.map(leaf, table, rows)


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
    h, d]`` fresh from ``CausalLM.prefill`` go to positions ``[0, L)`` of
    ``slots [T]``. A tier's padding rows carry slot index == S (one past the
    pool), so their writes drop and never dirty a live slot's pages. Encoded
    by :func:`encode` like every other write: a prefilled page is
    bit-identical to one the decode path would have written."""
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
    """Attention of ``q`` over one layer's cache, each query seeing cache
    positions ``<= position`` (clamped: an idle lane's sentinel reads
    garbage nobody uses). f32 score/context accumulation and exactly-0
    masking, as the full forward. An int8 side is never dequantized: the
    k-scale multiplies the scores after the QK^T product and the v-scale
    folds into the softmax weights before the context product — in that
    order in both callers, so verify columns stay bit-identical to the
    decode steps they replace."""
    k, k_scale = _operand(cache["k"])
    v, v_scale = _operand(cache["v"])
    position = jnp.minimum(position, k.shape[1] - 1)
    s = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32)
    between = tuple(range(1, s.ndim - 1))  # the axes between rows and L
    if k_scale is not None:
        s = s * jnp.expand_dims(k_scale, between)
    s = s * q.shape[-1] ** -0.5
    valid = jnp.arange(k.shape[1]) <= position[..., None]
    valid = jnp.expand_dims(valid, 1)  # heads
    s = jnp.where(valid, s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * valid
    if v_scale is not None:
        p = p * jnp.expand_dims(v_scale, between)
    return jnp.einsum(
        pv, p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def cached_attention(q, cache, position):
    """One token per slot: ``q: [S, h, d]``, the layer's cache ``[S, Lmax,
    ..]``, ``position: [S]`` the index the newest token sits at."""
    with jax.named_scope("cached_attention"):
        return _attend(q, cache, position, "shd,slhd->shl", "shl,slhd->shd")


def chunk_attention(q, cache, position):
    """A chunk of queries per row: ``q: [B, C, h, d]``, per-row caches ``[B,
    Lc, ..]``, ``position: [B, C]``. Cache positions beyond a row's written
    length hold zeros or a prior occupant's values — finite either way, with
    softmax weight exactly 0 under the causal mask."""
    return _attend(q, cache, position, "bchd,blhd->bhcl", "bhcl,blhd->bchd")
