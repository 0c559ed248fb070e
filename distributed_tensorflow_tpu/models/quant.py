"""Post-training int8 quantization for the serving path (ROADMAP item 4).

Two independent numerics modes, both opt-in per engine and both invisible
to training (checkpoints stay fp32 on disk):

**Weights** — per-output-channel absmax int8. Every Dense/DenseGeneral
``kernel`` leaf (ndim >= 2) is replaced IN PLACE in the param tree by a
two-leaf dict ``{"_q8": int8[kernel.shape], "_q8_scale": f32[out]}`` where
the scale is one absmax per trailing-axis channel (``max|w| / 127`` over
every axis but the last). Embeddings, biases, LayerNorms, the router, and
the MoE expert stacks stay in their checkpoint dtype — in particular the
TIED LM head (``word.attend``) scores against the exact fp32 embedding
table. Dequantization happens INSIDE each AOT executable
(:func:`dequantize_params` as the first line of the jitted body), so HBM
holds int8 kernels and XLA fuses the ``int8 -> f32 * scale`` convert into
the matmul operand read. The packed layout keeps ``bert_param_specs``'
suffix rules applicable: ``_q8`` shards exactly like the kernel it
replaced and ``_q8_scale`` carries the kernel's last-axis sharding, so TP
layouts restore shard-direct unchanged (models/bert.py spec rules).

**KV cache** — int8 pages with per-position scales. A quantized cache
operand is the pytree ``{"q": int8[..., heads * head_dim], "s":
f32[...]}``: one absmax scale per written position (per layer, per slot/
block, per token — the finest granularity an incremental decode write can
maintain without re-scaling a page). Writers quantize at the scatter
(:func:`quantize_kv`); attention never materializes a dequantized cache —
the k-scale factors into the score matrix after the QK^T product and the
v-scale folds into the softmax weights before the context product
(models/causal_lm.py). Page copies (prefix-pool publish/gather, disagg
export/import, stream migration) move ``q`` and ``s`` together bit-exactly,
which is why cached-vs-cold and spec-on-vs-off parity survive quantization
by construction.

``normalize_quant_dtype`` is the single knob validator: engines and
shardcheck's SC002 quant sweep route every ``weight_dtype`` / ``kv_dtype``
string through it so an unsupported mode dies in a clean ``ValueError`` at
plan time, never an XLA error mid-request.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "QUANT_DTYPES",
    "cast_params",
    "dequantize_kv",
    "dequantize_params",
    "fp32_equiv_nbytes",
    "free_replaced_leaves",
    "is_quantized_leaf",
    "is_quantized_tree",
    "normalize_quant_dtype",
    "quantize_kv",
    "quantize_params",
]

#: dtype names an engine accepts for weight_dtype / kv_dtype (None = keep
#: the model's compute dtype).
QUANT_DTYPES = ("float32", "bfloat16", "int8")

# absmax floor: an all-zero channel/position must quantize to scale > 0 so
# the dequant multiply never divides-by-zero upstream (q is 0 either way).
_EPS = 1e-8


def normalize_quant_dtype(value, what: str = "dtype") -> str | None:
    """Canonicalize a quantization knob: ``None`` means "keep the model
    dtype"; anything else must name one of :data:`QUANT_DTYPES`. Raises
    ``ValueError`` on unknown names — the clean-rejection contract
    shardcheck's SC002 quant sweep pins."""
    if value is None:
        return None
    name = str(np.dtype(value).name) if not isinstance(value, str) else value
    name = {"f32": "float32", "fp32": "float32", "bf16": "bfloat16"}.get(
        name, name
    )
    if name not in QUANT_DTYPES:
        raise ValueError(
            f"{what} {value!r} not supported: pick one of {QUANT_DTYPES} "
            "(or None to keep the model dtype)"
        )
    return name


def is_quantized_leaf(x) -> bool:
    """True for the packed ``{"_q8", "_q8_scale"}`` kernel dict."""
    return isinstance(x, dict) and "_q8" in x and "_q8_scale" in x


def is_quantized_tree(tree) -> bool:
    """True when any kernel leaf in ``tree`` is already int8-packed."""
    found = False
    for leaf in jax.tree.leaves(tree, is_leaf=is_quantized_leaf):
        if is_quantized_leaf(leaf):
            found = True
            break
    return found


def _path_names(path) -> tuple:
    return tuple(
        p.key for p in path if isinstance(p, jax.tree_util.DictKey)
    )


def _eligible(names, leaf) -> bool:
    # Dense/DenseGeneral kernels only: biases are 1-D, embeddings are named
    # "embedding" (the tied LM head must stay exact), MoE expert stacks use
    # their own leaf names and keep checkpoint dtype.
    return (
        bool(names)
        and names[-1] == "kernel"
        and getattr(leaf, "ndim", 0) >= 2
        and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    )


def quantize_params(params):
    """Per-output-channel absmax int8 over every eligible kernel leaf.

    Returns a new tree where each quantized kernel is the packed dict
    ``{"_q8": int8, "_q8_scale": f32[last_dim]}``; every other leaf is the
    ORIGINAL array (shared, not copied). Idempotent: already-packed leaves
    pass through untouched."""

    def q_leaf(path, leaf):
        if is_quantized_leaf(leaf):
            return leaf
        names = _path_names(path)
        if not _eligible(names, leaf):
            return leaf
        w = jnp.asarray(leaf, jnp.float32)
        red = tuple(range(w.ndim - 1))
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=red) / 127.0, _EPS)
        q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        return {"_q8": q, "_q8_scale": s.astype(jnp.float32)}

    return jax.tree_util.tree_map_with_path(
        q_leaf, params, is_leaf=is_quantized_leaf
    )


def dequantize_params(params, dtype=jnp.float32):
    """Unpack every ``{"_q8", "_q8_scale"}`` leaf back to a dense kernel in
    ``dtype``. Identity (same leaf objects) for unquantized trees, so every
    AOT executable body can call it unconditionally — under jit the
    int8->float convert fuses into the consuming matmul."""

    def dq(x):
        if is_quantized_leaf(x):
            return (
                x["_q8"].astype(jnp.float32) * x["_q8_scale"]
            ).astype(dtype)
        return x

    return jax.tree.map(dq, params, is_leaf=is_quantized_leaf)


def cast_params(params, dtype):
    """Cast every floating leaf (bf16 weight mode); ints and packed int8
    leaves pass through."""

    def c(x):
        if is_quantized_leaf(x):
            return x
        a = jnp.asarray(x)
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
            else x

    return jax.tree.map(c, params, is_leaf=is_quantized_leaf)


def fp32_equiv_nbytes(tree) -> int:
    """Bytes the tree's payload would occupy at fp32 — the baseline the
    ``/memz`` ``bytes_saved_vs_fp32`` ledger compares against. Packed int8
    kernels count their kernel elements only (the scale vector is overhead
    the ACTUAL byte count carries, so savings stay honest); quantized KV
    trees likewise count the ``q`` payload."""
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=is_quantized_leaf):
        if is_quantized_leaf(leaf):
            total += int(np.prod(leaf["_q8"].shape)) * 4
        elif isinstance(leaf, dict):  # pragma: no cover - defensive
            total += fp32_equiv_nbytes(leaf)
        else:
            total += int(np.prod(getattr(leaf, "shape", ()))) * 4
    return total


def free_replaced_leaves(old_tree, new_tree) -> int:
    """Delete the device buffers of every ``old_tree`` leaf that
    ``new_tree`` REPLACED (quantized or cast — leaves shared by identity
    survive). Returns the bytes reclaimed; the quantize-at-restore path
    feeds this into the memory registry's released ledger."""
    new_by_path = {
        path: leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            new_tree, is_leaf=is_quantized_leaf
        )[0]
    }
    reclaimed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(old_tree)[0]:
        new = new_by_path.get(path)
        if new is leaf or not isinstance(leaf, jax.Array):
            continue
        reclaimed += int(leaf.nbytes)
        leaf.delete()
    return reclaimed


# ---------------------------------------------------------------- KV cache


def quantize_kv(x):
    """Quantize K or V activations position-wise: absmax over a position's
    row, the merged ``heads * head_dim`` axis models/kvcache.py stores.
    ``x: [..., c]`` -> ``(q int8[..., c], scale f32[...])``."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, _EPS)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype=jnp.float32):
    """Materialize a quantized KV stage back to dense (wire/debug paths
    only — attention uses the factored form and never calls this)."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)
