"""Decoder-only causal LM — the generative serving workload (ROADMAP item 2).

The transformer block is the BERT one (models/bert.py) reassembled for
decoding: post-LayerNorm residual blocks, learned positions, tanh-GELU FFN,
Megatron column/row tensor-parallel projections with the bias applied after
the psum, and a TIED LM head (logits against the word-embedding table, the
``mlm_transform -> ln -> attend`` recipe of ``BertForPreTraining._heads``).
Param leaf names intentionally match BERT's (``query``/``key``/``value``/
``out``/``intermediate``/``output`` + the post-psum ``*_bias`` twins), so
:func:`bert_param_specs`' suffix rules shard this model unchanged —
:func:`causal_param_specs` just delegates.

Three forwards share one param tree:

- ``__call__(input_ids, attention_mask) -> logits [B, L, V]`` — the full
  causally-masked forward: training loss, scoring, and the one-shot
  reference the serving decode path is tested against.
- ``prefill(input_ids, attention_mask) -> (logits, k [nl,B,L,h,d], v)`` —
  same math, but also returns every layer's projected K/V so the serving
  engine can scatter them into its slot cache (serve/engine.py
  ``CausalLMEngine``).
- ``decode_step(token [S], position [S], k_cache, v_cache) -> (logits [S,V],
  k_cache', v_cache')`` — ONE token per cache slot: embed at the slot's
  position, write the new K/V at ``position``, attend positions
  ``<= position``. Shapes are fixed by the slot count, so slot
  assignment/reuse never retraces (the "fixed pool of per-slot cache
  pages" contract). It writes by select, not by scatter (below).
- ``prefill_chunk(input_ids [B, C], positions [B, C], k_cache, v_cache) ->
  (logits [B, C, V], k_cache', v_cache')`` — a CHUNK of each row's prompt
  at arbitrary ABSOLUTE positions against per-row caches ``[nl, B, Lc, h,
  d]``: write the chunk's K/V at ``positions``, attend the cache causally
  (each query sees positions ``<= its own``). One method covers both
  prefix-cache suffix prefill (one chunk starting at ``cached_len``) and
  fixed-size chunked prefill of long prompts; padding lanes carry the
  out-of-range sentinel position ``Lc`` so their cache writes drop
  (``mode="drop"``) while attention/embedding use the clamped position.
- ``verify_step(tokens [S, K+1], positions [S, K+1], k_cache, v_cache) ->
  (logits [S, K+1, V], k_cache', v_cache')`` — speculative decoding's
  batched verify: score a slot's last verified token plus up to K draft
  tokens in ONE dispatch. Same math as ``prefill_chunk`` (it delegates),
  which is the point: column j's logits are bit-identical to what
  ``decode_step`` would produce after j accepted tokens, so greedy
  accept-matching preserves the exact non-speculative stream.

Numerics: both attention paths accumulate scores and context in f32 with
the same masking convention (fully-masked rows -> exactly 0), so a token
decoded step-by-step matches the full forward's argmax at the same
position — tests/test_serve_decode.py pins greedy parity exactly.

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

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_tensorflow_tpu.models.bert import _tp_psum, bert_param_specs
from distributed_tensorflow_tpu.models.quant import quantize_kv

_MASK_VALUE = -1e30


def _layer_cache(cache, i):
    """Slice layer ``i`` out of a stacked cache — plain ``[nl, ...]`` array
    or the quantized ``{"q", "s"}`` pytree (models/quant.py)."""
    if isinstance(cache, dict):
        return {"q": cache["q"][i], "s": cache["s"][i]}
    return cache[i]


def _stack_cache(layers):
    """Stack per-layer cache returns, preserving the quantized pytree
    structure when present. Part of ``kv_write``: the per-layer tables the
    scatters of ``prefill_chunk`` produced become the new slot table, and the
    per-layer rows of ``decode_step`` the ``[nl, S, h, d]`` it writes."""
    with jax.named_scope("kv_write"):
        if isinstance(layers[0], dict):
            return {
                "q": jnp.stack([c["q"] for c in layers]),
                "s": jnp.stack([c["s"] for c in layers]),
            }
        return jnp.stack(layers)


def _select_rows(table, rows, position, slot_axis):
    """``table`` with ``rows`` at each slot's ``position``, as a select —
    never a scatter (module docstring). ``table`` is ``[.., S, L, ..]`` with
    the slots at ``slot_axis`` and the cache positions after them, ``rows``
    the same without the position axis, ``position: [S]``; both may be the
    int8 ``{"q", "s"}`` pytree, whose leaves differ only in trailing axes.
    A position of ``L`` or more matches nothing: the slot keeps its pages.
    """

    def leaf(t, r):
        hit = jnp.arange(t.shape[slot_axis + 1]) == position[:, None]  # [S, L]
        hit = hit.reshape(hit.shape + (1,) * (t.ndim - slot_axis - 2))
        return jnp.where(hit, jnp.expand_dims(r, slot_axis + 1), t)

    return jax.tree.map(leaf, table, rows)


@dataclasses.dataclass(frozen=True)
class CausalLMConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    dtype: jnp.dtype = jnp.float32
    # Megatron tensor parallelism, same contract as BertConfig: params are
    # created GLOBAL (init with model_parallel=1) and sliced by
    # causal_param_specs; inside shard_map the module builds local-head /
    # local-FFN projections and psums the row-parallel outputs.
    model_axis: str | None = None
    model_parallel: int = 1

    def __post_init__(self):
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_heads {self.num_heads}"
            )


def causal_lm_base(**overrides) -> CausalLMConfig:
    return CausalLMConfig(**overrides)


def _causal_attention(q, k, v, pad_mask):
    """Full-sequence causally-masked attention.

    ``q, k, v: [B, L, h, d]``; ``pad_mask: [B, L]`` True = real token.
    f32 score/context accumulation, fully-masked query rows -> exactly 0
    (same conventions as parallel/ring_attention.dense_attention).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("blhd,bkhd->bhlk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    l = q.shape[1]
    causal = jnp.tril(jnp.ones((l, l), bool))
    m = causal[None, None, :, :] & pad_mask[:, None, None, :]
    s = jnp.where(m, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * m
    return jnp.einsum(
        "bhlk,bkhd->blhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _cached_attention(q, k_cache, v_cache, position, k_scale=None,
                      v_scale=None):
    """One-token-per-slot attention against the slot cache.

    ``q: [S, h, d]``; caches ``[S, Lmax, h, d]``; ``position: [S]`` — the
    index the newest token was just written at (attends ``<= position``).
    ``k_scale``/``v_scale`` (``[S, Lmax]``) carry the int8 cache's
    per-position dequant factors: the k-scale multiplies the score matrix
    after the QK^T product and the v-scale folds into the softmax weights
    before the context product, so the dense cache is never materialized.
    """
    with jax.named_scope("cached_attention"):
        scale = q.shape[-1] ** -0.5
        kc = k_cache if k_scale is None else k_cache.astype(jnp.float32)
        s = jnp.einsum(
            "shd,slhd->shl", q, kc, preferred_element_type=jnp.float32
        )
        if k_scale is not None:
            s = s * k_scale[:, None, :]
        s = s * scale
        valid = jnp.arange(k_cache.shape[1])[None, :] <= position[:, None]
        s = jnp.where(valid[:, None, :], s, _MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1) * valid[:, None, :]
        vc = v_cache
        if v_scale is not None:
            p = p * v_scale[:, None, :]
            vc = v_cache.astype(jnp.float32)
        return jnp.einsum(
            "shl,slhd->shd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)


def _chunk_attention(q, k_cache, v_cache, position, k_scale=None,
                     v_scale=None):
    """Chunk-of-queries attention against per-row caches.

    ``q: [B, C, h, d]``; caches ``[B, Lc, h, d]``; ``position: [B, C]`` —
    the (clamped) cache index each query was written at; each attends
    ``<= its own position``. Same f32 score/context accumulation and
    exactly-0 masking as ``_cached_attention``, so a prompt prefilled in
    chunks matches the full forward's argmax position-for-position.
    Cache positions beyond a row's written length hold zeros or a prior
    occupant's values — finite either way, and their softmax weight is
    exactly 0 under the causal mask, so they never reach the output.
    ``k_scale``/``v_scale`` (``[B, Lc]``): the int8 cache's per-position
    dequant factors, applied in the SAME factored order as
    ``_cached_attention`` so verify columns stay bit-identical to the
    decode steps they replace under quantization.
    """
    scale = q.shape[-1] ** -0.5
    kc = k_cache if k_scale is None else k_cache.astype(jnp.float32)
    s = jnp.einsum(
        "bchd,blhd->bhcl", q, kc, preferred_element_type=jnp.float32
    )
    if k_scale is not None:
        s = s * k_scale[:, None, None, :]
    s = s * scale
    valid = (
        jnp.arange(k_cache.shape[1])[None, None, :]
        <= position[:, :, None]
    )  # [B, C, Lc]
    m = valid[:, None, :, :]
    s = jnp.where(m, s, _MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * m
    vc = v_cache
    if v_scale is not None:
        p = p * v_scale[:, None, None, :]
        vc = v_cache.astype(jnp.float32)
    return jnp.einsum(
        "bhcl,blhd->bchd", p.astype(vc.dtype), vc,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


class CausalSelfAttention(nn.Module):
    """The BERT attention block, setup-style so the full and cached paths
    share params. Column-parallel Q/K/V over local heads, row-parallel out
    projection with the bias added once, after the psum, then post-LN."""

    cfg: CausalLMConfig

    def setup(self):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads
        local_heads = cfg.num_heads // cfg.model_parallel
        init = nn.initializers.normal(0.02)
        dense = lambda: nn.DenseGeneral(  # noqa: E731
            (local_heads, head_dim), dtype=cfg.dtype, kernel_init=init
        )
        self.query, self.key, self.value = dense(), dense(), dense()
        self.out = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, kernel_init=init,
        )
        self.out_bias = self.param(
            "out_bias", nn.initializers.zeros_init(), (cfg.hidden_size,)
        )
        self.ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)

    def _finish(self, x, ctx):
        out = _tp_psum(self.cfg, self.out(ctx))
        out = out + self.out_bias.astype(out.dtype)
        return self.ln(x + out)

    def __call__(self, x, pad_mask):
        q, k, v = self.query(x), self.key(x), self.value(x)
        ctx = _causal_attention(q, k, v, pad_mask)
        # K/V returned pre-attention: prefill scatters exactly these into
        # the slot cache, so the decode path attends identical values.
        return self._finish(x, ctx), k, v

    def decode(self, x, k_cache, v_cache, position):
        """One token per slot against this layer's table AS THE STEP FOUND
        IT. Returns ``(x', k_row, v_row)``: the rows ``[S, h, d]`` (or the
        int8 ``{"q", "s"}`` pair) the step has to write at ``position``,
        which ``CausalLM.decode_step`` writes for all layers at once.
        Attention reads the table with the row selected in, the operand
        values of write-then-attend without the write."""
        # position == Lmax marks an idle lane: no cache position matches, so
        # nothing is written (writing anywhere could corrupt a mid-chunk-
        # prefill slot's pages) and its attention clamps — the lane's
        # output is garbage nobody reads.
        q, k, v = self.query(x), self.key(x), self.value(x)  # [S, h, d]
        with jax.named_scope("kv_write"):
            if isinstance(k_cache, dict):
                # int8 KV mode: quantize the new token per slot at the
                # write, attend with the factored per-position scales.
                k_row = dict(zip(("q", "s"), quantize_kv(k)))
                v_row = dict(zip(("q", "s"), quantize_kv(v)))
            else:
                k_row = k.astype(k_cache.dtype)
                v_row = v.astype(v_cache.dtype)
        with jax.named_scope("cached_attention"):
            k_read = _select_rows(k_cache, k_row, position, slot_axis=0)
            v_read = _select_rows(v_cache, v_row, position, slot_axis=0)
        if isinstance(k_cache, dict):
            ctx = _cached_attention(
                q, k_read["q"], v_read["q"],
                jnp.minimum(position, k_read["q"].shape[1] - 1),
                k_scale=k_read["s"], v_scale=v_read["s"],
            )
        else:
            ctx = _cached_attention(
                q, k_read, v_read,
                jnp.minimum(position, k_read.shape[1] - 1),
            )
        return self._finish(x, ctx), k_row, v_row

    def prefill_chunk(self, x, positions, k_cache, v_cache):
        # x [B, C, H]; positions [B, C] absolute (sentinel == Lc on
        # padding lanes -> the scatter drops); caches [B, Lc, h, d].
        q, k, v = self.query(x), self.key(x), self.value(x)  # [B, C, h, d]
        rows = jnp.arange(x.shape[0])[:, None]
        if isinstance(k_cache, dict):
            # int8 KV mode, chunk-wise: per-(row, position) scales written
            # with the pages keep verify columns bit-identical to the
            # decode steps they stand in for (same quantize-at-write, same
            # factored dequant order).
            qk, sk = quantize_kv(k)
            qv, sv = quantize_kv(v)
            k_cache = {
                "q": k_cache["q"].at[rows, positions].set(qk, mode="drop"),
                "s": k_cache["s"].at[rows, positions].set(sk, mode="drop"),
            }
            v_cache = {
                "q": v_cache["q"].at[rows, positions].set(qv, mode="drop"),
                "s": v_cache["s"].at[rows, positions].set(sv, mode="drop"),
            }
            ctx = _chunk_attention(
                q, k_cache["q"], v_cache["q"],
                jnp.minimum(positions, k_cache["q"].shape[1] - 1),
                k_scale=k_cache["s"], v_scale=v_cache["s"],
            )
            return self._finish(x, ctx), k_cache, v_cache
        k_cache = k_cache.at[rows, positions].set(
            k.astype(k_cache.dtype), mode="drop"
        )
        v_cache = v_cache.at[rows, positions].set(
            v.astype(v_cache.dtype), mode="drop"
        )
        ctx = _chunk_attention(
            q, k_cache, v_cache,
            jnp.minimum(positions, k_cache.shape[1] - 1),
        )
        return self._finish(x, ctx), k_cache, v_cache


class CausalLmLayer(nn.Module):
    """Attention + FFN, both post-LN — BertLayer's shape with the cached
    decode twin. Leaf names (``intermediate``/``output``/``output_bias``)
    keep bert_param_specs' Megatron suffix rules applicable."""

    cfg: CausalLMConfig

    def setup(self):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        self.attention = CausalSelfAttention(cfg)
        self.intermediate = nn.Dense(
            cfg.intermediate_size // cfg.model_parallel,
            dtype=cfg.dtype, kernel_init=init,
        )
        self.output = nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype, kernel_init=init
        )
        self.output_bias = self.param(
            "output_bias", nn.initializers.zeros_init(), (cfg.hidden_size,)
        )
        self.ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)

    def _ffn(self, x):
        y = nn.gelu(self.intermediate(x), approximate=True)
        y = _tp_psum(self.cfg, self.output(y))
        y = y + self.output_bias.astype(y.dtype)
        return self.ln(x + y)

    def __call__(self, x, pad_mask):
        x, k, v = self.attention(x, pad_mask)
        return self._ffn(x), k, v

    def decode(self, x, k_cache, v_cache, position):
        x, k_row, v_row = self.attention.decode(
            x, k_cache, v_cache, position
        )
        return self._ffn(x), k_row, v_row

    def prefill_chunk(self, x, positions, k_cache, v_cache):
        x, k_cache, v_cache = self.attention.prefill_chunk(
            x, positions, k_cache, v_cache
        )
        return self._ffn(x), k_cache, v_cache


class CausalLM(nn.Module):
    """Decoder-only LM over :class:`CausalLmLayer` blocks with a tied head.

    ``__call__`` is the one-shot reference; ``prefill``/``decode_step`` are
    the serving pair (see module docstring for shapes).
    """

    cfg: CausalLMConfig

    def setup(self):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        self.word = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=init,
            dtype=cfg.dtype,
        )
        self.position = nn.Embed(
            cfg.max_position, cfg.hidden_size, embedding_init=init,
            dtype=cfg.dtype,
        )
        self.embed_ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)
        self.layers = [
            CausalLmLayer(cfg, name=f"layer_{i}")
            for i in range(cfg.num_layers)
        ]
        self.lm_transform = nn.Dense(
            cfg.hidden_size, dtype=cfg.dtype, kernel_init=init
        )
        self.lm_ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)
        self.lm_bias = self.param(
            "lm_bias", nn.initializers.zeros_init(), (cfg.vocab_size,)
        )

    def _embed(self, token_ids, positions):
        return self.embed_ln(self.word(token_ids) + self.position(positions))

    def _head(self, h):
        # Tied decoder against the embedding table (BertForPreTraining's
        # _heads recipe): transform -> LN -> attend + bias.
        with jax.named_scope("lm_head"):
            h = self.lm_ln(nn.gelu(self.lm_transform(h), approximate=True))
            return self.word.attend(h) + self.lm_bias.astype(self.cfg.dtype)

    def __call__(self, input_ids, attention_mask):
        l = input_ids.shape[1]
        x = self._embed(input_ids, jnp.arange(l)[None, :])
        for layer in self.layers:
            x, _, _ = layer(x, attention_mask)
        return self._head(x)

    def prefill(self, input_ids, attention_mask):
        l = input_ids.shape[1]
        x = self._embed(input_ids, jnp.arange(l)[None, :])
        ks, vs = [], []
        for layer in self.layers:
            x, k, v = layer(x, attention_mask)
            ks.append(k)
            vs.append(v)
        return self._head(x), jnp.stack(ks), jnp.stack(vs)

    def decode_step(self, token, position, k_cache, v_cache):
        # Clamp for the position-embedding lookup only; the raw (possibly
        # idle-lane sentinel) position drives the layers' dropped writes.
        x = self._embed(
            token, jnp.minimum(position, self.cfg.max_position - 1)
        )  # [S, H]
        k_rows, v_rows = [], []
        for i, layer in enumerate(self.layers):
            x, k_row, v_row = layer.decode(
                x, _layer_cache(k_cache, i), _layer_cache(v_cache, i),
                position,
            )
            k_rows.append(k_row)
            v_rows.append(v_row)
        # Every layer read the step's INPUT table; the [nl, S, h, d] of new
        # rows go into it here, once, in the layout it lives in (module
        # docstring, "Why decode_step writes by select").
        k_rows, v_rows = _stack_cache(k_rows), _stack_cache(v_rows)
        with jax.named_scope("kv_write"):
            k_cache = _select_rows(k_cache, k_rows, position, slot_axis=1)
            v_cache = _select_rows(v_cache, v_rows, position, slot_axis=1)
        return self._head(x), k_cache, v_cache

    def prefill_chunk(self, input_ids, positions, k_cache, v_cache):
        # Absolute-position chunk prefill against the slot cache: caches
        # ahead of a row's written length may hold garbage, but the causal
        # mask gives them exactly-0 weight and every such page is
        # re-written (by this row's later chunks or decode steps) before
        # anything attends it — the same dead-store argument decode_step
        # relies on for slot reuse. Positions are clamped for embedding /
        # attention; raw (possibly sentinel) positions drive the writes.
        Lc = (k_cache["q"] if isinstance(k_cache, dict) else k_cache).shape[2]
        x = self._embed(input_ids, jnp.minimum(positions, Lc - 1))
        new_k, new_v = [], []
        for i, layer in enumerate(self.layers):
            x, kc, vc = layer.prefill_chunk(
                x, positions, _layer_cache(k_cache, i),
                _layer_cache(v_cache, i)
            )
            new_k.append(kc)
            new_v.append(vc)
        return self._head(x), _stack_cache(new_k), _stack_cache(new_v)

    def verify_step(self, tokens, positions, k_cache, v_cache):
        # Speculative-decoding verify over the slot table: [S, K+1] tokens
        # at absolute positions against per-slot caches. Column 0 is each
        # slot's last verified token re-scored at its current position;
        # columns 1..d are drafts; dead columns carry the sentinel position
        # Lc so their writes drop. This IS prefill_chunk's contract with
        # C = K+1 — delegating (rather than re-deriving the masking) keeps
        # the `valid = pos <= position` and `mode="drop"` invariants in one
        # place. K/V written for columns past the accepted prefix sit
        # beyond the rolled-back slot position: masked dead, overwritten by
        # the slot's next real tokens — rollback costs nothing.
        return self.prefill_chunk(tokens, positions, k_cache, v_cache)


def sample_tokens(logits, temperature, seed, step):
    """Per-row next-token choice: greedy at ``temperature == 0``, seeded
    categorical otherwise.

    The sampling key is ``fold_in(PRNGKey(seed), step)`` with ``step`` the
    ABSOLUTE position being generated — a function of the request alone,
    never of its batchmates or slot, so a request decoded mid-flight draws
    the identical token stream it would draw solo (the determinism contract
    tests/test_serve_decode.py pins).
    """
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(row, t, s, c):
            key = jax.random.fold_in(jax.random.PRNGKey(s), c)
            scaled = row.astype(jnp.float32) / jnp.maximum(t, 1e-6)
            return jax.random.categorical(key, scaled).astype(jnp.int32)

        sampled = jax.vmap(one)(logits, temperature, seed, step)
        return jnp.where(temperature > 0.0, sampled, greedy)


def causal_param_specs(params, model_axis: str | None = "model"):
    """PartitionSpec tree for Megatron-TP sharding of the causal LM.

    The block reuses BERT's leaf names, so this is exactly
    :func:`bert_param_specs`' suffix rules with the expert/pipeline
    families off — embeddings, LayerNorms, post-psum biases, and the tied
    head stay replicated."""
    return bert_param_specs(
        params, model_axis=model_axis, expert_axis=None, pipeline_axis=None
    )


def _next_token_stats(logits, batch):
    """Shift-by-one CE sums: position t's logits score token t+1; pad
    positions and the final position carry zero weight. Returns ``(ce_sum,
    weight_sum, correct_sum)`` in f32 from the storage dtype — the same
    on-the-fly recipe as the BERT loss (_mlm_stats)."""
    targets = batch["input_ids"][:, 1:]
    logits = logits[:, :-1]
    weights = batch["attention_mask"][:, 1:].astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits.astype(jnp.float32) - m.astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(
        jnp.float32
    )
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce_sum = jnp.sum((lse - tgt.astype(jnp.float32)) * weights)
    correct = jnp.sum(
        (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32) * weights
    )
    return ce_sum, jnp.sum(weights), correct


def make_causal_lm_loss(model: CausalLM):
    """Next-token cross-entropy LossFn for the training engine over
    ``{"input_ids" [B, L], "attention_mask" [B, L]}`` batches."""

    def loss_fn(params, model_state, batch, rng):
        del rng  # no dropout in the decoder blocks
        logits = model.apply(
            {"params": params}, batch["input_ids"], batch["attention_mask"]
        )
        ce_sum, den, correct = _next_token_stats(logits, batch)
        den = jnp.maximum(den, 1.0)
        loss = ce_sum / den
        return loss, (model_state, {
            "lm_loss": loss,
            "lm_accuracy": correct / den,
        })

    return loss_fn


def make_causal_lm_eval_metrics(model: CausalLM):
    """Eval ``metric_fn`` for ``make_eval_step``: next-token loss and
    accuracy as ``(num, den)`` pairs so the eval step reduces them as
    global ratios over the DP axes (variable pad counts per shard)."""

    def metric_fn(params, model_state, batch):
        del model_state
        logits = model.apply(
            {"params": params}, batch["input_ids"], batch["attention_mask"]
        )
        ce_sum, den, correct = _next_token_stats(logits, batch)
        return {"lm_loss": (ce_sum, den), "lm_accuracy": (correct, den)}

    return metric_fn
