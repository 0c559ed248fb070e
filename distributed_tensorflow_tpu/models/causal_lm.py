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

Five forwards share one param tree:

- ``__call__(input_ids, attention_mask) -> logits [B, L, V]`` — the full
  causally-masked forward: training loss, scoring, and the one-shot
  reference the serving decode path is tested against.
- ``prefill(input_ids, attention_mask) -> (logits, k [nl,B,L,h*d], v)`` —
  same math, but also returns every layer's projected K/V, each position
  one merged row as the cache holds it, so the serving engine can write
  them into its slot cache (``kvcache.write_prompt``, from serve/engine.py
  ``CausalLMEngine``).

- ``prefill_rows(input_ids, attention_mask, lengths) -> (logits [B, V],
  fresh)`` — what the engine's prefill executable calls: ``prefill`` with
  the logits at each row's last real position and the K/V as the tree
  ``kvcache.write_prompt`` takes.

The other three take and return ONE ``cache``: a pytree whose every leaf is
``[nl, slots, positions, *trailing]``. What the leaves are
(dense K/V, or int8 payloads with their scales) is models/kvcache.py's
business alone; the methods here are written once, over its operations.
``cache_layout`` and ``param_specs`` are what the engine asks the model for.

- ``decode_step(token [S], position [S], cache) -> (logits [S,V], cache')``
  — ONE token per cache slot: embed at the slot's position, write the new
  K/V at ``position``, attend positions ``<= position``. Shapes are fixed
  by the slot count, so slot assignment/reuse never retraces (the "fixed
  pool of per-slot cache pages" contract). It writes the step's rows in
  place (models/kvcache.py, "How decode_step writes and reads").
- ``prefill_chunk(input_ids [B, C], positions [B, C], cache) -> (logits
  [B, C, V], cache')`` — a CHUNK of each row's prompt at arbitrary ABSOLUTE
  positions against per-row caches ``[nl, B, Lc, ..]``: write the chunk's
  K/V at ``positions``, attend the cache causally (each query sees
  positions ``<= its own``). One method covers both prefix-cache suffix
  prefill (one chunk starting at ``cached_len``) and fixed-size chunked
  prefill of long prompts; padding lanes carry the out-of-range sentinel
  position ``Lc`` so their cache writes drop (``mode="drop"``) while
  attention/embedding use the clamped position.
- ``verify_step(tokens [S, K+1], positions [S, K+1], cache) -> (logits
  [S, K+1, V], cache')`` — speculative decoding's batched verify: score a
  slot's last verified token plus up to K draft tokens in ONE dispatch.
  Same math as ``prefill_chunk`` (it delegates), which is the point:
  column j's logits are bit-identical to what ``decode_step`` would
  produce after j accepted tokens, so greedy accept-matching preserves the
  exact non-speculative stream.

Numerics: both attention paths accumulate scores and context in f32 with
the same masking convention (fully-masked rows -> exactly 0), so a token
decoded step-by-step matches the full forward's argmax at the same
position — tests/test_serve_decode.py pins greedy parity exactly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.bert import _tp_psum, bert_param_specs


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
    s = jnp.where(m, s, kvcache.MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1) * m
    return jnp.einsum(
        "bhlk,bkhd->blhd", p.astype(v.dtype), v,
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

    def _qkv(self, x):
        """``q [.., h, d]`` and the rows ``k, v [.., h * d]``: a cached
        position is one merged row (models/kvcache.py), so only the query
        is split into heads."""
        rows = kvcache.merge_heads
        return self.query(x), rows(self.key(x)), rows(self.value(x))

    def _finish(self, x, ctx):
        out = _tp_psum(self.cfg, self.out(ctx))
        out = out + self.out_bias.astype(out.dtype)
        return self.ln(x + out)

    def __call__(self, x, pad_mask):
        q, k, v = self.query(x), self.key(x), self.value(x)
        ctx = _causal_attention(q, k, v, pad_mask)
        # K/V returned pre-attention, as the rows a cache holds: prefill
        # scatters exactly these into the slot cache, so the decode path
        # attends identical values.
        rows = kvcache.merge_heads
        return self._finish(x, ctx), rows(k), rows(v)

    def decode(self, x, cache, layer: int, position):
        """One token per slot against layer ``layer`` of the stacked table
        AS THE STEP FOUND IT. Returns ``(x', rows)``: the rows ``[S, ..]``,
        in the table's form, that the step has to write at ``position``,
        which ``CausalLM.decode_step`` writes for all layers at once.
        Attention reads the table and the rows beside it
        (``kvcache.cached_attention``): the operand values of
        write-then-attend without the write."""
        # position == Lmax marks an idle lane: no cache position matches, so
        # nothing is written (writing anywhere could corrupt a mid-chunk-
        # prefill slot's pages) and its attention clamps — the lane's
        # output is garbage nobody reads.
        q, k, v = self._qkv(x)  # [S, h, d], [S, h * d] x 2
        rows = kvcache.encode(cache, {"k": k, "v": v})
        ctx = kvcache.cached_attention(
            q, cache, position, rows, layer=layer,
            sharded=self.cfg.model_axis is not None,
        )
        return self._finish(x, ctx), rows

    def prefill_chunk(self, x, positions, cache):
        # x [B, C, H]; positions [B, C] absolute (sentinel == Lc on
        # padding lanes -> the scatter drops); cache [B, Lc, ..].
        q, k, v = self._qkv(x)  # [B, C, h, d], [B, C, h * d] x 2
        cache = kvcache.scatter_rows(
            cache, kvcache.encode(cache, {"k": k, "v": v}), positions
        )
        ctx = kvcache.chunk_attention(q, cache, positions)
        return self._finish(x, ctx), cache


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

    def decode(self, x, cache, layer: int, position):
        x, rows = self.attention.decode(x, cache, layer, position)
        return self._ffn(x), rows

    def prefill_chunk(self, x, positions, cache):
        x, cache = self.attention.prefill_chunk(x, positions, cache)
        return self._ffn(x), cache


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

    def prefill_rows(self, input_ids, attention_mask, lengths):
        logits, k, v = self.prefill(input_ids, attention_mask)
        rows = jnp.arange(input_ids.shape[0])
        return logits[rows, jnp.maximum(lengths, 1) - 1], {"k": k, "v": v}

    def cache_layout(self, kv_dtype: str):
        return kvcache.cache_layout(self.cfg, kv_dtype)

    def param_specs(self, params, model_axis: str | None = "model"):
        return causal_param_specs(params, model_axis=model_axis)

    def decode_step(self, token, position, cache):
        # Clamp for the position-embedding lookup only; the raw (possibly
        # idle-lane sentinel) position drives the layers' dropped writes.
        x = self._embed(
            token, jnp.minimum(position, self.cfg.max_position - 1)
        )  # [S, H]
        rows = []
        for i, layer in enumerate(self.layers):
            x, row = layer.decode(x, cache, i, position)
            rows.append(row)
        # Every layer read the step's INPUT table; the [nl, S, ..] of new
        # rows go into it here, once and in place (models/kvcache.py, "How
        # decode_step writes and reads").
        cache = kvcache.write_rows(cache, kvcache.stack_layers(rows), position)
        return self._head(x), cache

    def prefill_chunk(self, input_ids, positions, cache):
        # Absolute-position chunk prefill against the slot cache: caches
        # ahead of a row's written length may hold garbage, but the causal
        # mask gives them exactly-0 weight and every such page is
        # re-written (by this row's later chunks or decode steps) before
        # anything attends it — the same dead-store argument decode_step
        # relies on for slot reuse. Positions are clamped for embedding /
        # attention; raw (possibly sentinel) positions drive the writes.
        x = self._embed(
            input_ids, jnp.minimum(positions, kvcache.cache_len(cache) - 1)
        )
        layers = []
        for i, layer in enumerate(self.layers):
            x, table = layer.prefill_chunk(
                x, positions, kvcache.take_layer(cache, i)
            )
            layers.append(table)
        return self._head(x), kvcache.stack_layers(layers)

    def verify_step(self, tokens, positions, cache):
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
        return self.prefill_chunk(tokens, positions, cache)


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
