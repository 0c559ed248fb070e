"""BERT-base pretraining — the reference's transformer workload (BASELINE.json:11).

The reference pretrains BERT-base data-parallel, stressing the large
embedding-table allreduce (SURVEY.md §2 workload rows, §7 hard-part 4). This
rebuild keeps that capability (pure-DP: the 30k-vocab embedding gradient
rides the same fused psum as everything else) and adds what the TF-1.x
harness never had: exact sequence/context parallelism — set
``config.seq_axis`` and the encoder runs ring attention over the ``"seq"``
mesh axis (parallel/ring_attention.py), with position offsets, pooling, and
the MLM loss all seq-shard-aware.

Architecture is the original BERT-base (Devlin et al.): post-LayerNorm
encoder, learned positions, GELU FFN, tied MLM decoder, NSP head.
12L/768H/12A/3072FF/vocab 30522 ≈ 109.5M params (encoder+embeddings+pooler).

Training objective: masked-LM cross-entropy over masked positions
(targets < 0 are ignored) + next-sentence-prediction cross-entropy —
``make_bert_pretraining_loss`` plugs into the standard engine
(train/step.py), including ``mode="stale"``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax import lax

from distributed_tensorflow_tpu.parallel.ring_attention import (
    dense_attention,
    ring_attention,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    # Mesh axis name for sequence parallelism, or None for single-shard
    # attention. With an axis set, the model must run inside shard_map with
    # the sequence dim of all [B, L] inputs sharded over that axis.
    seq_axis: str | None = None
    # Sequence-parallel strategy: "ring" streams K/V blocks around the ICI
    # ring (parallel/ring_attention.py, no head-count constraint);
    # "ulysses" re-partitions sharding from sequence to heads with two
    # all_to_alls and runs full-sequence attention per local head group
    # (parallel/ulysses.py; needs num_heads % ring size == 0). Both exact.
    sp_impl: str = "ring"
    # Tensor (model) parallelism: Megatron-style sharding of attention heads
    # and the FFN hidden dim over ``model_axis`` with ``model_parallel``
    # shards. Params are created GLOBAL (init with model_parallel=1 config)
    # and sliced by ``bert_param_specs``; inside shard_map the module builds
    # local-head/local-FFN projections and psums the row-parallel outputs.
    model_axis: str | None = None
    model_parallel: int = 1
    # Attention implementation: "auto" (flash for L >= 256, dense below —
    # the r3 measured crossover: flash beats dense 1.8-2.3x at L in
    # {512, 2048} but loses at L=128 where one fused dense matmul wins),
    # "dense" (XLA-composed), or "flash" (Pallas kernel,
    # ops/flash_attention.py). With seq_axis set the choice also selects
    # the ring's inner step ("flash" = Pallas kernel per streamed block).
    attn_impl: str = "auto"
    # Mixture-of-experts FFN: > 0 replaces every layer's dense FFN with a
    # switch-routed MoE of ``moe_experts`` experts (parallel/moe.py). With
    # ``expert_axis``/``expert_parallel`` set, experts shard over that mesh
    # axis (params init GLOBAL with expert_parallel=1, sliced by
    # ``bert_param_specs``). The load-balance aux loss is sown into the
    # "intermediates" collection; make_bert_pretraining_loss adds it.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    expert_axis: str | None = None
    expert_parallel: int = 1
    # "replicated": every expert shard routes all tokens, partial outputs
    # psum (exact global capacity order). "alltoall": capacity-buffer
    # dispatch over the expert axis with tokens replicated outside the MoE
    # (parallel/moe.py moe_apply_a2a). "sharded": the PRODUCTION GShard
    # layout — the batch itself shards over the expert axis (expert group ≡
    # data group), so attention/embeddings/heads compute 1/E of the rows
    # per shard (zero redundant non-MoE compute) and the a2a routes from
    # the local slice with no trailing all_gather. Requires the loaders'
    # expert_sharded batch layout (data/text.py bert_batch_specs).
    moe_dispatch: str = "replicated"
    # Routing fan-out: 1 = Switch (top-1), 2 = GShard top-2 (renormalized
    # gates, first-choice queue priority, per-expert capacity UNCHANGED —
    # so top-2 doubles capacity pressure; parallel/moe.py
    # switch_route_topk). Works with all three dispatch layouts.
    moe_topk: int = 1
    # Pipeline parallelism (GPipe schedule, parallel/pipeline.py): with
    # ``pipeline_axis`` set the encoder's params are a stacked
    # ``[num_layers, ...]`` tree (created by nn.scan; shard dim 0 over the
    # pipeline axis via ``bert_param_specs``) and the encoder runs
    # ``pipeline_apply`` over ``pipeline_microbatches`` microbatches inside
    # shard_map. Embeddings/pooler/heads stay replicated across stages.
    # Outside shard_map (init, CPU tests) the same stacked params run as a
    # sequential scan — mathematically identical, so one checkpoint serves
    # both. num_layers must divide by pipeline_parallel; the global batch by
    # pipeline_microbatches.
    pipeline_axis: str | None = None
    pipeline_parallel: int = 1
    pipeline_microbatches: int = 0  # 0 -> 4 * pipeline_parallel
    # Activation rematerialisation (jax.checkpoint) over encoder layers:
    # each layer's activations are recomputed during backward instead of
    # saved, trading ~1 extra forward pass of layer FLOPs for O(num_layers)
    # less activation memory — the standard lever for longer L / larger
    # per-chip batch. Applies to all three encoder forms (module list,
    # sequential scan, GPipe schedule); the math is unchanged, so
    # trajectories are identical (tests/test_bert.py pins it).
    remat: bool = False


def bert_base(**overrides) -> BertConfig:
    return BertConfig(**overrides)


def _seq_offset(cfg: BertConfig, l_local: int):
    """Global position of this shard's first token (0 without seq axis)."""
    if cfg.seq_axis is None:
        return 0
    return lax.axis_index(cfg.seq_axis) * l_local


class BertEmbeddings(nn.Module):
    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        self.word = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=init, dtype=cfg.dtype
        )
        self.position = nn.Embed(
            cfg.max_position, cfg.hidden_size, embedding_init=init, dtype=cfg.dtype
        )
        self.token_type = nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size, embedding_init=init, dtype=cfg.dtype
        )
        self.ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)
        self.dropout = nn.Dropout(cfg.dropout_rate)

    def __call__(self, input_ids, token_type_ids, *, train: bool = False):
        l_local = input_ids.shape[1]
        positions = _seq_offset(self.cfg, l_local) + jnp.arange(l_local)
        x = (
            self.word(input_ids)
            + self.position(positions)[None]
            + self.token_type(token_type_ids)
        )
        return self.dropout(self.ln(x), deterministic=not train)


def _tp_psum(cfg: BertConfig, y):
    """Sum row-parallel partial outputs across the model axis (no-op tp=1)."""
    if cfg.model_axis is not None and cfg.model_parallel > 1:
        return lax.psum(y, cfg.model_axis)
    return y


class BertSelfAttention(nn.Module):
    """Multi-head attention, Megatron-sharded over ``cfg.model_axis``.

    Column-parallel Q/K/V (each shard projects its ``num_heads /
    model_parallel`` local heads), attention runs per-head locally (the
    seq ring composes: each ring step attends the local heads), and the
    row-parallel output projection psums partial [B,L,H] results. The
    output bias lives OUTSIDE the projection (``out_bias``) so it is added
    once, after the psum, not once per shard.
    """

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask, *, train: bool = False):
        cfg = self.cfg
        b, l, _ = x.shape
        head_dim = cfg.hidden_size // cfg.num_heads
        local_heads = cfg.num_heads // cfg.model_parallel
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (local_heads, head_dim),
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            name=name,
        )
        q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        impl = cfg.attn_impl
        if impl == "auto":
            # Measured crossover (docs/PERF.md r3): the Pallas kernel wins
            # from L ~ 256 up; below, one fused dense matmul is faster. The
            # decision length is the one the inner attention actually sees:
            # the local shard for the ring (its inner runs per L_local
            # block), but the full gathered sequence for Ulysses (its inner
            # runs over L = l * ring_size after the all-to-alls).
            eff_l = l
            if (
                cfg.seq_axis is not None
                and cfg.sp_impl == "ulysses"
                and _axis_bound(cfg.seq_axis)
            ):
                eff_l = l * lax.axis_size(cfg.seq_axis)
            impl = "flash" if eff_l >= 256 else "dense"
        if cfg.seq_axis is not None:
            if cfg.sp_impl == "ulysses":
                from distributed_tensorflow_tpu.parallel.ulysses import (
                    ulysses_attention,
                )

                ctx = ulysses_attention(
                    q, k, v, cfg.seq_axis, mask=mask,
                    inner="flash" if impl == "flash" else "dense",
                )
            else:
                # The choice picks the ring's inner step too: "flash" runs
                # the Pallas kernel per streamed K/V block (logsumexp merge).
                inner = "flash" if impl == "flash" else "einsum"
                ctx = ring_attention(
                    q, k, v, cfg.seq_axis, mask=mask, inner=inner
                )
        elif impl == "flash":
            from distributed_tensorflow_tpu.ops import flash_attention

            ctx = flash_attention(q, k, v, mask=mask)
        else:
            ctx = dense_attention(q, k, v, mask=mask)
        out = nn.DenseGeneral(
            cfg.hidden_size,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            name="out",
        )(ctx)
        out = _tp_psum(cfg, out)
        out = out + self.param(
            "out_bias", nn.initializers.zeros_init(), (cfg.hidden_size,)
        ).astype(out.dtype)
        out = nn.Dropout(cfg.dropout_rate)(out, deterministic=not train)
        # Post-LN (original BERT): LN over the residual sum.
        return nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype, name="ln")(x + out)


class MoeFfn(nn.Module):
    """Switch-routed MoE FFN: the expert-parallel alternative to the dense
    intermediate/output projections (parallel/moe.py does routing/dispatch;
    this module owns the router and the stacked expert params)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, *, train: bool = False):
        from distributed_tensorflow_tpu.parallel.moe import moe_apply, moe_apply_a2a

        cfg = self.cfg
        # All three sharding families compose here: expert-parallel (stacked
        # expert dim over "expert"), sequence-parallel (routing statistics
        # psum over the seq ring — engine's global-loss contract), and
        # tensor-parallel (each expert's FFN hidden dim Megatron-sharded
        # over "model": column-parallel w1/b1, row-parallel w2 with the
        # partial outputs psum'd after dispatch; b2 enters as b2/tp on each
        # shard so the psum reconstructs it exactly once).
        if cfg.moe_dispatch not in ("replicated", "alltoall", "sharded"):
            raise ValueError(f"unknown moe_dispatch {cfg.moe_dispatch!r}")
        if cfg.moe_dispatch == "sharded" and cfg.expert_parallel <= 1:
            raise ValueError(
                "moe_dispatch='sharded' routes from the expert-sharded batch "
                "— it requires expert_parallel > 1"
            )
        b, l, h = x.shape
        tp = cfg.model_parallel
        ff_local = cfg.intermediate_size // tp
        e_local = cfg.moe_experts // cfg.expert_parallel
        init = nn.initializers.normal(0.02)
        router = nn.Dense(
            cfg.moe_experts,
            use_bias=False,
            dtype=jnp.float32,
            kernel_init=init,
            name="router",
        )
        w1 = self.param("experts_w1", init, (e_local, h, ff_local), jnp.float32)
        b1 = self.param(
            "experts_b1", nn.initializers.zeros_init(), (e_local, ff_local), jnp.float32
        )
        w2 = self.param("experts_w2", init, (e_local, ff_local, h), jnp.float32)
        b2 = self.param(
            "experts_b2", nn.initializers.zeros_init(), (e_local, h), jnp.float32
        )

        def expert_fn(p, tokens):
            # tanh-approx gelu: google-bert's ORIGINAL formulation, and
            # measured 14 ms/step faster than erf at L=512 b=48 (r5).
            t = nn.gelu(
                tokens @ p["w1"].astype(cfg.dtype) + p["b1"].astype(cfg.dtype),
                approximate=True,
            )
            # 1/tp of the bias per model shard: the post-dispatch _tp_psum
            # sums the row-parallel partials AND reassembles b2 exactly once.
            return t @ p["w2"].astype(cfg.dtype) + p["b2"].astype(cfg.dtype) / tp

        tokens = x.reshape(b * l, h)
        logits = router(tokens)
        # Token-sharding axes: the aux-loss statistics must psum over every
        # axis the tokens are split across so the loss is the global ratio
        # on all shards (seq contract, train/step.py). The a2a dispatch
        # additionally shards tokens over the expert axis itself.
        stats_axes = () if cfg.seq_axis is None else (cfg.seq_axis,)
        ep_active = cfg.expert_parallel > 1
        use_a2a = cfg.moe_dispatch == "alltoall" and ep_active
        apply_kwargs = dict(
            capacity_factor=cfg.moe_capacity_factor,
            # PAD positions must not consume routing capacity or bias the
            # load-balance aux — only attention-mask-valid tokens route.
            valid=None if mask is None else mask.reshape(b * l),
            topk=cfg.moe_topk,
        )
        experts = {"w1": w1, "b1": b1, "w2": w2, "b2": b2}
        if cfg.moe_dispatch == "sharded":
            # Production GShard layout (expert group ≡ data group): the
            # batch arrives ALREADY sharded over the expert axis — b here is
            # the local slice, attention/embeddings/heads computed it 1/E-
            # sized, and the a2a routes straight from it. Per-group aux
            # statistics (no expert psum): each group's aux is a complete
            # loss term that the engine's DP-mean averages like the rest.
            y, aux = moe_apply_a2a(
                expert_fn,
                experts,
                logits,
                tokens,
                axis_name=cfg.expert_axis,
                stats_axes=stats_axes,
                tokens_sharded=True,
                **apply_kwargs,
            )
        elif use_a2a:
            y, aux = moe_apply_a2a(
                expert_fn,
                experts,
                logits,
                tokens,
                axis_name=cfg.expert_axis,
                stats_axes=stats_axes + (cfg.expert_axis,),
                **apply_kwargs,
            )
        else:
            y, aux = moe_apply(
                expert_fn,
                experts,
                logits,
                tokens,
                axis_name=cfg.expert_axis if ep_active else None,
                stats_axes=stats_axes,
                **apply_kwargs,
            )
        y = _tp_psum(cfg, y)
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(b, l, h)


class BertLayer(nn.Module):
    cfg: BertConfig

    # ``train`` is positional-or-keyword (no ``*``) so nn.remat can mark it
    # static by argnum (self=0, x=1, mask=2, train=3) — see BertModel.setup.
    @nn.compact
    def __call__(self, x, mask, train: bool = False):
        cfg = self.cfg
        x = BertSelfAttention(cfg, name="attention")(x, mask, train=train)
        if cfg.moe_experts:
            # MoE FFN (dropped-overflow tokens emit 0 and ride the residual).
            y = MoeFfn(cfg, name="moe")(x, mask, train=train)
        else:
            # Column-parallel up-projection, row-parallel down-projection
            # with the bias applied post-psum (see BertSelfAttention).
            y = nn.Dense(
                cfg.intermediate_size // cfg.model_parallel,
                dtype=cfg.dtype,
                kernel_init=nn.initializers.normal(0.02),
                name="intermediate",
            )(x)
            # tanh-approx gelu == google-bert's original; 14 ms/step
            # faster than erf at the L=512 b=48 production config (r5).
            y = nn.gelu(y, approximate=True)
            y = nn.Dense(
                cfg.hidden_size,
                use_bias=False,
                dtype=cfg.dtype,
                kernel_init=nn.initializers.normal(0.02),
                name="output",
            )(y)
            y = _tp_psum(cfg, y)
            y = y + self.param(
                "output_bias", nn.initializers.zeros_init(), (cfg.hidden_size,)
            ).astype(y.dtype)
        y = nn.Dropout(cfg.dropout_rate)(y, deterministic=not train)
        return nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype, name="ln")(x + y)


class _ScanBertLayer(nn.Module):
    """nn.scan target: carry = hidden states; mask/train ride as broadcast
    positional args (train is a plain python bool — static through scan)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask, train):
        x = BertLayer(self.cfg, name="layer")(x, mask, train=train)
        return x, None


def _axis_bound(name: str) -> bool:
    """True iff ``name`` is a mesh axis bound by an enclosing shard_map."""
    try:
        lax.axis_size(name)
        return True
    except NameError:
        return False


class BertModel(nn.Module):
    """Encoder + pooler. Returns (hidden [B,L,H], pooled [B,H])."""

    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        self.embeddings = BertEmbeddings(cfg)
        if cfg.pipeline_axis is not None or cfg.pipeline_parallel > 1:
            # Every parallelism family composes with the pipeline: tp
            # (Megatron-sharded stacked layers), moe/ep (aux threaded
            # through the GPipe schedule with drain masking), and sp (the
            # microbatch split is over batch ROWS while the seq axis
            # shards length — orthogonal dims, so the ring/Ulysses
            # collectives simply run per (layer, microbatch) inside the
            # schedule; the attention-mask microbatching slices the
            # seq-LOCAL mask). Trajectories pinned in
            # tests/test_bert_pp.py.
            if cfg.num_layers % cfg.pipeline_parallel:
                raise ValueError(
                    f"num_layers {cfg.num_layers} not divisible by "
                    f"pipeline_parallel {cfg.pipeline_parallel}"
                )
            scan_target = _ScanBertLayer
            if cfg.remat:
                # remat INSIDE the scan: each layer recomputes during the
                # scan's backward sweep. prevent_cse=False — under scan the
                # XLA CSE hazard remat guards against cannot occur, and
                # leaving it True blocks useful fusion.
                scan_target = nn.remat(
                    _ScanBertLayer, static_argnums=(3,), prevent_cse=False
                )
            self.encoder = nn.scan(
                scan_target,
                # intermediates rides the scan too (stacked per layer):
                # the MoE FFN sows its aux loss there, and the sequential-
                # semantics path (init / single-stage runs) must carry it
                # exactly like the per-layer module list does.
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_layers,
                in_axes=(nn.broadcast, nn.broadcast),
            )(cfg, name="encoder")
            self.layers = None
        else:
            # prevent_cse=True (the default) is LOAD-BEARING here: under
            # plain jit XLA would otherwise CSE the backward's recomputed
            # forward against the saved one, silently restoring the full
            # activation footprint (measured at L=512 b=96 bf16: temp
            # 13.50 GiB unchanged with False; 5.12 GiB with True). Under
            # scan the loop boundary already blocks that CSE, so the scan
            # target above keeps False (the flax-recommended pairing).
            layer_cls = (
                nn.remat(BertLayer, static_argnums=(3,))
                if cfg.remat
                else BertLayer
            )
            self.layers = [
                layer_cls(cfg, name=f"layer_{i}") for i in range(cfg.num_layers)
            ]
        self.pooler = nn.Dense(
            cfg.hidden_size,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
        )

    def _encode_pipelined(self, x, attention_mask, *, train: bool):
        """GPipe the stacked encoder over the bound pipeline axis.

        Called inside shard_map where this stage's param slice has leading
        dim ``num_layers / S``. The per-(layer, microbatch) context slices
        the attention mask and folds the dropout rng; drained-phase ticks
        compute garbage that is never collected (parallel/pipeline.py).
        """
        from distributed_tensorflow_tpu.parallel.pipeline import pipeline_apply

        cfg = self.cfg
        S = lax.axis_size(cfg.pipeline_axis)
        M = cfg.pipeline_microbatches or 4 * S
        B, L = attention_mask.shape
        need_rng = train and cfg.dropout_rate > 0.0
        base_rng = self.make_rng("dropout") if need_rng else None
        mask_mb = attention_mask.reshape(M, B // M, L)
        stacked = self.variables["params"]["encoder"]["layer"]
        # parent=None: a detached functional instance — its .apply below runs
        # on explicit param slices, never registering as a submodule here.
        layer = BertLayer(cfg, parent=None)
        moe = cfg.moe_experts > 0

        def layer_fn(p_one, h, ctx):
            m = lax.dynamic_index_in_dim(
                mask_mb, ctx["microbatch"], 0, keepdims=False
            )
            rngs = None
            if need_rng:
                r = jax.random.fold_in(base_rng, ctx["layer"])
                rngs = {"dropout": jax.random.fold_in(r, ctx["microbatch"])}
            if moe:
                # The detached apply would drop sown intermediates — pull
                # the MoE aux out explicitly and let the schedule thread it
                # (pipeline_apply with_aux masks drain-phase garbage).
                h2, mods = layer.apply(
                    {"params": p_one}, h, m, train=train, rngs=rngs,
                    mutable=["intermediates"],
                )
                leaves = jax.tree.leaves(mods["intermediates"])
                return h2, sum(leaves) / len(leaves)
            return layer.apply({"params": p_one}, h, m, train=train, rngs=rngs)

        if cfg.remat:
            # Remat per (layer, microbatch) tick: the GPipe schedule's
            # backward sweep recomputes each tick's layer activations
            # instead of saving M x S of them. All layer_fn args are array
            # pytrees (ctx's indices are traced scan counters).
            layer_fn = jax.checkpoint(layer_fn, prevent_cse=False)

        out = pipeline_apply(
            layer_fn,
            stacked,
            x,
            axis_name=cfg.pipeline_axis,
            n_microbatches=M,
            with_context=True,
            with_aux=moe,
        )
        if moe:
            x, aux = out
            # Re-sow under this module so make_bert_pretraining_loss's
            # intermediates average finds it, same as the sequential path.
            self.sow("intermediates", "moe_aux", aux)
            return x
        return out

    def __call__(self, input_ids, attention_mask, token_type_ids, *, train=False):
        cfg = self.cfg
        x = self.embeddings(input_ids, token_type_ids, train=train)
        if self.layers is None:
            if (
                cfg.pipeline_axis is not None
                and not self.is_initializing()
                and _axis_bound(cfg.pipeline_axis)
            ):
                x = self._encode_pipelined(x, attention_mask, train=train)
            else:
                # Stacked params, sequential semantics (init / tests /
                # single-stage runs) — same math as the pipelined schedule.
                x, _ = self.encoder(x, attention_mask, train)
        else:
            for layer in self.layers:
                # train POSITIONALLY: with cfg.remat the layer class is
                # nn.remat(BertLayer, static_argnums=(3,)) and the static
                # marking only applies to positional args.
                x = layer(x, attention_mask, train)
        first = x[:, 0]
        if cfg.seq_axis is not None:
            # The global [CLS] token lives on seq-shard 0: psum-select it so
            # every shard pools the same vector (grads flow back to shard 0
            # only, and the engine's seq-psum counts them exactly once).
            is_first = (lax.axis_index(cfg.seq_axis) == 0).astype(first.dtype)
            first = lax.psum(first * is_first, cfg.seq_axis)
        pooled = jnp.tanh(self.pooler(first))
        return x, pooled


class BertForPreTraining(nn.Module):
    """MLM (tied decoder) + NSP heads over BertModel.

    ``__call__(batch, train) -> (mlm_logits [B,L,V], nsp_logits [B,2])``.
    """

    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        self.bert = BertModel(cfg)
        self.mlm_transform = nn.Dense(
            cfg.hidden_size,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
        )
        self.mlm_ln = nn.LayerNorm(epsilon=1e-12, dtype=cfg.dtype)
        self.mlm_bias = self.param(
            "mlm_bias", nn.initializers.zeros_init(), (cfg.vocab_size,)
        )
        self.nsp_head = nn.Dense(
            2, dtype=jnp.float32, kernel_init=nn.initializers.normal(0.02)
        )

    def mlm_logits(self, rows):
        """The MLM head over ``rows [..., H]``: transform, GELU, LayerNorm,
        tied decoder. Every position for the serving paths below; the
        masked rows alone for the loss (:func:`_mlm_head_stats`)."""
        h = self.mlm_ln(nn.gelu(self.mlm_transform(rows), approximate=True))
        # Tied decoder: logits against the word-embedding table. Logits
        # KEEP the compute dtype: at BERT geometry [rows, V] is the head's
        # biggest array (2.0 GB bf16 over all 64 x 512 positions, which
        # only the serving paths compute; 0.34 GB over the loss's gathered
        # rows), and the r5 trace showed the old f32 upcast doubling
        # every loss-side pass over it (the CE reduce, the argmax, and the
        # bwd softmax recompute — docs/PERF.md r5). _mlm_stats does its
        # reductions in f32 on the fly; bf16 storage costs no stability
        # (max is exact in bf16, exp/sum accumulate in f32).
        return self.bert.embeddings.word.attend(h) + self.mlm_bias.astype(
            self.cfg.dtype
        )

    def nsp_logits(self, pooled):
        return self.nsp_head(pooled).astype(jnp.float32)

    def _heads(self, hidden, pooled):
        with jax.named_scope("mlm_head"):
            mlm_logits = self.mlm_logits(hidden)
        return mlm_logits, self.nsp_logits(pooled)

    def encode(self, input_ids, attention_mask, token_type_ids, *, train=False):
        """``(hidden [B,L,H], nsp_logits [B,2])`` — what the loss and the
        eval metrics take, leaving the MLM head to run over the rows they
        choose."""
        hidden, pooled = self.bert(
            input_ids, attention_mask, token_type_ids, train=train
        )
        return hidden, self.nsp_logits(pooled)

    def __call__(self, input_ids, attention_mask, token_type_ids, *, train=False):
        hidden, pooled = self.bert(
            input_ids, attention_mask, token_type_ids, train=train
        )
        return self._heads(hidden, pooled)

    def serve_outputs(self, input_ids, attention_mask, token_type_ids):
        """Inference-only forward for the serving engine (serve/engine.py):
        one encoder pass yielding ``(mlm_logits, nsp_logits, pooled)`` —
        the MLM scoring surface plus the pooled [CLS] sentence embedding,
        without a second encoder pass for the embedding endpoint."""
        hidden, pooled = self.bert(
            input_ids, attention_mask, token_type_ids, train=False
        )
        mlm_logits, nsp_logits = self._heads(hidden, pooled)
        return mlm_logits, nsp_logits, pooled


def _mlm_stats(mlm_logits, targets):
    """Shared MLM statistics for the train loss and eval metrics: CE sum,
    masked-token count, and correct count over the given rows (the one
    masking/clamp recipe both paths must agree on). ``targets < 0`` marks a
    row that does not count; :func:`_mlm_head_stats` picks the rows and
    psums the three over the seq ring.

    The CE is computed in f32 ON THE FLY from the logits' storage dtype
    (bf16 at the production config): the row max is exact in bf16, the
    shifted exp/sum converts per element inside the fused reduce, and the
    backward emits the softmax cotangent in storage dtype. Versus upcasting
    the [rows, V] logits to f32 first, every pass over the head's biggest
    tensor moves half the bytes (measured 6.8 ms for the old f32 CE reduce
    alone, docs/PERF.md r5). Accuracy reuses the already-computed
    row max instead of a second full argmax pass over [rows, V]: a masked
    position counts correct iff its target logit equals the row max
    (ties — measure-zero in f32, rare in bf16 — count correct)."""
    weights = (targets >= 0).astype(jnp.float32)
    m = lax.stop_gradient(jnp.max(mlm_logits, axis=-1, keepdims=True))
    # Convert-then-subtract: the convert runs in-register inside the fused
    # reduce (no f32 materialization), and the shift itself is exact f32.
    shifted = mlm_logits.astype(jnp.float32) - m.astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(
        jnp.float32
    )
    tgt_logit = jnp.take_along_axis(
        mlm_logits, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    ce = lse - tgt_logit.astype(jnp.float32)
    num = jnp.sum(ce * weights)
    den = jnp.sum(weights)
    correct = jnp.sum(
        (tgt_logit == m[..., 0]).astype(jnp.float32) * weights
    )
    return num, den, correct


def mlm_gather_rows(n_rows: int, mask_prob: float) -> int | None:
    """How many rows the gathered MLM head runs over for a shard of
    ``n_rows`` positions masked at ``mask_prob``: the expected count plus
    eight standard deviations of Binomial(n_rows, mask_prob), rounded up to
    a multiple of 128 (5,504 of 32,768 at 0.15). ``None`` where that is not
    under half the rows: gathering buys too little there, and the head runs
    dense, statically."""
    sigma = math.sqrt(n_rows * mask_prob * (1.0 - mask_prob))
    k = 128 * math.ceil((n_rows * mask_prob + 8.0 * sigma) / 128)
    return k if 2 * k < n_rows else None


def _gathered(stats, k_rows: int):
    """``stats(head_params, rows [M,H], targets [M]) -> (num, den, correct)``
    run over the masked rows of ``[N, H]`` (at most ``k_rows``, a shape) in
    place of all ``N``, exactly: a shard that holds more masked rows than
    ``k_rows`` takes the dense head for that step (``lax.cond``), never a
    clipped loss.

    Differentiating through ``lax.cond`` would make each branch emit the
    other's residuals as zeros (the dense branch's are the [N, V] logits).
    So each branch computes its value AND its gradients inside the branch,
    and a ``custom_vjp`` hands them out scaled by the cotangent of ``num``:
    exact, because the outputs are scalars and only ``num`` depends on
    anything differentiable."""

    def fits(targets):
        return jnp.sum(targets >= 0) <= k_rows

    def pick(hidden, targets):
        # Ascending indices of the masked rows by one sort (0.02 ms at N =
        # 32,768 on a v5e; nonzero(size=) builds the same by a scatter in
        # 0.29), padded with N: a padded slot reads a row of zeros and gets
        # target -1, which is weight 0 in ``stats``.
        n_rows = targets.shape[0]
        masked = targets >= 0
        idx = jnp.sort(jnp.where(masked, jnp.arange(n_rows), n_rows))[:k_rows]
        rows = hidden.at[idx].get(mode="fill", fill_value=0)
        row_targets = targets.at[idx].get(mode="fill", fill_value=-1)
        return rows, row_targets

    def value_gathered(head_params, hidden, targets):
        return stats(head_params, *pick(hidden, targets))

    def num_and_grads(head_params, rows, row_targets):
        def num_first(p, r):
            num, den, correct = stats(p, r, row_targets)
            return num, (den, correct)

        (num, (den, correct)), grads = jax.value_and_grad(
            num_first, argnums=(0, 1), has_aux=True
        )(head_params, rows)
        return (num, den, correct), grads

    def grads_gathered(head_params, hidden, targets):
        out, (d_params, d_rows) = num_and_grads(
            head_params, *pick(hidden, targets)
        )
        # The gather's transpose, written as a gather: masked row n was slot
        # (masked rows before n) of ``rows``. A scatter-add of the same
        # 5,504 rows takes twice as long on the chip (0.39 against 0.20 ms).
        masked = targets >= 0
        slot = jnp.cumsum(masked) - 1
        d_hidden = jnp.where(
            masked[:, None], d_rows.at[slot].get(mode="clip"), 0
        )
        return out, (d_params, d_hidden)

    @jax.custom_vjp
    def head(head_params, hidden, targets):
        return lax.cond(
            fits(targets), value_gathered, stats, head_params, hidden, targets
        )

    def head_fwd(head_params, hidden, targets):
        return lax.cond(
            fits(targets), grads_gathered, num_and_grads,
            head_params, hidden, targets,
        )

    def head_bwd(grads, cotangents):
        g_num = cotangents[0]

        def scaled(x):
            # In float32: the cotangent (1 / masked tokens) rounded to bf16
            # would tilt the whole encoder's gradient by up to 2^-9.
            return (x.astype(jnp.float32) * g_num).astype(x.dtype)

        return (*jax.tree.map(scaled, grads), None)  # (params, hidden, targets)

    head.defvjp(head_fwd, head_bwd)
    return head


def _mlm_head_stats(model: BertForPreTraining, mask_prob: float):
    """``head_stats(params, hidden [B,L,H], targets [B,L]) -> (num, den,
    correct, share)``: the MLM head and :func:`_mlm_stats` over this
    shard's masked rows, for the train loss and the eval metrics alike, the
    three sums psum'd over the seq ring so they are GLOBAL. ``share`` is the
    part of the shard's rows the head ran over: ``k / N`` gathered, 1.0
    dense (statically, at shapes where :func:`mlm_gather_rows` says so, or
    for a step whose shard holds more than ``k`` masked rows)."""
    seq_axis = model.cfg.seq_axis

    def stats(head_params, rows, targets):
        logits = model.apply(
            {"params": head_params}, rows, method=BertForPreTraining.mlm_logits
        )
        return _mlm_stats(logits, targets)

    def head_stats(params, hidden, targets):
        # Only what the head reads: its gradients are taken inside a branch
        # (_gathered), where the encoder's would be 0.4 GB of zeros.
        head_params = {k: params[k] for k in ("mlm_transform", "mlm_ln", "mlm_bias")}
        head_params["bert"] = {
            "embeddings": {"word": params["bert"]["embeddings"]["word"]}
        }
        n_rows = targets.size
        k_rows = mlm_gather_rows(n_rows, mask_prob)
        with jax.named_scope("mlm_head"):
            if k_rows is None:
                num, den, correct = stats(head_params, hidden, targets)
                share = jnp.ones((), jnp.float32)
            else:
                num, den, correct = _gathered(stats, k_rows)(
                    head_params,
                    hidden.reshape(n_rows, hidden.shape[-1]),
                    targets.reshape(n_rows),
                )
                share = jnp.where(den <= k_rows, k_rows / n_rows, 1.0)
        if seq_axis is not None:
            num = lax.psum(num, seq_axis)
            den = lax.psum(den, seq_axis)
            correct = lax.psum(correct, seq_axis)
        return num, den, correct, share

    return head_stats


def make_bert_eval_metrics(model: BertForPreTraining, *, mask_prob: float = 0.15):
    """Eval ``metric_fn`` for :func:`make_eval_step`: MLM/NSP losses and
    accuracies on held-out batches, no dropout, no mutation. MLM entries are
    ``(num, den)`` pairs so the eval step reduces them as global ratios over
    the DP axes (variable masked-token counts per shard); the head, its row
    gather and the seq-parallel handling are shared with the training loss
    (:func:`_mlm_head_stats`)."""
    head_stats = _mlm_head_stats(model, mask_prob)

    def metric_fn(params, model_state, batch):
        del model_state
        hidden, nsp_logits = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["attention_mask"],
            batch["token_type_ids"],
            train=False,
            method=BertForPreTraining.encode,
        )
        num, den, correct, _ = head_stats(params, hidden, batch["mlm_targets"])
        b = batch["nsp_label"].shape[0]
        nsp_ce = optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, batch["nsp_label"]
        ).sum()
        nsp_correct = (
            (jnp.argmax(nsp_logits, -1) == batch["nsp_label"])
            .astype(jnp.float32)
            .sum()
        )
        rows = jnp.asarray(b, jnp.float32)
        return {
            "mlm_loss": (num, den),
            "mlm_accuracy": (correct, den),
            "nsp_loss": (nsp_ce, rows),
            "nsp_accuracy": (nsp_correct, rows),
        }

    return metric_fn


def bert_param_specs(
    params,
    model_axis: str | None = "model",
    expert_axis: str | None = None,
    pipeline_axis: str | None = None,
):
    """PartitionSpec tree for Megatron-TP / expert sharding of BERT params.

    Pass the GLOBAL params (init'd with ``model_parallel=1`` /
    ``expert_parallel=1``) and the mesh axes actually in use (``None``
    disables that sharding family — a spec must never name an axis the mesh
    doesn't have). Returns a matching tree: Q/K/V kernels
    ``P(None, model, None)`` / biases ``P(model, None)`` (column-parallel
    over heads), attention-out and FFN down-projection kernels
    row-parallel, FFN up-projection column-parallel, stacked MoE expert
    params over the expert axis, everything else (embeddings, LayerNorms,
    post-psum biases, router, pooler, heads) replicated. Feed to
    ``place_state``/``make_train_step`` as the param sharding contract
    (train/step.py).
    """
    from jax.sharding import PartitionSpec as P

    rules = ()
    if model_axis is not None:
        rules += (
            (("query", "kernel"), P(None, model_axis, None)),
            (("key", "kernel"), P(None, model_axis, None)),
            (("value", "kernel"), P(None, model_axis, None)),
            (("query", "bias"), P(model_axis, None)),
            (("key", "bias"), P(model_axis, None)),
            (("value", "bias"), P(model_axis, None)),
            (("out", "kernel"), P(model_axis, None, None)),
            (("intermediate", "kernel"), P(None, model_axis)),
            (("intermediate", "bias"), P(model_axis)),
            (("output", "kernel"), P(model_axis, None)),
        )
    if expert_axis is not None or model_axis is not None:
        # MoE expert stacks: dim 0 over the expert axis; with TP the FFN
        # hidden dim is additionally Megatron-sharded over the model axis
        # (w1 column-parallel, w2 row-parallel, b1 column-parallel, b2
        # replicated across model — it enters as b2/tp per shard).
        rules += (
            (("experts_w1",), P(expert_axis, None, model_axis)),
            (("experts_w2",), P(expert_axis, model_axis, None)),
            (("experts_b1",), P(expert_axis, model_axis)),
            (("experts_b2",), P(expert_axis, None)),
        )

    def spec_for(path, leaf) -> P:
        names = tuple(
            p.key for p in path if isinstance(p, jax.tree_util.DictKey)
        )
        # Int8-packed kernels (models/quant.py): the "_q8" payload shards
        # exactly like the fp32 kernel it replaced, and its per-output-
        # channel "_q8_scale" vector carries only the kernel's LAST-axis
        # sharding (replicated when the output axis is unsharded) — the
        # quantize reduction keeps the trailing axis, so a shard-direct
        # restore places both leaves without a resharding round-trip.
        # Engines reject quantization for the stacked pipeline variant, so
        # the encoder branch below never sees these suffixes.
        quant = names[-1] if names and names[-1] in ("_q8", "_q8_scale") \
            else None
        if quant is not None:
            names = names[:-1]
        # Stacked encoder (pipeline config): every leaf under "encoder"
        # carries a leading [num_layers] dim sharded over the pipeline axis.
        # TP/EP rules compose — the per-layer spec slots in behind the
        # stacking dim (e.g. a stacked Q kernel [L, H, heads, hd] gets
        # P("pipeline", None, "model", None)), so one leaf shards over both
        # axes and the engine's per-leaf grad contract scales by each.
        if pipeline_axis is not None and "encoder" in names:
            for suffix, spec in rules:
                if names[-len(suffix):] == suffix:
                    inner = tuple(spec) + (None,) * (leaf.ndim - 1 - len(spec))
                    return P(pipeline_axis, *inner)
            return P(pipeline_axis, *(None,) * (leaf.ndim - 1))
        matched = P()
        for suffix, spec in rules:
            if names[-len(suffix):] == suffix:
                matched = spec
                break
        if quant == "_q8_scale":
            last = tuple(matched)[-1] if len(tuple(matched)) else None
            return P(last) if last is not None else P()
        return matched

    return jax.tree_util.tree_map_with_path(spec_for, params)


def make_bert_pretraining_loss(model: BertForPreTraining, *, mask_prob: float = 0.15):
    """LossFn for the engine: MLM (ignore targets < 0) + NSP.

    Batches: ``input_ids, attention_mask, token_type_ids, mlm_targets`` all
    ``[B, L]`` (sharded over "seq" when seq-parallel) and ``nsp_label [B]``.
    With ``cfg.seq_axis`` set, the MLM numerator/denominator are psum'd over
    the seq ring so every shard returns the *global* loss — required by the
    engine's seq-grad contract (train/step.py).

    The MLM head runs over the masked rows only (:func:`_mlm_head_stats`);
    ``mask_prob`` is the rate the data masks at, which sizes the gather and
    never the result. The metric ``mlm_head_share`` says what share of the
    rows the head ran over: a mean above ``k / N`` over a run is the share
    of steps whose data was masked more densely than ``mask_prob`` says and
    took the dense head.
    """
    moe = model.cfg.moe_experts > 0
    head_stats = _mlm_head_stats(model, mask_prob)

    def loss_fn(params, model_state, batch, rng):
        # mutable=["intermediates"] is harmless for dense BERT (nothing is
        # sown; mods comes back empty) — one apply call for both paths.
        (hidden, nsp_logits), mods = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["attention_mask"],
            batch["token_type_ids"],
            train=True,
            rngs={"dropout": rng},
            mutable=["intermediates"],
            method=BertForPreTraining.encode,
        )
        if moe:
            # Leaves are scalars (per-layer module list; the pipelined
            # encoder's pre-averaged sow) or stacked [num_layers] arrays
            # (the nn.scan encoder) — jnp.mean handles both uniformly.
            aux_leaves = jax.tree.leaves(mods["intermediates"])
            moe_aux = sum(jnp.mean(a) for a in aux_leaves) / len(aux_leaves)
        num, den, correct, share = head_stats(params, hidden, batch["mlm_targets"])
        den = jnp.maximum(den, 1.0)
        mlm_loss = num / den
        nsp_loss = optax.softmax_cross_entropy_with_integer_labels(
            nsp_logits, batch["nsp_label"]
        ).mean()
        loss = mlm_loss + nsp_loss
        metrics = {
            "mlm_loss": mlm_loss,
            "nsp_loss": nsp_loss,
            "mlm_accuracy": correct / den,
            "mlm_head_share": share,
        }
        if moe:
            loss = loss + model.cfg.moe_aux_weight * moe_aux
            metrics["moe_aux"] = moe_aux
        return loss, (model_state, metrics)

    return loss_fn
