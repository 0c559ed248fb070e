"""DeepSeek-V2 decoder LM (``model_type: deepseek_v2``; DeepSeek-V2-Lite's
sizes by default): multi-head latent attention (MLA) whose cache is one
576-wide row a position for all heads, and sparse experts — served through
``CausalLMEngine`` with chunked prefill, like models/olmo_hybrid.py.

``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``. Token embedding unscaled, a
float32 residual stream, a final ``RMS``, an untied head. Every layer:
``h = x + Attn(RMS(x))``, ``out = h + F(RMS(h))``, where ``F`` is a dense
SiLU-gated FFN in the first ``first_k_dense_replace`` layers and the MoE
after them; ``FFN_w(u) = W_down (silu(W_gate u) * W_up u)``.

MLA, per token ``u`` and head ``i`` (no query compression: ``q_lora_rank``
null): ``[q_i^nope | q_i^pe] = (W_q u)_i``; ``[c | k^pe] = W_kva u``; ``c <-
RMS(c; w_kv)``; ``[k_i^nope | v_i] = (W_kvb c)_i``; ``q_i^pe`` and the one
``k^pe`` that every head shares rotated at the token's absolute position
(:func:`rotate`, YaRN); ``s_i(t, tau) = sigma (q_i^nope . k_i^nope + q_i^pe .
k^pe)``, causal float32 softmax, ``o_i = sum a_i v_i``, ``Attn = W_o [o_1 ..
o_h]``, with ``sigma`` = :func:`softmax_scale`. What a position caches is the
row ``[c | k^pe]`` (``kv_lora_rank + qk_rope_head_dim`` = 576 lanes, held as
640 with zeros: :attr:`DeepseekV2Config.row_width`), one group ``latent`` of
``num_layers`` rows a position.

Two forms of the same attention:

- decode, ABSORBED (:meth:`LatentAttention.absorbed`): ``q~_i = W_UK,i^T
  q_i^nope``, so that ``[q~_i | q_i^pe]`` scores the cached row itself, the
  latent context ``sum a_i c`` and then ``o_i = W_UV,i`` of it — one row a
  position read for all heads (``kvcache.latent_attention``: where the
  table is whole blocks, a slot's blocks below its position, once);
- prompt chunks, DECOMPRESSED (:meth:`LatentAttention.expanded`): the
  chunk's rows go into the table first, then ``W_kvb`` expands every cached
  row to per-head ``k^nope``, ``v`` for the chunk's queries, which spares
  the absorbed form's 576-wide scores for hundreds of queries.

MoE: router logits ``W_r u`` in float32 from the float32 normed ``u``,
softmax, greedy top-``k``, gates NOT renormalised (``norm_topk_prob``
false), times ``routed_scaling_factor``; ``F(u) = sum_{e in top-k} g_e
FFN_e(u) + FFN_shared(u)``, the shared experts one FFN of ``n_shared_experts
x moe_intermediate_size``. Dropless (``parallel/moe.py::moe_dropless``): no
token's result depends on its batch-mates.

Precision as the hybrids: ``cfg.dtype`` into the MXU and float32 out; the
residual stream, norms, router and softmax float32; the latent table in the
engine's K/V dtype.

Forwards (one param tree):

- ``__call__(input_ids, attention_mask) -> logits [B, L, V]``: every
  position, what the cached path is tested against;
- ``routes(input_ids, attention_mask) -> [moe layers, B, L, k]``: the
  experts each position chose, the same forward without the head;
- ``prefill_chunk(input_ids [T, C], positions [T, C], cache) -> (logits [T,
  V], cache')``: a chunk at absolute positions (the sentinel ``cache_len``
  on a pad lane), the head at each row's last real lane only;
- ``decode_step(token [S], position [S], cache) -> (logits [S, V],
  cache')``: an idle lane (``position == cache_len``) writes nothing.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.kvcache import Leaf
from distributed_tensorflow_tpu.models.olmo_hybrid import RMSNorm, _dense
from distributed_tensorflow_tpu.ops import decode_attention
from distributed_tensorflow_tpu.parallel.moe import moe_dropless

_QUERY_BLOCK = 128  # a chunk's queries whose scores are made at a time


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    # the published config.json's keys (benchmarks/configs/deepseek_v2_lite.json)
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944  # the dense layers' FFN
    moe_intermediate_size: int = 1408  # one expert's FFN
    num_layers: int = 27
    num_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # rope_scaling, type yarn
    rope_factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    max_position: int = 163840  # context the config declares; no table of it
    dtype: jnp.dtype = jnp.float32

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Lanes of the cached row: the normed latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """The cached row as the table holds it: :attr:`latent_width` and
        zeros up to whole lane tiles of 128 (576 -> 640, what the chip pads
        a 576-lane row to anyway). A row of part tiles cannot be scattered
        into the table in place: the TPU compiler copies the whole table
        (4.9 GB) to write 576-lane rows, and 0 B to write 640-lane ones."""
        return -(-self.latent_width // 128) * 128

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace \
            and layer % self.moe_layer_freq == 0

    @property
    def moe_layers(self) -> int:
        return sum(self.is_moe(l) for l in range(self.num_layers))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: DeepseekV2Config) -> float:
    """``q_head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``:
    0.114721 at DeepSeek-V2-Lite's sizes."""
    m = _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return cfg.q_head_dim ** -0.5 * m * m


def yarn_range(cfg: DeepseekV2Config) -> tuple[int, int]:
    """The rotary dimensions between which YaRN ramps from extrapolation to
    interpolation: ``floor(d(beta_fast))``, ``ceil(d(beta_slow))`` with
    ``d(r) = dim ln(original_max_position / (2 pi r)) / (2 ln theta)``,
    clipped to ``[0, dim - 1]`` (10 and 23 at the published sizes)."""
    dim = cfg.qk_rope_head_dim

    def d(r):
        return dim * math.log(cfg.original_max_position / (2 * math.pi * r)) \
            / (2 * math.log(cfg.rope_theta))

    return max(math.floor(d(cfg.beta_fast)), 0), \
        min(math.ceil(d(cfg.beta_slow)), dim - 1)


def yarn_frequencies(cfg: DeepseekV2Config) -> np.ndarray:
    """``f_j = f_j^inter ramp_j + f_j^extra (1 - ramp_j)``, ``j < dim / 2``:
    ``f^extra = theta^(-2j / dim)``, ``f^inter = f^extra / factor``, ``ramp_j
    = clip((j - low) / (high - low), 0, 1)`` (:func:`yarn_range`)."""
    half = cfg.qk_rope_head_dim // 2
    extra = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    low, high = yarn_range(cfg)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / cfg.rope_factor * ramp + extra * (1 - ramp)).astype(
        np.float32
    )


def rotate(cfg: DeepseekV2Config, x, positions):
    """``x [.., dim]`` rotated at ``positions`` (``x``'s leading shape, or
    one short of it), float32. The public implementation's pairing: the
    even lanes then the odd ones, then ``rotate_half``; the cos / sin factor
    ``mscale / mscale_all_dim`` is 1 at the published sizes."""
    freq = jnp.asarray(yarn_frequencies(cfg))
    angle = positions.astype(jnp.float32)[..., None] * freq
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    factor = _yarn_mscale(cfg.rope_factor, cfg.mscale) / _yarn_mscale(
        cfg.rope_factor, cfg.mscale_all_dim
    )
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1) * factor
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1) * factor
    x = x.astype(jnp.float32)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _lanes(cfg: DeepseekV2Config, x):
    """``x [.., latent_width]`` with zeros up to :attr:`row_width`."""
    pad = cfg.row_width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _init(std=0.02):
    return nn.initializers.normal(std)


class LatentAttention(nn.Module):
    cfg: DeepseekV2Config

    def setup(self):
        cfg = self.cfg
        h = cfg.num_heads
        self.q_proj = _dense(cfg, h * cfg.q_head_dim)
        self.kv_a = _dense(cfg, cfg.latent_width)  # c | k^pe
        self.kv_a_norm = RMSNorm(cfg.rms_norm_eps)
        # W_kvb as the two forms read it: [latent, head, k^nope | v]
        self.kv_b = self.param(
            "kv_b", _init(),
            (cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
        )
        self.o_proj = _dense(cfg, cfg.hidden_size)

    def project(self, x, positions):
        """``q^nope [.., h, d_nope]``, ``q^pe [.., h, d_rope]`` rotated, and
        the row ``[c | k^pe | 0] [.., row_width]`` a position caches, all
        float32."""
        cfg = self.cfg
        with jax.named_scope("mla_project"):
            q = self.q_proj(x)
            q = q.reshape(*q.shape[:-1], cfg.num_heads, cfg.q_head_dim)
            kv = self.kv_a(x)
            c = self.kv_a_norm(kv[..., : cfg.kv_lora_rank])
            k_pe = rotate(cfg, kv[..., cfg.kv_lora_rank:], positions)
            q_pe = rotate(cfg, q[..., cfg.qk_nope_head_dim:], positions)
            return q[..., : cfg.qk_nope_head_dim], q_pe, _lanes(
                cfg, jnp.concatenate([c, k_pe], axis=-1)
            )

    def _out(self, o):
        return self.o_proj(o.reshape(*o.shape[:-2], -1))

    def absorbed(self, q_nope, q_pe, table, position, row, layer=None):
        """Decode: ``q [S, h, ..]`` against the cached rows as the step found
        them — one layer's ``table [S, L, row_width]``, or with ``layer``
        that layer of the stacked table, which the read does not copy — and
        the step's own ``row [S, row_width]`` (encoded), ``W_UK`` folded
        into the query (its pad lanes zero) and ``W_UV`` applied to the
        latent context."""
        cfg = self.cfg
        dt = cfg.dtype
        w = self.kv_b.astype(dt)
        q = jnp.einsum(
            "shd,chd->shc", q_nope.astype(dt), w[..., : cfg.qk_nope_head_dim],
            preferred_element_type=jnp.float32,
        )
        q = _lanes(cfg, jnp.concatenate([q, q_pe], axis=-1)).astype(dt)
        ctx = kvcache.latent_attention(
            q, table, position, row, softmax_scale(cfg), layer=layer
        )
        o = jnp.einsum(
            "shc,chd->shd", ctx[..., : cfg.kv_lora_rank].astype(dt),
            w[..., cfg.qk_nope_head_dim:], preferred_element_type=jnp.float32,
        )
        return self._out(o)

    def expanded(self, q_nope, q_pe, rows, positions):
        """Prompt: ``q [B, C, h, ..]`` at ``positions [B, C]`` against each
        row's cached rows ``[B, L, row_width]`` (the chunk's own among them),
        each query seeing positions ``<=`` its own; ``W_kvb`` expands every
        cached row to per-head keys and values. Queries go ``_QUERY_BLOCK`` at a
        time where the chunk has whole blocks of them."""
        cfg = self.cfg
        dt = cfg.dtype
        with jax.named_scope("mla_chunk_attention"):
            rows = rows.astype(dt)
            kv = jnp.einsum(
                "blc,chd->blhd", rows[..., : cfg.kv_lora_rank],
                self.kv_b.astype(dt), preferred_element_type=jnp.float32,
            ).astype(dt)
            k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
            k_pe = rows[..., cfg.kv_lora_rank: cfg.latent_width]
            scale = softmax_scale(cfg)
            length = rows.shape[1]
            positions = jnp.minimum(positions, length - 1)

            def block(q_nope, q_pe, positions):  # [B, n, h, ..], [B, n]
                s = jnp.einsum(
                    "bqhd,blhd->bhql", q_nope.astype(dt), k_nope,
                    preferred_element_type=jnp.float32,
                ) + jnp.einsum(
                    "bqhr,blr->bhql", q_pe.astype(dt), k_pe,
                    preferred_element_type=jnp.float32,
                )
                valid = (jnp.arange(length) <= positions[..., None])[:, None]
                s = jnp.where(valid, s * scale, kvcache.MASK_VALUE)
                p = jax.nn.softmax(s, axis=-1) * valid
                o = jnp.einsum(
                    "bhql,blhd->bhqd", p.astype(dt), v,
                    preferred_element_type=jnp.float32,
                )
                return jnp.swapaxes(o, 1, 2)

            b, c = positions.shape
            if c <= _QUERY_BLOCK or c % _QUERY_BLOCK:
                o = block(q_nope, q_pe, positions)
            else:
                n = c // _QUERY_BLOCK

                def blocks(a):
                    a = a.reshape(b, n, _QUERY_BLOCK, *a.shape[2:])
                    return jnp.moveaxis(a, 1, 0)

                o = jax.lax.map(
                    lambda xs: block(*xs),
                    (blocks(q_nope), blocks(q_pe), blocks(positions)),
                )
                o = jnp.moveaxis(o, 0, 1).reshape(b, c, *o.shape[3:])
        return self._out(o)


class MoE(nn.Module):
    """The routed experts (held here: all of them, ``first`` 0) and the
    shared experts beside them. ``u [N, d]`` float32, normed."""

    cfg: DeepseekV2Config

    def setup(self):
        cfg = self.cfg
        e, d, f = cfg.n_routed_experts, cfg.hidden_size, \
            cfg.moe_intermediate_size
        self.router = self.param("router", _init(), (d, e))
        self.experts_gate_up = self.param("experts_gate_up", _init(),
                                          (e, d, 2 * f))
        self.experts_down = self.param("experts_down", _init(), (e, f, d))
        self.shared_gate_up = _dense(cfg, 2 * cfg.n_shared_experts * f)
        self.shared_down = _dense(cfg, d)

    def __call__(self, u):
        cfg = self.cfg
        dt = cfg.dtype
        with jax.named_scope("moe_route"):
            logits = jnp.dot(u, self.router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        y, choice = moe_dropless(
            u.astype(dt), logits,
            {"gate_up": self.experts_gate_up.astype(dt),
             "down": self.experts_down.astype(dt)},
            cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor,
        )
        with jax.named_scope("shared_experts"):
            g, up = jnp.split(self.shared_gate_up(u), 2, axis=-1)
            return y + self.shared_down(jax.nn.silu(g) * up), choice


class DenseMLP(nn.Module):
    cfg: DeepseekV2Config

    def setup(self):
        self.gate_up = _dense(self.cfg, 2 * self.cfg.intermediate_size)
        self.down = _dense(self.cfg, self.cfg.hidden_size)

    def __call__(self, u):
        with jax.named_scope("dense_mlp"):
            g, up = jnp.split(self.gate_up(u), 2, axis=-1)
            return self.down(jax.nn.silu(g) * up), None


class DecoderLayer(nn.Module):
    cfg: DeepseekV2Config
    moe: bool

    def setup(self):
        cfg = self.cfg
        self.attn_norm = RMSNorm(cfg.rms_norm_eps)
        self.attn = LatentAttention(cfg)
        self.ffn_norm = RMSNorm(cfg.rms_norm_eps)
        self.mlp = MoE(cfg) if self.moe else DenseMLP(cfg)

    def ffn(self, h):
        """``h + F(RMS(h))`` and the experts each token chose (None in a
        dense layer)."""
        u = self.ffn_norm(h)
        y, choice = self.mlp(u.reshape(-1, u.shape[-1]))
        if choice is not None:
            choice = choice.reshape(*h.shape[:-1], -1)
        return h + y.reshape(h.shape), choice


class DeepseekV2(nn.Module):
    cfg: DeepseekV2Config

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=_init(),
            dtype=cfg.dtype,
        )
        self.layers = [
            DecoderLayer(cfg, cfg.is_moe(l), name=f"layer_{l}")
            for l in range(cfg.num_layers)
        ]
        self.final_norm = RMSNorm(cfg.rms_norm_eps)
        self.lm_head = self.param(
            "lm_head", _init(), (cfg.vocab_size, cfg.hidden_size)
        )

    def _head(self, x):
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "...d,vd->...v", self.final_norm(x).astype(self.cfg.dtype),
                self.lm_head.astype(self.cfg.dtype),
                preferred_element_type=jnp.float32,
            )

    def _embed(self, ids):
        return self.embed(ids).astype(jnp.float32)  # the residual stream

    def _forward(self, input_ids):
        """Every position of rows that start at position 0 (a pad after
        every real token reaches none of them): the stream and the experts
        each MoE layer chose."""
        x = self._embed(input_ids)
        positions = jnp.broadcast_to(
            jnp.arange(input_ids.shape[1]), input_ids.shape
        )
        routes = []
        for layer in self.layers:
            q_nope, q_pe, row = layer.attn.project(
                layer.attn_norm(x), positions
            )
            h = x + layer.attn.expanded(
                q_nope, q_pe, row.astype(self.cfg.dtype), positions
            )
            x, choice = layer.ffn(h)
            if choice is not None:
                routes.append(choice)
        return x, routes

    def __call__(self, input_ids, attention_mask):
        del attention_mask  # causal: a pad lies after every real token
        return self._head(self._forward(input_ids)[0])

    def routes(self, input_ids, attention_mask):
        del attention_mask
        return jnp.stack(self._forward(input_ids)[1])

    def cache_layout(self, kv_dtype: str):
        cfg = self.cfg
        return {"latent": {"row": Leaf(
            (cfg.row_width,), jnp.dtype(kv_dtype), (None,),
            layers=cfg.num_layers, after=kvcache.POSITIONS, group="latent",
            pages=False, prefix_readers=cfg.num_layers,
            prefix_block=decode_attention.latent_block_for(cfg.row_width),
        )}}

    def decode_counters(self) -> dict[str, int]:
        """What a decode step does for each live lane beside its cache
        writes: ``routed_rows``, the rows its routers send to experts."""
        return {"routed_rows": self.cfg.moe_layers
                * self.cfg.num_experts_per_tok}

    def prefill_chunk(self, input_ids, positions, cache):
        latent = cache["latent"]
        mask = positions < kvcache.cache_len(latent)
        x = self._embed(input_ids)
        for i, layer in enumerate(self.layers):
            q_nope, q_pe, row = layer.attn.project(
                layer.attn_norm(x), positions
            )
            table = kvcache.take_layer(latent, i)
            table = kvcache.scatter_rows(
                table, kvcache.encode(table, {"row": row}), positions
            )
            latent = kvcache.put_layer(latent, i, table)
            h = x + layer.attn.expanded(q_nope, q_pe, table["row"], positions)
            x, _ = layer.ffn(h)
        # the head at each row's last real lane only
        last = jnp.maximum(jnp.sum(mask, axis=1), 1) - 1
        x = x[jnp.arange(x.shape[0]), last]
        return self._head(x), {"latent": latent}

    def decode_step(self, token, position, cache):
        latent = cache["latent"]
        x = self._embed(token)
        rows = []
        for i, layer in enumerate(self.layers):
            q_nope, q_pe, row = layer.attn.project(
                layer.attn_norm(x), position
            )
            # the table AS THE STEP FOUND IT and the new row beside it; the
            # layers' rows go in once, below
            row = kvcache.encode(latent, {"row": row})
            h = x + layer.attn.absorbed(
                q_nope, q_pe, latent["row"], position, row["row"], layer=i
            )
            rows.append(row)
            x, _ = layer.ffn(h)
        latent = kvcache.write_rows(latent, kvcache.stack_layers(rows), position)
        return self._head(x), {"latent": latent}


def deepseek_v2_init_params(model: DeepseekV2, key, dtype=None):
    """Random weights from ``key`` (normal 0.02; norm weights 1); ``dtype``
    casts every leaf (serving in bfloat16). Initialised over one short row:
    no parameter's shape depends on a length."""
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(key, ids, jnp.ones((1, 8), bool))["params"]
    if dtype is not None:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


__all__ = [
    "DeepseekV2", "DeepseekV2Config", "deepseek_v2_init_params", "rotate",
    "softmax_scale", "yarn_frequencies", "yarn_range",
]
