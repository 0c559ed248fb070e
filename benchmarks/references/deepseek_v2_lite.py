"""Plain reference of the DeepSeek-V2 decoder (``model_type: deepseek_v2``,
DeepSeek-V2-Lite): the whole forward in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, multi-head latent attention in
its DECOMPRESSED form (every position's keys and values expanded per head,
dense causal scores in blocks of queries), every expert computed over every
token and weighted by the token's gate where the expert is among its top
six (no sorting, no grouping, no capacity), no cache, no kernels, and no
code shared with models/deepseek_v2.py, models/kvcache.py or
parallel/moe.py — what the served path, which decodes in the ABSORBED form,
is compared with (tests/test_deepseek_v2.py on the CPU,
benchmarks/runners/serve_deepseek_v2.py on the chip).

``cfg`` is the configuration file's dict (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``first_k_dense_replace``, ``moe_layer_freq``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``rms_norm_eps``,
``rope_theta``, ``rope_scaling``); ``params`` is the served tree
(``embed``, ``layer_<l>`` with ``attn_norm``, ``attn`` {``q_proj``,
``kv_a``, ``kv_a_norm``, ``kv_b [512, h, 256]``, ``o_proj``}, ``ffn_norm``,
``mlp`` {``gate_up``, ``down``} or {``router``, ``experts_gate_up``,
``experts_down``, ``shared_gate_up``, ``shared_down``}; ``final_norm``,
``lm_head``), in any dtype: every leaf is cast to float32 where it is used,
so a caller can hand over one layer at a time.

The equations. ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``. Every layer:
``h = x + Attn(RMS(x))``, ``out = h + F(RMS(h))``, ``FFN(u) = (silu(u W_g) *
u W_u) W_d``. Attention, head ``i``: ``[q^nope_i | q^pe_i] = (x W_q)_i``, ``[c
| k^pe] = x W_kva``, ``c <- RMS(c)``, ``[k^nope_i | v_i] = (c W_kvb)_i``;
``q^pe_i``, ``k^pe`` rotated at the absolute position with YaRN's
frequencies (``f_j = f_j^extra (1 - ramp_j) + f_j^extra / factor ramp_j``,
``ramp_j = clip((j - low) / (high - low), 0, 1)``, ``low = floor(d(beta_fast))``,
``high = ceil(d(beta_slow))``, ``d(r) = dim ln(L_orig / (2 pi r)) / (2 ln
theta)``), the lane pair ``(2j, 2j + 1)`` turned by ``f_j`` and written to
lanes ``j`` and ``j + dim / 2``; scores ``(q^nope_i . k^nope_i + q^pe_i .
k^pe) * q_head_dim^-0.5 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
MoE: ``p = softmax(u W_r)``, the six largest, gates ``p_e`` (not
renormalised) times ``routed_scaling_factor``; plus the shared experts, one
FFN.

Departures from the published description, each because the repo has no
checkpoint to be faithful to, only shapes:
1. weights are random from a seed, so nothing here was ever compared with the
   released model's outputs;
2. ``kv_b`` is laid out ``[latent, head, k^nope | v]``, the fused FFN
   projections ``gate | up``: the checkpoint's own orders are not used.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG = -1e30
QUERY_BLOCK = 512  # attention's scores are made for this many queries at a time


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def mixer_kind(cfg: dict, l: int) -> str:
    """``"moe"`` or ``"dense"``: what follows the attention in layer ``l``."""
    moe = l >= cfg["first_k_dense_replace"] and l % cfg["moe_layer_freq"] == 0
    return "moe" if moe else "dense"


def _rms(w, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: dict):
    """The rotary frequencies ``[dim / 2]`` and the attention's scale."""
    rs = cfg["rope_scaling"]
    dim = cfg["qk_rope_head_dim"]
    theta, factor = cfg["rope_theta"], rs["factor"]

    def d(r):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(d(rs["beta_fast"])), 0)
    high = min(math.ceil(d(rs["beta_slow"])), dim - 1)
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (2 * j / dim)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = extra * (1 - ramp) + extra / factor * ramp
    q_head = cfg["qk_nope_head_dim"] + dim
    m = _mscale(factor, rs["mscale_all_dim"])
    return freq, q_head ** -0.5 * m * m


def _rope(x, pos, freq, amp):
    """Lanes ``(2j, 2j + 1)`` of ``x [.., L, dim]`` turned by ``pos * f_j``,
    the results at lanes ``j`` and ``j + dim / 2``; ``pos [L]``."""
    a = pos[:, None] * freq  # [L, dim / 2]
    if x.ndim == 4:  # [B, L, h, dim]
        a = a[:, None, :]
    cos, sin = jnp.cos(a) * amp, jnp.sin(a) * amp
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


def _attention(cfg, p, x, mask):
    """Causal latent attention over ``x [B, L, d]`` (normed), decompressed;
    ``mask [B, L]`` True on real tokens."""
    n, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    b, length, _ = x.shape
    freq, scale = yarn(cfg)
    rs = cfg["rope_scaling"]
    amp = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                       rs["mscale_all_dim"])
    pos = jnp.arange(length, dtype=jnp.float32)
    q = (x @ p["q_proj"]["kernel"]).reshape(b, length, n, -1)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, freq, amp)
    ckv = x @ p["kv_a"]["kernel"]
    c = _rms(p["kv_a_norm"]["scale"], ckv[..., :r], eps)
    k_pe = _rope(ckv[..., r:], pos, freq, amp)  # [B, L, dim], one for all heads
    kv = jnp.einsum("blc,chd->blhd", c, p["kv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    j = jnp.arange(length)[None, :]
    out = []
    for start in range(0, length, QUERY_BLOCK):
        rows = slice(start, min(start + QUERY_BLOCK, length))
        i = jnp.arange(length)[rows, None]
        seen = (j <= i)[None, None] & mask[:, None, None, :]
        s = jnp.einsum("bihd,bjhd->bhij", q_nope[:, rows], k_nope) \
            + jnp.einsum("bihd,bjd->bhij", q_pe[:, rows], k_pe)
        s = jnp.where(seen, s * scale, _NEG)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * seen
        w = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        out.append(jnp.einsum("bhij,bjhd->bihd", w, v))
    o = jnp.concatenate(out, axis=1).reshape(b, length, -1)
    return o @ p["o_proj"]["kernel"]


def _ffn(gate_up, down, u):
    gu = u @ gate_up
    half = gu.shape[-1] // 2
    return (_silu(gu[..., :half]) * gu[..., half:]) @ down


def _moe(cfg, p, u):
    """``(F(u), router probabilities [B, L, E])`` of ``u [B, L, d]``."""
    k = cfg["num_experts_per_tok"]
    logits = u @ p["router"]
    z = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = z / jnp.sum(z, axis=-1, keepdims=True)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * cfg["routed_scaling_factor"]
    experts = p["experts_down"].shape[0]

    def expert(acc, e):
        # this expert's gate for each token: p_e where e is one of its six
        g = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)
        y = _ffn(p["experts_gate_up"][e], p["experts_down"][e], u)
        return acc + g[..., None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(experts))
    shared = _ffn(p["shared_gate_up"]["kernel"], p["shared_down"]["kernel"], u)
    return routed + shared, probs


def embed(params, ids):
    return jnp.asarray(params["embed"]["embedding"][ids], jnp.float32)


def block(cfg: dict, kind: str, p, x, mask):
    """One layer of ``kind`` (:func:`mixer_kind`) over every position: ``x
    [B, L, d]`` float32, ``mask [B, L]`` True on real tokens (left-aligned).
    Returns ``(x', probs)``: the router's probabilities ``[B, L, E]`` of an
    MoE layer, None of a dense one."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        eps = cfg["rms_norm_eps"]
        h = x + _attention(cfg, p["attn"], _rms(p["attn_norm"]["scale"], x, eps),
                           mask)
        u = _rms(p["ffn_norm"]["scale"], h, eps)
        if kind == "moe":
            y, probs = _moe(cfg, p["mlp"], u)
        else:
            y, probs = _ffn(p["mlp"]["gate_up"]["kernel"],
                            p["mlp"]["down"]["kernel"], u), None
        return h + y, probs


def final_norm(cfg: dict, params, x):
    return _rms(jnp.asarray(params["final_norm"]["scale"], jnp.float32), x,
                cfg["rms_norm_eps"])


def logits(head, x):
    """``x [.., d]`` (after :func:`final_norm`) against rows of the untied
    head ``head [v, d]`` — all of it, or a block of its rows."""
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(head, jnp.float32).T


def forward(cfg: dict, params, ids, mask):
    """Logits ``[B, L, V]`` of the whole model at every position."""
    x = embed(params, ids)
    for l in range(cfg["num_hidden_layers"]):
        x, _ = block(cfg, mixer_kind(cfg, l), params[f"layer_{l}"], x, mask)
    return logits(params["lm_head"], final_norm(cfg, params, x))
