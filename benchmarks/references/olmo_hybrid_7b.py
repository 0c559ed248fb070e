"""Plain reference of the Olmo-Hybrid decoder (``model_type: olmo_hybrid``;
the gated delta rule of arXiv:2412.06464 beside full attention): the whole
forward in ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
the recurrence one position after another (a ``lax.scan`` over time, no
chunks), dense causal attention with explicit masks (in blocks of queries, so
that a long sequence's scores fit), no cache, no kernels, and no code shared
with models/olmo_hybrid.py or models/kvcache.py — what the served path is
compared with (tests/test_olmo_hybrid.py on the CPU,
benchmarks/runners/serve_olmo_hybrid.py on the chip).

``cfg`` is the configuration file's dict (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``layer_types``,
``linear_num_key_heads``, ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``, ``rms_norm_eps``);
``params`` is the served tree (``embed``, ``layer_<l>`` with ``mixer``,
``mixer_norm``, ``ffn_norm``, ``gate_up``, ``down``; ``final_norm``,
``lm_head``), in any dtype: every leaf is cast to float32 where it is used,
so a caller can hand over one layer at a time.

The equations. ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``. Every layer:
``h = x + RMS(Mixer(x))``, ``out = h + RMS(FFN(h))``, ``FFN(h) = (silu(h W_g) *
h W_u) W_d``. ``full_attention``: ``q = RMS(x W_q)``, ``k = RMS(x W_k)`` over
the whole projection, ``v = x W_v``, heads of ``hidden / heads``, causal
softmax, ``W_o``; no positional encoding. ``linear_attention``, a head at a
time: ``q~, k~, v~`` through a causal depthwise convolution of width 4 and
``silu``; ``q = q / |q| / sqrt(d_k)``, ``k = k / |k|`` with ``|x| = sqrt(sum
x^2 + 1e-6)``; ``beta = 2 sigmoid(x W_b)``; ``alpha = exp(-exp(A_log)
softplus(x W_a + dt_bias))``; the state ``S [d_v, d_k]`` from zero,

    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t

then ``o = RMS(o; w_o) * silu(z)`` per head, ``z = x W_z``, and ``W_o``.

Departures from the published description, each because the repo has no
checkpoint to be faithful to, only shapes:
1. weights are random from a seed, so nothing here was ever compared with the
   released model's outputs;
2. the fused projections are laid out ``q | k | v | z``, ``a | b``, ``q | k |
   v`` and ``gate | up``, the depthwise conv kernel is ``[4, channels]`` over
   the channels ``q | k | v`` (tap 3 is the current input) with no bias: the
   released checkpoint's own orders were not available to check against;
3. where the norm sits in a block, the QK-norm over the whole projection and
   the absence of rotary are the family's, assumed (the config file's
   ``assumed`` list says from what).
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
    return cfg["layer_types"][l]


def _rms(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta(cfg, p, x):
    """The gated delta-rule mixer over ``x [B, L, d]``."""
    h = cfg["linear_num_key_heads"]
    d_k, d_v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    b, length = x.shape[:2]
    y = x @ p["in_proj"]["kernel"]
    channels = h * (2 * d_k + d_v)
    qkv, z = y[..., :channels], y[..., channels:]
    # causal depthwise conv: position t sees inputs t - taps + 1 .. t
    padded = jnp.concatenate(
        [jnp.zeros((b, taps - 1, channels), qkv.dtype), qkv], axis=1
    )
    qkv = _silu(sum(
        padded[:, tap:tap + length] * p["conv_kernel"][tap]
        for tap in range(taps)
    ))
    q = _unit(qkv[..., : h * d_k].reshape(b, length, h, d_k)) / math.sqrt(d_k)
    k = _unit(qkv[..., h * d_k: 2 * h * d_k].reshape(b, length, h, d_k))
    v = qkv[..., 2 * h * d_k:].reshape(b, length, h, d_v)
    ab = x @ p["ab_proj"]["kernel"]
    alpha = jnp.exp(
        -jnp.exp(p["A_log"]) * _softplus(ab[..., :h] + p["dt_bias"])
    )
    beta = 1.0 / (1.0 + jnp.exp(-ab[..., h:]))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta

    def step(s, xs):  # s [B, H, d_v, d_k]
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[..., None, None] * s
        err = v_t - jnp.einsum("bhvk,bhk->bhv", s, k_t)
        s = s + (b_t[..., None] * err)[..., :, None] * k_t[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q_t)

    s0 = jnp.zeros((b, h, d_v, d_k), jnp.float32)
    _, o = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta))
    )
    o = jnp.moveaxis(o, 0, 1)  # [B, L, H, d_v]
    o = _rms(p["o_norm"], o, cfg["rms_norm_eps"]) \
        * _silu(z.reshape(b, length, h, d_v))
    return o.reshape(b, length, h * d_v) @ p["out_proj"]["kernel"]


def _attention(cfg, p, x, mask):
    """Full causal attention over ``x [B, L, d]``; ``mask [B, L]`` True on
    real tokens."""
    n, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    b, length, d = x.shape
    qkv = x @ p["qkv"]["kernel"]
    q = _rms(p["q_norm"], qkv[..., :d], eps).reshape(b, length, n, d // n)
    k = _rms(p["k_norm"], qkv[..., d: 2 * d], eps).reshape(b, length, n, d // n)
    v = qkv[..., 2 * d:].reshape(b, length, n, d // n)
    j = jnp.arange(length)[None, :]
    out = []
    for start in range(0, length, QUERY_BLOCK):
        rows = slice(start, min(start + QUERY_BLOCK, length))
        i = jnp.arange(length)[rows, None]
        seen = (j <= i)[None, None] & mask[:, None, None, :]
        s = jnp.einsum("bihd,bjhd->bhij", q[:, rows], k) / math.sqrt(d // n)
        s = jnp.where(seen, s, _NEG)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * seen
        w = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        out.append(jnp.einsum("bhij,bjhd->bihd", w, v))
    o = jnp.concatenate(out, axis=1).reshape(b, length, d)
    return o @ p["out"]["kernel"]


def embed(params, ids):
    return jnp.asarray(params["embed"]["embedding"][ids], jnp.float32)


def block(cfg: dict, kind: str, p, x, mask):
    """One layer of ``kind`` (:func:`mixer_kind`) over every position: ``x
    [B, L, d]`` float32, ``mask [B, L]`` True on real tokens (left-aligned: a
    pad lies after every real token and reaches none of them)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        eps = cfg["rms_norm_eps"]
        if kind == "linear_attention":
            mixed = _delta(cfg, p["mixer"], x)
        else:
            mixed = _attention(cfg, p["mixer"], x, mask)
        h = x + _rms(p["mixer_norm"], mixed, eps)
        gu = h @ p["gate_up"]["kernel"]
        half = gu.shape[-1] // 2
        ffn = (_silu(gu[..., :half]) * gu[..., half:]) @ p["down"]["kernel"]
        return h + _rms(p["ffn_norm"], ffn, eps)


def final_norm(cfg: dict, params, x):
    return _rms(_f32(params["final_norm"]), x, cfg["rms_norm_eps"])


def logits(head, x):
    """``x [.., d]`` (after :func:`final_norm`) against rows of the untied
    head ``head [v, d]`` — all of it, or a block of its rows."""
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(head, jnp.float32).T


def forward(cfg: dict, params, ids, mask):
    """Logits ``[B, L, V]`` of the whole model at every position."""
    x = embed(params, ids)
    for l in range(cfg["num_hidden_layers"]):
        x = block(cfg, mixer_kind(cfg, l), params[f"layer_{l}"], x, mask)
    return logits(params["lm_head"], final_norm(cfg, params, x))
