"""Plain reference of the SambaY decoder-hybrid-decoder (arXiv:2507.06607) as
Phi-4-mini-flash-reasoning configures it: the whole forward in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, one position after
another through the recurrence, explicit masks, no cache, no kernels, no
batching tricks, and no code shared with models/sambay.py or models/kvcache.py
— what the served path is compared with (tests/test_sambay*.py on the CPU,
benchmarks/runners/serve_sambay.py on the chip). The one copy; it lives with
the benchmark because a reference is the benchmark's to keep.

``cfg`` is the configuration file's dict (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``sliding_window``, ``mb_per_layer``, ``layer_norm_eps``, and the assumed
``d_state``, ``d_conv``, ``dt_rank``); ``params`` is the served tree
(``embed``, ``layer_<l>`` with ``ln1``, ``ln2``, ``mixer``, ``gate_up``,
``down``, and ``final_ln``), in any dtype: every leaf is cast to float32 where
it is used, so a caller can hand over one layer at a time.

Layer ``l`` with ``half = num_hidden_layers // 2``: Mamba-1 at even ``l <=
half`` (layer ``half`` also yields the memory, its output before the gate),
window attention at odd ``l < half``, full attention at ``l = half + 1``
(whose K and V every later attention reads), gated memory unit at even ``l >
half``, cross attention at odd ``l > half + 1``. Attention is differential
(arXiv:2410.05258): heads ``2p, 2p + 1`` are query pair ``p``; K/V heads
``2g, 2g + 1`` are K/V pair ``g``; pair ``p`` reads pair ``p // (pairs per
K/V pair)``.

Departures from the published description, each because the repo has no
checkpoint to be faithful to, only shapes:
1. weights are random from a seed, so nothing here was ever compared with the
   released model's outputs;
2. the fused projections are laid out ``q | k | v`` and ``gate | up``, the
   depthwise conv kernel is ``[d_conv, d_in]`` (tap ``d_conv - 1`` is the
   current input) and ``A_log`` is ``[d_in, d_state]``: the released
   checkpoint's own orders were not available to check against;
3. dropout (``embd_pdrop``, ``resid_pdrop``: 0 in the config) is left out;
4. the Mamba sizes are the family's defaults (the config does not give them).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG = -1e30


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def mixer_kind(cfg: dict, l: int) -> str:
    half = cfg["num_hidden_layers"] // 2
    if l % cfg["mb_per_layer"] == 0:
        return "mamba" if l <= half else "gmu"
    if l < half:
        return "window"
    return "full" if l == half + 1 else "cross"


def _layer_norm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _mamba(cfg, p, h):
    """``h [B, L, d]`` -> ``(out, y before the gate)``."""
    n, k = cfg["d_state"], cfg["d_conv"]
    r = cfg.get("dt_rank") or math.ceil(cfg["hidden_size"] / 16)
    uz = h @ p["in_proj"]["kernel"]
    d_in = uz.shape[-1] // 2
    u, z = uz[..., :d_in], uz[..., d_in:]
    # causal depthwise conv: position t sees inputs t - k + 1 .. t
    length = u.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((u.shape[0], k - 1, d_in), u.dtype), u], axis=1
    )
    conv = sum(
        padded[:, tap:tap + length] * p["conv_kernel"][tap] for tap in range(k)
    )
    u = _silu(conv + p["conv_bias"])
    dbc = u @ p["x_proj"]["kernel"]
    delta, b, c = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    delta = _softplus(delta @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"])
    a = -jnp.exp(p["A_log"])  # [d_in, N]

    def step(s, xs):  # s [B, d_in, N]
        delta_t, u_t, b_t, c_t = xs
        s = jnp.exp(delta_t[:, :, None] * a) * s \
            + (delta_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t)

    s0 = jnp.zeros((u.shape[0], d_in, n), jnp.float32)
    _, y = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (delta, u, b, c))
    )
    y = jnp.moveaxis(y, 0, 1) + p["D"] * u
    return (y * _silu(z)) @ p["out_proj"]["kernel"], y


def _attention(cfg, depth, p, h, kv, seen):
    """Differential attention of ``h [B, L, d]`` over K/V heads ``kv = (k, v)``
    each ``[B, L, n_kv, hd]``; ``seen [B, L, L]`` True where query ``i`` may
    read key ``j``; ``depth`` the layer's index. Returns the mixer's output."""
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // n_q
    q = h @ p["qkv"]["kernel"][:, : n_q * hd] + p["qkv"]["bias"][: n_q * hd]
    q = q.reshape(*h.shape[:2], n_q, hd)
    k, v = kv
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (
        jnp.exp(jnp.dot(p["lambda_q1"], p["lambda_k1"]))
        - jnp.exp(jnp.dot(p["lambda_q2"], p["lambda_k2"]))
        + lam_init
    )
    pairs, kv_pairs = n_q // 2, n_kv // 2

    def weights(qh, kh):
        s = jnp.einsum("bid,bjd->bij", qh, kh) / math.sqrt(hd)
        s = jnp.where(seen, s, _NEG)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * seen
        # a pad query past a short row's window sees nothing: weights 0, not
        # 0 / 0, or its NaN would reach real positions as 0 * NaN
        return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)

    outs = []
    for pair in range(pairs):
        g = pair // (pairs // kv_pairs)
        w = weights(q[:, :, 2 * pair], k[:, :, 2 * g]) \
            - lam * weights(q[:, :, 2 * pair + 1], k[:, :, 2 * g + 1])
        v12 = jnp.concatenate([v[:, :, 2 * g], v[:, :, 2 * g + 1]], axis=-1)
        o = jnp.einsum("bij,bjc->bic", w, v12)
        o = o / jnp.sqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg["layer_norm_eps"]
        )
        outs.append(o * p["subln"] * (1.0 - lam_init))
    o = jnp.concatenate(outs, axis=-1)
    return o @ p["out"]["kernel"] + p["out"]["bias"]


def _own_kv(cfg, p, h):
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // n_q
    x = h @ p["qkv"]["kernel"][:, n_q * hd:] + p["qkv"]["bias"][n_q * hd:]
    k, v = x[..., : n_kv * hd], x[..., n_kv * hd:]
    shape = (*h.shape[:2], n_kv, hd)
    return k.reshape(shape), v.reshape(shape)


def embed(params, ids):
    return jnp.asarray(params["embed"]["embedding"][ids], jnp.float32)


def block(cfg: dict, kind: str, keeps_memory: bool, depth, p, x, mask,
          carry: dict):
    """One layer of ``kind`` (:func:`mixer_kind`) over every position: ``x
    [B, L, d]`` float32, ``mask [B, L]`` True on real tokens, ``depth`` the
    layer's index (a number, or a traced scalar so that layers of one kind
    share a compiled program), ``carry`` what earlier layers hand on
    (``memory`` from the Mamba layer that ``keeps_memory``, ``kv`` from the
    full-attention layer). Returns ``(x', carry')``."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        eps = cfg["layer_norm_eps"]
        depth = jnp.asarray(depth, jnp.float32)
        h = _layer_norm(p["ln1"], x, eps)
        m = p["mixer"]
        if kind == "mamba":
            mixed, y = _mamba(cfg, m, h)
            if keeps_memory:
                carry = {**carry, "memory": y}
        elif kind == "gmu":
            gate = _silu(h @ m["in_proj"]["kernel"])
            mixed = (gate * carry["memory"]) @ m["out_proj"]["kernel"]
        else:
            length = x.shape[1]
            i = jnp.arange(length)[:, None]
            j = jnp.arange(length)[None, :]
            seen = j <= i
            if kind == "window":
                seen = seen & (j > i - cfg["sliding_window"])
            seen = seen[None] & mask[:, None, :]
            if kind == "cross":
                kv = carry["kv"]
            else:
                kv = _own_kv(cfg, m, h)
                if kind == "full":
                    carry = {**carry, "kv": kv}
            mixed = _attention(cfg, depth, m, h, kv, seen)
        x = x + mixed
        gu = _layer_norm(p["ln2"], x, eps) @ p["gate_up"]["kernel"]
        half = gu.shape[-1] // 2
        x = x + (gu[..., half:] * _silu(gu[..., :half])) @ p["down"]["kernel"]
        return x, carry


def layer(cfg: dict, l: int, p, x, mask, carry: dict):
    """Layer ``l``: :func:`block` of its kind; layer ``num_hidden_layers //
    2`` is the Mamba layer that keeps its memory."""
    return block(
        cfg, mixer_kind(cfg, l), l == cfg["num_hidden_layers"] // 2, l,
        p, x, mask, carry,
    )


def final_norm(cfg: dict, params, x):
    return _layer_norm(_f32(params["final_ln"]), x, cfg["layer_norm_eps"])


def logits(embedding, x):
    """``x [.., d]`` (after :func:`final_norm`) against rows of the tied
    table ``embedding [v, d]`` — all of it, or a block of its rows."""
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(embedding, jnp.float32).T


def forward(cfg: dict, params, ids, mask):
    """Logits ``[B, L, V]`` of the whole model at every position."""
    x, carry = embed(params, ids), {}
    for l in range(cfg["num_hidden_layers"]):
        x, carry = layer(cfg, l, params[f"layer_{l}"], x, mask, carry)
    return logits(params["embed"]["embedding"], final_norm(cfg, params, x))
