"""SambaY decoder-hybrid-decoder LM (arXiv:2507.06607), the block of
Phi-4-mini-flash-reasoning — served through ``CausalLMEngine`` like
models/causal_lm.py, with three kinds of cached state side by side.

No positional encoding of any kind. Every layer is pre-norm with LayerNorm
(bias, eps 1e-5): ``x += Mixer_l(LN1(x))``, ``x += MLP(LN2(x))``, ``MLP(h) =
W_d (u * silu(g))`` with ``(g, u) = split(W_gu h)``, no bias; a final
LayerNorm and a tied head. With ``half = num_layers // 2`` the mixer of layer
``l`` is (:func:`layer_kinds`):

- ``mamba`` (``l`` even, ``l <= half``): Mamba-1. ``(u, z) = split(W_in h)``;
  ``u <- silu(causal_depthwise_conv(u) + b_c)``; ``(delta, B, C) = split(W_x
  u)``; ``Delta = softplus(W_dt delta + b_dt)``; ``A = -exp(A_log)``; ``s_t =
  exp(Delta_t * A) * s_{t-1} + (Delta_t * u_t) (x) B_t``; ``y_t = s_t C_t + D
  * u_t``; out ``= W_out (y * silu(z))``. Layer ``half`` also hands on ``m_t
  = y_t`` (before the gate): the memory.
- ``window`` (``l`` odd, ``l < half``): differential attention
  (arXiv:2410.05258) over the last ``sliding_window`` positions, grouped-query:
  adjacent heads pair, query pair ``p`` reads K/V pair ``p // 2`` (at the
  published 40 and 20 heads), ``o_p = (softmax(q1 k1) - lam * softmax(q2
  k2)) [v1; v2]``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 l)``, then ``RMSNorm(o_p) * (1 -
  lam_init)`` and ``W_o``.
- ``full`` (``l = half + 1``): the same attention, causal, no window. Its K
  and V are THE full cache.
- ``gmu`` (``l`` even, ``l >= half + 2``): ``W_out (silu(W_in h) * m)``, ``m``
  layer ``half``'s memory of the same token. No state of its own.
- ``cross`` (``l`` odd, ``l >= half + 3``): a query only; attends layer ``half
  + 1``'s K and V.

What a sequence caches (:meth:`SambaY.cache_layout`, three groups of
``kvcache.Leaf``): ``state`` — per Mamba layer the float32 scan state ``[N,
d_in]`` (``d_in`` minor: ``[d_in, 16]`` would pad 16 lanes to 128) and the
conv's last ``d_conv - 1`` inputs as one row, no positions; ``window`` — per
window layer a ring of ``sliding_window`` merged K and V rows, a position at
row ``position % window``; ``full`` — ONE layer's K and V at every position,
read by ``1 + (num_layers - half - 2) // 2`` layers.

Forwards (one param tree):

- ``__call__(input_ids, attention_mask) -> logits [B, L, V]`` — everything at
  every position: scoring, and what the cached path is tested against.
- ``prefill_rows(input_ids, attention_mask, lengths) -> (logits [B, V],
  fresh)`` — layers ``<= half + 1`` over the padded bucket, the rest at each
  row's LAST REAL position only (they cache nothing: the architecture's
  linear prefill, exact). ``fresh`` is every group's state at the row's own
  length, not at the bucket's padded end: a pad position has ``Delta = 0``
  and leaves the scan state untouched, the conv tail is the last real inputs,
  the ring holds the last ``min(len, window)`` rows where they live.
- ``decode_step(token [S], position [S], cache) -> (logits [S, V], cache')``
  — one token per slot: state updated in place, the new K/V row into the ring
  at ``position % window`` and into the full table at ``position``, every
  window layer attending its ring as the step found it with the new row
  selected in (models/kvcache.py, "How decode_step writes and reads"), every
  reader of the full table attending it after its one writer, as far as the
  slot's length (``kvcache.prefix_attention``: the slot's live blocks only,
  where the table admits the kernel). An idle lane (``position ==
  cache_len``) writes nothing in any group and reads nothing of the table.
- ``prefill_chunk`` / ``verify_step`` refuse: a recurrence has no page to
  resume from (``kvcache.require_pages`` stops the engine first).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.kvcache import Leaf
from distributed_tensorflow_tpu.ops import decode_attention


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    # the published config.json's keys (benchmarks/configs/phi4_mini_flash.json)
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    max_position: int = 262144  # context the config declares; no table of it
    layer_norm_eps: float = 1e-5
    # not in it: the Mamba family's defaults
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None  # ceil(hidden_size / 16)
    dtype: jnp.dtype = jnp.float32
    state_dtype: jnp.dtype = jnp.float32  # the scan state, whatever `dtype`

    def __post_init__(self):
        if self.hidden_size % self.num_heads or self.num_heads % 4 \
                or self.num_heads != 2 * self.num_kv_heads:
            raise ValueError(
                "differential grouped-query attention as published needs "
                "heads in fours, two query heads a K/V head, dividing "
                f"hidden_size: {self.num_heads}, {self.num_kv_heads}, "
                f"{self.hidden_size}"
            )
        if self.num_layers % 4 or self.mb_per_layer != 2:
            raise ValueError(
                "the layer rule is written for mb_per_layer 2 and "
                f"num_layers in fours, got {self.mb_per_layer}, "
                f"{self.num_layers}"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or math.ceil(self.hidden_size / 16)


def layer_kinds(cfg: SambaYConfig) -> tuple[str, ...]:
    """The mixer of each layer, by the published modeling file's rule."""
    half = cfg.num_layers // 2
    kinds = []
    for l in range(cfg.num_layers):
        if l % cfg.mb_per_layer == 0:
            kinds.append("mamba" if l <= half else "gmu")
        elif l < half:
            kinds.append("window")
        else:
            kinds.append("full" if l == half + 1 else "cross")
    return tuple(kinds)


def _init(std=0.02):
    return nn.initializers.normal(std)


def _dense(cfg: SambaYConfig, features: int, use_bias: bool = False, **kw):
    """``cfg.dtype`` into the MXU, float32 out of it: a projection rounds its
    input once and its result never. The residual stream, the gates and the
    recurrence stay float32 between projections (each bfloat16 rounding is
    0.2% and a layer had six: tests and PERF.md PR 35 hold the served logits
    against a float32 reference)."""
    return nn.Dense(
        features, use_bias=use_bias, dtype=cfg.dtype,
        kernel_init=kw.pop("kernel_init", _init()),
        dot_general=functools.partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32
        ),
        **kw,
    )


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # softplus(bias) log-uniform in [1e-3, 1e-1]: the family's time steps
    dt = jnp.exp(
        jax.random.uniform(key, shape) * (math.log(0.1) - math.log(1e-3))
        + math.log(1e-3)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    del key  # A = -(1 .. N) in every channel
    return jnp.log(
        jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)
    ).astype(dtype)


class Mamba(nn.Module):
    cfg: SambaYConfig

    def setup(self):
        cfg = self.cfg
        d_in, n, r = cfg.d_inner, cfg.d_state, cfg.rank
        self.in_proj = _dense(cfg, 2 * d_in)
        self.conv_kernel = self.param(
            "conv_kernel", _init(cfg.d_conv ** -0.5), (cfg.d_conv, d_in)
        )
        self.conv_bias = self.param(
            "conv_bias", nn.initializers.zeros_init(), (d_in,)
        )
        self.x_proj = _dense(cfg, r + 2 * n)
        self.dt_proj = _dense(
            cfg, d_in, use_bias=True, kernel_init=_init(r ** -0.5),
            bias_init=_dt_bias_init,
        )
        self.A_log = self.param("A_log", _a_log_init, (d_in, n))
        self.D = self.param("D", nn.initializers.ones_init(), (d_in,))
        self.out_proj = _dense(cfg, cfg.hidden_size)

    def _conv(self, window):
        """``silu(sum_k w_k * window[.., k, :] + b)``: ``window [.., d_conv,
        d_in]`` is an input with the ``d_conv - 1`` before it, oldest first."""
        w = self.conv_kernel.astype(jnp.float32)
        u = jnp.sum(window.astype(jnp.float32) * w, axis=-2)
        return jax.nn.silu(u + self.conv_bias)

    def _selective(self, u):
        """``(Delta, Delta * u, B, C)`` of conv'd inputs ``u [.., d_in]``, in
        float32: what one step of the recurrence reads."""
        n, r = self.cfg.d_state, self.cfg.rank
        dbc = self.x_proj(u)
        delta = jax.nn.softplus(self.dt_proj(dbc[..., :r]))
        return delta, delta * u, dbc[..., r:r + n], dbc[..., r + n:]

    def _a(self):
        return -jnp.exp(self.A_log.astype(jnp.float32)).T  # [N, d_in]

    @staticmethod
    def _advance(a, s, delta, du, b, c):
        """One step of ``s [B, N, d_in]``: returns ``(s', s' C)``."""
        s = jnp.exp(delta[:, None, :] * a) * s \
            + du[:, None, :] * b[:, :, None]
        return s, jnp.sum(s * c[:, :, None], axis=1)

    def _finish(self, y, u, z):
        """``(out, memory)``: the gated projection, and ``y`` before the
        gate, which layer ``half`` hands to the gated memory units."""
        y = y + self.D * u
        return self.out_proj(y * jax.nn.silu(z)), y

    def __call__(self, h, mask, lengths):
        """The prompt: ``h [B, L, d]``, ``mask [B, L]`` True on real tokens
        (left-aligned), ``lengths [B]``. Returns ``(out, memory, state)``,
        ``state = {"ssm" [B, N, d_in], "conv" [B, (d_conv - 1) * d_in]}`` as
        it stands after each row's last real token."""
        cfg = self.cfg
        k = cfg.d_conv
        u, z = jnp.split(self.in_proj(h), 2, axis=-1)
        u = u.astype(cfg.dtype)  # the conv's input, as the tail keeps it
        padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        l = u.shape[1]
        window = jnp.stack([padded[:, i:i + l] for i in range(k)], axis=2)
        # the k - 1 inputs before position `length`: the row's last real ones
        tail = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k - 1)
        )(padded, lengths)
        u = self._conv(window)
        delta, du, b, c = self._selective(u)
        delta = delta * mask[..., None]  # a pad leaves the state untouched
        du = du * mask[..., None]

        with jax.named_scope("ssm_scan"):
            a = self._a()
            s0 = jnp.zeros((u.shape[0], cfg.d_state, cfg.d_inner), jnp.float32)
            time_major = [jnp.swapaxes(x, 0, 1) for x in (delta, du, b, c)]
            s, y = jax.lax.scan(
                lambda s, xs: self._advance(a, s, *xs), s0, time_major,
                unroll=8,
            )
            y = jnp.swapaxes(y, 0, 1)
        out, memory = self._finish(y, u, z)
        return out, memory, {
            "ssm": s.astype(cfg.state_dtype),
            "conv": tail.reshape(tail.shape[0], -1),
        }

    def step(self, h, state, idle):
        """One token per slot: ``h [S, d]``, ``state`` this layer's ``{"ssm"
        [S, N, d_in], "conv" [S, (d_conv - 1) * d_in]}``. An idle lane keeps
        its state."""
        cfg = self.cfg
        with jax.named_scope("ssm_step"):
            u, z = jnp.split(self.in_proj(h), 2, axis=-1)
            tail = state["conv"].reshape(u.shape[0], cfg.d_conv - 1, -1)
            window = jnp.concatenate(
                [tail, u[:, None].astype(tail.dtype)], axis=1
            )  # the new input rounded as the tail will keep it
            u = self._conv(window)
            s, y = self._advance(
                self._a(), state["ssm"].astype(jnp.float32),
                *self._selective(u),
            )
            out, memory = self._finish(y, u, z)
            new = {
                "ssm": s.astype(state["ssm"].dtype),
                "conv": window[:, 1:].reshape(state["conv"].shape),
            }
            keep = lambda old, fresh: jnp.where(  # noqa: E731
                idle.reshape(-1, *(1,) * (old.ndim - 1)), old, fresh
            )
            return out, memory, jax.tree.map(keep, state, new)


class DiffAttention(nn.Module):
    """Differential grouped-query attention; ``kind`` is ``window``, ``full``
    or ``cross`` (a query only, over another layer's K and V)."""

    cfg: SambaYConfig
    layer: int
    kind: str

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        kv = 0 if self.kind == "cross" else 2 * cfg.num_kv_heads
        self.qkv = _dense(cfg, (cfg.num_heads + kv) * d, use_bias=True)
        lam = lambda name: self.param(name, _init(0.1), (d,))  # noqa: E731
        self.lambdas = [lam(f"lambda_{n}") for n in ("q1", "k1", "q2", "k2")]
        self.subln = self.param(
            "subln", nn.initializers.ones_init(), (2 * d,)
        )
        self.out = _dense(cfg, cfg.hidden_size, use_bias=True)
        self.lam_init = 0.8 - 0.6 * math.exp(-0.3 * self.layer)

    def _lam(self):
        q1, k1, q2, k2 = (v.astype(jnp.float32) for v in self.lambdas)
        return jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) \
            + self.lam_init

    def project(self, h):
        """``q [.., 2P, d]`` and, unless this is a cross layer, the merged
        rows ``{"k", "v"}`` each ``[.., 2G * d]`` as a cache holds them."""
        cfg = self.cfg
        d, n_q = cfg.head_dim, cfg.num_heads
        x = self.qkv(h).astype(cfg.dtype)  # into the MXU again, and the cache
        q = x[..., : n_q * d].reshape(*x.shape[:-1], n_q, d)
        if self.kind == "cross":
            return q, None
        k, v = jnp.split(x[..., n_q * d:], 2, axis=-1)
        return q, {"k": k, "v": v}

    def finish(self, o):
        """``o [.., P, 2d]`` float32, the pairs' differences: the pair norm,
        ``1 - lam_init``, the output projection."""
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.cfg.layer_norm_eps
        )
        o = o * self.subln * (1.0 - self.lam_init)
        return self.out(o.reshape(*o.shape[:-2], -1))

    def dense(self, q, kv, mask):
        """Every position of the prompt: ``q [B, L, 2P, d]``, ``kv`` rows
        ``[B, L, 2G * d]``, ``mask [B, L]``. Causal, and inside the window
        for a window layer: key ``j`` is seen by query ``i`` iff ``i - window
        < j <= i``."""
        cfg = self.cfg
        b, l, n_q, d = q.shape
        g = cfg.num_kv_heads // 2
        q = q.reshape(b, l, g, n_q // (2 * g), 2, d)  # [.., G, j, r, d]
        k = kv["k"].reshape(b, l, g, 2, d)
        v = kv["v"].reshape(b, l, g, 2 * d)
        s = jnp.einsum(
            "blgjrd,bkgrd->bgjrlk", q, k, preferred_element_type=jnp.float32
        ) * d ** -0.5
        i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
        seen = j <= i
        if self.kind == "window":
            seen &= j > i - cfg.sliding_window
        seen = seen & mask[:, None, None, None, None, :]
        s = jnp.where(seen, s, kvcache.MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1) * seen
        w = p[:, :, :, 0] - self._lam() * p[:, :, :, 1]  # [B, G, j, L, K]
        o = jnp.einsum(
            "bgjlk,bkgc->blgjc", w.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return self.finish(o.reshape(b, l, n_q // 2, 2 * d))

    def cached(self, q, table, valid):
        """One query a row over the rows ``valid [S, L]`` of a layer's ring
        or of a prompt's K/V ``[S, L, 2G * d]``."""
        return self.finish(
            kvcache.paired_attention(q, table, valid, self._lam())
        )

    def prefix(self, q, table, lengths):
        """One query a row over the first ``lengths [S]`` positions of a
        table ``[S, L, 2G * d]`` that already holds them."""
        return self.finish(
            kvcache.prefix_attention(q, table, lengths, self._lam())
        )


class GatedMemory(nn.Module):
    cfg: SambaYConfig

    def setup(self):
        self.in_proj = _dense(self.cfg, self.cfg.d_inner)
        self.out_proj = _dense(self.cfg, self.cfg.hidden_size)

    def __call__(self, h, memory):
        with jax.named_scope("gmu"):
            return self.out_proj(jax.nn.silu(self.in_proj(h)) * memory)


class SambaYLayer(nn.Module):
    cfg: SambaYConfig
    layer: int
    kind: str

    def setup(self):
        cfg = self.cfg
        ln = lambda: nn.LayerNorm(  # noqa: E731
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype
        )
        self.ln1, self.ln2 = ln(), ln()
        if self.kind == "mamba":
            self.mixer = Mamba(cfg)
        elif self.kind == "gmu":
            self.mixer = GatedMemory(cfg)
        else:
            self.mixer = DiffAttention(cfg, self.layer, self.kind)
        self.gate_up = _dense(cfg, 2 * cfg.intermediate_size)
        self.down = _dense(cfg, cfg.hidden_size)

    def finish(self, x, mixed):
        """The mixer's residual, then the gated MLP with its own."""
        x = x + mixed
        g, u = jnp.split(self.gate_up(self.ln2(x)), 2, axis=-1)
        return x + self.down(u * jax.nn.silu(g))


def _scope(kind: str) -> str:
    return "window_attention" if kind == "window" else "full_attention"


class SambaY(nn.Module):
    cfg: SambaYConfig

    def setup(self):
        cfg = self.cfg
        self.kinds = layer_kinds(cfg)
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=_init(),
            dtype=cfg.dtype,
        )
        self.layers = [
            SambaYLayer(cfg, l, kind, name=f"layer_{l}")
            for l, kind in enumerate(self.kinds)
        ]
        self.final_ln = nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=cfg.dtype
        )

    def _head(self, x):
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "...d,vd->...v", self.final_ln(x),
                self.embed.embedding.astype(self.cfg.dtype),
                preferred_element_type=jnp.float32,
            )

    def _embed(self, ids):
        return self.embed(ids).astype(jnp.float32)  # the residual stream

    def _prompt(self, input_ids, mask, lengths, upto: int):
        """Layers ``[0, upto)`` over every position. Returns ``(x, memory,
        full K/V rows, what the Mamba and window layers cache, layer by
        layer)``."""
        x = self._embed(input_ids)
        memory = full = None
        ssm, conv, ring = [], [], []
        for l, layer in enumerate(self.layers[:upto]):
            kind, h = self.kinds[l], layer.ln1(x)
            if kind == "mamba":
                mixed, memory, state = layer.mixer(h, mask, lengths)
                ssm.append(state["ssm"])
                conv.append(state["conv"])
            elif kind == "gmu":
                mixed = layer.mixer(h, memory)
            else:
                q, kv = layer.mixer.project(h)
                if kind == "window":
                    ring.append(_ring_rows(kv, lengths, self.cfg.sliding_window))
                elif kind == "full":
                    full = kv
                with jax.named_scope(_scope(kind)):
                    mixed = layer.mixer.dense(q, full if kv is None else kv, mask)
            x = layer.finish(x, mixed)
        return x, memory, full, ({"ssm": ssm, "conv": conv}, ring)

    def __call__(self, input_ids, attention_mask):
        lengths = jnp.sum(attention_mask, axis=1).astype(jnp.int32)
        x, *_ = self._prompt(
            input_ids, attention_mask, lengths, self.cfg.num_layers
        )
        return self._head(x)

    def prefill_rows(self, input_ids, attention_mask, lengths):
        half = self.cfg.num_layers // 2
        x, memory, full, (state, rings) = self._prompt(
            input_ids, attention_mask, lengths, half + 2
        )
        fresh = {
            "state": {name: jnp.stack(xs) for name, xs in state.items()},
            "window": kvcache.stack_layers(rings),
            "full": jax.tree.map(lambda a: a[None], full),
        }
        # The layers past `full` cache nothing, so only each row's last real
        # position goes through them: one query a row over the prompt's K/V.
        rows = jnp.arange(input_ids.shape[0])
        last = jnp.maximum(lengths, 1) - 1
        x, memory = x[rows, last], memory[rows, last]
        valid = jnp.arange(input_ids.shape[1]) <= last[:, None]
        for l, layer in enumerate(self.layers[half + 2:], half + 2):
            h = layer.ln1(x)
            if self.kinds[l] == "gmu":
                mixed = layer.mixer(h, memory)
            else:
                q, _ = layer.mixer.project(h)
                with jax.named_scope("full_attention"):
                    mixed = layer.mixer.cached(q, full, valid)
            x = layer.finish(x, mixed)
        return self._head(x), fresh

    def cache_layout(self, kv_dtype: str):
        cfg = self.cfg
        kinds = layer_kinds(cfg)

        def group(name, layers, after, leaves, **reads):
            return {
                key: Leaf(
                    shape, jnp.dtype(dtype), (None,) * len(shape),
                    layers=layers, after=after, group=name, **reads,
                )
                for key, (shape, dtype) in leaves.items()
            }

        lanes = cfg.num_kv_heads * cfg.head_dim
        row = ((lanes,), kv_dtype)
        return {
            "state": group("state", kinds.count("mamba"), None, {
                "ssm": ((cfg.d_state, cfg.d_inner), cfg.state_dtype),
                "conv": (((cfg.d_conv - 1) * cfg.d_inner,), cfg.dtype),
            }),
            "window": group(
                "window", kinds.count("window"), cfg.sliding_window,
                {"k": row, "v": row},
            ),
            "full": group(
                "full", 1, kvcache.POSITIONS, {"k": row, "v": row},
                prefix_readers=kinds.count("full") + kinds.count("cross"),
                prefix_block=decode_attention.block_for(
                    cfg.num_heads, cfg.head_dim, lanes
                ),
            ),
        }

    def decode_step(self, token, position, cache):
        state, window, full = cache["state"], cache["window"], cache["full"]
        cache_len, ring = full["k"].shape[2], window["k"].shape[2]
        idle = position >= cache_len
        position = jnp.minimum(position, cache_len - 1)
        ring_at = jnp.where(idle, ring, position % ring)  # ring: no row
        full_at = jnp.where(idle, cache_len, position)
        ring_seen = jnp.arange(ring) < jnp.minimum(position + 1, ring)[:, None]
        full_len = jnp.where(idle, 0, position + 1)  # an idle lane reads nothing

        x = self._embed(token)
        memory = table = None
        n_mamba, ring_rows = 0, []
        for l, layer in enumerate(self.layers):
            kind, h = self.kinds[l], layer.ln1(x)
            if kind == "mamba":
                # read from and written into the running table, layer after
                # layer: a chain the compiler updates in place (reading the
                # step's input while writing its output copies the table)
                mixed, memory, new = layer.mixer.step(
                    h, kvcache.take_layer(state, n_mamba), idle
                )
                state = kvcache.put_layer(state, n_mamba, new)
                n_mamba += 1
            elif kind == "gmu":
                mixed = layer.mixer(h, memory)
            else:
                q, kv = layer.mixer.project(h)
                if kind == "window":
                    # a ring is attended AS THE STEP FOUND IT with the new
                    # row selected in; the rings' rows go in once, below
                    rows = kvcache.encode(window, kv)
                    with jax.named_scope("window_attention"):
                        read = kvcache.select_rows(
                            kvcache.take_layer(window, len(ring_rows)),
                            rows, ring_at, slot_axis=0,
                        )
                        mixed = layer.mixer.cached(q, read, ring_seen)
                    ring_rows.append(rows)
                else:
                    if kind == "full":
                        # one writer, many readers: the row goes in first
                        # and every reader attends the table it is in, as
                        # far as the slot's length
                        rows = jax.tree.map(
                            lambda a: a[None], kvcache.encode(full, kv)
                        )
                        full = kvcache.write_rows(full, rows, full_at)
                        table = kvcache.take_layer(full, 0)
                    with jax.named_scope("full_attention"):
                        mixed = layer.mixer.prefix(q, table, full_len)
            x = layer.finish(x, mixed)
        window = kvcache.write_rows(
            window, kvcache.stack_layers(ring_rows), ring_at
        )
        return self._head(x), {"state": state, "window": window, "full": full}

    def prefill_chunk(self, input_ids, positions, cache):
        raise NotImplementedError(
            "SambaY has no chunked prefill: the scan state and the window "
            "rings are not pages a later chunk can resume from"
        )

    def verify_step(self, tokens, positions, cache):
        raise NotImplementedError(
            "SambaY has no speculative verify: a rejected draft cannot be "
            "rolled back out of the scan state or a ring"
        )


def _ring_rows(kv, lengths, window: int):
    """A prompt's K/V rows ``[B, L, c]`` as the rows of a ring of ``window``:
    position ``p`` lives at row ``p % window``, and of the positions that
    share a row the last real one stays. A prompt no longer than the ring is
    its own first rows."""
    l = kv["k"].shape[1]
    if l <= window:
        return kv
    row = jnp.arange(window)
    last = jnp.maximum(lengths, 1)[:, None] - 1  # [B, 1]
    at = row + window * ((last - row) // window)  # largest p <= last, p % W = row
    at = jnp.clip(at, 0, l - 1)  # a row no position has reached yet: unread
    return jax.tree.map(
        lambda a: jnp.take_along_axis(a, at[..., None], axis=1), kv
    )


def sambay_init_params(model: SambaY, key, dtype=None):
    """Random weights from ``key``; ``dtype`` casts every leaf (serving in
    bfloat16). Initialised over one short row: no parameter's shape depends
    on a length."""
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(key, ids, jnp.ones((1, 8), bool))["params"]
    if dtype is not None:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


__all__ = [
    "SambaY", "SambaYConfig", "layer_kinds", "sambay_init_params",
]
