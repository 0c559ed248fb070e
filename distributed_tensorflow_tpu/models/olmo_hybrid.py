"""Olmo-Hybrid decoder LM (``model_type: olmo_hybrid``): periods of three
gated delta-rule layers (arXiv:2412.06464) and one full-attention layer —
served through ``CausalLMEngine`` like models/causal_lm.py and
models/sambay.py, with a matrix state and four K/V tables side by side, and
prompts that enter a chunk at a time with the state as the carry.

No bias anywhere, no rotary (``rope_theta: null``: the recurrent layers carry
order). ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``. Every layer (the
family's reordered norm): ``h = x + RMS(Mixer(x))``, ``out = h + RMS(FFN(h))``,
``FFN(h) = W_down (silu(W_gate h) * W_up h)``. Token embedding unscaled, a
final ``RMS``, an untied head. The mixer of layer ``l`` (:func:`layer_kinds`,
the config's ``layer_types``):

- ``full_attention``: ``q = RMS(W_q x)``, ``k = RMS(W_k x)`` over the whole
  projection before it splits into heads, ``v = W_v x``; causal ``softmax(q
  k^T / sqrt(d)) v``; ``W_o``. Caches its own merged K row and V row at every
  position: a table a full layer, one reader each.
- ``linear_attention``, per head with ``d_k`` keys and ``d_v`` values: ``(q~,
  k~, v~, z) = split(W_in x)``; each channel of ``q~ | k~ | v~`` through a
  causal depthwise convolution of width 4 over time, then ``silu``; ``q <-
  q / |q| / sqrt(d_k)``, ``k <- k / |k|`` per head (``|x| = sqrt(sum x^2 +
  1e-6)``); ``beta_t = 2 sigmoid(W_b x_t)`` (the 2 is
  ``linear_allow_neg_eigval``); ``alpha_t = exp(-exp(A_log) * softplus(W_a x_t
  + dt_bias))``. The state ``S [d_k, d_v]`` a head, float32, zero at the
  start (the paper's ``S`` transposed: the value axis minor, so that the two
  reductions a step makes run down the sublanes):

      S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
      o_t = S_t^T q_t

  then ``o_t <- RMS(o_t) * silu(z_t)`` per head and ``y_t = W_o [o_t]``.
  Caches ``S`` and the last three inputs of the convolution (one row of ``3 x
  (2 d_k + d_v) x heads``).

The recurrence above is the definition (:meth:`DeltaMixer.step` is it, one
token a slot). A prompt takes it in chunks of ``delta_chunk`` positions
(:func:`delta_chunks`, the WY / UT-transform form of the paper): inside a
chunk matrix products and one unit-lower-triangular solve, between chunks the
state; a pad position has ``beta = 0``, ``alpha = 1`` and leaves ``S`` and the
conv tail untouched. tests/test_olmo_hybrid.py holds it to the recurrence.

Precision as models/sambay.py: ``cfg.dtype`` into the MXU and float32 out of
it for every projection and for the K/V tables; the residual stream, gates,
norms, the delta rule's own products and ``S`` float32.

Forwards (one param tree):

- ``__call__(input_ids, attention_mask) -> logits [B, L, V]`` — every
  position from zero state: scoring, and what the cached path is tested
  against.
- ``prefill_chunk(input_ids [T, C], positions [T, C], rows) -> (logits [T,
  V], rows')`` — a chunk of each row's prompt at absolute positions (the
  sentinel ``cache_len`` on a pad lane) against the rows' slots' cache: a row
  whose first lane is position 0 starts from zero state, any other from what
  its earlier chunk left. The logits are those of each row's LAST REAL lane
  (a head of 100,352 at every lane would be 0.8 TFLOP a chunk for one row of
  use).
- ``decode_step(token [S], position [S], cache) -> (logits [S, V], cache')``
  — one token a slot: state and tails updated in place, the new K/V rows as
  ``CausalLM`` writes them (models/kvcache.py, "How decode_step writes and
  reads"). An idle lane (``position == cache_len``) writes nothing in either
  group.

There is no ``prefill_rows``: the engine serves this model with
``prefill_chunk > 0``, and a prompt that fits one chunk is one chunk.
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

LINEAR, FULL = "linear_attention", "full_attention"
_EXACT = jax.lax.Precision.HIGHEST  # the delta rule's own products: float32


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    # the published config.json's keys (benchmarks/configs/olmo_hybrid_7b.json)
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 30  # full attention; num_key_value_heads is the same
    layer_types: tuple[str, ...] | None = None  # None: linear x 3, full x 1
    linear_num_heads: int = 30  # key heads and value heads alike
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position: int = 65536  # context the config declares; no table of it
    rms_norm_eps: float = 1e-6
    # positions the prompt's recurrence takes at a time (arXiv:2412.06464)
    delta_chunk: int = 64
    dtype: jnp.dtype = jnp.float32
    state_dtype: jnp.dtype = jnp.float32  # S, whatever `dtype`

    def __post_init__(self):
        if self.hidden_size % self.num_heads:
            raise ValueError(
                f"num_heads {self.num_heads} must divide hidden_size "
                f"{self.hidden_size}"
            )
        kinds = layer_kinds(self)
        if len(kinds) != self.num_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"{LINEAR!r} or {FULL!r}: {self.layer_types}"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def key_dim(self) -> int:
        return self.linear_num_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_heads * self.linear_value_head_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim  # q | k | v


def layer_kinds(cfg: OlmoHybridConfig) -> tuple[str, ...]:
    """The mixer of each layer: the config's ``layer_types`` (a model cut in
    depth keeps the first ``num_layers`` of the published list), or the
    published period."""
    if cfg.layer_types is not None:
        return tuple(cfg.layer_types)
    return tuple(
        FULL if l % 4 == 3 else LINEAR for l in range(cfg.num_layers)
    )


def _init(std=0.02):
    return nn.initializers.normal(std)


def _dense(cfg: OlmoHybridConfig, features: int):
    """``cfg.dtype`` into the MXU, float32 out of it, no bias
    (models/sambay.py::_dense has the reason)."""
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, kernel_init=_init(),
        dot_general=functools.partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32
        ),
    )


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(), x.shape[-1:])
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return x * scale


def _a_log_init(key, shape, dtype=jnp.float32):
    # A uniform in (0, 16): the gated delta rule's published initialisation
    return jnp.log(
        jax.random.uniform(key, shape, minval=1e-3, maxval=16.0)
    ).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # softplus(bias) log-uniform in [1e-3, 1e-1]: the family's time steps
    dt = jnp.exp(
        jax.random.uniform(key, shape) * (math.log(0.1) - math.log(1e-3))
        + math.log(1e-3)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


_SOLVE_BLOCK = 16  # rows that _unit_lower_inverse substitutes one by one


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a [.., C, C]``, float32.
    Forward substitution, a row at a time, inside the diagonal blocks of 16
    (exact products on the VPU; backward stable where a product of ``a``'s
    powers would cancel catastrophically: ``beta`` reaches 2 and a repeated
    key makes entries of 2), then neighbouring blocks merged pairwise by
    matrix products, ``[[X, 0], [-Y L X, Y]]``, up to ``C``. XLA's own
    triangular solve inverts the 64 x 64 block row by row, 63 dependent steps:
    1.2 ms a layer on the chip, 14.5 of a chunk's 54 ms (PERF.md, PR 37)."""
    c = a.shape[-1]
    lead = a.shape[:-2]
    size = _SOLVE_BLOCK
    if c % size or (c // size) & (c // size - 1):
        size = c  # not a power of two of blocks: one block, row by row
    nb = c // size
    blocks = a.reshape(*lead, nb, size, nb, size)
    diag = jnp.stack([blocks[..., b, :, b, :] for b in range(nb)], axis=-3)
    eye = jnp.eye(size, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], (*diag.shape[:-2], size))]
    for i in range(1, size):
        above = jnp.stack(rows, axis=-2)  # [.., nb, i, size]
        rows.append(
            eye[i] - jnp.sum(diag[..., i, :i, None] * above, axis=-2)
        )
    inv = jnp.stack(rows, axis=-2)  # [.., nb, size, size]
    while nb > 1:
        nb //= 2
        x, y = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        blocks = a.reshape(*lead, nb, 2, size, nb, 2, size)
        low = jnp.stack(
            [blocks[..., b, 1, :, b, 0, :] for b in range(nb)], axis=-3
        )
        corner = -jnp.einsum(
            "...ij,...jk,...kl->...il", y, low, x, precision=_EXACT
        )
        inv = jnp.concatenate([
            jnp.concatenate([x, jnp.zeros_like(x)], axis=-1),
            jnp.concatenate([corner, y], axis=-1),
        ], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def delta_chunks(q, k, v, g, beta, state, chunk: int):
    """The gated delta rule over ``L`` positions, ``chunk`` at a time. ``q, k
    [B, L, H, d_k]`` (normalised, ``q`` scaled), ``v [B, L, H, d_v]``, ``g [B,
    L, H]`` the log of ``alpha`` (``<= 0``), ``beta [B, L, H]``, ``state [B,
    H, d_k, d_v]``; all float32, ``L`` a multiple of ``chunk``. Returns ``(o
    [B, L, H, d_v], state')``. A position with ``beta = 0`` and ``g = 0``
    changes nothing and is read by nobody.

    With ``G_t`` the product of the chunk's ``alpha`` up to ``t``, the rows
    ``v'_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)`` that the recurrence adds
    to the state solve ``(I + A) V' = beta V - (beta k G) S_0``, ``A_tj =
    beta_t (k_t . k_j) G_t / G_j`` below the diagonal: one unit-lower-
    triangular solve a chunk gives ``u = (I + A)^-1 beta V`` and ``w = (I +
    A)^-1 (beta k G)`` for every chunk at once, and only ``V' = u - w S``,
    the output and the state's update walk the chunks in order."""
    b, length, h, d_k = q.shape
    d_v = v.shape[-1]
    n = length // chunk

    def chunks(x):  # [B, L, H, ..] -> [n, B, H, chunk, ..]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # log G_t, within the chunk
    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    # G_t / G_j where t >= j, else 0 (the exponent masked, not the result:
    # above the diagonal it is positive and may overflow)
    ratio = jnp.exp(
        jnp.where(i >= j, gc[..., :, None] - gc[..., None, :], -jnp.inf)
    )
    kb = k * beta[..., None]
    dot = functools.partial(jnp.einsum, precision=_EXACT)
    a = jnp.where(i > j, dot("...td,...jd->...tj", kb, k) * ratio, 0.0)
    solved = dot(
        "...tj,...jd->...td", _unit_lower_inverse(a),
        jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1
        ),
    )
    u, w = solved[..., :d_v], solved[..., d_v:]
    qk = dot("...td,...jd->...tj", q, k) * ratio  # t >= j
    q_in = q * jnp.exp(gc)[..., None]  # what a query reads of S_0
    last = gc[..., -1:]
    k_out = k * jnp.exp(last - gc)[..., None]  # what a row adds to S_end

    def one(s, xs):
        u_c, w_c, qk_c, q_c, k_c, decay = xs
        fresh = u_c - dot("...td,...dv->...tv", w_c, s)
        o = dot("...td,...dv->...tv", q_c, s) \
            + dot("...tj,...jv->...tv", qk_c, fresh)
        s = s * decay[..., None] \
            + dot("...td,...tv->...dv", k_c, fresh)
        return s, o

    state, o = jax.lax.scan(
        one, state, (u, w, qk, q_in, k_out, jnp.exp(last))
    )
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, n, chunk, H, d_v]
    return o.reshape(b, length, h, d_v), state


class DeltaMixer(nn.Module):
    cfg: OlmoHybridConfig

    def setup(self):
        cfg = self.cfg
        h = cfg.linear_num_heads
        self.in_proj = _dense(cfg, cfg.conv_channels + cfg.value_dim)
        self.ab_proj = _dense(cfg, 2 * h)  # a | b
        self.conv_kernel = self.param(
            "conv_kernel", _init(cfg.linear_conv_kernel_dim ** -0.5),
            (cfg.linear_conv_kernel_dim, cfg.conv_channels),
        )
        self.A_log = self.param("A_log", _a_log_init, (h,))
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        self.o_norm = RMSNorm(cfg.rms_norm_eps)
        self.out_proj = _dense(cfg, cfg.hidden_size)

    def _project(self, x):
        """``(q~ | k~ | v~ as the conv reads and the tail keeps them, z [..,
        H, d_v], g = log alpha [.., H], beta [.., H])`` of ``x [.., d]``."""
        cfg = self.cfg
        h = cfg.linear_num_heads
        y = self.in_proj(x)
        ab = self.ab_proj(x)
        g = -jnp.exp(self.A_log.astype(jnp.float32)) * jax.nn.softplus(
            ab[..., :h] + self.dt_bias.astype(jnp.float32)
        )
        beta = jax.nn.sigmoid(ab[..., h:])
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        z = y[..., cfg.conv_channels:]
        return (
            y[..., : cfg.conv_channels].astype(cfg.dtype),
            z.reshape(*z.shape[:-1], h, -1), g, beta,
        )

    def _conv(self, taps):
        """``silu(sum_i c_i * tap_i)`` of the convolution's ``d_conv`` inputs
        (oldest first, each ``[.., channels]``), split into ``q, k [.., H,
        d_k]`` (normalised, ``q`` scaled) and ``v [.., H, d_v]``."""
        cfg = self.cfg
        h, d_k = cfg.linear_num_heads, cfg.linear_key_head_dim
        with jax.named_scope("short_conv"):
            w = self.conv_kernel.astype(jnp.float32)
            x = jax.nn.silu(sum(
                tap.astype(jnp.float32) * w[i] for i, tap in enumerate(taps)
            ))
            heads = lambda a: a.reshape(*a.shape[:-1], h, -1)  # noqa: E731
            q = _unit(heads(x[..., : cfg.key_dim])) * d_k ** -0.5
            k = _unit(heads(x[..., cfg.key_dim: 2 * cfg.key_dim]))
            return q, k, heads(x[..., 2 * cfg.key_dim:])

    def _finish(self, o, z):
        """``o [.., H, d_v]``: the head's norm, the gate, ``W_o``."""
        o = self.o_norm(o) * jax.nn.silu(z)
        return self.out_proj(o.reshape(*o.shape[:-2], -1))

    def __call__(self, x, mask, state):
        """A chunk of a prompt: ``x [B, C, d]``, ``mask [B, C]`` True on real
        tokens (left-aligned), ``state`` this layer's ``{"ssm" [B, H, d_k,
        d_v], "conv" [B, (d_conv - 1) * channels]}`` as the row's earlier
        chunk left it (zeros before the first). Returns ``(out, state')``,
        the state as it stands after each row's last real token."""
        cfg = self.cfg
        taps = cfg.linear_conv_kernel_dim
        b, c = x.shape[:2]
        qkv, z, g, beta = self._project(x)
        tail = state["conv"].reshape(b, taps - 1, -1)
        padded = jnp.concatenate([tail, qkv], axis=1)
        q, k, v = self._conv([padded[:, i:i + c] for i in range(taps)])
        # the taps - 1 inputs before each row's first pad: its last real ones
        tail = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, taps - 1)
        )(padded, jnp.sum(mask, axis=1).astype(jnp.int32))
        # a pad leaves the state untouched
        g, beta = g * mask[..., None], beta * mask[..., None]
        with jax.named_scope("delta_chunk"):
            short = -c % cfg.delta_chunk  # the rule takes whole chunks

            def whole(a):
                return jnp.pad(a, ((0, 0), (0, short)) + ((0, 0),) * (a.ndim - 2))

            o, s = delta_chunks(
                *(whole(a) for a in (q, k, v, g, beta)),
                state["ssm"].astype(jnp.float32), cfg.delta_chunk,
            )
        return self._finish(o[:, :c], z), {
            "ssm": s.astype(state["ssm"].dtype),
            "conv": tail.reshape(state["conv"].shape),
        }

    def step(self, x, state, idle):
        """One token a slot: ``x [S, d]``, ``state`` as above with the slots
        leading. The recurrence as the module docstring defines it; both
        reductions over ``S_{t-1}`` are taken in one pass (``S_t^T q =
        alpha S^T q + (k . q) v'``). An idle lane keeps its state."""
        cfg = self.cfg
        taps = cfg.linear_conv_kernel_dim
        with jax.named_scope("delta_step"):
            qkv, z, g, beta = self._project(x)
            tail = state["conv"].reshape(x.shape[0], taps - 1, -1)
            window = jnp.concatenate([tail, qkv[:, None]], axis=1)
            q, k, v = self._conv([window[:, i] for i in range(taps)])
            s = state["ssm"].astype(jnp.float32)  # [S, H, d_k, d_v]
            alpha = jnp.exp(g)[..., None]
            sk = jnp.sum(s * k[..., None], axis=-2)
            sq = jnp.sum(s * q[..., None], axis=-2)
            fresh = beta[..., None] * (v - alpha * sk)
            o = alpha * sq + jnp.sum(k * q, axis=-1, keepdims=True) * fresh
            s = alpha[..., None] * s + k[..., None] * fresh[..., None, :]
            new = {
                "ssm": s.astype(state["ssm"].dtype),
                "conv": window[:, 1:].reshape(state["conv"].shape),
            }
            keep = lambda old, fresh: jnp.where(  # noqa: E731
                idle.reshape(-1, *(1,) * (old.ndim - 1)), old, fresh
            )
            return self._finish(o, z), jax.tree.map(keep, state, new)


class FullAttention(nn.Module):
    cfg: OlmoHybridConfig

    def setup(self):
        cfg = self.cfg
        self.qkv = _dense(cfg, 3 * cfg.hidden_size)  # q | k | v
        self.q_norm = RMSNorm(cfg.rms_norm_eps)
        self.k_norm = RMSNorm(cfg.rms_norm_eps)
        self.out = _dense(cfg, cfg.hidden_size)

    def project(self, x):
        """``q [.., h, d]`` and the merged rows ``{"k", "v"}`` each ``[.., h *
        d]`` as a cache holds them, all in ``cfg.dtype``."""
        cfg = self.cfg
        q, k, v = jnp.split(self.qkv(x), 3, axis=-1)
        q = self.q_norm(q).astype(cfg.dtype)
        return q.reshape(*q.shape[:-1], cfg.num_heads, -1), {
            "k": self.k_norm(k).astype(cfg.dtype), "v": v.astype(cfg.dtype),
        }

    def dense(self, q, kv, mask):
        """Every position of a whole sequence: ``q [B, L, h, d]``, ``kv``
        rows ``[B, L, h * d]``, ``mask [B, L]``; causal."""
        b, l, h, d = q.shape
        k, v = (kv[name].reshape(b, l, h, d) for name in ("k", "v"))
        s = jnp.einsum(
            "bihd,bjhd->bhij", q, k, preferred_element_type=jnp.float32
        ) * d ** -0.5
        seen = (jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]) \
            & mask[:, None, None, :]
        s = jnp.where(seen, s, kvcache.MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1) * seen
        o = jnp.einsum(
            "bhij,bjhd->bihd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return o.reshape(b, l, h * d)


class OlmoHybridLayer(nn.Module):
    cfg: OlmoHybridConfig
    kind: str

    def setup(self):
        cfg = self.cfg
        self.mixer = DeltaMixer(cfg) if self.kind == LINEAR \
            else FullAttention(cfg)
        self.mixer_norm = RMSNorm(cfg.rms_norm_eps)
        self.ffn_norm = RMSNorm(cfg.rms_norm_eps)
        self.gate_up = _dense(cfg, 2 * cfg.intermediate_size)  # gate | up
        self.down = _dense(cfg, cfg.hidden_size)

    def finish(self, x, mixed):
        """The mixer's residual, then the gated FFN with its own: both
        branches are normed on their way out."""
        h = x + self.mixer_norm(mixed)
        g, u = jnp.split(self.gate_up(h), 2, axis=-1)
        return h + self.ffn_norm(self.down(jax.nn.silu(g) * u))


class OlmoHybrid(nn.Module):
    cfg: OlmoHybridConfig

    def setup(self):
        cfg = self.cfg
        self.kinds = layer_kinds(cfg)
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=_init(),
            dtype=cfg.dtype,
        )
        self.layers = [
            OlmoHybridLayer(cfg, kind, name=f"layer_{l}")
            for l, kind in enumerate(self.kinds)
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

    def _zero_state(self, rows: int):
        cfg = self.cfg
        return {
            name: jnp.zeros((rows, *leaf.shape), leaf.dtype)
            for name, leaf in self.cache_layout(cfg.dtype)["state"].items()
        }

    def __call__(self, input_ids, attention_mask):
        x = self._embed(input_ids)
        zero = self._zero_state(input_ids.shape[0])
        for kind, layer in zip(self.kinds, self.layers):
            if kind == LINEAR:
                mixed, _ = layer.mixer(x, attention_mask, zero)
            else:
                q, kv = layer.mixer.project(x)
                with jax.named_scope("full_attention"):
                    mixed = layer.mixer.out(
                        layer.mixer.dense(q, kv, attention_mask)
                    )
            x = layer.finish(x, mixed)
        return self._head(x)

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

        row = ((cfg.hidden_size,), kv_dtype)
        return {
            "state": group("state", kinds.count(LINEAR), None, {
                "ssm": ((cfg.linear_num_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim), cfg.state_dtype),
                "conv": (((cfg.linear_conv_kernel_dim - 1)
                          * cfg.conv_channels,), cfg.dtype),
            }),
            "full": group(
                "full", kinds.count(FULL), kvcache.POSITIONS,
                {"k": row, "v": row},
                # each full layer's decode read stops at the slot's length
                # where kvcache.cached_attention takes the kernel
                prefix_readers=kinds.count(FULL),
                prefix_block=decode_attention.block_for(
                    cfg.num_heads, cfg.head_dim, cfg.hidden_size,
                    paired=False,
                ),
            ),
        }

    def prefill_chunk(self, input_ids, positions, cache):
        state, full = cache["state"], cache["full"]
        cache_len = kvcache.cache_len(full)
        mask = positions < cache_len
        # a row whose first lane is position 0 starts from nothing, whoever
        # held its slot before
        begins = positions[:, 0] == 0
        state = jax.tree.map(
            lambda a: jnp.where(
                begins.reshape(1, -1, *(1,) * (a.ndim - 2)), 0, a
            ).astype(a.dtype),
            state,
        )
        x = self._embed(input_ids)
        n_linear = n_full = 0
        for kind, layer in zip(self.kinds, self.layers):
            if kind == LINEAR:
                mixed, new = layer.mixer(
                    x, mask, kvcache.take_layer(state, n_linear)
                )
                state = kvcache.put_layer(state, n_linear, new)
                n_linear += 1
            else:
                q, kv = layer.mixer.project(x)
                table = kvcache.take_layer(full, n_full)
                table = kvcache.scatter_rows(
                    table, kvcache.encode(table, kv), positions
                )
                full = kvcache.put_layer(full, n_full, table)
                with jax.named_scope("full_attention"):
                    ctx = kvcache.chunk_attention(q, table, positions)
                    mixed = layer.mixer.out(ctx.reshape(*ctx.shape[:2], -1))
                n_full += 1
            x = layer.finish(x, mixed)
        # the head at each row's last real lane only
        last = jnp.maximum(jnp.sum(mask, axis=1), 1) - 1
        x = x[jnp.arange(x.shape[0]), last]
        return self._head(x), {"state": state, "full": full}

    def decode_step(self, token, position, cache):
        state, full = cache["state"], cache["full"]
        idle = position >= kvcache.cache_len(full)
        x = self._embed(token)
        n_linear, rows = 0, []
        for kind, layer in zip(self.kinds, self.layers):
            if kind == LINEAR:
                # read from and written into the running state, layer after
                # layer: a chain the compiler updates in place
                mixed, new = layer.mixer.step(
                    x, kvcache.take_layer(state, n_linear), idle
                )
                state = kvcache.put_layer(state, n_linear, new)
                n_linear += 1
            else:
                # the table AS THE STEP FOUND IT and the new row beside it;
                # the four layers' rows go in once, below
                q, kv = layer.mixer.project(x)
                row = kvcache.encode(full, kv)
                with jax.named_scope("full_attention"):
                    ctx = kvcache.cached_attention(
                        q, full, position, row, layer=len(rows)
                    )
                    mixed = layer.mixer.out(ctx.reshape(ctx.shape[0], -1))
                rows.append(row)
            x = layer.finish(x, mixed)
        full = kvcache.write_rows(full, kvcache.stack_layers(rows), position)
        return self._head(x), {"state": state, "full": full}


def olmo_hybrid_init_params(model: OlmoHybrid, key, dtype=None):
    """Random weights from ``key``; ``dtype`` casts every leaf (serving in
    bfloat16). Initialised over one short row: no parameter's shape depends
    on a length."""
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(key, ids, jnp.ones((1, 8), bool))["params"]
    if dtype is not None:
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    return params


__all__ = [
    "OlmoHybrid", "OlmoHybridConfig", "delta_chunks", "layer_kinds",
    "olmo_hybrid_init_params",
]
