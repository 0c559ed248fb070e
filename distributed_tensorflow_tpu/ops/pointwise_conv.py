"""1x1 convolution with Pallas backward kernels — the ResNet-50 hot path.

Why this exists (r3 perf frontier, VERDICT r2 Missing #1): the
r3 trace of the b=128 ResNet-50 step (docs/PERF.md) shows XLA:TPU's
*backward* machinery for 1x1 convolutions running at 8–25 TF/s and
~80–160 GB/s — 4–5x below this chip's measured ~650 GB/s streaming bandwidth
(scripts/roofline.py), 16.7 ms of dgrad + 11.2 ms of wgrad in a 46.4 ms step.
The r2 attempt to express these as ``jnp.dot`` failed because XLA
canonicalizes spatial-reshape dots back into convolution HLO (docs/PERF.md
"dead ends").  A ``jax.custom_vjp`` whose backward calls Pallas kernels is
opaque to that canonicalization: the dgrad and wgrad become plain tiled
matmuls on the MXU with streaming-bound traffic.

The forward stays ``jnp.dot`` on purpose: the trace shows XLA's fused
BN+ReLU→1x1-conv forward already saturates bandwidth (~650 GB/s), and keeping
it in XLA-land lets the preceding BatchNorm/ReLU keep fusing into the conv's
input read — a Pallas forward would force that producer chain to materialize.

Math (x2: [M, K] = flattened [H*W*B, Cin], w: [K, N]):
    fwd:    y  = x2 @ w                      (XLA)
    dgrad:  dx = g @ w^T     — Pallas when K >= 128, else XLA
    wgrad:  dw = x2^T @ g    — XLA (jnp.dot; canonicalized to conv-wgrad)

Selectivity is measured, not guessed (standalone kernel duels vs the
in-step XLA times from the same trace, b=128):

    shape (M, K, N)        XLA dgrad   Pallas dgrad     XLA wgrad  Pallas
    401408, 256,  64        1.2-1.5 ms   0.32 ms (810GB/s)  0.34    0.44
    401408,  64, 256        0.6-0.7      0.96 (263GB/s!)    0.55    0.93
    100352, 512, 128        0.5-0.7      0.16 (825)         0.21    0.15
    100352, 128, 512        0.35         0.13 (1021)        0.17    0.23
     25088,1024, 256        ~0.3         0.10 (665)         —       0.12

Pallas dgrad wins 3-5x whenever the output's minor dim K >= 128; at K=64
Mosaic's half-empty lanes lose to XLA, so those convs keep the XLA path.
Pallas wgrad never beats XLA's in-step fused wgrad convincingly, so the
custom bwd computes dw as a plain dot and lets XLA canonicalize it into
exactly the conv-wgrad it runs today.

Reference parity: this replaces the reference's cuDNN-backed 1x1 conv
layers inside its ResNet-50 allreduce workload (SURVEY.md §2 "ResNet-50 /
ImageNet workload" row); semantics are bit-identical to
``nn.Conv(features, (1,1))`` up to f32-accumulation rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block working sets stay < ~4 MB each so double-buffered pipelines fit VMEM
# comfortably (v5e); 1024 caps the M tile, K/N are never tiled (<= 2048 for
# every 1x1 in ResNet-50/Inception).
_MAX_TILE_M = 1024
_MAX_KN = 4096


def _tile_m(m: int) -> int | None:
    """Largest multiple-of-16 divisor of m, capped at _MAX_TILE_M."""
    for t in range(min(_MAX_TILE_M, m), 15, -16):
        if t % 16 == 0 and m % t == 0:
            return t
    return None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dgrad_kernel(g_ref, w_ref, o_ref):
    # dx[TM, K] = g[TM, N] @ w[K, N]^T, contracted on N without an explicit
    # transpose (Mosaic handles the transposed operand internally).
    o_ref[:] = jax.lax.dot_general(
        g_ref[:],
        w_ref[:],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _wgrad_kernel(x_ref, g_ref, o_ref):
    part = jax.lax.dot_general(
        x_ref[:],
        g_ref[:],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[:] = part

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        o_ref[:] = o_ref[:] + part


def _dgrad_pallas(g, w, *, interpret: bool):
    m, n = g.shape
    k = w.shape[0]
    tm = _tile_m(m)
    return pl.pallas_call(
        _dgrad_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, k), g.dtype),
        interpret=interpret,
    )(g, w)


def _wgrad_pallas(x2, g, *, interpret: bool):
    m, k = x2.shape
    n = g.shape[1]
    tm = _tile_m(m)
    return pl.pallas_call(
        _wgrad_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((k, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(x2, g)


def _supported(m: int, k: int, n: int) -> bool:
    # Both channel dims >= 128: (a) K = 64 dgrad output leaves half of every
    # 128-lane register empty and measures slower than XLA; (b) any C = 64
    # activation gets XLA's B-minor layout {0,3,2,1}, so the H,W,B,C flatten
    # at the Pallas boundary materializes a relayout copy instead of a
    # bitcast — the copy tax exceeds the kernel win (measured step-level).
    return (
        _tile_m(m) is not None and 128 <= k <= _MAX_KN and 128 <= n <= _MAX_KN
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pw_matmul(x2, w, interpret):
    return jnp.dot(x2, w)


def _pw_fwd(x2, w, interpret):
    return jnp.dot(x2, w), (x2, w)


def _pw_bwd(interpret, res, g):
    x2, w = res
    dx = _dgrad_pallas(g, w, interpret=interpret)
    # wgrad deliberately stays in XLA-land: the plain dot is canonicalized
    # into the same fused conv-wgrad XLA runs for nn.Conv, which beats the
    # Pallas split-K kernel at these shapes (module docstring table).
    dw = jax.lax.dot_general(
        x2, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_pw_matmul.defvjp(_pw_fwd, _pw_bwd)


def pointwise_matmul(x2: jax.Array, w: jax.Array) -> jax.Array:
    """``x2 @ w`` with Pallas dgrad/wgrad when shapes allow, else plain dot.

    x2: [M, K]; w: [K, N].  Off-TPU the Pallas kernels run in interpreter
    mode so CPU tests exercise the identical code path.
    """
    m, k = x2.shape
    n = w.shape[1]
    if not _supported(m, k, n):
        return jnp.dot(x2, w)
    return _pw_matmul(x2, w, not _on_tpu())


# ---------------------------------------------------------------------------
# Layout-native dgrad for N=64 outputs (stage-1 Conv_0: the worst op class)
# ---------------------------------------------------------------------------
#
# A 64-channel activation gets XLA:TPU layout {0,3,2,1} — physically
# (H, W, C, B) with B in the lanes — so the generic [M, C] flattening
# materializes a relayout at the Pallas boundary. This path instead bitcasts
# the cotangent to its native [H*W, C, B] view and contracts C in-kernel
# (Mosaic handles the sublane contraction), emitting dx in the [H*W, B, K]
# view that bitcasts straight into the consumer's {3,0,2,1} layout.
# Standalone: 0.28-0.31 ms at b=128 stage-1 geometry vs XLA's 1.24-1.51 ms
# (840-922 GB/s vs ~150). In-step it STILL nets negative (51.9 vs 48.4
# ms/step with only this path enabled) — the BN-backward reductions and
# relu masks that ride XLA's dgrad fusions cost more as standalone passes
# than the kernel saves. Third integration strategy, same verdict: only a
# kernel that absorbs the fused epilogue work can win (docs/PERF.md r3).


def _dgrad_n64_kernel(g_ref, wt_ref, o_ref):
    # g: [thw, C, B]; wt: [C, K]; o: [thw, B, K] — contraction over C.
    o_ref[:] = jax.lax.dot_general(
        g_ref[:],
        wt_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _dgrad_n64(g4, w, *, interpret: bool):
    """dx4 [B,H,W,K] from g4 [B,H,W,64] via the native-layout views."""
    b, h, w_, n = g4.shape
    k = w.shape[0]
    hw = h * w_
    thw = next((t for t in (112, 56, 16, 8, 4, 2, 1) if hw % t == 0))
    gv = g4.transpose(1, 2, 3, 0).reshape(hw, n, b)
    dxv = pl.pallas_call(
        _dgrad_n64_kernel,
        grid=(hw // thw,),
        in_specs=[
            pl.BlockSpec((thw, n, b), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((thw, b, k), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((hw, b, k), g4.dtype),
        interpret=interpret,
    )(gv, jnp.swapaxes(w, 0, 1))  # w [K, N] -> wt [N, K]
    return dxv.reshape(h, w_, b, k).transpose(2, 0, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pw4d_n64(x4, w, interpret):
    b, h, w_, k = x4.shape
    return jnp.dot(x4.reshape(b * h * w_, k), w).reshape(b, h, w_, w.shape[1])


def _pw4d_n64_fwd(x4, w, interpret):
    return _pw4d_n64(x4, w, interpret), (x4, w)


def _pw4d_n64_bwd(interpret, res, g4):
    x4, w = res
    dx4 = _dgrad_n64(g4, w, interpret=interpret)
    # wgrad stays in XLA-land (canonicalized into its fused conv-wgrad).
    dw = jax.lax.dot_general(
        x4.reshape(-1, x4.shape[-1]),
        g4.reshape(-1, g4.shape[-1]),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dx4, dw.astype(w.dtype)


_pw4d_n64.defvjp(_pw4d_n64_fwd, _pw4d_n64_bwd)


def pointwise_conv_n64(x4: jax.Array, kernel2: jax.Array) -> jax.Array:
    """1x1 conv to 64 features with the layout-native Pallas dgrad."""
    return _pw4d_n64(x4, kernel2, not _on_tpu())


def pointwise_conv(x: jax.Array, kernel: jax.Array, strides: int = 1) -> jax.Array:
    """NHWC 1x1 convolution with Pallas backward.

    x: [B, H, W, Cin]; kernel: [1, 1, Cin, Cout] (or [Cin, Cout]).  A strided
    1x1 conv reads only the top-left pixel of each window, so stride-s is a
    spatial slice before the matmul (its VJP scatters zeros back — cheap
    relative to the dgrad it replaces).
    """
    if kernel.ndim == 4:
        kernel = kernel[0, 0]
    if strides > 1:
        x = x[:, ::strides, ::strides, :]
    b, h, w_, cin = x.shape
    y = pointwise_matmul(x.reshape(b * h * w_, cin), kernel)
    return y.reshape(b, h, w_, kernel.shape[1])
