"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

The hot op of the BERT workload (SURVEY.md §7 step 8: "Pallas kernels ...
attention for BERT if MFU < target"). Blockwise online-softmax attention:
O(L) memory instead of materializing the [L, L] score matrix in HBM, with
the K/V stream resident in VMEM and every matmul on the MXU.

Semantics match ``parallel.ring_attention.dense_attention`` exactly (same
layout ``[B, L, H, D]``, same key-padding-mask contract, f32 accumulation) —
the equivalence test in tests/test_flash_attention.py pins it. Ring
composition is implemented, not just possible: ``flash_attention_block``
returns (o, lse) per K/V block and ``ring_attention(..., inner="flash")``
merges the streamed blocks by logsumexp (ring = outer loop over ICI,
flash = inner loop over VMEM; tests/test_ring_attention.py pins the
composition against dense attention, gradients included).

Kernel structure (one (batch, head, q-block) program per grid point):
  fwd:  stream K/V blocks from VMEM, online softmax, save per-row logsumexp
  bwd:  dQ pass gridded over q-blocks; dK/dV pass gridded over k-blocks;
        both recompute P from the saved logsumexp (no [L,L] residual)

Two kernel families share that structure (``packing=`` selects; None=auto):
  "bh"   — operands transposed to [B*H, L, D] in HBM (4 relayouts per
           layer-direction, ~200 GB/s copies; measured 11.9 ms/step at the
           L=512 b=32 BERT config before r5).
  "flat" — r5: operands stay FLAT [B, L, H*D] (the layout the surrounding
           projections produce/consume — zero HBM relayouts); the kernel
           isolates heads by lane-masking aligned 128-lane tiles, which
           costs no extra MXU passes. Measured at BERT-base production
           geometry (b=32, L=512): fwd 0.862 -> 0.648 ms, fwd+bwd
           2.395 -> 1.780 ms per layer vs "bh". See the packed-section
           comment below for the masking identity and its constraints.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
# Base-2 softmax domain (r5): folding log2(e) into the score scale turns
# every VPU exp into the cheaper exp2 — measured 4% off the fwd kernel
# (1.043 -> 0.998 ms at bh=576, L=512) at |o| diff <= 1 bf16 ulp. The
# saved lse stays in NATURAL log (public contract for the ring merge);
# kernels convert at their boundaries.
_LOG2E = math.log2(math.e)
# Block defaults re-swept in r5 at the production geometry (bh=576, L=512,
# D=64 — the L=512 b=48 BERT config): bq = bk = 512 wins every kernel
# (fwd 1.145 -> 1.01 ms, dq 1.164 -> 0.894, dkv 1.639 -> 1.109 per layer;
# /tmp-sweep recorded in docs/PERF.md r5). At L <= 512 that means ONE
# whole-sequence tile per program — fewer programs, zero online-softmax
# rescale rounds; at longer L the q/k loops re-engage with 512-sized
# blocks (the r3 L=2048 sweep also preferred 512/512).
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 512


@contextlib.contextmanager
def _kernel_scope(name: str):
    """Names one of the three kernels (``flash_fwd``, ``flash_dq``,
    ``flash_dkv``) in its custom call's ``op_name``, so a profile tells them
    apart by more than their times. The TPU compiler names a Mosaic custom
    call after the innermost scope around it, so ``attention`` stays
    innermost: the instruction is ``%attention.N`` whatever module calls
    the kernel, which is what benchmarks/layer_metrics/kernel.flash_ms
    selects (tests/test_chip_compile.py pins both)."""
    with jax.named_scope(name), jax.named_scope("attention"):
        yield


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fit_block(default: int, l: int) -> int:
    """Largest block size <= default that divides l (lane-friendly steps).

    Ring/Ulysses shard lengths are not always powers of two (e.g. a ring
    shard of L_local = 384 fits 192-blocks): clamping to the default and
    demanding divisibility would reject valid geometries the einsum inner
    handles. Only multiple-of-8 blocks are accepted (the sublane floor);
    a length with no such divisor still raises — silently falling back to
    one l-sized block would defeat the blocking for large shards (a
    [l, l] f32 score tile in VMEM) instead of surfacing the geometry error.
    """
    b = min(default, l)
    if b >= 8 and b % 8 == 0 and l % b == 0:
        return b
    b -= b % 8
    while b >= 8 and l % b:
        b -= 8
    if b >= 8 and l % b == 0:
        return b
    raise ValueError(
        f"block length {l} has no multiple-of-8 divisor <= {default}; "
        "pad the shard or pick a different ring size"
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, block_k, scale):
    # q_ref: [BQ, D]; k_ref/v_ref: [L, D]; mask_ref: [1, L]; o: [BQ, D];
    # lse: [1, BQ]. One program per (b*h, q-block).
    #
    # MXU discipline: operands stay in their storage dtype (bf16) with f32
    # accumulation via preferred_element_type — casting inputs to f32 first
    # would force 8x-slower f32 systolic passes (the r2 kernel's mistake;
    # dense attention never paid it). P is cast back to the value dtype for
    # the PV matmul, exactly like the dense path's p.astype(v.dtype).
    bq, d = q_ref.shape
    l = k_ref.shape[0]
    q = q_ref[:]

    def body(j, carry):
        o, m, denom = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        # Scores land directly in the base-2 domain (scale * log2e folded).
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK] f32
        mask_blk = mask_ref[0, pl.ds(j * block_k, block_k)]
        s = jnp.where(mask_blk[None, :] != 0, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        p = p * mask_blk[None, :]
        corr = jnp.exp2(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        o = o * corr[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype),
            v_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o, m_new, denom

    o = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq,), _NEG, jnp.float32)
    denom = jnp.zeros((bq,), jnp.float32)
    o, m, denom = jax.lax.fori_loop(0, l // block_k, body, (o, m, denom))
    safe = jnp.maximum(denom, 1e-37)
    o_ref[:] = (o / safe[:, None]).astype(o_ref.dtype)
    # Natural-log logsumexp per query row (ln(denom * 2^m)); fully-masked
    # rows get _NEG (o stays 0).
    lse_ref[0, :] = jnp.where(denom > 0, m / _LOG2E + jnp.log(safe), _NEG)


def _fwd(q, k, v, mask, block_q, block_k, interpret):
    bh, l, d = q.shape
    scale = d**-0.5
    grid = (bh, l // block_q)
    kernel = functools.partial(_fwd_kernel, block_k=block_k, scale=scale)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, l, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, l), jnp.float32),
        ],
        interpret=interpret,
    )
    with _kernel_scope("flash_fwd"):
        o, lse = call(q, k, v, mask)
    return o, lse.reshape(bh, l)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dq_ref, *, block_k, scale
):
    bq, d = q_ref.shape
    l = k_ref.shape[0]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]

    def body(j, dq):
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        mask_blk = mask_ref[0, pl.ds(j * block_k, block_k)]
        # P recomputed in the base-2 domain (see _fwd_kernel); the natural-
        # domain derivative ds = p * (dp - delta) is unchanged.
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # Mask in the SCALED domain: a fully-masked row carries lse = _NEG
        # (natural log), so the recompute must cancel _NEG * _LOG2E against
        # _NEG * _LOG2E exactly — masking with plain _NEG would make the
        # difference +4e29 and exp2 of it inf (NaN after the mask multiply).
        s = jnp.where(mask_blk[None, :] != 0, s, _NEG * _LOG2E)
        p = jnp.exp2(s - (_LOG2E * lse)[:, None]) * mask_blk[None, :]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype),
            k_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jnp.zeros((bq, d), jnp.float32)
    dq = jax.lax.fori_loop(0, l // block_k, body, dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q, scale,
):
    bk, d = k_ref.shape
    l = q_ref.shape[0]
    k = k_ref[:]
    v = v_ref[:]
    j = pl.program_id(1)
    mask_blk = mask_ref[0, pl.ds(j * bk, bk)]

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q)]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q)]
        s = (scale * _LOG2E) * jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK] base-2 domain (see _fwd_kernel)
        # Scaled-domain mask value — see _bwd_dq_kernel.
        s = jnp.where(mask_blk[None, :] != 0, s, _NEG * _LOG2E)
        p = jnp.exp2(s - (_LOG2E * lse_blk)[:, None]) * mask_blk[None, :]
        p_lo = p.astype(do_blk.dtype)
        dv = dv + jax.lax.dot_general(
            p_lo, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_blk[:, None])
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype),
            q_blk,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, l // block_q, body, (dk, dv))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_impl(block_q, block_k, interpret, residuals, do, dlse=None):
    """Shared backward: flash-attention kernels over saved (q, k, v, lse).

    ``dlse`` (the logsumexp cotangent, used by the ring-composable block op
    whose lse output feeds the cross-block merge) folds into the delta term:
    dL/ds_ij = p_ij (dp_ij - delta_i) + p_ij dlse_i, so passing
    delta' = delta - dlse to the unchanged kernels is the exact extension.
    """
    q, k, v, mask, o, lse = residuals
    bh, l, d = q.shape
    scale = d**-0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [bh,l]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = delta.reshape(bh, 1, l)
    lse3 = lse.reshape(bh, 1, l)

    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, scale=scale),
        grid=(bh, l // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, l, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, d), q.dtype),
        interpret=interpret,
    )
    with _kernel_scope("flash_dq"):
        dq = dq_call(q, k, v, mask, do, lse3, delta)

    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, scale=scale),
        grid=(bh, l // block_k),
        in_specs=[
            pl.BlockSpec((None, l, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, 1, l), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, l, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, d), k.dtype),
            jax.ShapeDtypeStruct((bh, l, d), v.dtype),
        ],
        interpret=interpret,
    )
    with _kernel_scope("flash_dkv"):
        dk, dv = dkv_call(q, k, v, mask, do, lse3, delta)
    return dq, dk, dv, None


def _bwd(block_q, block_k, interpret, residuals, g):
    return _bwd_impl(block_q, block_k, interpret, residuals, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, mask, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, mask, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, mask, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, mask, block_q, block_k, interpret)
    return o, (q, k, v, mask, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_block(q, k, v, mask, block_q, block_k, interpret):
    return _fwd(q, k, v, mask, block_q, block_k, interpret)


def _flash_block_fwd(q, k, v, mask, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, mask, block_q, block_k, interpret)
    return (o, lse), (q, k, v, mask, o, lse)


def _flash_block_bwd(block_q, block_k, interpret, residuals, g):
    do, dlse = g
    return _bwd_impl(block_q, block_k, interpret, residuals, do, dlse)


_flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


# ---------------------------------------------------------------------------
# Packed (layout-native) kernels — r5
# ---------------------------------------------------------------------------
#
# The bh-major kernels above require [B*H, L, D], which costs four HBM
# relayouts per layer-direction ([B,L,H,D] <-> [B,H,L,D] for q/k/v in and
# o out, again in backward) — measured 11.9 ms/step at the shipped L=512
# b=32 BERT config, ~200 GB/s copies the bucket table files under "other"
# (docs/PERF.md r5). A head-minor BlockSpec ((1, bq, H, D)) was built and
# rejected: (H=12, D=64) minor dims violate the (8,128) tile rule and
# Mosaic pads 12->16 x 64->128 on every operand.
#
# This variant threads the needle: operands stay FLAT [B, L, H*D] — the
# exact layout the surrounding projections produce and consume, and
# (8,128)-clean since H*D = 768. Heads are separated WITHOUT lane slicing
# (Mosaic also rejects sub-128 lane-offset loads: "cannot statically prove
# that index in dimension 2 is a multiple of 128" — measured this round):
# the kernel loads aligned 128-lane tiles holding 128/D heads each and
# isolates head h by LANE MASKING the q (resp. do/ds) operand before the
# matmul. Because MXU contraction and output tiles are 128 wide, a masked
# 128-wide matmul costs exactly the same systolic passes as the bh
# kernels' 64-wide one — the mask just zeroes the cross-head terms:
#   (q * mask_h) @ k_tile^T == q_h @ k_h^T            (contraction side)
#   (p_h @ v_tile) * mask_h == p_h @ v_h  in h's lanes (output side)
# so the per-head math is exactly the bh kernels'; only the addressing
# changed. VMEM per program: q/k/v/o blocks at bq = bk = L = 512 total
# ~4 MB of the ~16 MB budget. The lse contract also improves: the kernel
# writes [B, H, L] natural-log lse directly (what the ring merge wants).


def _lane_masks(d: int, dtype):
    """Per-head lane masks for one 128-lane tile holding 128//d heads."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return [
        ((lane >= e * d) & (lane < (e + 1) * d)).astype(dtype)
        for e in range(128 // d)
    ]


def _fwd_kernel_packed(
    q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *, block_k, scale, heads
):
    # q_ref: [BQ, HD]; k_ref/v_ref: [L, HD]; mask_ref: [1, L];
    # o_ref: [BQ, HD]; lse_ref: FULL [H, L] (each program writes its
    # q-range — an L-sized lane slice per q-block would break the
    # 128-lane rule for small blocks). One program per (batch, q-block).
    bq, hd = q_ref.shape
    l = k_ref.shape[0]
    d = hd // heads
    hpt = 128 // d  # heads per 128-lane tile
    qi = pl.program_id(1)
    for t in range(hd // 128):
        q_t = q_ref[:, 128 * t : 128 * (t + 1)]
        msks = _lane_masks(d, q_t.dtype)
        q_heads = [q_t * msks[e] for e in range(hpt)]

        def body(j, carry, t=t, q_heads=q_heads):
            k_t = k_ref[pl.ds(j * block_k, block_k), 128 * t : 128 * (t + 1)]
            v_t = v_ref[pl.ds(j * block_k, block_k), 128 * t : 128 * (t + 1)]
            mask_blk = mask_ref[0, pl.ds(j * block_k, block_k)]
            out = []
            for e in range(hpt):
                o, m, denom = carry[e]
                # Contraction over all 128 lanes of the masked q is
                # exactly q_h @ k_h^T: the mask zeroes other heads' terms.
                s = (scale * _LOG2E) * jax.lax.dot_general(
                    q_heads[e], k_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s = jnp.where(mask_blk[None, :] != 0, s, _NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp2(s - m_new[:, None])
                p = p * mask_blk[None, :]
                corr = jnp.exp2(m - m_new)
                denom = denom * corr + jnp.sum(p, axis=-1)
                # p @ v_tile: head h's lanes carry p_h @ v_h; other heads'
                # lanes carry garbage that the write-combine masks off.
                o = o * corr[:, None] + jax.lax.dot_general(
                    p.astype(v_t.dtype),
                    v_t,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                out.append((o, m_new, denom))
            return tuple(out)

        init = tuple(
            (
                jnp.zeros((bq, 128), jnp.float32),
                jnp.full((bq,), _NEG, jnp.float32),
                jnp.zeros((bq,), jnp.float32),
            )
            for _ in range(hpt)
        )
        carry = jax.lax.fori_loop(0, l // block_k, body, init)
        o_tile = jnp.zeros((bq, 128), jnp.float32)
        for e in range(hpt):
            o, m, denom = carry[e]
            safe = jnp.maximum(denom, 1e-37)
            o_tile = o_tile + (o / safe[:, None]) * msks[e].astype(jnp.float32)
            lse_ref[t * hpt + e, pl.ds(qi * bq, bq)] = jnp.where(
                denom > 0, m / _LOG2E + jnp.log(safe), _NEG
            )
        o_ref[:, 128 * t : 128 * (t + 1)] = o_tile.astype(o_ref.dtype)


def _fwd_packed(q, k, v, mask, heads, block_q, block_k, interpret):
    b, l, hd = q.shape
    scale = (hd // heads) ** -0.5
    call = pl.pallas_call(
        functools.partial(
            _fwd_kernel_packed, block_k=block_k, scale=scale, heads=heads
        ),
        grid=(b, l // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, l, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, heads, l), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, hd), q.dtype),
            jax.ShapeDtypeStruct((b, heads, l), jnp.float32),
        ],
        interpret=interpret,
    )
    with _kernel_scope("flash_fwd"):
        o, lse = call(q, k, v, mask)
    return o, lse


def _bwd_dq_kernel_packed(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, block_k, scale, heads,
):
    # q/do/dq: [BQ, HD]; k/v: [L, HD]; mask: [1, L]; lse/delta: FULL [H, L]
    # (sliced per program — see _fwd_kernel_packed).
    bq, hd = q_ref.shape
    l = k_ref.shape[0]
    d = hd // heads
    hpt = 128 // d
    qi = pl.program_id(1)
    for t in range(hd // 128):
        q_t = q_ref[:, 128 * t : 128 * (t + 1)]
        do_t = do_ref[:, 128 * t : 128 * (t + 1)]
        msks = _lane_masks(d, q_t.dtype)
        q_heads = [q_t * msks[e] for e in range(hpt)]
        do_heads = [do_t * msks[e] for e in range(hpt)]
        lses = [
            lse_ref[t * hpt + e, pl.ds(qi * bq, bq)] for e in range(hpt)
        ]
        deltas = [
            delta_ref[t * hpt + e, pl.ds(qi * bq, bq)] for e in range(hpt)
        ]

        def body(j, dqs, t=t, q_heads=q_heads, do_heads=do_heads,
                 lses=lses, deltas=deltas):
            k_t = k_ref[pl.ds(j * block_k, block_k), 128 * t : 128 * (t + 1)]
            v_t = v_ref[pl.ds(j * block_k, block_k), 128 * t : 128 * (t + 1)]
            mask_blk = mask_ref[0, pl.ds(j * block_k, block_k)]
            out = []
            for e in range(hpt):
                s = (scale * _LOG2E) * jax.lax.dot_general(
                    q_heads[e], k_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                # Scaled-domain mask value — see _bwd_dq_kernel.
                s = jnp.where(mask_blk[None, :] != 0, s, _NEG * _LOG2E)
                p = (
                    jnp.exp2(s - (_LOG2E * lses[e])[:, None])
                    * mask_blk[None, :]
                )
                dp = jax.lax.dot_general(
                    do_heads[e], v_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - deltas[e][:, None])
                # ds @ k_tile: head h's lanes carry ds_h @ k_h; the
                # write-combine below masks the rest.
                out.append(
                    dqs[e]
                    + jax.lax.dot_general(
                        ds.astype(k_t.dtype),
                        k_t,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )
            return tuple(out)

        init = tuple(jnp.zeros((bq, 128), jnp.float32) for _ in range(hpt))
        dqs = jax.lax.fori_loop(0, l // block_k, body, init)
        dq_tile = jnp.zeros((bq, 128), jnp.float32)
        for e in range(hpt):
            dq_tile = dq_tile + dqs[e] * msks[e].astype(jnp.float32)
        dq_ref[:, 128 * t : 128 * (t + 1)] = (dq_tile * scale).astype(
            dq_ref.dtype
        )


def _bwd_dkv_kernel_packed(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q, scale, heads,
):
    # k/v/dk/dv: [BK, HD]; q/do: [L, HD]; mask/lse/delta: FULL [1|H, L].
    bk, hd = k_ref.shape
    l = q_ref.shape[0]
    d = hd // heads
    hpt = 128 // d
    kj = pl.program_id(1)
    mask_blk = mask_ref[0, pl.ds(kj * bk, bk)]
    for t in range(hd // 128):
        k_t = k_ref[:, 128 * t : 128 * (t + 1)]
        v_t = v_ref[:, 128 * t : 128 * (t + 1)]
        msks = _lane_masks(d, k_t.dtype)

        def body(i, carry, t=t, k_t=k_t, v_t=v_t, msks=msks):
            q_blk = q_ref[pl.ds(i * block_q, block_q), 128 * t : 128 * (t + 1)]
            do_blk = do_ref[
                pl.ds(i * block_q, block_q), 128 * t : 128 * (t + 1)
            ]
            out = []
            for e in range(hpt):
                dk, dv = carry[e]
                lse_blk = lse_ref[t * hpt + e, pl.ds(i * block_q, block_q)]
                delta_blk = delta_ref[
                    t * hpt + e, pl.ds(i * block_q, block_q)
                ]
                q_h = q_blk * msks[e]
                s = (scale * _LOG2E) * jax.lax.dot_general(
                    q_h, k_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s = jnp.where(mask_blk[None, :] != 0, s, _NEG * _LOG2E)
                p = (
                    jnp.exp2(s - (_LOG2E * lse_blk)[:, None])
                    * mask_blk[None, :]
                )
                p_lo = p.astype(do_blk.dtype)
                # p^T @ do_tile: head h's lanes carry p_h^T @ do_h
                # (garbage elsewhere, masked in the write-combine).
                dv = dv + jax.lax.dot_general(
                    p_lo, do_blk, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dp = jax.lax.dot_general(
                    do_blk * msks[e], v_t, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = p * (dp - delta_blk[:, None])
                dk = dk + jax.lax.dot_general(
                    ds.astype(q_blk.dtype),
                    q_blk,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                out.append((dk, dv))
            return tuple(out)

        init = tuple(
            (
                jnp.zeros((bk, 128), jnp.float32),
                jnp.zeros((bk, 128), jnp.float32),
            )
            for _ in range(hpt)
        )
        carry = jax.lax.fori_loop(0, l // block_q, body, init)
        dk_tile = jnp.zeros((bk, 128), jnp.float32)
        dv_tile = jnp.zeros((bk, 128), jnp.float32)
        for e in range(hpt):
            dk, dv = carry[e]
            f32m = msks[e].astype(jnp.float32)
            dk_tile = dk_tile + dk * f32m
            dv_tile = dv_tile + dv * f32m
        dk_ref[:, 128 * t : 128 * (t + 1)] = (dk_tile * scale).astype(
            dk_ref.dtype
        )
        dv_ref[:, 128 * t : 128 * (t + 1)] = dv_tile.astype(dv_ref.dtype)


def _bwd_impl_packed(heads, block_q, block_k, interpret, residuals, do, dlse=None):
    """Packed backward. ``dlse`` folds into delta exactly as in _bwd_impl."""
    q, k, v, mask, o, lse = residuals  # lse: [B, H, L]
    b, l, hd = q.shape
    d = hd // heads
    scale = d**-0.5
    # Per-head delta_i = sum_d do*o — [B, L, H] reduce, then head-major.
    delta = (
        (do.astype(jnp.float32) * o.astype(jnp.float32))
        .reshape(b, l, heads, d)
        .sum(axis=-1)
        .transpose(0, 2, 1)
    )  # [B, H, L] — small (B*H*L f32), the transpose is noise next to qkv
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    dq_call = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel_packed, block_k=block_k, scale=scale, heads=heads
        ),
        grid=(b, l // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, l, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, l, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, heads, l), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, heads, l), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, l, hd), q.dtype),
        interpret=interpret,
    )
    with _kernel_scope("flash_dq"):
        dq = dq_call(q, k, v, mask, do, lse, delta)

    dkv_call = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel_packed, block_q=block_q, scale=scale, heads=heads
        ),
        grid=(b, l // block_k),
        in_specs=[
            pl.BlockSpec((None, l, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, 1, l), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, l, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, heads, l), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, heads, l), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, hd), k.dtype),
            jax.ShapeDtypeStruct((b, l, hd), v.dtype),
        ],
        interpret=interpret,
    )
    with _kernel_scope("flash_dkv"):
        dk, dv = dkv_call(q, k, v, mask, do, lse, delta)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_packed(q, k, v, mask, heads, block_q, block_k, interpret):
    o, _ = _fwd_packed(q, k, v, mask, heads, block_q, block_k, interpret)
    return o


def _flash_packed_fwd(q, k, v, mask, heads, block_q, block_k, interpret):
    o, lse = _fwd_packed(q, k, v, mask, heads, block_q, block_k, interpret)
    return o, (q, k, v, mask, o, lse)


def _flash_packed_bwd(heads, block_q, block_k, interpret, residuals, g):
    return _bwd_impl_packed(heads, block_q, block_k, interpret, residuals, g)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_block_packed(q, k, v, mask, heads, block_q, block_k, interpret):
    return _fwd_packed(q, k, v, mask, heads, block_q, block_k, interpret)


def _flash_block_packed_fwd(q, k, v, mask, heads, block_q, block_k, interpret):
    o, lse = _fwd_packed(q, k, v, mask, heads, block_q, block_k, interpret)
    return (o, lse), (q, k, v, mask, o, lse)


def _flash_block_packed_bwd(heads, block_q, block_k, interpret, residuals, g):
    do, dlse = g
    return _bwd_impl_packed(
        heads, block_q, block_k, interpret, residuals, do, dlse
    )


_flash_block_packed.defvjp(_flash_block_packed_fwd, _flash_block_packed_bwd)


def _packing_ok(h: int, d: int) -> bool:
    """Packed-path geometry: whole heads must tile 128-lane groups — D a
    divisor of 128 (64 for BERT-base: two heads per tile) and H*D a
    multiple of 128. Covers tp shards with an even local head count
    (12, 6, 4, 2 heads at D=64); odd shards (tp=4 -> 3 heads, 192 lanes)
    fall back to the bh kernels."""
    return d <= 128 and 128 % d == 0 and (h * d) % 128 == 0


def _flat_vmem_est(l, hd, block_q, block_k, esize=2) -> int:
    """Rough VMEM bytes for one packed-kernel program: the K/V streams stay
    RESIDENT at full [L, H*D] (double-buffered by Mosaic) — 12x the bh
    kernels' per-head residency, which is what caps the packed path's L."""
    kv = 2 * 2 * l * hd * esize          # k + v, double-buffered
    blocks = 3 * block_q * hd * esize     # q/o/do-class blocks
    scores = block_q * block_k * 4        # one f32 score tile
    carries = 6 * block_q * 128 * 4       # per-tile o/m/denom f32 carries
    return kv + blocks + scores + carries


# Measured on this chip: l=2048 hd=768 blows the 16 MB scoped-vmem budget
# (Mosaic: 18.21M requested); l <= 1024 at hd=768 fits. 14 MB keeps margin.
_FLAT_VMEM_LIMIT = 14 * 1024 * 1024


def _flat_auto(h, d, block_q, block_k, interpret, l=0, esize=2) -> bool:
    # Compiled-mode lane slices (lse/delta/mask at block offsets) need
    # 128-aligned blocks; interpret mode has no such constraint. ``esize``
    # is the operand element size — f32 K/V streams are twice the bf16
    # residency, so the auto rule must see the real dtype or it selects
    # 'flat' at geometries that blow the scoped-vmem budget.
    if not _packing_ok(h, d):
        return False
    if interpret:
        return True
    if block_q % 128 or block_k % 128:
        return False
    return _flat_vmem_est(l, h * d, block_q, block_k, esize) <= _FLAT_VMEM_LIMIT


def _require_flat(h, d, block_q, block_k, interpret, l=0, esize=2) -> None:
    """Loud guard for EXPLICIT packing="flat": an unsupported geometry must
    not reach the kernels — the head loop covers only hd//128 lane tiles, so
    e.g. H*D=192 leaves lanes 128-191 unread and returns garbage (silently
    in interpret mode; as an opaque Mosaic internal error compiled)."""
    if not _packing_ok(h, d):
        raise ValueError(
            f"packing='flat' needs whole heads tiling 128-lane groups "
            f"(D | 128 and H*D % 128 == 0); got H={h}, D={d}. "
            "Use packing='bh' or None (auto)."
        )
    if not interpret and (block_q % 128 or block_k % 128):
        raise ValueError(
            f"packing='flat' compiled for TPU needs 128-aligned blocks "
            f"(lane-slice rule); got block_q={block_q}, block_k={block_k}. "
            "Use packing='bh' or None (auto)."
        )
    if not interpret and (
        _flat_vmem_est(l, h * d, block_q, block_k, esize) > _FLAT_VMEM_LIMIT
    ):
        raise ValueError(
            f"packing='flat' keeps K/V resident at [L={l}, H*D={h * d}] in "
            f"VMEM — past the ~16 MB budget at this geometry (est "
            f"{_flat_vmem_est(l, h * d, block_q, block_k, esize) >> 20} MB). "
            "Use packing='bh' or None (auto)."
        )


def flash_attention_block(
    q,
    k,
    v,
    mask=None,
    *,
    block_q: int = _DEFAULT_BLOCK_Q,
    block_k: int = _DEFAULT_BLOCK_K,
    interpret: bool | None = None,
    packing: str | None = None,
):
    """One flash block with its logsumexp: the ring's inner step.

    Layout ``[B, L, H, D]`` like :func:`flash_attention`, but L must already
    be a multiple of both blocks (ring shards are) and the return is
    ``(o [B, L, H, D], lse [B, H, L])`` — block-normalized output plus the
    per-row logsumexp, which parallel/ring_attention.py uses to merge blocks
    exactly (numerically stable weighted combine). Differentiable in both
    outputs (the lse cotangent rides the same backward kernels).

    ``packing``: ``"flat"`` (layout-native packed kernels, the r5 default
    where head geometry allows — see module comment), ``"bh"`` (the
    transpose-into-[B*H, L, D] kernels), or None for the auto rule.
    """
    if interpret is None:
        interpret = _use_interpret()
    b, l, h, d = q.shape
    # The ring streams fixed-length shards — no padding allowed here, so fit
    # the blocks to the shard length instead (largest divisor <= default).
    block_q = _fit_block(block_q, l)
    block_k = _fit_block(block_k, l)
    if mask is None:
        mask = jnp.ones((b, l), bool)
    if packing is None:
        packing = (
            "flat"
            if _flat_auto(
                h, d, block_q, block_k, interpret, l, q.dtype.itemsize
            )
            else "bh"
        )
    elif packing == "flat":
        _require_flat(h, d, block_q, block_k, interpret, l, q.dtype.itemsize)

    if packing == "flat":
        mask_f = mask.astype(jnp.float32).reshape(b, 1, l)
        o, lse = _flash_block_packed(
            q.reshape(b, l, h * d),
            k.reshape(b, l, h * d),
            v.reshape(b, l, h * d),
            mask_f,
            h,
            block_q,
            block_k,
            interpret,
        )
        return o.reshape(b, l, h, d), lse

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)

    mask_bh = jnp.repeat(mask.astype(jnp.float32), h, axis=0).reshape(b * h, 1, l)
    o, lse = _flash_block(
        to_bh(q), to_bh(k), to_bh(v), mask_bh, block_q, block_k, interpret
    )
    o = o.reshape(b, h, l, d).transpose(0, 2, 1, 3)
    return o, lse.reshape(b, h, l)


def flash_attention(
    q,
    k,
    v,
    mask=None,
    *,
    block_q: int = _DEFAULT_BLOCK_Q,
    block_k: int = _DEFAULT_BLOCK_K,
    interpret: bool | None = None,
    packing: str | None = None,
):
    """Exact attention, flash-style. Layout ``[B, L, H, D]``, mask ``[B, L]``.

    Pads L up to a block multiple internally (padded keys masked out, padded
    query rows sliced off). ``interpret=None`` auto-selects interpreter mode
    off-TPU so tests run on CPU. ``packing`` as in
    :func:`flash_attention_block` (None = auto: layout-native packed kernels
    when the head geometry is lane-aligned, else the bh-major kernels).
    """
    if interpret is None:
        interpret = _use_interpret()
    b, l, h, d = q.shape
    block_q = min(block_q, max(l, 8))
    block_k = min(block_k, max(l, 8))
    # Pad to a common multiple of BOTH blocks: padding to only the larger one
    # leaves trailing q rows outside the grid (uninitialized output).
    step = math.lcm(block_q, block_k)
    l_pad = -(-l // step) * step
    if mask is None:
        mask = jnp.ones((b, l), bool)
    if l_pad != l:
        pad = ((0, 0), (0, l_pad - l), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        mask = jnp.pad(mask, ((0, 0), (0, l_pad - l)))
    if packing is None:
        packing = (
            "flat"
            if _flat_auto(
                h, d, block_q, block_k, interpret, l_pad, q.dtype.itemsize
            )
            else "bh"
        )
    elif packing == "flat":
        _require_flat(
            h, d, block_q, block_k, interpret, l_pad, q.dtype.itemsize
        )

    if packing == "flat":
        mask_f = mask.astype(jnp.float32).reshape(b, 1, l_pad)
        o = _flash_packed(
            q.reshape(b, l_pad, h * d),
            k.reshape(b, l_pad, h * d),
            v.reshape(b, l_pad, h * d),
            mask_f,
            h,
            block_q,
            block_k,
            interpret,
        )
        return o.reshape(b, l_pad, h, d)[:, :l]

    # [B, L, H, D] -> [B*H, L, D]
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, l_pad, d)

    qh, kh, vh = to_bh(q), to_bh(k), to_bh(v)
    mask_bh = jnp.repeat(mask.astype(jnp.float32), h, axis=0).reshape(
        b * h, 1, l_pad
    )
    o = _flash(qh, kh, vh, mask_bh, block_q, block_k, interpret)
    o = o.reshape(b, h, l_pad, d).transpose(0, 2, 1, 3)
    return o[:, :l]
