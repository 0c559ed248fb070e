"""Operations and bytes an algorithm needs, computed from shapes.

The inventory of ``scripts/bench_bert.py`` (copied; its arithmetic is sound):
matmuls only, forward x 3 for a training step, recomputed work not counted.
Embedding lookups, LayerNorms and softmax are left out, which makes an MFU
from these numbers mildly conservative (the 6ND convention).
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def chip_peaks(device_kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in peaks or device_kind.startswith("_"):
        raise SystemExit(
            f"no peaks known for device_kind {device_kind!r}; add it to "
            f"benchmarks/peaks.json with its source"
        )
    return peaks[device_kind]


def encoder_fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward matmul FLOPs per token of a BERT-style stack: per layer QKVO
    8 d^2, FFN 4 d ff, attention 4 L d (QK^T and PV over the whole
    sequence); the MLM transform 2 d^2 and the tied decoder 2 d V, computed
    at every position as the model does."""
    d = cfg["hidden_size"]
    ff = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    per_layer = 8 * d * d + 4 * d * ff + 4 * seq_len * d
    return cfg["num_hidden_layers"] * per_layer + 2 * d * d + 2 * d * v


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3.0 * encoder_fwd_flops_per_token(cfg, seq_len)


def flash_attention_train_work(cfg: dict, seq_len: int, rows: int) -> dict:
    """FLOPs and HBM bytes of the flash kernels for one training step of
    ``rows`` sequences on one chip (``ops/flash_attention.py``: forward,
    dQ pass, dK/dV pass; bidirectional, so no causal saving).

    Per sequence and layer, with d = heads x head size and 2 FLOPs a
    multiply-add: forward QK^T + PV = 4 L^2 d; dQ pass recomputes S and
    forms dP and dQ = 6 L^2 d; dK/dV pass recomputes S and forms dV, dP, dK
    = 8 L^2 d. The lane-masked 'flat' layout does more MXU work than this;
    that is the kernel's cost, not the algorithm's, so it is not counted.

    Bytes: every operand read once and every result written once, bf16:
    forward reads q, k, v and writes o (4 tensors); dQ reads q, k, v, do and
    writes dq (5); dK/dV reads q, k, v, do and writes dk, dv (6) — 15
    tensors of L x d. The float32 logsumexp and delta rows (L x heads) are
    under 1% of that and left out."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    flops = 18.0 * seq_len * seq_len * d * rows * layers
    nbytes = 15.0 * seq_len * d * 2 * rows * layers
    return {"flops": flops, "bytes": nbytes}


KERNEL_WORK = {
    "flash_attention_train": flash_attention_train_work,
}
