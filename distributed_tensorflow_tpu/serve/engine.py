"""Inference engines: checkpoint-loaded, mesh-sharded, AOT-compiled forwards.

Design (the serving half of the training engine's "one trace, one
executable" rule): every forward an engine will ever run is lowered and
compiled at STARTUP — one executable per (batch tier x sequence bucket)
for BERT, one per (batch tier x image geometry) for the classifiers — so
no user request ever pays a trace or an XLA compile. Requests of
arbitrary length pad up to the smallest bucket that fits
(``BertInferenceEngine.buckets``, default {128, 256, 512} clamped to the
model's ``max_position``); partial batches pad with inert rows to the
SMALLEST batch tier that holds them (``batch_tiers``, default {1, 2, 4, 8}
clamped to ``max_batch``), so a lone request runs a 1-row executable
instead of paying a full ``max_batch``-row forward.

The request path is split ``assemble -> dispatch -> fetch``: ``dispatch``
stages host buffers (drawn from a reusable pool) into the right
executable and returns an :class:`InFlightBatch` of device refs WITHOUT
blocking; ``fetch`` is the only point that calls ``jax.device_get``. The
batcher exploits the split to pipeline batch k+1's host assembly against
batch k's device compute (``max_in_flight``). ``run_batch`` remains the
blocking composition of the two for direct callers.

Placement mirrors training: on a DP-only mesh params live replicated (the
serving analog of ``place_state``); on a mesh with ``model`` / ``expert`` /
``pipeline`` axes the BERT engine shards them with the SAME
``bert_param_specs`` contract training uses, and every executable in the
grid becomes a ``shard_map`` of the forward over those bound axes —
Megatron TP attention/FFN, replicated-dispatch expert-parallel MoE, and
the GPipe schedule all reuse the train-side module code unchanged. The
grid is therefore (batch tier x bucket x mesh layout): one engine serves
one layout (``layout_label``), and the layout rides every dispatch into
the metrics. Batches shard their leading dim over the data axes when the
tier divides the DP width and fall back to replicated otherwise — a 7-row
flush must degrade to redundant compute, never to a shape error.

Checkpoints come from training via :func:`ckpt.restore_serving_state`: the
template TrainState rebuilds the training structure and carries the TARGET
layout's shardings, so tensorstore reads each shard straight into place —
no single-device staging round-trip.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models import kvcache
from distributed_tensorflow_tpu.models.causal_lm import sample_tokens
from distributed_tensorflow_tpu.models.quant import (
    cast_params,
    dequantize_params,
    fp32_equiv_nbytes,
    is_quantized_tree,
    normalize_quant_dtype,
    quantize_params,
)
from distributed_tensorflow_tpu.obs.memory import default_registry, tree_nbytes
from distributed_tensorflow_tpu.parallel.mesh import (
    batch_sharding,
    build_mesh,
    data_axes,
    layout_label,
    replicated_sharding,
)

logger = logging.getLogger(__name__)


class RequestError(ValueError):
    """A malformed or un-servable request (maps to HTTP 400, not 500)."""


def plan_serve_mesh(
    tp: int = 1,
    pp: int = 1,
    ep: int = 1,
    n_devices: int | None = None,
) -> tuple[dict, bool]:
    """Serving-mesh spec for the requested model parallelism, with graceful
    degradation: returns ``(spec, fell_back)``.

    The model axes need ``tp * pp * ep`` devices and the remainder goes to
    data parallelism, so the product must divide the device count. When it
    does not (dev box with fewer chips than the production flags assume),
    serving falls back to single-chip-per-replica DP with a warning —
    a wrong-sized ``--tp`` must degrade to slower serving, never die in an
    XLA shape error at startup.
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    need = max(tp, 1) * max(pp, 1) * max(ep, 1)
    if need <= 1:
        return {"data": -1}, False
    if need > n_devices or n_devices % need:
        logger.warning(
            "requested serving mesh (tp=%d pp=%d ep=%d) needs %d devices "
            "to divide the %d available; falling back to single-chip "
            "data-parallel serving",
            tp, pp, ep, need, n_devices,
        )
        return {"data": -1}, True
    spec = {"data": -1}
    if pp > 1:
        spec["pipeline"] = pp
    if ep > 1:
        spec["expert"] = ep
    if tp > 1:
        spec["model"] = tp
    return spec, False


def _batch_sharding_or_replicated(mesh, max_batch: int):
    """Shard the batch dim over the DP axes when the fixed batch divides the
    DP width; otherwise serve replicated (small-batch engines on wide
    meshes must work, just without the speedup)."""
    n = math.prod(mesh.shape[a] for a in data_axes(mesh)) if data_axes(mesh) else 1
    if n > 1 and max_batch % n == 0:
        return batch_sharding(mesh)
    if n > 1:
        logger.info(
            "serve batch %d not divisible by %d-way DP mesh; "
            "replicating inference batches", max_batch, n,
        )
    return replicated_sharding(mesh)


def _normalize_tiers(tiers, max_batch: int) -> tuple[int, ...]:
    """Clamp the tier ladder to ``max_batch`` and guarantee a full-batch
    rung — the grid must always hold a ``max_batch``-row flush."""
    tiers = tuple(tiers) if tiers else (1, 2, 4, 8)
    t = {min(int(x), max_batch) for x in tiers if int(x) >= 1}
    t.add(max_batch)
    return tuple(sorted(t))


@dataclasses.dataclass
class InFlightBatch:
    """A dispatched-but-unfetched batch: device refs + host bookkeeping.

    ``out`` holds un-materialized device arrays (dispatch is async); the
    staging buffers ride along so ``fetch`` can return them to the pool
    once the transfer out is complete.
    """

    out: dict
    key: tuple          # (tier, bucket) executable key
    n: int              # real rows (the rest of the tier is padding)
    meta: list          # per-row bookkeeping (e.g. unpadded lengths)
    buffers: tuple      # host staging arrays to recycle on fetch
    # Mesh layout the batch was dispatched on (``out`` holds refs sharded
    # per that layout); the batcher keys per-layout phase histograms on it.
    layout: str = ""
    # Phase-boundary stamps (time.monotonic) the batcher turns into the
    # per-request breakdown: host staging buffers filled (ends the
    # batch_assemble phase) / jax.device_get returned (ends device).
    t_assembled: float = 0.0
    t_got: float = 0.0
    # What a decode step writes into the cache, by group
    # (kvcache.step_writes): a row per layer and leaf for each active lane,
    # the bytes of positionless state (an idle lane writes nothing) — and
    # what it reads of a group whose readers stop at the lane's length
    # (kvcache.step_reads): blocks read, and blocks there are — or, of a
    # group read whole, the positions live and passed over
    # (kvcache.step_positions); what the model counts a live lane for (its
    # ``decode_counters``); and whether
    # the step's inputs went up from the host (``inputs_uploaded``, 0 or 1).
    moved: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CompileRecord:
    """One AOT grid-cell compile: what it was, what it cost, whether it
    landed. ``size_bytes`` is the executable's generated-code size where
    the backend's ``memory_analysis()`` reports it, else None."""

    key: str
    seconds: float
    size_bytes: int | None = None
    ok: bool = True
    error: str | None = None


class _AotEngine:
    """Shared AOT plumbing: compile-per-shape at startup, place-and-call.

    Subclasses provide ``dispatch``/``fetch``; this base owns the tier
    ladder, per-tier batch shardings, the staging-buffer pool, and the
    per-dispatch metrics recording (``self.metrics`` is wired by
    :class:`serve.server.Client`; it stays ``None`` for bare engines).

    Every grid-cell compile routes through :meth:`_compile_cell`, which
    times it into a :class:`CompileRecord`; :meth:`grid_status` aggregates
    the records into the ``GET /compilez`` digest and the warm fraction
    the warmup-gated readiness contract reads. Large device residencies
    (params, KV caches, staging buffers) register with ``self.memory`` —
    the process-wide :class:`~..obs.memory.MemoryRegistry` unless a caller
    injects its own — so ``GET /memz`` accounts this engine's footprint.
    """

    # Grid records and the staging pool are written by worker threads and
    # read by HTTP handlers; _grid_lock / _buf_lock order every access.
    _RACETRACE_ATTRS = ("_buf_pool", "_compile_records", "_cells_planned")

    def __init__(self, mesh, max_batch: int, batch_tiers=None, memory=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.mesh = mesh if mesh is not None else build_mesh({"data": -1})
        self.layout = layout_label(self.mesh)
        self.max_batch = max_batch
        self.batch_tiers = _normalize_tiers(batch_tiers, max_batch)
        self.metrics = None
        self.memory = memory if memory is not None else default_registry()
        self._param_sharding = replicated_sharding(self.mesh)
        self._tier_sharding = {
            t: _batch_sharding_or_replicated(self.mesh, t)
            for t in self.batch_tiers
        }
        self._buf_lock = threading.Lock()
        self._buf_pool: dict[tuple, list[tuple]] = {}
        self._grid_lock = threading.Lock()
        self._compile_records: list[CompileRecord] = []
        self._cells_planned = 0

    # -- AOT grid observability ----------------------------------------

    def _plan_cells(self, n: int) -> None:
        """Announce ``n`` upcoming grid cells BEFORE compiling them, so a
        mid-warmup ``grid_status`` reports a warm fraction < 1 instead of
        pretending the cells it has not seen yet do not exist."""
        with self._grid_lock:
            self._cells_planned += int(n)

    def _compile_cell(self, key: str, build):
        """Run one grid-cell compile (``build`` returns the Compiled
        object), recording wall time, executable size, and failure. A
        failed compile records then re-raises — startup still dies loudly,
        but the record survives into any dump a wrapper takes."""
        t0 = time.monotonic()
        try:
            exe = build()
        except Exception as e:
            with self._grid_lock:
                self._compile_records.append(CompileRecord(
                    key=key, seconds=time.monotonic() - t0, ok=False,
                    error=f"{type(e).__name__}: {e}",
                ))
            raise
        seconds = time.monotonic() - t0
        size = None
        try:
            ma = exe.memory_analysis()
            size = int(getattr(ma, "generated_code_size_in_bytes", 0)) or None
        except Exception:  # noqa: BLE001 — size is best-effort per backend
            size = None
        with self._grid_lock:
            self._compile_records.append(
                CompileRecord(key=key, seconds=seconds, size_bytes=size)
            )
        return exe

    def grid_status(self) -> dict:
        """The ``GET /compilez`` digest: cell counts, cumulative compile
        seconds, warm fraction, the coldest (most expensive) cell, and the
        full per-cell record list."""
        with self._grid_lock:
            records = list(self._compile_records)
            planned = self._cells_planned
        compiled = sum(1 for r in records if r.ok)
        failed = len(records) - compiled
        total = max(planned, len(records))
        ok_records = [r for r in records if r.ok]
        coldest = max(ok_records, key=lambda r: r.seconds, default=None)
        return {
            "cells_total": total,
            "cells_compiled": compiled,
            "cells_failed": failed,
            "compile_seconds_total": sum(r.seconds for r in records),
            "warm_fraction": (compiled / total) if total else 1.0,
            "coldest_cell": (
                {"key": coldest.key, "seconds": coldest.seconds}
                if coldest is not None else None
            ),
            "cells": [dataclasses.asdict(r) for r in records],
        }

    def tier_for(self, n: int) -> int:
        """Smallest compiled batch tier holding ``n`` rows."""
        for t in self.batch_tiers:
            if n <= t:
                return t
        raise ValueError(
            f"batch of {n} exceeds max_batch {self.max_batch}"
        )

    def _place(self, tree):
        return jax.device_put(tree, self._param_sharding)

    def _struct(self, shape, dtype, tier: int):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=self._tier_sharding[tier]
        )

    def _put(self, x, tier: int):
        return jax.device_put(x, self._tier_sharding[tier])

    def _take_buffers(self, key: tuple, make) -> tuple:
        """Pop a staging-buffer set for ``key`` or allocate a fresh one.
        Buffers return to the pool in ``fetch`` (after ``device_get``, when
        reuse provably cannot race the transfer in)."""
        with self._buf_lock:
            pool = self._buf_pool.get(key)
            if pool:
                return pool.pop()
        buffers = make()
        # Fresh allocation: grow the staging-buffer reservation. Outside
        # _buf_lock — the registry has its own lock and must never nest.
        self.memory.add("staging_buffers", tree_nbytes(buffers))
        return buffers

    def _give_buffers(self, key: tuple, buffers: tuple) -> None:
        with self._buf_lock:
            self._buf_pool.setdefault(key, []).append(buffers)

    def mesh_info(self) -> dict:
        """Mesh topology digest (``GET /statusz``): which layout this engine
        serves, the axis sizes behind it, and the chips one batch spans."""
        return {
            "layout": self.layout,
            "mesh_shape": dict(self.mesh.shape),
            "devices_per_engine": int(self.mesh.size),
            "platform": self.mesh.devices.flat[0].platform,
        }

    def _record_dispatch(self, tier: int, bucket, n: int) -> None:
        m = self.metrics
        if m is None:
            return
        m.tier_hits.inc(tier)
        m.layout_tier_hits.inc(f"{self.layout}/{tier}")
        if bucket is not None:
            m.bucket_hits.inc(bucket)
            m.layout_bucket_hits.inc(f"{self.layout}/{bucket}")
        m.tier_occupancy.observe(tier, n)
        m.padded_rows.inc(tier - n)

    # -- blocking compatibility surface --------------------------------

    def run_batch(self, payloads: list[dict]) -> list[dict]:
        """Blocking execute: ``fetch(dispatch(payloads))``."""
        return self.fetch(self.dispatch(payloads))


def _make_bert_forward(model, return_logits: bool):
    """The serving forward for one model variant (closure, not a method:
    per-tier pipeline variants each need their own)."""

    def forward(params, input_ids, attention_mask, token_type_ids,
                mlm_targets):
        # Int8 weight mode: unpack {"_q8","_q8_scale"} kernels in-graph —
        # HBM holds int8; XLA fuses the convert into each matmul read.
        params = dequantize_params(params, model.cfg.dtype)
        mlm_logits, nsp_logits, pooled = model.apply(
            {"params": params},
            input_ids,
            attention_mask,
            token_type_ids,
            method="serve_outputs",
        )
        # Per-ROW MLM statistics, f32 on the fly from the storage dtype —
        # the same masking/clamp recipe as the training loss (_mlm_stats),
        # but without the cross-row reduction: serving scores examples.
        weights = (mlm_targets >= 0).astype(jnp.float32)
        m = jnp.max(mlm_logits, axis=-1, keepdims=True)
        shifted = mlm_logits.astype(jnp.float32) - m.astype(jnp.float32)
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(
            jnp.float32
        )
        tgt_logit = jnp.take_along_axis(
            mlm_logits, jnp.maximum(mlm_targets, 0)[..., None], axis=-1
        )[..., 0].astype(jnp.float32)
        ce = (lse - tgt_logit) * weights
        out = {
            "pred_ids": jnp.argmax(mlm_logits, axis=-1).astype(jnp.int32),
            "nll": jnp.sum(ce, axis=-1),
            "count": jnp.sum(weights, axis=-1),
            "embedding": pooled.astype(jnp.float32),
            "nsp_probs": jax.nn.softmax(nsp_logits, axis=-1),
        }
        if return_logits:
            out["mlm_logits"] = mlm_logits
        return out

    return forward


class BertInferenceEngine(_AotEngine):
    """MLM scoring / masked-token prediction / sentence embedding over a
    trained :class:`BertForPreTraining` checkpoint.

    Request payload (numpy, one example per request):

    - ``input_ids``: ``[l]`` int — already-tokenized ids, ``l`` <= the
      largest bucket. Positions holding the MASK id are what
      ``pred_ids`` answers for.
    - ``token_type_ids``: optional ``[l]`` int (default zeros).
    - ``mlm_targets``: optional ``[l]`` int, ``-1`` = unscored. When any
      position is >= 0 the response carries ``score`` — the mean log-prob
      of the targets (MLM pseudo-log-likelihood), the standard
      BERT-as-scorer surface.

    Response per request: ``pred_ids [l]`` (argmax token at every
    position), ``score`` (float or None), ``embedding [H]`` (pooled [CLS]),
    ``nsp_probs [2]``, ``bucket`` (the padded length actually run).

    Mesh layouts: pass a DP-only mesh (or None) and the engine behaves as
    before — replicated params, plain-jit executables. Pass a mesh carrying
    ``model`` / ``expert`` / ``pipeline`` axes and the engine becomes
    model-parallel: params shard per ``bert_param_specs`` (the training
    contract, so ``restore_serving_state`` can place a checkpoint straight
    into this layout) and every (tier, bucket) executable is a
    ``shard_map`` of the forward — Megatron TP (``num_heads`` and
    ``intermediate_size`` must divide by the axis size), replicated-
    dispatch expert-parallel MoE (``moe_experts`` must divide), and the
    GPipe pipeline (the model must already be the STACKED
    ``pipeline_parallel == axis size`` variant; microbatches re-derive per
    tier since GPipe needs M | batch). Numerics match the single-chip
    engine to the tolerances pinned by tests/test_serve_mesh.py.
    """

    def __init__(
        self,
        model,
        params,
        mesh=None,
        *,
        buckets: tuple[int, ...] = (128, 256, 512),
        max_batch: int = 8,
        batch_tiers: tuple[int, ...] | None = None,
        return_logits: bool = False,
        weight_dtype: str | None = None,
        memory=None,
    ):
        super().__init__(mesh, max_batch, batch_tiers, memory=memory)
        tp = self.mesh.shape.get("model", 1)
        ep = self.mesh.shape.get("expert", 1)
        pp = self.mesh.shape.get("pipeline", 1)
        self._model_sharded = tp > 1 or ep > 1 or pp > 1
        serve_cfg = self._serve_config(model.cfg, tp, ep, pp)
        self.model = (
            type(model)(serve_cfg) if serve_cfg is not model.cfg else model
        )
        cfg = self.model.cfg
        self.weight_dtype = self._plan_quant(
            cfg, tp=tp, ep=ep, pp=pp, weight_dtype=weight_dtype
        )
        if is_quantized_tree(params):
            self.weight_dtype = "int8"
        elif self.weight_dtype == "int8":
            params = quantize_params(params)
        elif jnp.dtype(self.weight_dtype) != jnp.dtype(cfg.dtype):
            params = cast_params(params, jnp.dtype(self.weight_dtype))
        self.buckets = tuple(
            sorted({min(int(b), cfg.max_position) for b in buckets})
        )
        if not self.buckets:
            raise ValueError("need at least one sequence bucket")
        self.return_logits = return_logits
        if self._model_sharded:
            from distributed_tensorflow_tpu.models.bert import bert_param_specs

            # The same spec tree training shards by (test_bert_tp.py /
            # test_bert_pp.py pin it) — when restore_serving_state already
            # placed the checkpoint into this layout, the device_put in
            # _place is a per-array no-op (no staging round-trip).
            self._param_specs = bert_param_specs(
                params,
                model_axis="model" if tp > 1 else None,
                expert_axis="expert" if ep > 1 else None,
                pipeline_axis="pipeline" if pp > 1 else None,
            )
            self._param_sharding = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self._param_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        else:
            self._param_specs = None
        self.params = self._place(params)
        self.memory.register_tree(
            "bert_params", self.params, dtype=self.weight_dtype,
            fp32_nbytes=fp32_equiv_nbytes(self.params),
        )
        # AOT-compile one executable per (batch tier, sequence bucket) NOW:
        # startup pays every trace/compile, the request path pays none (jit
        # cache lookups included — these are Compiled objects, not jit
        # wrappers). A partial flush dispatches at the smallest tier that
        # fits instead of padding to max_batch.
        self._compiled = {}
        self._plan_cells(len(self.batch_tiers) * len(self.buckets))
        for T in self.batch_tiers:
            fwd = self._tier_forward(T)
            for L in self.buckets:
                b = (T, L)
                self._compiled[T, L] = self._compile_cell(
                    f"bert/{self.layout}/t{T}/b{L}",
                    lambda fwd=fwd, b=b, T=T: (
                        jax.jit(fwd)
                        .lower(
                            self.params,
                            self._struct(b, jnp.int32, T),
                            self._struct(b, jnp.bool_, T),
                            self._struct(b, jnp.int32, T),
                            self._struct(b, jnp.int32, T),
                        )
                        .compile()
                    ),
                )
        logger.info(
            "BERT engine ready: layout=%s buckets=%s tiers=%s (%d executables)",
            self.layout, self.buckets, self.batch_tiers, len(self._compiled),
        )

    @staticmethod
    def _plan_quant(cfg, *, tp: int = 1, ep: int = 1, pp: int = 1,
                    weight_dtype: str | None = None) -> str:
        """Validate the weight-quantization knob for this config/layout and
        return the concrete dtype name (``None`` resolves to the model's
        compute dtype). Raises ``ValueError`` loudly at startup, the SC002
        clean-rejection contract. int8 x pipeline rejects: the stacked
        ``[pp, ...]`` pipeline kernels would fold the stage axis into the
        per-channel absmax reduction, silently sharing scales across
        stages. MoE expert stacks simply stay fp32 (quantize_params skips
        non-"kernel" leaf names), so ep needs no constraint."""
        del tp, ep
        w = normalize_quant_dtype(weight_dtype, "weight_dtype")
        if w == "int8" and pp > 1:
            raise ValueError(
                f"weight_dtype=int8 does not support the stacked "
                f"pipeline-parallel variant (pipeline axis of {pp}): "
                "per-channel scales would span pipeline stages"
            )
        return w or str(np.dtype(cfg.dtype).name)

    @staticmethod
    def _serve_config(cfg, tp: int, ep: int, pp: int):
        """Bind the model config to the mesh's model axes, validating the
        same divisibility contracts training enforces — loudly, at startup,
        never as a shape error mid-request."""
        if tp > 1:
            if cfg.num_heads % tp or cfg.intermediate_size % tp:
                raise ValueError(
                    f"model axis of {tp} must divide num_heads "
                    f"({cfg.num_heads}) and intermediate_size "
                    f"({cfg.intermediate_size})"
                )
            cfg = dataclasses.replace(
                cfg, model_axis="model", model_parallel=tp
            )
        if ep > 1:
            if not cfg.moe_experts or cfg.moe_experts % ep:
                raise ValueError(
                    f"expert axis of {ep} needs a MoE model with "
                    f"moe_experts divisible by it (got {cfg.moe_experts})"
                )
            # Replicated dispatch: every expert shard routes the full batch
            # and partial outputs psum — exact, and free of the capacity
            # a2a's batch-layout requirements (serving batches are tiny).
            cfg = dataclasses.replace(
                cfg,
                expert_axis="expert",
                expert_parallel=ep,
                moe_dispatch="replicated",
            )
        if pp > 1:
            if cfg.pipeline_parallel != pp:
                raise ValueError(
                    f"pipeline axis of {pp} needs the stacked "
                    f"pipeline_parallel={pp} model/checkpoint (got "
                    f"pipeline_parallel={cfg.pipeline_parallel}); pass the "
                    "training run's --pipeline-parallel to cli/serve"
                )
            cfg = dataclasses.replace(cfg, pipeline_axis="pipeline")
        return cfg

    def _tier_forward(self, tier: int):
        """Build the function to compile for one batch tier: the plain
        forward on a DP-only mesh, or its ``shard_map`` over the model axes
        (the TP/EP/PP module code runs psums that need bound axes)."""
        cfg = self.model.cfg
        model = self.model
        if self._model_sharded and cfg.pipeline_axis is not None:
            # GPipe needs n_microbatches | rows, and inside shard_map the
            # pipeline sees the PER-SHARD rows (tier/dp when the tier is
            # dp-sharded — must mirror _batch_sharding_or_replicated): per
            # tier, the largest M dividing both the local rows and the
            # configured M (gcd; a 1-row shard runs M=1 — bubble-heavy but
            # correct).
            dp = math.prod(self.mesh.shape[a] for a in data_axes(self.mesh))
            local = tier // dp if dp > 1 and tier % dp == 0 else tier
            m = math.gcd(
                local, cfg.pipeline_microbatches or 4 * cfg.pipeline_parallel
            )
            model = type(model)(
                dataclasses.replace(cfg, pipeline_microbatches=m)
            )
        fwd = _make_bert_forward(model, self.return_logits)
        if not self._model_sharded:
            return fwd
        # Batch spec matches the tier's placement rule: sharded over the DP
        # axes when the tier divides them, replicated otherwise. All inputs
        # and every output leaf are leading-dim-batch, so one spec serves
        # as prefix for both sides; params use the bert_param_specs tree.
        bspec = self._tier_sharding[tier].spec
        return jax.shard_map(
            fwd,
            mesh=self.mesh,
            in_specs=(self._param_specs, bspec, bspec, bspec, bspec),
            out_specs=bspec,
            check_vma=False,
        )

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        raise RequestError(
            f"sequence length {length} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    def validate(self, payload: dict) -> None:
        """Reject un-servable payloads BEFORE they enqueue — a bad request
        must fail alone, never poison the batch it would have ridden in."""
        ids = np.asarray(payload.get("input_ids", ()))
        if ids.ndim != 1 or ids.size == 0:
            raise RequestError("input_ids must be a non-empty 1-D id list")
        self.bucket_for(ids.shape[0])
        for k in ("token_type_ids", "mlm_targets"):
            if k in payload and np.asarray(payload[k]).shape != ids.shape:
                raise RequestError(f"{k} shape must match input_ids")

    def request_bucket(self, payload: dict) -> int:
        """Queue key for bucket-aware batching: the sequence bucket this
        payload would pad to (batcher groups same-bucket requests)."""
        return self.bucket_for(np.asarray(payload["input_ids"]).shape[0])

    def dispatch(self, payloads: list[dict]) -> InFlightBatch:
        """Assemble one micro-batch and launch it; returns WITHOUT blocking
        on device compute (the returned refs materialize in ``fetch``).

        Pads every row to the batch's bucket — the smallest bucket holding
        the LONGEST member (mixed-length batches pay the longest member's
        bucket; per-bucket queues in the batcher avoid assembling such
        batches in the first place) — and pads missing rows to the
        smallest batch TIER that fits with inert rows (mask True only at
        position 0: fully-masked rows would softmax over zero keys; the
        padded rows' outputs are sliced off anyway, but NaNs should never
        exist in a served buffer).
        """
        if len(payloads) > self.max_batch:
            raise ValueError(
                f"batch of {len(payloads)} exceeds max_batch {self.max_batch}"
            )
        lens = [np.asarray(p["input_ids"]).shape[0] for p in payloads]
        L = self.bucket_for(max(lens))
        T = self.tier_for(len(payloads))
        key = (T, L)

        def _make():
            return (
                np.zeros((T, L), np.int32),
                np.zeros((T, L), bool),
                np.zeros((T, L), np.int32),
                np.full((T, L), -1, np.int32),
            )

        ids, mask, types, targets = buffers = self._take_buffers(key, _make)
        ids.fill(0)
        mask.fill(False)
        types.fill(0)
        targets.fill(-1)
        for r, (p, l) in enumerate(zip(payloads, lens)):
            ids[r, :l] = np.asarray(p["input_ids"], np.int32)
            mask[r, :l] = True
            if "token_type_ids" in p:
                types[r, :l] = np.asarray(p["token_type_ids"], np.int32)
            if "mlm_targets" in p:
                targets[r, :l] = np.asarray(p["mlm_targets"], np.int32)
        mask[len(payloads):, 0] = True
        t_assembled = time.monotonic()
        out = self._compiled[key](
            self.params,
            self._put(ids, T),
            self._put(mask, T),
            self._put(types, T),
            self._put(targets, T),
        )
        self._record_dispatch(T, L, len(payloads))
        return InFlightBatch(
            out=out, key=key, n=len(payloads), meta=lens, buffers=buffers,
            layout=self.layout, t_assembled=t_assembled,
        )

    def fetch(self, inflight: InFlightBatch) -> list[dict]:
        """Block on the in-flight batch and slice out per-row results."""
        out = jax.device_get(inflight.out)
        inflight.t_got = time.monotonic()
        self._give_buffers(inflight.key, inflight.buffers)
        L = inflight.key[1]
        results = []
        for r, l in enumerate(inflight.meta):
            count = float(out["count"][r])
            res = {
                "pred_ids": out["pred_ids"][r, :l],
                "score": (-float(out["nll"][r]) / count) if count else None,
                "embedding": out["embedding"][r],
                "nsp_probs": out["nsp_probs"][r],
                "bucket": L,
            }
            if self.return_logits:
                res["mlm_logits"] = out["mlm_logits"][r, :l]
            results.append(res)
        return results


# -- LM grid executable bodies. A cache (slot table, prefix pool, a stage
# -- of either) is ONE pytree operand whose every leaf is [layers, slots or
# -- blocks, ...]; these bodies address the slot axis and map over the
# -- leaves. What the leaves are, and what follows their slots, is the
# -- model's (its cache_layout) and models/kvcache.py's. The bodies that
# -- address a positions axis too (insert, verify) are compiled only for a
# -- layout whose every group has one (kvcache.require_pages); the chunk
# -- body also for one with positionless state, which it hands from chunk
# -- to chunk as it finds it in the row's slot (kvcache.require_carry).


def _make_causal_prefill(model):
    """Prefill executable body for one (tier, bucket): run the full causal
    forward, write what every group caches of each admitted row AT THE ROW'S
    OWN LENGTH into its slot (``kvcache.write_prompt``: padding rows drop,
    pages are encoded as the decode path would have), and sample each row's
    FIRST generated token on-device."""

    def prefill_fn(params, cache, last, ids, mask, slots, lengths, temps,
                   seeds):
        params = dequantize_params(params, model.cfg.dtype)
        last_logits, fresh = model.apply(
            {"params": params}, ids, mask, lengths, method="prefill_rows"
        )
        tok = sample_tokens(last_logits, temps, seeds, lengths)
        cache = kvcache.write_prompt(cache, slots, fresh)
        last = last.at[slots].set(tok, mode="drop")
        return cache, last, tok

    return prefill_fn


def _make_causal_decode(model, cache_len: int):
    """Decode-step executable body (ONE shape: the full slot table): write
    each slot's pending token at its position, attend the cache prefix,
    sample the next token. ``last`` only advances where ``active``, and
    idle lanes carry the out-of-bounds position ``cache_len`` so their
    garbage K/V scatters DROP — a mid-chunk-prefill slot rides decode
    steps inactive, and a stray write would corrupt pages its earlier
    chunks already filled (chunked prefill never re-writes them).

    The per-lane inputs are ONE ``int32[4, slots]`` operand ``step`` —
    lengths, live lanes (1 or 0), temperatures (float32 bits) and seeds —
    and come back advanced by the step, each live lane's length + 1: the
    next step's operand wherever the batcher's plan held, so it stays on
    the device beside ``cache`` and ``last`` (``CausalLMEngine.decode``)."""

    def decode_fn(params, cache, last, step):
        params = dequantize_params(params, model.cfg.dtype)
        lengths, live, seeds = step[0], step[1], step[3]
        temps = jax.lax.bitcast_convert_type(step[2], jnp.float32)
        active = live != 0
        pos = jnp.where(
            active, jnp.minimum(lengths, cache_len - 1), cache_len
        )
        logits, cache = model.apply(
            {"params": params}, last, pos, cache, method="decode_step"
        )
        tok = sample_tokens(logits, temps, seeds, lengths + 1)
        last = jnp.where(active, tok, last)
        return cache, last, tok, step.at[0].add(live)

    return decode_fn


def _make_causal_verify(model, cache_len: int, k: int):
    """Speculative-verify executable body (ONE shape: the full slot table,
    ``k+1`` columns): score each verifying slot's last token plus up to
    ``k`` host-drafted candidates in one forward, sample every column with
    the SAME (seed, absolute position) keys successive decode steps would
    use, and compute the accepted prefix on-device so ``last_token`` stays
    coherent without a host round-trip.

    Column ``j`` of a verifying lane sits at absolute position
    ``lengths + j``; lanes beyond a slot's ``n_input`` (and every lane of
    a non-verifying slot, ``n_input == 0``) carry the sentinel position
    ``cache_len`` so their K/V scatters drop — the decode path's idle-lane
    invariant, column-wise. Acceptance is exact match: draft ``j`` survives
    iff it equals the sampled token at column ``j-1`` AND every earlier
    draft survived (the cumprod), so with ``m`` accepted drafts the lane
    emits ``m+1`` tokens (``tok[:, :m+1]`` — the first mismatch column IS
    the verified model token; a full reject still advances one token).
    K/V written past ``lengths + m`` are dead stores the rolled-back slot
    position masks; the host rollback is just not advancing its length."""

    def verify_fn(params, cache, last, drafts, lengths, n_input, temps,
                  seeds):
        params = dequantize_params(params, model.cfg.dtype)
        tokens = jnp.concatenate([last[:, None], drafts], axis=1)  # [S, k+1]
        cols = jnp.arange(k + 1)[None, :]
        pos = lengths[:, None] + cols
        wpos = jnp.where(cols < n_input[:, None], pos, cache_len)
        logits, cache = model.apply(
            {"params": params}, tokens, wpos, cache, method="verify_step"
        )
        # Column j's sampling key is position lengths + j + 1 — exactly the
        # key the (j+1)-th plain decode step after this point would fold
        # in, so seeded streams stay bit-identical however many columns
        # each step accepts.
        tok = jax.vmap(
            lambda lg, st: sample_tokens(lg, temps, seeds, st),
            in_axes=(1, 1), out_axes=1,
        )(logits, pos + 1)
        dcols = jnp.arange(k)[None, :]
        matches = (tok[:, :-1] == drafts) & (dcols < n_input[:, None] - 1)
        m = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)
        new_last = tok[jnp.arange(tok.shape[0]), m]
        last = jnp.where(n_input > 0, new_last, last)
        return cache, last, tok

    return verify_fn


def _make_causal_chunk_prefill(model, cache_len: int, block_tokens: int,
                               pooled: bool = True):
    """Chunk-prefill executable body for one (tier, chunk bucket): a fused
    page-gather prologue + one absolute-position prompt chunk + on-device
    first-token sampling where the chunk completes its row's prompt.

    Every leaf of the rows' slots goes to the model's ``prefill_chunk`` and
    comes back: a table of positions with the chunk's rows in it, positionless
    state as the chunk left it — the carry a recurrence resumes from. An
    engine without a prefix pool (``pooled`` false: every layout that holds
    such state, whose leaves no pool page could fill) has no chain to gather
    and compiles no prologue: its one-block dummy pool rides along unread.

    The prologue materializes each row's matched prefix chain (pool block
    ids in ``chain``, first ``n_gather`` entries real) into the row's slot
    pages by gather-and-blend — fusing it here instead of a separate
    executable saves a dispatch/completion round per admission. Rows past
    their first chunk (and cache-miss rows) pass ``n_gather == 0`` and
    blend back their own pages unchanged. Pool pages are READ-ONLY in this
    executable: requests diverging after a shared head extend private
    copies, which is the pool's copy-on-read isolation contract.

    Per-lane validity comes from ``starts``/``lengths``: lane ``c`` of row
    ``t`` holds absolute position ``starts[t] + c`` when in range and the
    out-of-range sentinel ``cache_len`` otherwise, so padding lanes (and
    whole padding rows, which also carry slot index == S) write nowhere.
    ``is_last`` rows sample their first token at the prompt's final lane,
    keyed on absolute position exactly like the monolithic prefill — bit
    parity with the cold path follows. A model whose head is too wide to
    apply at every lane returns that lane's logits alone, ``[T, V]`` (the
    last lane whose position is in range: models/olmo_hybrid.py); from
    ``[T, C, V]`` (``CausalLM``, whose verify step reads every column) the
    lane is taken here."""

    def chunk_fn(params, cache, last, pool, ids, starts, lengths, chain,
                 n_gather, slots, temps, seeds):
        params = dequantize_params(params, model.cfg.dtype)
        T, C = ids.shape
        span = chain.shape[1] * block_tokens
        sel_rows = (
            jnp.arange(span)[None, :] < (n_gather * block_tokens)[:, None]
        )

        # Every gather / blend / scatter maps over all leaves, so prefix
        # pages move WITH their scales bit-exactly (the cached-vs-cold
        # parity contract).
        def blend(r, p):
            g = p[:, chain].reshape(p.shape[0], T, span, *p.shape[3:])
            sel = sel_rows.reshape((1, T, span) + (1,) * (p.ndim - 3))
            return r.at[:, :, :span].set(jnp.where(sel, g, r[:, :, :span]))

        rows = jax.tree.map(lambda a: a[:, slots], cache)  # padding ix clamps
        if pooled:
            rows = jax.tree.map(blend, rows, pool)
        pos = starts[:, None] + jnp.arange(C)[None, :]
        wpos = jnp.where(pos < lengths[:, None], pos, cache_len)
        logits, rows = model.apply(
            {"params": params}, ids, wpos, rows, method="prefill_chunk"
        )
        cache = jax.tree.map(
            lambda c, n: c.at[:, slots].set(n, mode="drop"), cache, rows
        )
        is_last = starts + C >= lengths
        li = jnp.clip(lengths - 1 - starts, 0, C - 1)
        if logits.ndim == 3:
            logits = logits[jnp.arange(T), li]
        tok = sample_tokens(logits, temps, seeds, lengths)
        upd = jnp.where(is_last, tok, jnp.take(last, slots, mode="clip"))
        last = last.at[slots].set(upd, mode="drop")
        return cache, last, tok

    return chunk_fn


def _make_prefix_insert(cache_len: int, block_tokens: int):
    """Publish-to-pool executable body: copy a finished slot's prefix
    pages into newly allocated pool blocks (``block_ids``/``block_pos``
    padded with the out-of-pool sentinel, whose scatters drop).

    The slot cache is DONATED and returned untouched so the donation
    chain through the engine's device state stays linear — every
    executable (chunk -> insert -> decode) consumes the previous one's
    outputs, and XLA aliases buffers instead of copying to protect a
    still-referenced operand."""
    nb = cache_len // block_tokens

    def insert_fn(pool, cache, slot, block_ids, block_pos):
        bp = jnp.minimum(block_pos, nb - 1)

        def publish(p, c):
            src = c[:, slot, : nb * block_tokens].reshape(
                c.shape[0], nb, block_tokens, *c.shape[3:]
            )
            return p.at[:, block_ids].set(src[:, bp], mode="drop")

        return jax.tree.map(publish, pool, cache), cache

    return insert_fn


def _make_export():
    """Gather-for-transfer executable body (serve/disagg.py), compiled at
    two operand shapes. Over the prefix pool with a ``[max_chain]`` vector
    of block ids: a pinned chain's pages as a fixed ``[nl, max_chain,
    block_tokens, ..]`` stage (pad lanes repeat block 0; the importer's
    sentinel ids drop them). Over the slot table with ONE slot index: a
    live stream's ``[nl, cache_len, ..]`` lane (stream migration). The
    operand is NOT donated either way — export copies, the table stays
    live; for the pool the caller's ``KVBlockPool.match`` pin keeps the
    gathered blocks immutable for the duration."""

    def export_fn(table, idx):
        return jax.tree.map(lambda a: jnp.take(a, idx, axis=1), table)

    return export_fn


def _make_pool_import():
    """Adopt-transferred-pages executable body (serve/disagg.py): scatter
    a fixed ``[nl, max_chain, block_tokens, ..]`` stage of received KV
    pages into the prefix pool at ``block_ids`` (padded with the
    out-of-pool sentinel, whose scatters drop — pad lanes carry garbage
    pages that never land). The pool is DONATED like every other
    executable in the chain; the import dispatches between decode steps on
    the loop thread, so the decode executable itself is untouched."""

    def import_fn(pool, pages, block_ids):
        return jax.tree.map(
            lambda a, b: a.at[:, block_ids].set(b, mode="drop"), pool, pages
        )

    return import_fn


def _make_slot_import():
    """Resume-a-migrated-stream executable body: scatter a received
    ``[nl, cache_len, ..]`` stage into ONE slot's cache lane and seed
    ``last_token[slot]`` with the stream's newest token, so the very next
    decode step continues the generation mid-flight. Cache / last_token
    operands are DONATED like every executable in the decode chain;
    dispatches between decode steps on the loop thread."""

    def import_fn(cache, last, stage, slot, tok):
        cache = jax.tree.map(lambda a, b: a.at[:, slot].set(b), cache, stage)
        return cache, last.at[slot].set(tok)

    return import_fn


class CausalLMEngine(_AotEngine):
    """Autoregressive generation over a trained :class:`CausalLM` checkpoint
    with a paged, slot-addressed KV cache.

    The cache is a FIXED pool of per-slot pages — one pytree whose every
    leaf is ``[layers, slots, ...]`` (which leaves in which groups, and
    what follows the slots, is the model's
    ``cache_layout``: for ``CausalLM`` one group of dense K and V rows of
    ``heads * head_dim`` at ``cache_len`` positions, or int8 payloads with
    their scales; for a hybrid model positionless state, window rings and a
    K/V table side by side, docs/DEPLOY.md "Hybrid-state models") plus a
    ``last_token [slots]`` vector — living on device for the engine's
    lifetime and threaded functionally through every executable with
    buffer donation, so each step updates the pool in place and slot
    assignment/reuse never changes a shape (= never recompiles, the decode
    analog of the tier grid's "startup pays every compile" rule). The AOT
    grid is:

    - ``prefill`` per (batch tier x prompt bucket): the full causal
      forward + a scatter of the prompt's K/V into the admitted rows'
      pages + on-device sampling of each row's first token (the
      time-to-first-token reply needs exactly that one small fetch).
    - ``decode`` — ONE executable at the full slot-table shape: every
      step embeds each slot's pending token, extends its pages, samples
      the next token. Idle slots ride along masked; the batcher admits /
      frees between steps without ever touching a compiled shape.
    - ``verify`` (``spec_tokens > 0`` only) — ONE executable at
      ``[slots, k+1]``: speculative decoding's batched verify of host-
      drafted candidates (:func:`_make_causal_verify`), same donation
      chain and idle-lane masking as decode, timed through
      ``_compile_cell`` like every other cell so ``/compilez`` and
      warm-fraction readiness gating see it.

    ``last_token`` stays device-resident, so step k+1 dispatches against
    step k's un-fetched output — the host fetch of sampled tokens (finish
    detection, streaming) overlaps the next step's device compute via the
    batcher's completion thread. So do the decode step's per-lane inputs,
    advanced by the step itself: a step whose plan did not change uploads
    nothing (:meth:`decode`).

    Sampling is greedy at ``temperature == 0`` and seeded-categorical
    otherwise, keyed on (seed, absolute position) only — a request's token
    stream is a function of the request, not of its batchmates, so
    continuous batching is bit-identical to a solo run.

    Tensor parallelism (a mesh with a ``model`` axis) shards the cache
    leaves as the layout says (the head axis of the pages) and the params
    per the model's ``param_specs``; batch
    inputs replicate (every model shard sees every slot — slot state must
    stay coherent, and decode batches are tiny). Expert/pipeline axes are
    rejected at startup. DP axes likewise replicate: a decode engine is
    one replica; fleet scale-out is N engines behind the router contract.

    **Chunked mode** (``prefix_cache_mb > 0`` or ``prefill_chunk > 0``)
    swaps the monolithic prefill grid for a CHUNK grid — one executable
    per (tier x chunk bucket), each a fused page-gather prologue + one
    absolute-position prompt chunk (see :func:`_make_causal_chunk_prefill`)
    — so prompt admission becomes a sequence of bounded chunk dispatches
    the batcher interleaves with decode steps. With a prefix-cache budget
    the engine also owns a device-resident pool of KV pages ``[nl,
    n_blocks, block_tokens, *trailing]`` (the same layout, sharded like the
    slot cache, so TP gathers pages with per-shard head dims) indexed by a host
    :class:`~..serve.kvpool.KVBlockPool` trie, plus one ``insert``
    executable that publishes a finished slot's prefix pages back to the
    pool. A chunk at ``start == 0`` with nothing to gather is exactly the
    monolithic prefill, so legacy mode (both knobs 0) keeps the original
    grid and byte-identical behavior.
    """

    def __init__(
        self,
        model,
        params,
        mesh=None,
        *,
        buckets: tuple[int, ...] = (64, 128, 256),
        slots: int = 8,
        max_batch: int = 4,
        batch_tiers: tuple[int, ...] | None = None,
        max_new_tokens: int = 32,
        prefix_cache_mb: float = 0.0,
        block_tokens: int = 16,
        prefill_chunk: int = 0,
        spec_tokens: int = 0,
        spec_min_match: int = 2,
        spec_backoff: float = 0.25,
        kv_transfer: bool = False,
        stream_migrate: bool = False,
        weight_dtype: str | None = None,
        kv_dtype: str | None = None,
        memory=None,
    ):
        if slots < 1:
            raise ValueError(f"need at least one cache slot, got {slots}")
        super().__init__(mesh, min(max_batch, slots), batch_tiers,
                         memory=memory)
        tp = self.mesh.shape.get("model", 1)
        ep = self.mesh.shape.get("expert", 1)
        pp = self.mesh.shape.get("pipeline", 1)
        self._model_sharded = tp > 1
        # Modes that move a cached position about as a page of its own
        # refuse, at construction and by the group's name, a model that
        # also caches what is not one (kvcache.require_pages).
        asked = {
            "model sharding": tp > 1,
            "the prefix cache": prefix_cache_mb > 0,
            "speculative verify": spec_tokens > 0,
            "KV-page transfer": bool(kv_transfer),
            "stream migration": bool(stream_migrate),
            "int8 K/V": normalize_quant_dtype(kv_dtype, "kv_dtype") == "int8",
        }
        for mode in (mode for mode, on in asked.items() if on):
            kvcache.require_pages(model.cache_layout("float32"), mode)
        if prefill_chunk > 0:
            # a carry, not a page: positionless state goes from chunk to chunk
            kvcache.require_carry(model.cache_layout("float32"))
        serve_cfg = self._serve_config(model.cfg, tp=tp, ep=ep, pp=pp)
        self.model = (
            type(model)(serve_cfg) if serve_cfg is not model.cfg else model
        )
        cfg = self.model.cfg
        # Quantized serving (ROADMAP item 4; docs/DEPLOY.md "Quantized
        # serving"): weight_dtype packs kernels to int8 at engine build
        # (idempotent — restore_serving_state may have packed them already),
        # kv_dtype picks the cache layout (models/kvcache.py).
        self.weight_dtype, self.kv_dtype = self._plan_quant(
            cfg, tp=tp, weight_dtype=weight_dtype, kv_dtype=kv_dtype
        )
        if is_quantized_tree(params):
            self.weight_dtype = "int8"
        elif self.weight_dtype == "int8":
            params = quantize_params(params)
        elif jnp.dtype(self.weight_dtype) != jnp.dtype(cfg.dtype):
            params = cast_params(params, jnp.dtype(self.weight_dtype))
        self.slots = slots
        self.buckets = tuple(
            sorted({min(int(b), cfg.max_position) for b in buckets})
        )
        if not self.buckets:
            raise ValueError("need at least one prompt bucket")
        # Every slot's pages hold prompt + generated tokens; validate()
        # rejects requests that could not fit before they ever enqueue.
        self.cache_len = min(self.buckets[-1] + max_new_tokens,
                             cfg.max_position)
        self.max_new_tokens = max_new_tokens
        # Speculative decoding (serve/spec.py; docs/DEPLOY.md "Speculative
        # decoding"): k > 0 compiles ONE extra verify executable at
        # [slots, k+1] and hands the batcher a SpecConfig to draft against.
        from distributed_tensorflow_tpu.serve.spec import SpecConfig

        self.spec_tokens = self._plan_spec(
            cfg, tp=tp, spec_tokens=spec_tokens, min_match=spec_min_match,
            max_new_tokens=max_new_tokens,
        )
        self.spec = (
            SpecConfig(
                spec_tokens=self.spec_tokens, min_match=spec_min_match,
                backoff_threshold=spec_backoff,
            )
            if self.spec_tokens > 0 else None
        )

        # What a sequence caches: the model declares the groups and their
        # leaves, the engine handles the slot axis of whatever they are and
        # asks the layout for sizes.
        self._layout = self.model.cache_layout(self.kv_dtype)
        # what a decode step writes for each live lane, by group, and what
        # else the model counts one for (its ``decode_counters``): the
        # counters the dispatch span carries
        self._writes_per_lane = {
            **kvcache.step_writes(self._layout, 1),
            **getattr(self.model, "decode_counters", dict)(),
        }
        self._prefix_reads = kvcache.prefix_reads(self._layout, self.cache_len)
        self._whole_reads = kvcache.whole_reads(self._layout, self.cache_len)
        table = (slots, self.cache_len)
        if self._model_sharded:
            self._param_specs = self.model.param_specs(
                params, model_axis="model"
            )
            self._param_sharding = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self._param_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        else:
            self._param_specs = None
        self._cache_sharding = kvcache.shardings(self._layout, self.mesh)
        self._rep = replicated_sharding(self.mesh)
        self.params = self._place(params)
        self._cache = kvcache.zeros(self._layout, table, self._cache_sharding)
        self._last_token = jax.device_put(
            jnp.zeros((slots,), jnp.int32), self._rep
        )
        self.memory.register_tree(
            "lm_params", self.params, dtype=self.weight_dtype,
            fp32_nbytes=fp32_equiv_nbytes(self.params),
        )
        fp32_layout = self.model.cache_layout("float32")
        fp32 = kvcache.components(fp32_layout, table)
        #: bytes and storage dtype of each cache group, by the name /memz
        #: and /statusz show it under (``kv_slot_cache``, or ``cache.state``
        #: / ``cache.window`` / ``cache.full`` side by side)
        self.cache_groups = kvcache.components(self._layout, table)
        for component, (nbytes, dtype) in self.cache_groups.items():
            self.memory.register(
                component, nbytes, dtype=dtype,
                fp32_nbytes=fp32[component][0],
            )
        # Per-slot share of the slot table, every group: the batcher
        # multiplies this by slots_active so /statusz and /memz agree on
        # active bytes.
        self.slot_page_bytes = tree_nbytes(self._cache) // slots

        # Prefix-cache / chunked-prefill plumbing. Legacy mode (both knobs
        # 0) compiles the original monolithic prefill grid; chunked mode
        # compiles the chunk grid INSTEAD (a start-0 chunk subsumes it),
        # so startup never pays both.
        from distributed_tensorflow_tpu.serve.kvpool import KVBlockPool

        self.block_tokens = int(block_tokens)
        self._chunked_mode = prefix_cache_mb > 0 or prefill_chunk > 0
        self.prefix_cache = None
        if self._chunked_mode:
            chunk = int(prefill_chunk) if prefill_chunk > 0 \
                else self.buckets[-1]
            self.prefill_chunk_size = min(chunk, self.buckets[-1])
            self._chunk_buckets = tuple(sorted(
                {b for b in self.buckets if b <= self.prefill_chunk_size}
                | {self.prefill_chunk_size}
            ))
            self._max_chain = max(1, self.buckets[-1] // self.block_tokens)
            if prefix_cache_mb > 0:
                n_blocks, self._bytes_per_block = self._plan_prefix_cache(
                    cfg, tp=tp, prefix_cache_mb=prefix_cache_mb,
                    block_tokens=self.block_tokens, kv_dtype=self.kv_dtype,
                )
                self.prefix_cache = KVBlockPool(
                    n_blocks, self.block_tokens, self._bytes_per_block,
                    dtype=self.kv_dtype,
                )
            else:
                n_blocks = 1  # dummy pool keeps one chunk operand layout
            pool = (n_blocks, self.block_tokens)
            self._pool_blocks = n_blocks
            # Orders every dispatch that DONATES the pool (insert/import,
            # decode-loop thread) against the one that reads it from
            # another thread (export): a read dispatched while its buffer
            # was being donated hit deleted-array errors or never
            # completed, and one dispatched before the publish of a block
            # it had matched shipped that block's stale bytes.
            self._pool_lock = threading.Condition()
            self._pool = kvcache.zeros(
                self._layout, pool, self._cache_sharding
            )
            self.memory.register(
                "kv_prefix_pool", tree_nbytes(self._pool),
                dtype=self.kv_dtype,
                fp32_nbytes=n_blocks * self.block_tokens
                * kvcache.bytes_per_token(fp32_layout),
            )
        else:
            self.prefill_chunk_size = 0

        # The grid: prefill per (tier x bucket) — or chunk-prefill per
        # (tier x chunk bucket) — + ONE decode step. Cache / last_token
        # operands are donated — XLA updates the pool in place, and the
        # engine swaps its refs for the returned ones at dispatch.
        self._prefill_compiled = {}
        self._chunk_compiled = {}
        self._export_compiled = None
        self._import_compiled = None
        self._kv_transfer = False
        # Live-stream migration (serve/disagg.py): two extra AOT cells —
        # slot export (checkpoint a live generation's KV lane) and slot
        # import (resume it here) — valid in BOTH prefill modes.
        self.stream_migrate = bool(stream_migrate)
        self._slot_export_compiled = None
        self._slot_import_compiled = None
        n_spec_cells = 1 if self.spec_tokens else 0
        n_mig_cells = 2 if self.stream_migrate else 0
        # Each cell states its operands where it is compiled: ``cache`` is
        # the layout's spec tree (slot table, pool and page stages alike),
        # everything batch-like replicates.
        cache, rep = kvcache.specs(self._layout), P()
        table_s = kvcache.structs(self._layout, table, self._cache_sharding)

        def i32(*shape):
            return self._rep_struct(shape, jnp.int32)

        def f32(*shape):
            return self._rep_struct(shape, jnp.float32)

        if not self._chunked_mode:
            self._plan_cells(
                len(self.batch_tiers) * len(self.buckets) + 1 + n_spec_cells
                + n_mig_cells
            )
            fn = self._wrap(
                _make_causal_prefill(self.model),
                (self._param_specs, cache, rep) + (rep,) * 6,
                (cache, rep, rep),
            )
            for T in self.batch_tiers:
                for L in self.buckets:
                    self._prefill_compiled[T, L] = self._aot(
                        f"prefill/t{T}/b{L}", fn, (1, 2),
                        self.params, table_s, i32(slots), i32(T, L),
                        self._rep_struct((T, L), jnp.bool_), i32(T), i32(T),
                        f32(T), i32(T),
                    )
        else:
            self._kv_transfer = (
                bool(kv_transfer) and self.prefix_cache is not None
            )
            self._plan_cells(
                len(self.batch_tiers) * len(self._chunk_buckets) + 1
                + (1 if self.prefix_cache is not None else 0)
                + (2 if self._kv_transfer else 0) + n_spec_cells
                + n_mig_cells
            )
            pool_s = kvcache.structs(self._layout, pool, self._cache_sharding)
            M = self._max_chain
            fn = self._wrap(
                _make_causal_chunk_prefill(
                    self.model, self.cache_len, self.block_tokens,
                    pooled=self.prefix_cache is not None,
                ),
                (self._param_specs, cache, rep, cache) + (rep,) * 8,
                (cache, rep, rep),
            )
            for T in self.batch_tiers:
                for C in self._chunk_buckets:
                    self._chunk_compiled[T, C] = self._aot(
                        f"chunk/t{T}/c{C}", fn, (1, 2),
                        self.params, table_s, i32(slots), pool_s, i32(T, C),
                        i32(T), i32(T), i32(T, M), i32(T), i32(T), f32(T),
                        i32(T),
                    )
            if self.prefix_cache is not None:
                self._insert_compiled = self._aot(
                    "insert",
                    self._wrap(
                        _make_prefix_insert(self.cache_len, self.block_tokens),
                        (cache, cache, rep, rep, rep), (cache, cache),
                    ),
                    (0, 1), pool_s, table_s, i32(), i32(M), i32(M),
                )
            if self._kv_transfer:
                # Export gathers pinned pages OUT of the pool — the pool is
                # NOT donated (it must survive the gather; eager ops over
                # the donation-aliased pool are exactly what this AOT cell
                # exists to avoid).
                self._export_compiled = self._aot(
                    "export",
                    self._wrap(_make_export(), (cache, rep), cache),
                    (), pool_s, i32(M),
                )
                self._import_compiled = self._aot(
                    "import",
                    self._wrap(
                        _make_pool_import(), (cache, cache, rep), cache
                    ),
                    (0,), pool_s,
                    kvcache.structs(
                        self._layout, (M, self.block_tokens),
                        self._cache_sharding,
                    ),
                    i32(M),
                )
        self._decode_compiled = self._aot(
            "decode",
            self._wrap(
                _make_causal_decode(self.model, self.cache_len),
                (self._param_specs, cache, rep, rep), (cache, rep, rep, rep),
            ),
            (1, 2, 3), self.params, table_s, i32(slots), i32(4, slots),
        )
        # The decode step's operand on the device, and the host's copy of
        # it as the step left it: None while nothing is there (decode).
        self._step_inputs = self._step_mirror = None
        # What the decode program reserves beside its operands, and how much
        # of the donated slot table it updates in place (per device; None
        # where the backend reports no memory analysis). Decided at compile
        # time, so it is read once, here: an operator who sizes --slots
        # reads it on the ready line or /statusz, not as an OOM.
        self.decode_scratch_bytes = self.decode_aliased_bytes = None
        try:
            ma = self._decode_compiled.memory_analysis()
            self.decode_scratch_bytes = int(ma.temp_size_in_bytes)
            self.decode_aliased_bytes = int(ma.alias_size_in_bytes)
        except Exception:  # noqa: BLE001 — best-effort per backend
            pass
        self._verify_compiled = None
        if self.spec_tokens:
            self._verify_compiled = self._aot(
                "verify",
                self._wrap(
                    _make_causal_verify(
                        self.model, self.cache_len, self.spec_tokens
                    ),
                    (self._param_specs, cache, rep) + (rep,) * 5,
                    (cache, rep, rep),
                ),
                (1, 2), self.params, table_s, i32(slots),
                i32(slots, self.spec_tokens), i32(slots), i32(slots),
                f32(slots), i32(slots),
            )
        if self.stream_migrate:
            # One slot's lane drops the slot axis: [nl, cache_len, ..].
            lane = kvcache.specs(self._layout, lane=True)
            self._lane_sharding = kvcache.shardings(
                self._layout, self.mesh, lane=True
            )
            # Slot export reads the live cache between decode steps — the
            # cache is NOT donated (the stream may stay resident if the
            # push fails and the batcher re-adopts it locally).
            self._slot_export_compiled = self._aot(
                "slot_export",
                self._wrap(_make_export(), (cache, rep), lane),
                (), table_s, i32(),
            )
            self._slot_import_compiled = self._aot(
                "slot_import",
                self._wrap(
                    _make_slot_import(),
                    (cache, rep, lane, rep, rep), (cache, rep),
                ),
                (0, 1), table_s, i32(slots),
                kvcache.structs(
                    self._layout, (self.cache_len,), self._lane_sharding,
                ),
                i32(), i32(),
            )
        self._load_grid()
        logger.info(
            "causal-LM engine ready: layout=%s slots=%d cache_len=%d "
            "buckets=%s tiers=%s chunk=%s pool_blocks=%s spec_k=%s "
            "decode_scratch_bytes=%s decode_aliased_bytes=%s "
            "(%d executables)",
            self.layout, slots, self.cache_len, self.buckets,
            self.batch_tiers, self.prefill_chunk_size or None,
            self.prefix_cache.n_blocks if self.prefix_cache else None,
            self.spec_tokens or None,
            self.decode_scratch_bytes, self.decode_aliased_bytes,
            len(self._prefill_compiled) + len(self._chunk_compiled) + 1
            + (1 if self.prefix_cache is not None else 0)
            + (2 if self._kv_transfer else 0) + n_spec_cells + n_mig_cells,
        )

    def _load_grid(self) -> None:
        """Execute every prefill (or chunk) cell and the step executables
        once, over padding rows and idle lanes, before anything is served.

        A compiled program's FIRST execution loads it onto the device, and
        with a large model that is not free: the first tier-2 admission of
        a long prompt, arriving with a hundred streams live, held all of
        them and the arrivals behind it for 0.3 s (PERF.md section 6,
        PR 35). Paid here it is half a second of start-up for the whole
        grid. A padding row's slot index is out of the pool and an idle
        lane's position is past the cache, so no slot is written; the
        staging buffers this allocates are the ones serving reuses. The
        page-moving cells (insert, export, import, slot export / import)
        still load at their first use."""
        zeros = np.zeros((self.slots,), np.int32)
        for T, L in self._prefill_compiled:
            pad = {"slot": self.slots, "input_ids": np.zeros((L,), np.int32)}
            self.fetch_step(self.prefill([pad] * T))
        for T, C in self._chunk_compiled:
            pad = {"slot": self.slots, "input_ids": np.zeros((C,), np.int32),
                   "start": 0, "n_tokens": C, "length": C}
            self.fetch_step(self.prefill_chunks([pad] * T))
        self.fetch_step(self.decode(
            zeros, zeros.astype(bool), zeros.astype(np.float32), zeros
        ))
        if self._verify_compiled is not None:
            self.fetch_step(self.verify(
                np.zeros((self.slots, self.spec_tokens), np.int32), zeros,
                zeros, zeros.astype(np.float32), zeros,
            ))

    def release_cache(self) -> None:
        """Give the slot table (and the page pool) back to the device; the
        engine serves nothing afterwards. For a caller that has closed its
        batcher and needs the room while the client, the batcher's threads
        or a status hook still point at the engine: the benchmark scores a
        hybrid model's streams against a float32 reference that does not fit
        beside 5 GB of cache (benchmarks/runners/serve_olmo_hybrid.py)."""
        for leaf in jax.tree.leaves((self._cache, getattr(self, "_pool", ()))):
            leaf.delete()

    @staticmethod
    def _serve_config(cfg, tp: int = 1, ep: int = 1, pp: int = 1):
        """Bind the decode model to the mesh's model axes — TP only. The
        slot cache has no expert routing and a pipelined decode step would
        bubble ~(pp-1)/pp of every token; both reject loudly at startup so
        shardcheck's sweep (SC002) sees a clean plan/serve/reject story."""
        if ep > 1:
            raise ValueError(
                f"expert axis of {ep}: the decode engine does not support "
                "expert parallelism (no MoE decoder variant)"
            )
        if pp > 1:
            raise ValueError(
                f"pipeline axis of {pp}: the decode engine does not support "
                "pipeline parallelism (a one-token step cannot fill a "
                "GPipe schedule)"
            )
        if tp > 1:
            if cfg.num_heads % tp or cfg.intermediate_size % tp:
                raise ValueError(
                    f"model axis of {tp} must divide num_heads "
                    f"({cfg.num_heads}) and intermediate_size "
                    f"({cfg.intermediate_size})"
                )
            cfg = dataclasses.replace(
                cfg, model_axis="model", model_parallel=tp
            )
        return cfg

    @staticmethod
    def _plan_prefix_cache(cfg, *, tp: int = 1, prefix_cache_mb: float = 0.0,
                           block_tokens: int = 16,
                           kv_dtype: str | None = None) -> tuple[int, int]:
        """Size + validate the prefix-page pool for this config/layout:
        ``(n_blocks, bytes_per_block)``. Raises ``ValueError`` loudly at
        startup (shardcheck's SC002 sweep crosses layouts with these
        configs) — a budget smaller than one block or a TP degree that
        cannot split the pages' head axis must never become a shape error
        mid-request."""
        if block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}"
            )
        if tp > 1 and cfg.num_heads % tp:
            raise ValueError(
                f"model axis of {tp} must divide num_heads "
                f"({cfg.num_heads}) to shard prefix-cache pages"
            )
        kv = normalize_quant_dtype(kv_dtype, "kv_dtype") \
            or str(np.dtype(cfg.dtype).name)
        bytes_per_block = block_tokens * kvcache.bytes_per_token(
            kvcache.cache_layout(cfg, kv)
        )
        n_blocks = int(prefix_cache_mb * 2**20 // bytes_per_block)
        if prefix_cache_mb > 0 and n_blocks < 1:
            raise ValueError(
                f"--prefix-cache-mb {prefix_cache_mb:g} holds no "
                f"{bytes_per_block}-byte block (num_layers="
                f"{cfg.num_layers}, block_tokens={block_tokens}, "
                f"hidden={cfg.hidden_size})"
            )
        return n_blocks, bytes_per_block

    @staticmethod
    def _plan_spec(cfg, *, tp: int = 1, spec_tokens: int = 0,
                   min_match: int = 2, max_new_tokens: int = 32) -> int:
        """Validate the speculation knobs for this config/layout and return
        the verify width ``k`` (0 = disabled). Raises ``ValueError`` loudly
        at startup (shardcheck's SC002 sweep crosses layouts with these
        configs, like ``_plan_prefix_cache``) — a draft window the cache or
        generation budget can never use must not wait for a request to
        fail. ``tp`` imposes no extra constraint beyond ``_serve_config``'s
        head-divisibility (the verify executable shards exactly like
        decode), but stays in the signature so the sweep exercises every
        layout through one call shape."""
        del tp
        if spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {spec_tokens}"
            )
        if spec_tokens == 0:
            return 0
        if min_match < 1:
            raise ValueError(
                f"spec min_match must be >= 1, got {min_match}"
            )
        if spec_tokens >= max_new_tokens:
            raise ValueError(
                f"spec_tokens {spec_tokens} >= max_new_tokens "
                f"{max_new_tokens}: a draft can never exceed the remaining "
                "generation budget"
            )
        if spec_tokens + 1 > cfg.max_position:
            raise ValueError(
                f"spec_tokens {spec_tokens} + 1 exceeds max_position "
                f"{cfg.max_position}"
            )
        return int(spec_tokens)

    @staticmethod
    def _plan_quant(cfg, *, tp: int = 1, weight_dtype: str | None = None,
                    kv_dtype: str | None = None) -> tuple[str, str]:
        """Validate the quantization knobs for this config/layout and
        return concrete ``(weight_dtype, kv_dtype)`` names (``None`` knobs
        resolve to the model's compute dtype). Raises ``ValueError`` loudly
        at startup — shardcheck's SC002 quant sweep crosses these with
        every serving layout, so an unsupported mode must reject cleanly
        here, never surface as an XLA error mid-request. ``tp`` imposes no
        extra constraint: packed ``_q8`` kernels shard exactly like the
        kernels they replace, weight scales are per-last-axis-channel (the
        axis TP splits, so each shard owns its scales), and KV scales drop
        the sharded head axes entirely."""
        del tp
        w = normalize_quant_dtype(weight_dtype, "weight_dtype")
        k = normalize_quant_dtype(kv_dtype, "kv_dtype")
        default = str(np.dtype(cfg.dtype).name)
        return (w or default, k or default)

    def kv_bytes_per_token(self) -> int:
        """Slot-cache bytes ONE more cached token occupies (K + V across
        the layers that keep positions, plus scales at int8; a ring and
        positionless state cost the same at any length) — the
        `serve_kv_bytes_per_token{dtype=}` gauge and DEPLOY.md's sizing math
        both read this."""
        return kvcache.bytes_per_token(self._layout)

    def _rep_struct(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._rep)

    def _wrap(self, fn, in_specs, out_specs):
        """shard_map an executable body over the model axis when sharded:
        a cache's leaves split as the layout says (per-shard gathers and
        scatters of pages stay local — no cross-shard page traffic),
        everything batch-like replicates (post-psum logits are identical
        across shards, so replicated outs are safe)."""
        if not self._model_sharded:
            return fn
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _aot(self, cell: str, fn, donate: tuple[int, ...], *args):
        """Lower ``fn`` at ``args`` and compile it as grid cell ``cell``,
        timed through ``_compile_cell`` like every other cell so
        ``/compilez`` and warm-fraction readiness gating see it."""
        return self._compile_cell(
            f"lm/{self.layout}/{cell}",
            lambda: jax.jit(fn, donate_argnums=donate).lower(*args).compile(),
        )

    # -- request surface ------------------------------------------------

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        raise RequestError(
            f"prompt length {length} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    def _chunk_bucket_for(self, n: int) -> int:
        for c in self._chunk_buckets:
            if n <= c:
                return c
        raise ValueError(
            f"chunk of {n} exceeds prefill_chunk_size "
            f"{self.prefill_chunk_size}"
        )

    def validate(self, payload: dict) -> None:
        ids = np.asarray(payload.get("input_ids", ()))
        if ids.ndim != 1 or ids.size == 0:
            raise RequestError("input_ids must be a non-empty 1-D id list")
        max_new = int(payload.get("max_new_tokens", self.max_new_tokens))
        if max_new < 1:
            raise RequestError("max_new_tokens must be >= 1")
        # Migration replay: ``resume_tokens`` are already-delivered
        # generated tokens the re-prefill treats as prompt suffix — the
        # effective prompt must bucket, and the stream must still owe
        # tokens (a fully-satisfied stream has nothing to resume).
        res = np.asarray(payload.get("resume_tokens", ()))
        if res.size and res.ndim != 1:
            raise RequestError("resume_tokens must be a 1-D id list")
        if res.size >= max_new:
            raise RequestError(
                f"resume_tokens of {res.size} already satisfy "
                f"max_new_tokens {max_new}: nothing left to generate"
            )
        if not self._chunked_mode:
            # Monolithic prefill pads the whole effective prompt into one
            # bucket executable; chunked mode splits it, so there the only
            # real bound is the cache-page check below (a migrated stream's
            # prompt + resumed prefix routinely exceeds the largest bucket).
            self.bucket_for(ids.shape[0] + res.size)
        if ids.shape[0] + max_new > self.cache_len:
            raise RequestError(
                f"prompt of {ids.shape[0]} + max_new_tokens {max_new} "
                f"exceeds the {self.cache_len}-token cache pages"
            )
        if float(payload.get("temperature", 0.0)) < 0.0:
            raise RequestError("temperature must be >= 0")
        # Priority scheduling (serve/batcher.py): class 0 is the most
        # urgent; deadline_ms is a TTFT deadline relative to enqueue that
        # EDF admission orders on (and preemption rescues).
        pri = payload.get("priority")
        if pri is not None:
            try:
                pri = int(pri)
            except (TypeError, ValueError):
                raise RequestError("priority must be an integer") from None
            if pri < 0:
                raise RequestError(f"priority must be >= 0, got {pri}")
        ddl = payload.get("deadline_ms")
        if ddl is not None:
            try:
                ddl = float(ddl)
            except (TypeError, ValueError):
                raise RequestError(
                    "deadline_ms must be a number of milliseconds"
                ) from None
            if not (ddl > 0.0):
                raise RequestError(
                    f"deadline_ms must be > 0, got {ddl}"
                )

    def request_bucket(self, payload: dict) -> int:
        n = np.asarray(payload["input_ids"]).shape[0]
        n += np.asarray(payload.get("resume_tokens", ())).size
        if self._chunked_mode and n > self.buckets[-1]:
            return self.buckets[-1]  # queue key only: chunks split the rest
        return self.bucket_for(n)

    # -- the two dispatch points (decode-loop thread only: both swap the
    # -- engine's device-state refs, which is single-writer by contract) --

    def prefill(self, admissions: list[dict]) -> InFlightBatch:
        """Admit up to a tier of requests into their assigned slots.

        ``admissions`` rows: ``{"slot", "input_ids", "temperature",
        "seed"}``. Returns without blocking; ``fetch_step`` yields the
        [tier]-shaped first-token vector (real rows = admitted order)."""
        if self._chunked_mode:
            raise RuntimeError(
                "engine compiled in chunked-prefill mode (prefix cache / "
                "prefill_chunk); admissions go through prefill_chunks"
            )
        if len(admissions) > self.max_batch:
            raise ValueError(
                f"admitting {len(admissions)} exceeds max_batch "
                f"{self.max_batch}"
            )
        lens = [np.asarray(a["input_ids"]).shape[0] for a in admissions]
        L = self.bucket_for(max(lens))
        T = self.tier_for(len(admissions))
        key = ("prefill", T, L)

        def _make():
            return (
                np.zeros((T, L), np.int32),
                np.zeros((T, L), bool),
                np.full((T,), self.slots, np.int32),
                np.zeros((T,), np.int32),
                np.zeros((T,), np.float32),
                np.zeros((T,), np.int32),
            )

        ids, mask, slot_ix, lengths, temps, seeds = buffers = (
            self._take_buffers(key, _make)
        )
        ids.fill(0)
        mask.fill(False)
        slot_ix.fill(self.slots)  # out-of-pool: padding rows scatter-drop
        lengths.fill(0)
        temps.fill(0.0)
        seeds.fill(0)
        for r, (a, l) in enumerate(zip(admissions, lens)):
            ids[r, :l] = np.asarray(a["input_ids"], np.int32)
            mask[r, :l] = True
            slot_ix[r] = int(a["slot"])
            lengths[r] = l
            temps[r] = float(a.get("temperature", 0.0))
            seeds[r] = int(a.get("seed", 0))
        mask[len(admissions):, 0] = True
        t_assembled = time.monotonic()
        self._cache, self._last_token, tok = self._prefill_compiled[T, L](
            self.params, self._cache, self._last_token,
            jax.device_put(ids, self._rep), jax.device_put(mask, self._rep),
            jax.device_put(slot_ix, self._rep),
            jax.device_put(lengths, self._rep),
            jax.device_put(temps, self._rep),
            jax.device_put(seeds, self._rep),
        )
        self._record_dispatch(T, L, len(admissions))
        return InFlightBatch(
            out={"tok": tok}, key=key, n=len(admissions),
            meta=[int(s) for s in slot_ix[: len(admissions)]],
            buffers=buffers, layout=self.layout, t_assembled=t_assembled,
        )

    def prefill_chunks(self, rows: list[dict]) -> InFlightBatch:
        """Dispatch ONE prefill chunk for up to a tier of admitted slots.

        ``rows``: ``{"slot", "input_ids" (the FULL prompt), "start",
        "n_tokens", "length", "chain" (pool block ids — non-empty only on
        a row's first chunk, when its matched prefix gathers),
        "temperature", "seed"}``. The executable slices nothing: the host
        stages ``input_ids[start : start + n_tokens]`` per row, pads to
        the smallest (tier, chunk-bucket) cell, and rows whose chunk
        completes the prompt sample their first token on-device (rows
        mid-prompt return garbage lanes the batcher ignores)."""
        if not self._chunked_mode:
            raise RuntimeError(
                "prefill_chunks needs chunked mode (prefix_cache_mb or "
                "prefill_chunk at construction)"
            )
        if len(rows) > self.max_batch:
            raise ValueError(
                f"admitting {len(rows)} exceeds max_batch {self.max_batch}"
            )
        T = self.tier_for(len(rows))
        C = self._chunk_bucket_for(max(int(r["n_tokens"]) for r in rows))
        M = self._max_chain
        key = ("chunk", T, C)

        def _make():
            return (
                np.zeros((T, C), np.int32),
                np.zeros((T,), np.int32),
                np.zeros((T,), np.int32),
                np.zeros((T, M), np.int32),
                np.zeros((T,), np.int32),
                np.full((T,), self.slots, np.int32),
                np.zeros((T,), np.float32),
                np.zeros((T,), np.int32),
            )

        ids, starts, lengths, chain, n_gather, slot_ix, temps, seeds = (
            buffers
        ) = self._take_buffers(key, _make)
        ids.fill(0)
        starts.fill(0)
        lengths.fill(0)
        chain.fill(0)
        n_gather.fill(0)
        slot_ix.fill(self.slots)  # out-of-pool: padding rows scatter-drop
        temps.fill(0.0)
        seeds.fill(0)
        for r, row in enumerate(rows):
            s0, n = int(row["start"]), int(row["n_tokens"])
            ids[r, :n] = np.asarray(
                row["input_ids"][s0:s0 + n], np.int32
            )
            starts[r] = s0
            lengths[r] = int(row["length"])
            blocks = row.get("chain") or ()
            if len(blocks) > M:
                raise ValueError(
                    f"prefix chain of {len(blocks)} exceeds max chain {M}"
                )
            chain[r, :len(blocks)] = blocks
            n_gather[r] = len(blocks)
            slot_ix[r] = int(row["slot"])
            temps[r] = float(row.get("temperature", 0.0))
            seeds[r] = int(row.get("seed", 0))
        t_assembled = time.monotonic()
        self._cache, self._last_token, tok = self._chunk_compiled[T, C](
            self.params, self._cache, self._last_token, self._pool,
            jax.device_put(ids, self._rep),
            jax.device_put(starts, self._rep),
            jax.device_put(lengths, self._rep),
            jax.device_put(chain, self._rep),
            jax.device_put(n_gather, self._rep),
            jax.device_put(slot_ix, self._rep),
            jax.device_put(temps, self._rep),
            jax.device_put(seeds, self._rep),
        )
        self._record_dispatch(T, C, len(rows))
        return InFlightBatch(
            out={"tok": tok}, key=key, n=len(rows),
            meta=[int(s) for s in slot_ix[: len(rows)]],
            buffers=buffers, layout=self.layout, t_assembled=t_assembled,
        )

    def insert_prefix(self, slot: int, blocks: list[tuple[int, int]]) -> None:
        """Publish a fully-prefilled slot's prefix pages into the pool:
        ``blocks`` are ``(block_id, block_index)`` pairs from
        ``KVBlockPool.insert``. Dispatch-only (nothing to fetch — the
        batcher never blocks on it); stream order guarantees the pages
        hold the prompt's K/V before any later chunk can gather them."""
        if self.prefix_cache is None:
            raise RuntimeError("engine has no prefix cache")
        M = self._max_chain
        if len(blocks) > M:
            raise ValueError(
                f"inserting {len(blocks)} blocks exceeds max chain {M}"
            )
        ids = np.full((M,), self._pool_blocks, np.int32)  # sentinel: drop
        pos = np.zeros((M,), np.int32)
        for j, (bid, bix) in enumerate(blocks):
            ids[j] = int(bid)
            pos[j] = int(bix)
        args = (
            jax.device_put(np.int32(slot), self._rep),
            jax.device_put(ids, self._rep),
            jax.device_put(pos, self._rep),
        )
        with self._pool_lock:
            self._pool, self._cache = self._insert_compiled(
                self._pool, self._cache, *args
            )
            self.prefix_cache.mark_published(bid for bid, _ in blocks)
            self._pool_lock.notify_all()

    # -- disaggregated-serving page transfer (serve/disagg.py) ----------

    def export_prefix_pages(self, blocks: list[int]):
        """Gather published pool pages for a PINNED chain of block ids:
        returns device arrays ``[nl, max_chain, block_tokens, heads,
        head_dim]`` (k, v) — the chain's pages in order, pad lanes
        repeating block 0 (the importer's sentinel ids drop them).
        Requires ``kv_transfer=True`` at construction (the AOT export
        cell — same no-trace rule as every other dispatch).

        Safe OFF the decode-loop thread, unlike every dispatch method: it
        never swaps the engine's device-state refs, and the caller holds
        a ``KVBlockPool.match`` pin, so the gathered blocks hold the
        prompt's bytes for the duration. ``_pool_lock`` orders the read
        against a concurrent publish, which donates the pool buffer and
        rebinds the ref on the loop thread, and the gather waits for the
        publish of any block the caller matched while it was only
        indexed."""
        if self._export_compiled is None:
            raise RuntimeError(
                "engine built without kv_transfer=True (no pool-export "
                "cell)"
            )
        M = self._max_chain
        if len(blocks) > M:
            raise ValueError(
                f"exporting {len(blocks)} blocks exceeds max chain {M}"
            )
        idx = np.zeros((M,), np.int32)
        idx[: len(blocks)] = blocks
        jdx = jax.device_put(idx, self._rep)
        with self._pool_lock:
            if not self._pool_lock.wait_for(
                lambda: not self.prefix_cache.unpublished(blocks),
                timeout=30.0,
            ):
                raise RuntimeError(
                    f"blocks {list(blocks)} are indexed but their pages "
                    "were never published to the device pool"
                )
            return kvcache.split_kv(
                self._export_compiled(self._pool, jdx),
                self.model.cfg.num_heads,
            )

    def import_prefix_pages(
        self, blocks: list[tuple[int, int]], pages_k, pages_v
    ) -> None:
        """Adopt transferred KV pages into this engine's prefix pool:
        ``blocks`` are ``(block_id, chain_index)`` pairs from
        ``KVBlockPool.insert`` on THIS engine's pool — chain_index picks
        the page lane out of the received stage (a chain partially cached
        here imports only its new blocks); ``pages_*`` are ``[nl,
        max_chain, block_tokens, heads, head_dim]`` stages (host numpy
        from the wire path, or device arrays from the D2D path).
        Decode-loop thread only — it swaps the pool refs, like
        ``insert_prefix``; dispatch-only, nothing to fetch. Requires
        ``kv_transfer=True`` at construction (the AOT import cell)."""
        if self._import_compiled is None:
            raise RuntimeError(
                "engine built without kv_transfer=True (no pool-import "
                "cell)"
            )
        M = self._max_chain
        if len(blocks) > M:
            raise ValueError(
                f"importing {len(blocks)} blocks exceeds max chain {M}"
            )
        ids = np.full((M,), self._pool_blocks, np.int32)  # sentinel: drop
        for bid, cix in blocks:
            if not 0 <= int(cix) < M:
                raise ValueError(
                    f"chain index {cix} outside the {M}-lane page stage"
                )
            ids[int(cix)] = int(bid)
        args = (
            jax.device_put(
                kvcache.join_kv(pages_k, pages_v), self._cache_sharding
            ),
            jax.device_put(ids, self._rep),
        )
        with self._pool_lock:
            self._pool = self._import_compiled(self._pool, *args)
            self.prefix_cache.mark_published(bid for bid, _ in blocks)
            self._pool_lock.notify_all()

    def page_meta(self) -> dict:
        """Static page-geometry digest the wire format stamps into its
        header (serve/disagg.py) — two pools are transfer-compatible iff
        these match."""
        if self.prefix_cache is None:
            raise RuntimeError("engine has no prefix cache")
        return {
            "block_tokens": int(self.block_tokens),
            **kvcache.page_geometry(self.model.cfg, self._layout),
            "max_chain": int(self._max_chain),
        }

    # -- live-stream migration (serve/disagg.py stream wire) ------------

    def export_slot_pages(self, slot: int):
        """Checkpoint ONE live slot's KV lane: returns device arrays
        ``[nl, cache_len, heads, head_dim]`` (k, v). Decode-loop thread
        only, between dispatches with nothing in flight — the batcher's
        ``export_streams`` guarantees the lane is settled, so unlike
        ``export_prefix_pages`` there is no donation race to retry.
        Requires ``stream_migrate=True`` at construction."""
        if self._slot_export_compiled is None:
            raise RuntimeError(
                "engine built without stream_migrate=True (no slot-export "
                "cell)"
            )
        return kvcache.split_kv(
            self._slot_export_compiled(
                self._cache, jax.device_put(np.int32(slot), self._rep)
            ),
            self.model.cfg.num_heads,
        )

    def import_slot_pages(self, slot: int, pages_k, pages_v,
                          last_token: int) -> None:
        """Adopt a migrated stream's KV lane into ``slot`` and seed
        ``last_token[slot]`` so the next decode step continues the
        generation. ``pages_*`` are full ``[nl, cache_len, heads,
        head_dim]`` stages (the wire path pads short payloads back up —
        trailing positions are dead weight the causal mask never reads).
        Decode-loop thread only: swaps the cache refs like every
        dispatch. Requires ``stream_migrate=True`` at construction."""
        if self._slot_import_compiled is None:
            raise RuntimeError(
                "engine built without stream_migrate=True (no slot-import "
                "cell)"
            )
        self._cache, self._last_token = self._slot_import_compiled(
            self._cache, self._last_token,
            jax.device_put(
                kvcache.join_kv(pages_k, pages_v), self._lane_sharding
            ),
            jax.device_put(np.int32(slot), self._rep),
            jax.device_put(np.int32(last_token), self._rep),
        )

    def stream_page_meta(self) -> dict:
        """Slot-lane geometry digest the stream wire format stamps into
        its header — two engines can ship live streams between each other
        iff these match (``cache_len`` may differ: the receiver re-pads,
        refusing only streams longer than its own lanes)."""
        return {
            "cache_len": int(self.cache_len),
            **kvcache.page_geometry(self.model.cfg, self._layout),
        }

    def decode(self, lengths, active, temps, seeds) -> InFlightBatch:
        """Dispatch ONE decode step over the full slot table (host arrays
        are snapshots; the batcher advances its lengths at dispatch so
        steps pipeline). Returns without blocking.

        The step's inputs stay on the device: the program hands them back
        advanced by the step, and where the planned arrays equal that — the
        plan held, every live lane one longer — the step runs on them and
        uploads nothing. Any other plan (an admission, a finish, a lane back
        from verify, a new temperature or seed) is one upload. The
        comparison is exact, so a reused operand is the upload bit for bit;
        the span counter ``inputs_uploaded`` says which it was."""
        key = ("decode",)

        def _make():
            return (np.zeros((4, self.slots), np.int32),)

        (plan,) = buffers = self._take_buffers(key, _make)
        blen, bact = plan[0], plan[1]
        np.copyto(blen, lengths)
        np.copyto(bact, active)
        np.copyto(plan[2].view(np.float32), temps)
        np.copyto(plan[3], seeds)
        upload = self._step_mirror is None or not np.array_equal(
            plan, self._step_mirror
        )
        t_assembled = time.monotonic()
        step = jax.device_put(plan, self._rep) if upload else self._step_inputs
        # donated: nothing is on the device until the call returns
        self._step_inputs = self._step_mirror = None
        (self._cache, self._last_token, tok,
         self._step_inputs) = self._decode_compiled(
            self.params, self._cache, self._last_token, step,
        )
        self._step_mirror = plan.copy()
        self._step_mirror[0] += bact
        n = int(np.sum(bact))
        moved = {name: n * one for name, one in self._writes_per_lane.items()}
        moved["inputs_uploaded"] = int(upload)
        if self._prefix_reads or self._whole_reads:
            # as the step sees them: position + 1, and 0 for an idle lane
            seen = np.where(bact, np.minimum(blen, self.cache_len - 1) + 1, 0)
            moved.update(kvcache.step_reads(self._prefix_reads, seen))
            moved.update(kvcache.step_positions(self._whole_reads, seen))
        return InFlightBatch(
            out={"tok": tok}, key=key, n=n, meta=None,
            buffers=buffers, layout=self.layout, t_assembled=t_assembled,
            moved=moved,
        )

    def verify(self, drafts, lengths, n_input, temps, seeds) -> InFlightBatch:
        """Dispatch ONE speculative verify step over the full slot table.

        ``drafts [slots, k]``: host-proposed candidate tokens;
        ``n_input``: drafted+1 for verifying lanes, 0 for everyone else
        (idle slots AND slots riding the plain-decode path this step).
        Unlike ``decode``, the batcher advances a verifying slot's length
        at FETCH, not dispatch — the accepted count is data-dependent — so
        a verifying slot never re-dispatches until its verdict lands.
        Returns without blocking; ``fetch_step`` yields the [slots, k+1]
        sampled-token matrix (the host re-derives the accepted prefix from
        its own drafts)."""
        if self._verify_compiled is None:
            raise RuntimeError(
                "engine built without speculation (spec_tokens=0)"
            )
        key = ("verify",)

        def _make():
            s = self.slots
            return (
                np.zeros((s, self.spec_tokens), np.int32),
                np.zeros((s,), np.int32),
                np.zeros((s,), np.int32),
                np.zeros((s,), np.float32),
                np.zeros((s,), np.int32),
            )

        bdr, blen, bnin, btmp, bseed = buffers = self._take_buffers(
            key, _make
        )
        np.copyto(bdr, drafts)
        np.copyto(blen, lengths)
        np.copyto(bnin, n_input)
        np.copyto(btmp, temps)
        np.copyto(bseed, seeds)
        t_assembled = time.monotonic()
        self._cache, self._last_token, tok = self._verify_compiled(
            self.params, self._cache, self._last_token,
            jax.device_put(bdr, self._rep), jax.device_put(blen, self._rep),
            jax.device_put(bnin, self._rep), jax.device_put(btmp, self._rep),
            jax.device_put(bseed, self._rep),
        )
        return InFlightBatch(
            out={"tok": tok}, key=key, n=int(np.sum(bnin > 0)), meta=None,
            buffers=buffers, layout=self.layout, t_assembled=t_assembled,
        )

    def fetch_step(self, inflight: InFlightBatch) -> np.ndarray:
        """Block on a step's sampled-token vector — or a verify step's
        [slots, k+1] token matrix — the ONLY device_get on the decode path
        (everything else stays resident; analysis/baseline.json designates
        this method for JL003, and the verify path reuses it rather than
        growing a second blocking point)."""
        tok = np.asarray(jax.device_get(inflight.out["tok"]))
        inflight.t_got = time.monotonic()
        self._give_buffers(inflight.key, inflight.buffers)
        return tok


class ImageClassifierEngine(_AotEngine):
    """Top-k classification over a trained image-classifier checkpoint
    (LeNet/ResNet/Inception — anything with ``apply(vars, image,
    train=False) -> logits``).

    Request payload: ``image`` ``[H, W, C]`` float32 at the engine's
    geometry (the model's training geometry — there is one image "bucket").
    Response: ``top_ids [k]``, ``top_probs [k]``.
    """

    def __init__(
        self,
        model,
        params,
        model_state=None,
        mesh=None,
        *,
        image_shape: tuple[int, int, int],
        max_batch: int = 8,
        batch_tiers: tuple[int, ...] | None = None,
        top_k: int = 5,
        memory=None,
    ):
        super().__init__(mesh, max_batch, batch_tiers, memory=memory)
        self.model = model
        self.image_shape = tuple(image_shape)
        self.top_k = top_k
        self.variables = self._place(
            {"params": params, **(model_state or {})}
        )
        self.memory.register_tree("image_params", self.variables)
        self._plan_cells(len(self.batch_tiers))
        self._compiled = {
            T: self._compile_cell(
                f"image/{self.layout}/t{T}",
                lambda T=T: (
                    jax.jit(self._forward)
                    .lower(
                        self.variables,
                        self._struct((T, *self.image_shape), jnp.float32, T),
                    )
                    .compile()
                ),
            )
            for T in self.batch_tiers
        }
        logger.info(
            "image engine ready: shape=%s tiers=%s top_k=%d",
            self.image_shape, self.batch_tiers, top_k,
        )

    def _forward(self, variables, image):
        logits = self.model.apply(variables, image, train=False)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        k = min(self.top_k, probs.shape[-1])
        top_probs, top_ids = jax.lax.top_k(probs, k)
        return {"top_ids": top_ids.astype(jnp.int32), "top_probs": top_probs}

    def validate(self, payload: dict) -> None:
        img = np.asarray(payload.get("image", ()))
        if img.shape != self.image_shape:
            raise RequestError(
                f"image shape {img.shape} != engine geometry {self.image_shape}"
            )

    def request_bucket(self, payload: dict) -> int:
        return 0  # one geometry: every request shares the single bucket

    def dispatch(self, payloads: list[dict]) -> InFlightBatch:
        if len(payloads) > self.max_batch:
            raise ValueError(
                f"batch of {len(payloads)} exceeds max_batch {self.max_batch}"
            )
        T = self.tier_for(len(payloads))

        def _make():
            return (np.zeros((T, *self.image_shape), np.float32),)

        (imgs,) = buffers = self._take_buffers((T,), _make)
        imgs.fill(0.0)
        for r, p in enumerate(payloads):
            imgs[r] = np.asarray(p["image"], np.float32)
        t_assembled = time.monotonic()
        out = self._compiled[T](self.variables, self._put(imgs, T))
        self._record_dispatch(T, None, len(payloads))
        return InFlightBatch(
            out=out, key=(T,), n=len(payloads), meta=[], buffers=buffers,
            layout=self.layout, t_assembled=t_assembled,
        )

    def fetch(self, inflight: InFlightBatch) -> list[dict]:
        out = jax.device_get(inflight.out)
        inflight.t_got = time.monotonic()
        self._give_buffers(inflight.key, inflight.buffers)
        return [
            {"top_ids": out["top_ids"][r], "top_probs": out["top_probs"][r]}
            for r in range(inflight.n)
        ]
