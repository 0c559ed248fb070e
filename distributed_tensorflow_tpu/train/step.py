"""The compiled SPMD train step — sync-DP, async-stale-DP, and eval.

This one module supersedes all three data-parallel flavors of the reference
(SURVEY.md §2 parallelism inventory):

- **sync PS** (``SyncReplicasOptimizer``, SURVEY.md §3b) and **sync NCCL
  allreduce** (SURVEY.md §3d) both become ``mode="sync"``: gradients are
  ``lax.pmean``'d across the DP mesh axes inside the compiled step. The
  accumulators, chief token queue, and worker barrier are implied by the
  AllReduce; the NCCL ring becomes the ICI ring XLA lowers psum onto.
- **async PS with stale gradients** (SURVEY.md §3c) becomes
  ``mode="stale"``: a deterministic K-step delayed-gradient ring buffer.
  True PS asynchrony (races on variable state) cannot exist under SPMD —
  the emulation preserves the *statistical* property the workload stresses
  (updates computed against K-step-old information) while staying
  reproducible and testable. The divergence is documented, deliberate, and
  strictly better for debugging (SURVEY.md §7 hard-part 1).

Design notes (TPU-first):
- The step is built with ``shard_map`` over the mesh so every collective is
  explicit, then ``jit``'d with buffer donation: params/opt-state update in
  place in HBM, and XLA fuses the pmean into the backward pass.
- Loss functions should compute in bf16 where possible and return f32
  scalars; the engine does not impose a dtype policy.
- Nothing in the step depends on Python-level step count or data values —
  one trace, one executable, zero retraces across the run.
"""

from __future__ import annotations

from collections.abc import Callable
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel import collectives as coll
from distributed_tensorflow_tpu.parallel.mesh import batch_pspec, data_axes
from distributed_tensorflow_tpu.train.state import TrainState

# loss_fn(params, model_state, batch, rng) -> (loss, (new_model_state, metrics))
LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, tuple[Any, dict]]]


def _spec_axes(spec) -> tuple[str, ...]:
    """Flatten a PartitionSpec's entries into the mesh axis names it uses."""
    return tuple(
        a
        for entry in (spec or ())
        if entry is not None
        for a in ((entry,) if isinstance(entry, str) else tuple(entry))
    )


def _batch_dim_axes(batch_spec) -> set[str]:
    """Mesh axes the batch's LEADING dim is sharded over, across all leaves.

    Under the GShard token-sharded MoE layout the batch rows split over the
    ``expert`` axis in addition to the DP axes (data/text.py
    ``bert_batch_specs(expert_sharded=True)``); the engine must then reduce
    metrics/model_state over that axis too — it carries data, like DP.
    """
    axes: set[str] = set()
    for s in jax.tree.leaves(
        batch_spec, is_leaf=lambda x: isinstance(x, P)
    ):
        if isinstance(s, P) and len(s) and s[0] is not None:
            entry = s[0]
            axes |= set((entry,) if isinstance(entry, str) else tuple(entry))
    return axes


def _extra_batch_axes(batch_spec, dp_axes) -> tuple[str, ...]:
    """Non-DP mesh axes carrying batch rows (data-like reductions apply).

    Shared by the train and eval steps so their notion of "data-carrying
    axis" can never diverge.
    """
    return tuple(
        a
        for a in ("pipeline", "expert", "model")
        if a in _batch_dim_axes(batch_spec) and a not in dp_axes
    )


def make_rng(seed: int, impl: str = "auto") -> jax.Array:
    """The per-step rng key under the framework's PRNG policy.

    ``"auto"`` = rbg on TPU (the counter-based hardware generator; dropout
    bit generation via software threefry measured +36 ms/step on BERT-base
    L=512 b=48 — docs/PERF.md r5 — and the reference's TF dropout used the
    same Philox family), threefry elsewhere (bit-stable across versions and
    backends). One definition shared by the CLI trainer and every benchmark
    so "the benched step is the production step" stays true by
    construction.
    """
    if impl == "auto":
        impl = "rbg" if jax.devices()[0].platform == "tpu" else "threefry2x32"
    elif impl == "threefry":
        impl = "threefry2x32"
    return jax.random.key(seed, impl=impl)


def make_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    mesh,
    *,
    mode: str = "sync",
    staleness: int = 0,
    batch_spec: P | None = None,
    state_specs: "TrainState | None" = None,
    clip_norm: float = 0.0,
    donate: bool = True,
    grad_accum: int = 1,
):
    """Build the compiled ``train_step(state, batch, rng) -> (state, metrics)``.

    Args:
      loss_fn: ``(params, model_state, batch, rng) -> (loss, (model_state,
        metrics))``. Runs on the per-device batch shard; the engine averages
        gradients/metrics/model_state across the DP axes.
      tx: optax transformation (the inner optimizer the reference would wrap
        in SyncReplicasOptimizer, SURVEY.md §1 L4).
      mesh: the device mesh; DP axes are ``("replica", "data")`` ∩ mesh axes.
      mode: ``"sync"`` or ``"stale"`` (K-step delayed gradients).
      staleness: K for ``mode="stale"``; state must be created with the same K.
      batch_spec: PartitionSpec for batch leaves; default: leading dim over
        the DP axes (replicated along any other mesh axes).
      state_specs: a :class:`TrainState` pytree of PartitionSpecs for runs
        with sharded params (see :func:`make_state_specs`); default fully
        replicated. With a ``"model"`` (tensor-parallel), ``"pipeline"``
        (stage-sharded stack), or ``"expert"`` (MoE) mesh axis, the engine resolves the grad
        contract per leaf: axis-sharded leaves keep their local grad
        (scaled 1/t for the psum-transpose factor), replicated leaves pmean
        their partial grads across that axis — verified against unsharded
        models in tests/test_bert_tp.py and tests/test_pipeline.py.
      clip_norm: > 0 enables global-norm gradient clipping INSIDE the step.
        Clipping must live here, not in an ``optax.clip_by_global_norm``
        chained into ``tx``: inside shard_map each shard's grad leaves hold
        only the local slice of model/pipeline/expert-sharded params, so an
        optax-side "global" norm — and hence the clip scale — differs per
        shard, and replicated leaves silently desynchronize across shards.
        The engine computes the spec-aware global norm (sharded-leaf squared
        norms psum'd over their sharding axes) and applies one identical
        scale everywhere. Semantics match optax.clip_by_global_norm.
      donate: donate state buffers so params update in place in HBM.
      grad_accum: > 1 splits each device's batch rows into that many
        micro-slices and accumulates their gradients in one lax.scan
        BEFORE the DP/shard-axis reductions (which are linear, so the
        grad contract is untouched) — the standard big-global-batch lever
        when activations for the full per-device batch don't fit
        (composes with --remat). Semantics, stated: the accumulated grad
        is the MEAN of per-slice grads — exactly the full-batch grad for
        row-mean losses (pinned in tests/test_grad_accum.py), and the
        conventional mean-of-ratios for ratio-normalized losses like
        BERT's MLM (each slice normalizes by its own masked-token count).
        Dropout draws fold a per-slice rng (same distribution, different
        draws than the unsliced step); batch-norm models see per-slice
        batch statistics with EMAs averaged — the same ghost-BN semantics
        the DP axes already have (models/resnet.py).
    """
    if mode not in ("sync", "stale"):
        raise ValueError(f"mode must be 'sync' or 'stale', got {mode!r}")
    if mode == "stale" and staleness < 1:
        raise ValueError("mode='stale' requires staleness >= 1")
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    dp_axes = data_axes(mesh)
    if batch_spec is None:
        batch_spec = batch_pspec(mesh)
    # Non-DP axes the batch rows are split over (the expert axis under the
    # token-sharded MoE layout) reduce metrics/model_state like DP axes; the
    # GRAD contract needs no change — the per-leaf shard-axis loop below
    # already pmeans replicated leaves over those axes and scales sharded
    # leaves 1/t.
    extra_batch_axes = _extra_batch_axes(batch_spec, dp_axes)
    metric_axes = tuple(dp_axes) + extra_batch_axes
    if state_specs is None:
        state_spec_tree = P()
        param_specs = None
    else:
        state_spec_tree = state_specs
        param_specs = state_specs.params

    def per_device_step(state: TrainState, batch, rng: jax.Array):
        if mode == "stale":
            # Trace-time state validation: XLA clamps out-of-range dynamic
            # indices silently, so a buffer/staleness mismatch would corrupt
            # training with no error. Shapes are static — check here.
            if state.grad_buffer is None:
                raise ValueError(
                    "mode='stale' needs a state built with create_train_state"
                    f"(..., staleness={staleness})"
                )
            depth = jax.tree.leaves(state.grad_buffer)[0].shape[0]
            if depth != staleness:
                raise ValueError(
                    f"state.grad_buffer depth {depth} != staleness {staleness}"
                )
        # Per-device RNG: fold in the global step and the device's coordinate
        # along every batch-sharding axis (DP axes, any non-DP row-carrying
        # axis like "expert" under the token-sharded MoE layout, and "seq"
        # under sequence parallelism) so dropout/augmentation is iid per
        # step and per shard — without the fold, shards along that axis
        # would draw the SAME dropout mask for different data.
        rng = jax.random.fold_in(rng, state.step)
        rng_axes = (
            list(dp_axes)
            + list(extra_batch_axes)
            + (["seq"] if "seq" in mesh.axis_names else [])
        )
        for ax in rng_axes:
            rng = jax.random.fold_in(rng, lax.axis_index(ax))

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        if grad_accum > 1:
            rows = jax.tree.leaves(batch)[0].shape[0]
            if rows % grad_accum:
                raise ValueError(
                    f"per-device batch rows {rows} not divisible by "
                    f"grad_accum {grad_accum}"
                )
            micro = jax.tree.map(
                lambda x: x.reshape((grad_accum, rows // grad_accum) + x.shape[1:]),
                batch,
            )

            def accum_body(carry, mb_a):
                mb, a = mb_a
                (loss_a, (ms_a, metrics_a)), g_a = grad_fn(
                    state.params,
                    state.model_state,
                    mb,
                    jax.random.fold_in(rng, a),
                )
                g_sum, l_sum, ms_sum, m_sum = carry
                g_sum = jax.tree.map(jnp.add, g_sum, g_a)
                ms_sum = jax.tree.map(jnp.add, ms_sum, ms_a)
                m_sum = jax.tree.map(jnp.add, m_sum, dict(metrics_a))
                return (g_sum, l_sum + loss_a, ms_sum, m_sum), None

            # One probe trace sizes the carry zeros (shapes only, no FLOPs
            # at runtime — eval_shape never executes).
            shapes = jax.eval_shape(
                grad_fn,
                state.params,
                state.model_state,
                jax.tree.map(lambda x: x[0], micro),
                rng,
            )
            (_, (ms_shape, metric_shape)), g_shape = shapes
            zeros = lambda t: jax.tree.map(  # noqa: E731
                lambda s: jnp.zeros(s.shape, s.dtype), t
            )
            init = (
                zeros(g_shape),
                jnp.zeros((), jnp.float32),
                zeros(ms_shape),
                zeros(dict(metric_shape)),
            )
            (g_sum, l_sum, ms_sum, m_sum), _ = lax.scan(
                accum_body, init, (micro, jnp.arange(grad_accum))
            )
            inv = 1.0 / grad_accum

            def _slice_mean(leaf):
                # Inexact leaves average in f32 (casting 1/ga to the leaf
                # dtype would be fine for floats but ROUNDS TO ZERO for any
                # integer leaf, silently zeroing it); integer leaves — e.g.
                # a future count metric — stay as the accumulated SUM, the
                # only mean-free reduction that keeps them meaningful.
                if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                    return leaf
                return (leaf.astype(jnp.float32) * inv).astype(leaf.dtype)

            grads = jax.tree.map(_slice_mean, g_sum)
            loss = l_sum * inv
            model_state = jax.tree.map(_slice_mean, ms_sum)
            metrics = jax.tree.map(_slice_mean, m_sum)
        else:
            (loss, (model_state, metrics)), grads = grad_fn(
                state.params, state.model_state, batch, rng
            )
        metrics = dict(metrics)
        metrics["loss"] = loss

        with jax.named_scope("grad_reduce"):
            for shard_axis in ("model", "pipeline", "expert"):
                if shard_axis not in mesh.axis_names:
                    continue
                # Param-sharded-axis grad contract (mirrors the seq contract
                # below, but per-leaf; applies to tensor AND pipeline
                # parallelism): forward psums over the axis (row-parallel TP
                # outputs; the pipeline's last-stage output broadcast) transpose
                # to psums (check_vma=False), so every grad path through the
                # sharded branches carries one factor of t = |axis|. Sharded
                # leaves hold their LOCAL slice's grad — scale it 1/t;
                # replicated leaves hold t x their local partial — pmean sums
                # the partials and removes the factor in one collective.
                # Verified against unsharded models in tests/test_bert_tp.py
                # and tests/test_pipeline.py.
                t = mesh.shape[shard_axis]

                def _fix(g, spec, axis=shard_axis, t=t):
                    if axis in _spec_axes(spec):
                        return g / t
                    return lax.pmean(g, axis)

                if param_specs is None:
                    grads = jax.tree.map(
                        lambda g, axis=shard_axis: lax.pmean(g, axis), grads
                    )
                else:
                    grads = jax.tree.map(_fix, grads, param_specs)
            if "seq" in mesh.axis_names:
                # Sequence-parallel contract: the loss_fn must return the
                # *global* scalar on every seq shard (psum its numerator/
                # denominator over "seq" — see models/bert.py). Under shard_map
                # without replication tracking (check_vma=False), psum transposes
                # to psum, so each shard's backward already carries the global
                # cotangent and every param-grad path picks up exactly one factor
                # of the ring size — whether the path crosses a loss psum
                # (partitioned compute) or is shard-replicated (post-psum heads).
                # pmean removes that uniform factor exactly; verified against the
                # dense model in tests/test_bert.py.
                grads = coll.pmean_tree(grads, "seq")
            if dp_axes:
                # THE sync point: one fused AllReduce over ICI replaces the
                # reference's entire ps round-trip / NCCL ring (SURVEY.md §3b/3d).
                grads = coll.pmean_tree(grads, dp_axes)
            if metric_axes:
                metrics = coll.pmean_tree(metrics, metric_axes)
                if model_state:
                    model_state = coll.pmean_tree(model_state, metric_axes)

        new_buffer, new_index = state.grad_buffer, state.buffer_index
        if mode == "stale":
            # Ring buffer: apply the gradient from K steps ago, store the
            # fresh one in its slot — the deterministic image of async-PS
            # staleness (SURVEY.md §3c: "updates computed against stale
            # weights"; here the staleness is exactly K instead of a race).
            idx = state.buffer_index
            apply_grads = jax.tree.map(
                lambda buf: lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False),
                state.grad_buffer,
            )
            new_buffer = jax.tree.map(
                lambda buf, g: lax.dynamic_update_index_in_dim(
                    buf, g.astype(buf.dtype), idx, 0
                ),
                state.grad_buffer,
                grads,
            )
            new_index = (idx + 1) % staleness
            grads = apply_grads
            metrics["staleness"] = jnp.asarray(staleness, jnp.float32)

        with jax.named_scope("clip"):
            shard_axes = tuple(
                a for a in ("model", "pipeline", "expert") if a in mesh.axis_names
            )
            if param_specs is not None and shard_axes:
                # Sharded leaves hold only this shard's slice: psum their
                # squared norms over the sharding axes so grad_norm is the
                # GLOBAL norm on every shard (out_specs=P() would otherwise
                # surface one shard's partial value).
                def _sq(g, spec):
                    s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                    axes = _spec_axes(spec)
                    for ax in shard_axes:
                        if ax in axes:
                            s = lax.psum(s, ax)
                    return s

                total = sum(jax.tree.leaves(jax.tree.map(_sq, grads, param_specs)))
                grad_norm = jnp.sqrt(total)
            else:
                grad_norm = coll.global_norm(grads)
            if clip_norm > 0:
                # Spec-aware global-norm clipping (see the docstring): one scale,
                # identical on every shard, from the true global norm. Same
                # trust-ratio form as optax.clip_by_global_norm.
                scale = clip_norm / jnp.maximum(grad_norm, clip_norm)
                grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)

        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        metrics["grad_norm"] = grad_norm

        new_state = TrainState(
            step=state.step + 1,
            params=params,
            opt_state=opt_state,
            model_state=model_state,
            grad_buffer=new_buffer,
            buffer_index=new_index,
        )
        return new_state, metrics

    # State/rng replicated; batch sharded over DP axes. Outputs replicated —
    # identical on every device by construction (same reduced grads, same
    # update), which is exactly the post-allreduce invariant of SURVEY.md §3d.
    smapped = jax.shard_map(
        per_device_step,
        mesh=mesh,
        in_specs=(state_spec_tree, batch_spec, P()),
        out_specs=(state_spec_tree, P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0,) if donate else ())


def make_eval_step(
    metric_fn: Callable[[Any, Any, Any], dict],
    mesh,
    *,
    batch_spec: P | None = None,
    state_specs: "TrainState | None" = None,
    return_sums: bool = False,
):
    """Build ``eval_step(state, batch) -> metrics`` (metrics reduced over DP).

    ``metric_fn(params, model_state, batch) -> dict`` runs on the shard.
    Plain scalar values are pmean'd across the DP axes. A ``(num, den)``
    tuple value is reduced as a GLOBAL ratio — psum both then divide — for
    metrics whose per-shard denominators differ (e.g. MLM loss over a
    variable number of masked tokens, where an unweighted mean-of-ratios
    would over-weight sparse shards). ``state_specs`` matches the train
    step's (sharded params evaluate in their sharded layout — the
    metric_fn's model must carry the same tp/pp config). The reference had
    no eval path beyond running the train graph without the train op
    (SURVEY.md §5) — this is the deliberate do-better (SURVEY.md §4
    "Consequence for the rebuild").

    With ``return_sums=True`` every metric comes back as a ``(num, den)``
    pair of global sums instead of a ratio (scalars become
    ``(pmean(v), 1.0)``), so a multi-batch eval loop can carry numerators
    and denominators across the whole pass and divide ONCE — the same
    mean-of-ratios bias the per-shard reduction avoids would otherwise
    reappear at the batch level (variable masked-token counts per batch).
    Aggregate with :func:`aggregate_metric_sums`.
    """
    dp_axes = data_axes(mesh)
    if batch_spec is None:
        batch_spec = batch_pspec(mesh)
    # Mirror the train step: batch rows split over a non-DP axis (the
    # expert axis in the token-sharded MoE layout) reduce like DP.
    red_axes = tuple(dp_axes) + _extra_batch_axes(batch_spec, dp_axes)
    state_spec_tree = P() if state_specs is None else state_specs

    def per_device_eval(state: TrainState, batch):
        metrics = metric_fn(state.params, state.model_state, batch)
        out = {}
        for k, v in dict(metrics).items():
            if isinstance(v, tuple):
                num, den = v
                if red_axes:
                    num = lax.psum(num, red_axes)
                    den = lax.psum(den, red_axes)
                if return_sums:
                    out[k] = (num, den)
                else:
                    out[k] = num / jnp.maximum(den, 1.0)
            else:
                val = lax.pmean(v, red_axes) if red_axes else v
                out[k] = (val, jnp.float32(1.0)) if return_sums else val
        return out

    smapped = jax.shard_map(
        per_device_eval,
        mesh=mesh,
        in_specs=(state_spec_tree, batch_spec),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)


def aggregate_metric_sums(batch_metrics) -> dict:
    """Reduce an iterable of ``{k: (num, den)}`` dicts to global ratios.

    The companion of ``make_eval_step(..., return_sums=True)``: numerators
    and denominators accumulate across the whole eval pass and divide once
    at the end, so batches with more masked tokens (larger ``den``) weigh
    proportionally more — the global ratio, not a mean of per-batch ratios.
    """
    nums: dict[str, float] = {}
    dens: dict[str, float] = {}
    for metrics in batch_metrics:
        for k, (num, den) in metrics.items():
            nums[k] = nums.get(k, 0.0) + float(num)
            dens[k] = dens.get(k, 0.0) + float(den)
    return {k: nums[k] / max(dens[k], 1e-12) for k in nums}


def make_state_specs(state: TrainState, tx, param_specs) -> TrainState:
    """Build the TrainState-of-PartitionSpecs for a sharded-param run.

    ``param_specs`` is a tree matching ``state.params`` (e.g.
    ``models.bert.bert_param_specs``). Optimizer slots inherit their param's
    spec (via ``optax.tree_map_params``); the stale grad ring buffer gets
    the param spec behind its leading K dim; everything else is replicated.
    """
    import optax as _optax

    opt_specs = _optax.tree_map_params(
        tx,
        lambda _, spec: spec,
        state.opt_state,
        param_specs,
        transform_non_params=lambda _: P(),
    )
    buf_specs = None
    if state.grad_buffer is not None:
        buf_specs = jax.tree.map(lambda s: P(None, *s), param_specs)
    return TrainState(
        step=P(),
        params=param_specs,
        opt_state=opt_specs,
        model_state=jax.tree.map(lambda _: P(), state.model_state),
        grad_buffer=buf_specs,
        buffer_index=None if state.buffer_index is None else P(),
    )


def place_state(state: TrainState, mesh, state_specs: TrainState | None = None) -> TrainState:
    """Put a host-built TrainState onto the mesh.

    Replicated by default (the DP-parity layout — SURVEY.md §2 inventory);
    pass ``state_specs`` (see :func:`make_state_specs`) to shard params and
    optimizer slots over a ``model`` axis (tensor parallelism) and/or a
    ``pipeline`` axis (stage-sharded layer stacks, parallel/pipeline.py).
    """
    if state_specs is None:
        return jax.device_put(state, NamedSharding(mesh, P()))
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(state, shardings)
