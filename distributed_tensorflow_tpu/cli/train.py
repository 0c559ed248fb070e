"""``python -m distributed_tensorflow_tpu.cli.train --config=<workload>``.

Workload presets mirror the reference's five configurations
(BASELINE.json "configs" / SURVEY.md §2 workload rows) one-to-one:

=========================  ====================================================
preset                     reference configuration it rebuilds
=========================  ====================================================
``mnist_lenet``            MNIST LeNet-5, single-process sync SGD sanity run
``cifar_resnet20``         CIFAR-10 ResNet-20, SyncReplicasOptimizer PS (sync DP)
``imagenet_resnet50``      ImageNet ResNet-50, 8-worker NCCL allreduce (sync DP)
``imagenet_inception_async`` ImageNet Inception-v3, async PS → stale-K emulation
``bert_base``              BERT-base pretraining (MLM+NSP), large-embedding DP
=========================  ====================================================

Every preset runs on any mesh size (DP width comes from the devices present,
not from the config — there is no worker count to configure away). Datasets
are seeded synthetic stand-ins with learnable structure (zero-egress
environment); point ``--data-dir`` at real data when present (data/readers:
MNIST idx, CIFAR pickles, ImageNet imagefolder/TFRecord caches).

Round-2 capabilities beyond the preset table: warmup+decay LR schedules per
workload, periodic held-out evaluation (``--eval-every``), the native C++
input pipeline feeding the image presets (random-resized-crop/flip on the
worker pool, prefetch off the Python thread), resume-correct data streams
(a restored run consumes batches N.. not 0..), and ``--profile-dir`` xprof
trace capture.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
from collections.abc import Callable, Iterator
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class WorkloadConfig:
    """One training workload: model + data + optimization, mesh-agnostic."""

    name: str
    build: Callable[["WorkloadConfig"], Any]  # cfg -> make(mesh) -> pieces
    global_batch: int
    num_steps: int
    learning_rate: float
    momentum: float = 0.9
    optimizer: str = "sgd"  # "sgd" | "adam" | "adamw"
    weight_decay: float = 0.0  # adamw decoupled weight decay
    clip_norm: float = 0.0  # >0: global-norm gradient clipping
    grad_accum: int = 1  # >1: micro-slice gradient accumulation in-step
    lr_schedule: str = "constant"  # "constant" | "warmup_cosine" | "piecewise"
    warmup_steps: int = 0
    mode: str = "sync"  # "sync" | "stale"
    staleness: int = 0
    seq_parallel: int = 0  # >0: seq axis size for ring attention (BERT)
    sp_impl: str = "ring"  # "ring" | "ulysses" (all-to-all head re-partition)
    tensor_parallel: int = 0  # >0: model axis size for Megatron-TP (BERT)
    moe_experts: int = 0  # >0: switch-MoE FFN with this many experts (BERT)
    expert_parallel: int = 0  # >0: expert axis size for MoE sharding (BERT)
    # "replicated" | "alltoall" (GShard a2a over replicated tokens) |
    # "sharded" (production GShard: batch sharded over the expert axis)
    moe_dispatch: str = "replicated"
    moe_topk: int = 1  # routing fan-out: 1 = Switch, 2 = GShard top-2
    pipeline_parallel: int = 0  # >0: pipeline axis size, stage-sharded encoder (BERT)
    pipeline_microbatches: int = 0  # GPipe M; 0 -> 4 * pipeline_parallel
    remat: bool = False  # activation remat over encoder layers (BERT)
    bert_layers: int = 0  # >0: override encoder depth (smoke runs)
    bert_hidden: int = 0  # >0: override hidden size (intermediate = 4x)
    bert_vocab: int = 0  # >0: override vocab size (smoke runs)
    image_size: int = 0  # overridable per run
    dataset: str = ""  # real-dataset name for data/readers.load_dataset
    data_dir: str = ""  # where to look for it; synthetic fallback otherwise
    augment: str = ""  # "" | "cifar" (pad-crop+flip) | "imagenet" (RRC+flip)
    native_input: bool = True  # use the C++ pipeline when buildable
    # > 0: pre-place this many batches in HBM and cycle them — the training
    # loop then runs at device rate with ZERO host->device transfers in the
    # hot path. For throughput/trajectory runs on feed-bound hosts (the r3
    # ImageNet runs were host-bound at ~0.2 steps/s); the
    # model revisits the pool every N steps, so it is NOT for convergence
    # claims beyond pool-sized epochs.
    device_pool: int = 0
    # Feed-stage lookahead (data/prefetch.py): a feeder thread runs batch
    # assembly + host->device transfer this many batches ahead of the step
    # stream, so the loop's next(it) is a queue pop in steady state. 0 =
    # synchronous feed (assembly on the critical path). Streams are
    # bit-identical either way — the wrapper never skips or reorders.
    prefetch: int = 2
    log_every: int = 50
    ckpt_every: int = 0


def make_lr_schedule(cfg: WorkloadConfig) -> optax.Schedule:
    """The per-workload LR schedule (reference-era ImageNet/BERT recipes).

    ``warmup_cosine``: linear warmup to the peak LR then cosine decay to ~0
    over ``num_steps`` (the standard large-batch ImageNet/BERT recipe — the
    linear-scaling rule's required companion). ``piecewise``: x0.1 at 50% and
    75% of the run (classic step-decay ResNet recipe). ``constant``: the
    reference harness's fixed LR.
    """
    if cfg.lr_schedule == "constant":
        return optax.constant_schedule(cfg.learning_rate)
    if cfg.lr_schedule == "warmup_cosine":
        warmup = cfg.warmup_steps or max(1, cfg.num_steps // 20)
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=cfg.learning_rate,
            warmup_steps=warmup,
            decay_steps=max(cfg.num_steps, warmup + 1),
            end_value=cfg.learning_rate * 1e-3,
        )
    if cfg.lr_schedule == "piecewise":
        return optax.piecewise_constant_schedule(
            cfg.learning_rate,
            {cfg.num_steps // 2: 0.1, (3 * cfg.num_steps) // 4: 0.1},
        )
    raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")


def _decay_mask(params):
    """AdamW decoupled-weight-decay mask: the canonical BERT recipe
    (google-research/bert AdamWeightDecayOptimizer exclude_from_weight_decay)
    applies decay to weight matrices/embeddings only — LayerNorm/BatchNorm
    scales and every bias are excluded. Name- and rank-based: 1-D leaves
    (biases, norm scales) never decay; nor does anything named like a bias
    (MoE expert bias stacks are 2-D) or living under a norm module."""

    def decays(path, leaf) -> bool:
        names = tuple(
            str(p.key) for p in path if isinstance(p, jax.tree_util.DictKey)
        )
        last = names[-1] if names else ""
        if leaf.ndim < 2 or "bias" in last or last in ("experts_b1", "experts_b2"):
            return False
        norm_mod = any(
            n == "ln" or n.endswith("_ln") or n.endswith("_bn")
            or "LayerNorm" in n or "BatchNorm" in n
            for n in names
        )
        return not norm_mod

    return jax.tree_util.tree_map_with_path(decays, params)


def _make_tx(cfg: WorkloadConfig) -> tuple[optax.GradientTransformation, optax.Schedule]:
    # Global-norm clipping (cfg.clip_norm) is deliberately NOT chained here:
    # optax.clip_by_global_norm inside the shard_mapped step sees per-shard
    # slices of sharded params and would clip with a different scale on each
    # shard (desynchronizing replicated leaves). The engine applies the
    # spec-aware clip instead — see make_train_step(clip_norm=...).
    schedule = make_lr_schedule(cfg)
    if cfg.optimizer == "adamw":
        tx = optax.adamw(
            schedule, weight_decay=cfg.weight_decay, mask=_decay_mask
        )
    elif cfg.optimizer == "adam":
        tx = optax.adam(schedule)
    elif cfg.momentum:
        tx = optax.sgd(schedule, momentum=cfg.momentum)
    else:
        tx = optax.sgd(schedule)
    return tx, schedule


def _image_batches(cfg, ds, mesh, model_hw, *, train, seed, start_step=0):
    """Train/eval batch stream over an image dataset: native C++ pipeline
    with augmentation when available, numpy fallback otherwise."""
    from distributed_tensorflow_tpu.data import device_batches, native_device_batches
    from distributed_tensorflow_tpu.data.native import native_available
    from distributed_tensorflow_tpu.data.readers import IMAGENET_MEAN, IMAGENET_STD

    store_hw = tuple(ds.images.shape[1:3])
    is_u8 = ds.images.dtype == np.uint8
    # Per-channel normalization belongs to the real-pixel path; synthetic
    # float templates are already ~N(0,1).
    mean = IMAGENET_MEAN if (is_u8 and cfg.augment == "imagenet") else None
    std = IMAGENET_STD if mean is not None else None
    out_size = model_hw if store_hw != model_hw else None
    native = train and cfg.native_input and native_available()
    if train:
        # One line per run saying which pipeline feeds it: a failed build of
        # the C++ library degrades to numpy, and that must be visible.
        logger.info(
            "input pipeline: %s",
            "native (native/libdata_pipeline.so)" if native
            else "numpy" + ("" if cfg.native_input else " (--no-native-input)"),
        )
    if native:
        return native_device_batches(
            ds,
            mesh,
            cfg.global_batch,
            out_size=out_size,
            pad=4 if cfg.augment == "cifar" else 0,
            flip=cfg.augment in ("cifar", "imagenet"),
            rrc=cfg.augment == "imagenet",
            mean=mean,
            stddev=std,
            seed=seed,
            start_step=start_step,
        )
    return device_batches(
        ds,
        mesh,
        cfg.global_batch,
        seed=seed,
        start_step=start_step,
        out_size=out_size,
        mean=mean,
        stddev=std,
    )


def _build_image_workload(
    model, image_shape, num_classes, n_examples=4096, model_factory=None
):
    """``model_factory(cfg, shape)`` (optional) builds the model per-config —
    for models whose architecture depends on the run geometry (Inception's
    aux head needs the full 299x299 train-time feature map)."""

    def build(cfg: WorkloadConfig):
        from distributed_tensorflow_tpu.data.readers import load_dataset
        from distributed_tensorflow_tpu.train.objectives import (
            init_model,
            make_classification_loss,
            make_classification_metrics,
        )

        shape = image_shape
        if cfg.image_size:
            shape = (cfg.image_size, cfg.image_size, image_shape[-1])
        net = model_factory(cfg, shape) if model_factory is not None else model

        def make(mesh):
            params, model_state = init_model(
                net, jax.random.key(0), jnp.zeros((1, *shape), jnp.float32)
            )

            def load(split):
                return load_dataset(
                    cfg.dataset or "synthetic",
                    cfg.data_dir or None,
                    split=split,
                    fallback_examples=max(n_examples, cfg.global_batch),
                    image_shape=shape,
                    num_classes=num_classes,
                    seed=0 if split == "train" else 1,
                )

            ds = load("train")
            store = tuple(ds.images.shape[1:3])
            if store != shape[:2] and (
                ds.images.dtype != np.uint8 or store[0] < shape[0] or store[1] < shape[1]
            ):
                raise ValueError(
                    f"dataset images are {ds.images.shape[1:]} but the model "
                    f"was configured for {shape}; a u8 store may only be "
                    "LARGER than the model geometry (train-time crop)"
                )
            # Val split loads lazily on the first eval pass — preparing a
            # real val cache (full PIL decode) must not tax runs that never
            # evaluate (--eval-every=0).
            eval_ds_box: list = []

            def eval_batches(n_batches: int) -> Iterator[dict]:
                if not eval_ds_box:
                    eval_ds_box.append(load("val"))
                it = _image_batches(
                    cfg, eval_ds_box[0], mesh, shape[:2], train=False, seed=101
                )
                for _ in range(n_batches):
                    yield next(it)

            return {
                "params": params,
                "model_state": model_state,
                # Serving hooks (cli/serve.py): the bare module + the input
                # geometry its executables must be compiled for.
                "model": net,
                "image_shape": shape,
                "loss_fn": make_classification_loss(net),
                "batches": lambda start_step=0: _image_batches(
                    cfg, ds, mesh, shape[:2], train=True, seed=1, start_step=start_step
                ),
                "batch_spec": None,
                "metric_fn": make_classification_metrics(net),
                "eval_batches": eval_batches,
            }

        return make

    return build


def _build_bert_workload(cfg_kwargs: dict):
    def build(cfg: WorkloadConfig):
        from distributed_tensorflow_tpu.data.text import (
            SyntheticMLM,
            SyntheticMLMConfig,
            TextCorpusConfig,
            TextCorpusMLM,
            bert_batch_specs,
            mlm_device_batches,
        )
        from distributed_tensorflow_tpu.models.bert import (
            BertConfig,
            BertForPreTraining,
            make_bert_pretraining_loss,
        )

        def make(mesh):
            from distributed_tensorflow_tpu.models.bert import bert_param_specs

            seq_parallel = cfg.seq_parallel and "seq" in mesh.axis_names
            tp = mesh.shape.get("model", 1)
            ep = mesh.shape.get("expert", 1)
            pp = mesh.shape.get("pipeline", 1)
            # GShard token-sharded layout: the expert axis carries batch rows
            # (expert group ≡ data group), so non-MoE compute shards over it
            # too and the MoE a2a routes straight from the local slice.
            expert_sharded = cfg.moe_dispatch == "sharded" and ep > 1
            if cfg.moe_dispatch == "sharded" and ep <= 1:
                raise ValueError(
                    "--moe-dispatch=sharded requires --expert-parallel > 1"
                )
            kwargs = dict(cfg_kwargs)
            if cfg.bert_layers:
                kwargs["num_layers"] = cfg.bert_layers
            if cfg.bert_hidden:
                kwargs["hidden_size"] = cfg.bert_hidden
                kwargs["intermediate_size"] = 4 * cfg.bert_hidden
            if cfg.bert_vocab:
                kwargs["vocab_size"] = cfg.bert_vocab
            init_cfg = BertConfig(**kwargs)
            if cfg.moe_experts:
                if cfg.moe_experts % max(ep, 1):
                    raise ValueError(
                        f"--moe-experts={cfg.moe_experts} not divisible by "
                        f"--expert-parallel={ep}"
                    )
                if not 1 <= cfg.moe_topk <= cfg.moe_experts:
                    raise ValueError(
                        f"--moe-topk={cfg.moe_topk} must be in "
                        f"[1, --moe-experts={cfg.moe_experts}]"
                    )
                # Init with the GLOBAL expert count (expert_parallel=1) and
                # the replicated dispatch — "sharded" needs a bound expert
                # axis and an expert-sharded batch, neither of which exists
                # at init time; the param tree is dispatch-independent.
                init_cfg = dataclasses.replace(
                    init_cfg,
                    moe_experts=cfg.moe_experts,
                    moe_topk=cfg.moe_topk,
                    moe_dispatch=(
                        "replicated"
                        if cfg.moe_dispatch == "sharded"
                        else cfg.moe_dispatch
                    ),
                )
            model_cfg = init_cfg
            if seq_parallel:
                model_cfg = dataclasses.replace(
                    model_cfg, seq_axis="seq", sp_impl=cfg.sp_impl
                )
            if tp > 1:
                model_cfg = dataclasses.replace(
                    model_cfg, model_axis="model", model_parallel=tp
                )
            if ep > 1:
                model_cfg = dataclasses.replace(
                    model_cfg,
                    expert_axis="expert",
                    expert_parallel=ep,
                    moe_dispatch=cfg.moe_dispatch or "replicated",
                )
            if pp > 1:
                # Per-DP-shard rows must split into the GPipe microbatches.
                dp = mesh.shape.get("data", 1) * mesh.shape.get("replica", 1)
                micro = cfg.pipeline_microbatches or 4 * pp
                rows = cfg.global_batch // dp
                if rows % micro:
                    raise ValueError(
                        f"per-shard batch {rows} (global {cfg.global_batch} / "
                        f"dp {dp}) not divisible by pipeline_microbatches "
                        f"{micro}"
                    )
                # Init config gets pipeline_parallel (stacked params, axis
                # unset so init runs the sequential scan outside shard_map);
                # the training model additionally binds the mesh axis.
                init_cfg = dataclasses.replace(
                    init_cfg, pipeline_parallel=pp, pipeline_microbatches=micro
                )
                model_cfg = dataclasses.replace(
                    model_cfg,
                    pipeline_axis="pipeline",
                    pipeline_parallel=pp,
                    pipeline_microbatches=micro,
                )
            elif cfg.pipeline_parallel > 1:
                # No pipeline mesh axis but a pipeline-trained config: the
                # SERVING fallback path (cli/serve.py restoring a stacked
                # checkpoint onto a mesh without the axis, e.g. single-chip
                # degradation). Stacked params with the axis unset run the
                # sequential scan — mathematically identical to the GPipe
                # schedule, so one checkpoint restores either way. Training
                # never lands here: run() always puts the axis on the mesh
                # when cfg.pipeline_parallel > 1.
                init_cfg = dataclasses.replace(
                    init_cfg, pipeline_parallel=cfg.pipeline_parallel
                )
                model_cfg = dataclasses.replace(
                    model_cfg, pipeline_parallel=cfg.pipeline_parallel
                )
            if cfg.remat:
                # Training model only — init's one forward needs no remat,
                # and the param tree is identical either way.
                model_cfg = dataclasses.replace(model_cfg, remat=True)
            # Init outside shard_map must not bind the seq axis; the param
            # tree is identical either way (tests/test_bert.py).
            init_model_ = BertForPreTraining(init_cfg)
            model = BertForPreTraining(model_cfg)
            L = init_cfg.max_position
            variables = init_model_.init(
                jax.random.key(0),
                jnp.zeros((1, L), jnp.int32),
                jnp.ones((1, L), bool),
                jnp.zeros((1, L), jnp.int32),
                train=False,
            )
            # Real corpus when --data-dir holds *.txt (one sentence per
            # line, blank line between documents — the classic BERT
            # pretraining input); seeded synthetic Markov chains otherwise.
            # A val/*.txt subdirectory provides genuinely unseen eval text
            # (tokenized with the TRAIN vocab).
            txt_files, val_files = [], []
            if cfg.data_dir:
                from pathlib import Path

                txt_files = sorted(Path(cfg.data_dir).glob("*.txt"))
                val_files = sorted((Path(cfg.data_dir) / "val").glob("*.txt"))
            eval_data = None
            if txt_files:
                corpus_cfg = TextCorpusConfig(
                    seq_len=L, vocab_size=init_cfg.vocab_size, seed=0
                )
                data = TextCorpusMLM(txt_files, corpus_cfg)
                if val_files:
                    eval_data = TextCorpusMLM(
                        val_files, corpus_cfg, vocab_from=data
                    )
                else:
                    logger.warning(
                        "no val/*.txt under %s; eval will RESAMPLE THE "
                        "TRAINING TEXT with fresh masking (not held-out "
                        "documents) — provide a val split for a true "
                        "held-out metric",
                        cfg.data_dir,
                    )
            else:
                if cfg.data_dir:
                    logger.warning(
                        "no *.txt under %s; FALLING BACK TO SYNTHETIC MLM DATA%s",
                        cfg.data_dir,
                        (
                            f" (IGNORING {len(val_files)} val/*.txt files — "
                            "training text must live at the top level)"
                            if val_files
                            else ""
                        ),
                    )
                data = SyntheticMLM(
                    SyntheticMLMConfig(
                        vocab_size=init_cfg.vocab_size, seq_len=L, seed=0
                    )
                )
            from distributed_tensorflow_tpu.models.bert import make_bert_eval_metrics

            def eval_batches(n_batches: int) -> Iterator[dict]:
                # Held-out stream: the val corpus when one exists, else a
                # disjoint seed over the training source (fresh sampling and
                # masking — for synthetic data that IS unseen data; for a
                # real corpus the build-time warning above applies).
                it = mlm_device_batches(
                    eval_data if eval_data is not None else data,
                    mesh,
                    cfg.global_batch,
                    seq_sharded=bool(seq_parallel),
                    expert_sharded=expert_sharded,
                    seed=900_001,
                )
                for _ in range(n_batches):
                    yield next(it)

            return {
                "params": variables["params"],
                # Serving hook (cli/serve.py): the axis-free model, exactly
                # as init used it (no seq/model/pipeline axes bound; stacked
                # pipeline params run the sequential scan). On a mesh WITH
                # model axes the serving engine re-binds them itself
                # (BertInferenceEngine._serve_config) — param_specs below
                # carries the matching sharding contract.
                "model": init_model_,
                "param_specs": (
                    bert_param_specs(
                        variables["params"],
                        model_axis="model" if tp > 1 else None,
                        expert_axis="expert" if ep > 1 else None,
                        pipeline_axis="pipeline" if pp > 1 else None,
                    )
                    if tp > 1 or ep > 1 or pp > 1
                    else None
                ),
                "model_state": {},
                "loss_fn": make_bert_pretraining_loss(
                    model, mask_prob=data.cfg.mask_prob
                ),
                "batches": lambda start_step=0: mlm_device_batches(
                    data,
                    mesh,
                    cfg.global_batch,
                    seq_sharded=bool(seq_parallel),
                    expert_sharded=expert_sharded,
                    seed=1,
                    start_step=start_step,
                ),
                "batch_spec": bert_batch_specs(
                    mesh,
                    seq_sharded=bool(seq_parallel),
                    expert_sharded=expert_sharded,
                ),
                "metric_fn": make_bert_eval_metrics(
                    model, mask_prob=data.cfg.mask_prob
                ),
                "eval_batches": eval_batches,
            }

        return make

    return build


def _build_causal_lm_workload(cfg_kwargs: dict):
    def build(cfg: WorkloadConfig):
        from distributed_tensorflow_tpu.data.text import (
            SyntheticLM,
            SyntheticMLMConfig,
            lm_batch_specs,
            mlm_device_batches,
        )
        from distributed_tensorflow_tpu.models.causal_lm import (
            CausalLM,
            CausalLMConfig,
            causal_param_specs,
            make_causal_lm_eval_metrics,
            make_causal_lm_loss,
        )

        def make(mesh):
            tp = mesh.shape.get("model", 1)
            ep = mesh.shape.get("expert", 1)
            pp = mesh.shape.get("pipeline", 1)
            if ep > 1 or pp > 1:
                raise ValueError(
                    "causal-LM workloads shard over data/model axes only "
                    "(no MoE or pipeline decoder variant)"
                )
            kwargs = dict(cfg_kwargs)
            if cfg.bert_layers:
                kwargs["num_layers"] = cfg.bert_layers
            if cfg.bert_hidden:
                kwargs["hidden_size"] = cfg.bert_hidden
                kwargs["intermediate_size"] = 4 * cfg.bert_hidden
            if cfg.bert_vocab:
                kwargs["vocab_size"] = cfg.bert_vocab
            init_cfg = CausalLMConfig(**kwargs)
            model_cfg = init_cfg
            if tp > 1:
                model_cfg = dataclasses.replace(
                    model_cfg, model_axis="model", model_parallel=tp
                )
            # Init outside shard_map must not bind the model axis; the
            # param tree is identical either way (same rule as BERT).
            init_model_ = CausalLM(init_cfg)
            model = CausalLM(model_cfg)
            L = init_cfg.max_position
            variables = init_model_.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, L), jnp.int32),
                jnp.ones((1, L), bool),
            )
            data = SyntheticLM(
                SyntheticMLMConfig(
                    vocab_size=init_cfg.vocab_size, seq_len=L, seed=0
                )
            )

            def eval_batches(n_batches: int) -> Iterator[dict]:
                it = mlm_device_batches(
                    data, mesh, cfg.global_batch, seed=900_001
                )
                for _ in range(n_batches):
                    yield next(it)

            return {
                "params": variables["params"],
                # Serving hooks (cli/serve.py): axis-free model + the
                # decode marker that routes the config to CausalLMEngine's
                # prefill/decode grid instead of the one-shot BERT path.
                "model": init_model_,
                "decode": True,
                "param_specs": (
                    causal_param_specs(variables["params"])
                    if tp > 1
                    else None
                ),
                "model_state": {},
                "loss_fn": make_causal_lm_loss(model),
                "batches": lambda start_step=0: mlm_device_batches(
                    data,
                    mesh,
                    cfg.global_batch,
                    seed=1,
                    start_step=start_step,
                ),
                "batch_spec": lm_batch_specs(mesh),
                "metric_fn": make_causal_lm_eval_metrics(model),
                "eval_batches": eval_batches,
            }

        return make

    return build


def _presets() -> dict[str, WorkloadConfig]:
    from distributed_tensorflow_tpu.models import (
        InceptionV3,
        LeNet5,
        ResNet20,
        ResNet50,
    )

    return {
        "mnist_lenet": WorkloadConfig(
            name="mnist_lenet",
            build=_build_image_workload(LeNet5(), (28, 28, 1), 10),
            global_batch=128,
            num_steps=1000,
            learning_rate=0.05,
            dataset="mnist",
        ),
        "cifar_resnet20": WorkloadConfig(
            name="cifar_resnet20",
            build=_build_image_workload(ResNet20(), (32, 32, 3), 10),
            global_batch=256,
            num_steps=2000,
            learning_rate=0.1,
            lr_schedule="piecewise",
            dataset="cifar10",
            augment="cifar",
        ),
        "imagenet_resnet50": WorkloadConfig(
            name="imagenet_resnet50",
            build=_build_image_workload(
                ResNet50(dtype=jnp.bfloat16), (224, 224, 3), 1000, n_examples=8192
            ),
            global_batch=256,
            num_steps=5000,
            learning_rate=0.4,  # linear-scaling rule for large global batch
            lr_schedule="warmup_cosine",
            dataset="imagenet",
            augment="imagenet",
        ),
        "imagenet_inception_async": WorkloadConfig(
            name="imagenet_inception_async",
            build=_build_image_workload(
                None,
                (299, 299, 3),
                1000,
                n_examples=8192,
                # Aux classifier on at the canonical 299x299 geometry (the
                # reference-era Inception-v3 recipe trains main + 0.3*aux);
                # smaller smoke geometries can't feed the aux head's 5x5
                # VALID conv, so it gates on the run's image size.
                model_factory=lambda cfg, shape: InceptionV3(
                    dtype=jnp.bfloat16, aux_logits=shape[0] >= 299
                ),
            ),
            global_batch=256,
            num_steps=5000,
            learning_rate=0.05,
            momentum=0.0,
            lr_schedule="warmup_cosine",
            mode="stale",
            staleness=4,
            dataset="imagenet",
            augment="imagenet",
        ),
        "bert_base": WorkloadConfig(
            name="bert_base",
            build=_build_bert_workload(
                dict(max_position=128, dropout_rate=0.1, dtype=jnp.bfloat16)
            ),
            global_batch=256,
            num_steps=10000,
            learning_rate=1e-4,
            # The canonical BERT pretraining recipe: AdamW with decoupled
            # weight decay (masked off LayerNorm scales and all biases —
            # _decay_mask) + spec-aware global-norm clipping at 1.0
            # (applied inside the step; see make_train_step clip_norm).
            optimizer="adamw",
            weight_decay=0.01,
            clip_norm=1.0,
            lr_schedule="warmup_cosine",
            warmup_steps=1000,
        ),
        "lm_base": WorkloadConfig(
            name="lm_base",
            build=_build_causal_lm_workload(
                dict(max_position=128, dtype=jnp.bfloat16)
            ),
            global_batch=256,
            num_steps=10000,
            learning_rate=1e-4,
            # Same decoupled-decay recipe as bert_base — the decoder reuses
            # its blocks, so the optimizer hygiene carries over unchanged.
            optimizer="adamw",
            weight_decay=0.01,
            clip_norm=1.0,
            lr_schedule="warmup_cosine",
            warmup_steps=1000,
        ),
    }


PRESETS = _presets()


def run(cfg: WorkloadConfig, args: argparse.Namespace):
    from distributed_tensorflow_tpu.ckpt import Checkpointer
    from distributed_tensorflow_tpu.obs import make_metric_hook, trace_steps
    from distributed_tensorflow_tpu.parallel.mesh import (
        build_mesh,
        initialize_runtime,
    )
    from distributed_tensorflow_tpu.runtime import describe_devices
    from distributed_tensorflow_tpu.train import (
        create_train_state,
        fit,
        make_eval_step,
        make_rng,
        make_train_step,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    # Multi-host bootstrap: a declared (flags) or detected cluster calls
    # jax.distributed.initialize; a single process calls nothing.
    initialize_runtime(
        coordinator_address=getattr(args, "coordinator_address", "") or None,
        num_processes=(
            args.num_processes
            if getattr(args, "num_processes", 0) > 0
            else None
        ),
        process_id=(
            args.process_id if getattr(args, "process_id", -1) >= 0 else None
        ),
    )
    mesh_spec = {"data": -1}
    if cfg.seq_parallel:
        mesh_spec["seq"] = cfg.seq_parallel
    if cfg.tensor_parallel:
        mesh_spec["model"] = cfg.tensor_parallel
    if cfg.expert_parallel:
        mesh_spec["expert"] = cfg.expert_parallel
    if cfg.pipeline_parallel:
        mesh_spec["pipeline"] = cfg.pipeline_parallel
    mesh = build_mesh(mesh_spec)
    if jax.process_index() == 0:
        logging.info("workload=%s mesh=%s", cfg.name, dict(mesh.shape))
        # What the run really runs on, as jax reports it (chip_smoke.py
        # reads this line: a run that fell to the CPU must not pass for one
        # on the chip).
        logging.info(
            "runtime: %s",
            json.dumps({**describe_devices(), "mesh": dict(mesh.shape)}),
        )

    pieces = cfg.build(cfg)(mesh)
    # A model/expert axis with no param actually sharded over it means every
    # group of those devices computes identical grads — silent N-fold waste,
    # never what the user asked for. Check each requested axis appears in at
    # least one param spec (a non-None but all-replicated tree is just as
    # wasteful as no tree).
    for axis, width in (
        ("model", cfg.tensor_parallel),
        ("expert", cfg.expert_parallel),
        ("pipeline", cfg.pipeline_parallel),
    ):
        if width <= 1:
            continue
        specs = pieces.get("param_specs")
        leaves = (
            jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
            )
            if specs is not None
            else []
        )
        from distributed_tensorflow_tpu.train.step import _spec_axes

        if not any(axis in _spec_axes(s) for s in leaves):
            raise ValueError(
                f"a {width}-way {axis!r} axis was requested but workload "
                f"{cfg.name!r} shards no params over it"
            )
    tx, lr_schedule = _make_tx(cfg)
    host_state = create_train_state(
        pieces["params"],
        tx,
        pieces["model_state"],
        staleness=cfg.staleness if cfg.mode == "stale" else 0,
    )
    state_specs = None
    if pieces.get("param_specs") is not None:
        from distributed_tensorflow_tpu.train.step import make_state_specs

        state_specs = make_state_specs(host_state, tx, pieces["param_specs"])
    state = place_state(host_state, mesh, state_specs)
    step = make_train_step(
        pieces["loss_fn"],
        tx,
        mesh,
        mode=cfg.mode,
        staleness=cfg.staleness if cfg.mode == "stale" else 0,
        batch_spec=pieces["batch_spec"],
        state_specs=state_specs,
        clip_norm=cfg.clip_norm,
        grad_accum=cfg.grad_accum,
    )

    # Flight recorder (--dump-dir, obs/flightrec.py): train records almost
    # nothing per step (the hot loop stays clean), but the resilience/
    # fault-injection paths record their events here and an unhandled
    # failure dumps the ring for postmortem. Built BEFORE the checkpointer
    # and feed so the injector hooks below can carry it.
    recorder = None
    dump_dir = getattr(args, "dump_dir", "") or ""
    if dump_dir:
        from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
        from distributed_tensorflow_tpu.obs.memory import default_registry

        recorder = FlightRecorder(dump_dir=dump_dir)
        recorder.attach(memz_fn=default_registry().snapshot)

    # Deterministic fault injection (--fault-plan, train/faultinject.py):
    # a seeded schedule of slow_step/feeder_error/nonfinite_loss/
    # ckpt_write_error/host_drop events carried into the loop, the feed
    # stage, and the checkpointer. Chaos rehearsals reproduce from the
    # same spec string.
    fault_injector = None
    fault_plan_spec = getattr(args, "fault_plan", "") or ""
    if fault_plan_spec:
        from distributed_tensorflow_tpu.train.faultinject import (
            FaultInjector,
            FaultPlan,
        )

        plan = FaultPlan.parse(fault_plan_spec, num_steps=cfg.num_steps)
        fault_injector = FaultInjector(plan, recorder=recorder)
        logging.info(
            "fault plan armed: %d scheduled events", len(plan.events)
        )

    resilient = bool(getattr(args, "resilient", False))
    if resilient and cfg.device_pool > 0:
        raise SystemExit(
            "--resilient does not compose with --device-pool (the pool is "
            "rebuilt per restart and would replay positions 0..N-1)"
        )
    ckpt = (
        Checkpointer(args.ckpt_dir, fault_injector=fault_injector)
        if args.ckpt_dir
        else None
    )
    start = 0
    if ckpt is not None:
        state, start = ckpt.restore_latest(state)
    # Resume-correct stream: batches start at N, not 0 (the fix for the
    # reference-era replay-on-restart). Resilient mode builds its streams
    # through make_batches below instead (one per restart segment).
    batches = None
    if not resilient:
        batches = pieces["batches"](0 if cfg.device_pool > 0 else start)
    if cfg.device_pool > 0:
        # Device-resident pool: materialize the first N batches in HBM once
        # and cycle — the host leaves the hot loop entirely. Safe to reuse
        # batches across steps: the train
        # step donates only the state, never the batch. Resume-correctness
        # for pool mode means something different than for streams: the
        # pool is ALWAYS stream positions 0..N-1 and a resumed run re-enters
        # the cycle at step % N, exactly reproducing the uninterrupted
        # trajectory (building the pool from position `start` instead would
        # silently train on different data after every restart).
        src = batches
        pool = [next(src) for _ in range(cfg.device_pool)]
        # Block on the WHOLE pool before rotating: after rotation pool[-1]
        # is no longer the last-enqueued transfer, so a single-leaf wait
        # would let later transfers bleed into the first timed step.
        jax.block_until_ready(pool)
        pool = pool[start % cfg.device_pool:] + pool[: start % cfg.device_pool]
        close_src = getattr(src, "close", None)
        if close_src is not None:
            close_src()
        if jax.process_index() == 0:
            logging.info(
                "device_pool=%d batches resident in HBM; host feed is out "
                "of the hot loop", cfg.device_pool,
            )

        import itertools

        batches = itertools.cycle(pool)

    from distributed_tensorflow_tpu.data.prefetch import prefetch
    from distributed_tensorflow_tpu.obs.metrics import FeedMetrics

    feed_metrics = FeedMetrics()
    if cfg.device_pool <= 0 and not resilient:
        # Async feed stage: assembly + host->device transfer run on a
        # feeder thread, cfg.prefetch batches ahead (0 = synchronous with
        # the same metrics surface). Device-pool runs skip it — the pool is
        # already resident in HBM, there is nothing to overlap.
        batches = prefetch(
            batches, cfg.prefetch, metrics=feed_metrics,
            fault_injector=fault_injector,
        )

    evaluate = None
    if args.eval_every and pieces.get("metric_fn") and pieces.get("eval_batches"):
        eval_step = make_eval_step(
            pieces["metric_fn"],
            mesh,
            batch_spec=pieces["batch_spec"],
            state_specs=state_specs,
            return_sums=True,
        )

        def evaluate(state):
            # (num, den) sums carry across the whole pass and divide once —
            # the global ratio, not a mean of per-batch ratios (which would
            # over-weight batches with few masked tokens).
            from distributed_tensorflow_tpu.train.step import aggregate_metric_sums

            return aggregate_metric_sums(
                eval_step(state, batch)
                for batch in pieces["eval_batches"](args.eval_batches)
            )

    def lr_hook(step_: int, state_, metrics: dict) -> None:
        # Mutates before the writers run (hook order) — `lr` lands in every
        # JSONL/TB record without touching the compiled step.
        if "loss" in metrics:
            metrics["lr"] = float(lr_schedule(step_ - 1))

    hook = make_metric_hook(logdir=args.tb_dir, jsonl=args.metrics_jsonl)

    # Fleet health beacon (--beacon-dir): per-step timeline + straggler
    # detector feeding one atomically-replaced JSON file per host, refreshed
    # at the log cadence. Aggregation is pull-based (obs/fleet.py
    # read_beacons / fleet_summary) — hosts never talk to each other.
    timeline = None
    hooks = (lr_hook, hook)
    beacon_dir = getattr(args, "beacon_dir", "") or ""
    if beacon_dir:
        from distributed_tensorflow_tpu.obs.fleet import HostBeacon, StepTimeline

        timeline = StepTimeline()
        beacon = HostBeacon(
            beacon_dir, jax.process_index(), timeline,
            extras=fault_injector.summary if fault_injector is not None else None,
        )

        def beacon_hook(step_: int, state_, metrics_: dict) -> None:
            beacon.write()

        hooks = (lr_hook, hook, beacon_hook)
    import contextlib

    # Host-side span tracing (obs/trace.py): ring-buffered step-phase
    # spans, exported as Chrome trace-event JSON at run end. Distinct from
    # --profile-dir, which captures the DEVICE side via jax.profiler.
    from distributed_tensorflow_tpu.obs.trace import Tracer

    trace_dir = getattr(args, "trace_dir", "") or ""
    tracer = (
        Tracer(buffer_size=getattr(args, "trace_buffer", 4096) or 4096)
        if trace_dir
        else None
    )
    profile_steps = getattr(args, "profile_steps", 0) or 0
    if profile_steps and not args.profile_dir:
        raise SystemExit("--profile-steps requires --profile-dir")
    profile_cm = (
        trace_steps(args.profile_dir, num_steps=profile_steps or None)
        if args.profile_dir
        else contextlib.nullcontext()
    )
    if recorder is not None and tracer is not None:
        recorder.attach(tracer_fn=tracer.summary)
    try:
        with profile_cm as win:
            step_fn = step
            if profile_steps:
                # Armed window: the profiler runs for exactly N dispatched
                # steps instead of the whole run.
                def step_fn(state_, batch_, rng_):
                    win.before_step()
                    out = step(state_, batch_, rng_)
                    win.after_step(out)
                    return out

            common = dict(
                num_steps=cfg.num_steps,
                rng=make_rng(args.seed, args.rng_impl),
                log_every=cfg.log_every,
                hooks=hooks,
                checkpointer=ckpt,
                ckpt_every=cfg.ckpt_every or args.ckpt_every,
                evaluate=evaluate,
                eval_every=args.eval_every,
                feed_metrics=feed_metrics,
                tracer=tracer,
                timeline=timeline,
                recorder=recorder,
                nonfinite=getattr(args, "nonfinite", "abort") or "abort",
            )
            if resilient:
                # Preemption-safe supervision (train/resilience.py):
                # SIGTERM/SIGINT -> final sync checkpoint + clean exit;
                # transient feeder/ckpt-IO failures restore from the last
                # checkpoint and re-enter the loop with backoff.
                from distributed_tensorflow_tpu.train.resilience import (
                    ResilienceConfig,
                    run_resilient,
                )

                def make_batches(start_step: int):
                    return prefetch(
                        pieces["batches"](start_step),
                        cfg.prefetch,
                        metrics=feed_metrics,
                        fault_injector=fault_injector,
                    )

                report = run_resilient(
                    state,
                    step_fn,
                    make_batches,
                    config=ResilienceConfig(
                        max_restarts=getattr(args, "max_restarts", 3)
                    ),
                    fault_injector=fault_injector,
                    **common,
                )
                state, last = report.state, report.metrics
                if report.preempted:
                    logging.info(
                        "preempted at step %d after %d restart(s); "
                        "checkpoint is durable",
                        report.final_step, report.restarts,
                    )
            else:
                state, last = fit(
                    state,
                    step_fn,
                    batches,
                    fault_injector=fault_injector,
                    **common,
                )
        if ckpt is not None and ckpt.latest_step() != int(state.step):
            ckpt.save(int(state.step), state, force=True)
        if jax.process_index() == 0:
            from distributed_tensorflow_tpu.obs.memory import default_registry

            logging.info(
                "device_memory: %s",
                json.dumps(default_registry().device_stats()),
            )
    except Exception as e:
        if recorder is not None:
            recorder.record("engine_failure", error=type(e).__name__)
            recorder.dump("train_failure", force=True)
        raise
    finally:
        if ckpt is not None:
            ckpt.close()
        for w in getattr(hook, "writers", ()):
            w.close()
        close = getattr(batches, "close", None)
        if close is not None:
            close()
        if timeline is not None:
            beacon.write()  # final state, even for runs shorter than log_every
        if tracer is not None and jax.process_index() == 0:
            out = tracer.export(Path(trace_dir) / "train_trace.json")
            logging.info("wrote host span trace to %s", out)
    return state, last


def main(argv: list[str] | None = None):
    from distributed_tensorflow_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(
        description="TPU-native distributed training (single SPMD entrypoint)"
    )
    parser.add_argument("--config", required=True, choices=sorted(PRESETS))
    parser.add_argument("--steps", type=int, default=0, help="override num_steps")
    parser.add_argument("--global-batch", type=int, default=0)
    parser.add_argument("--image-size", type=int, default=0)
    parser.add_argument("--seq-parallel", type=int, default=-1,
                        help="seq axis size for sequence parallelism (BERT)")
    parser.add_argument("--sp-impl", default="", choices=["", "ring", "ulysses"],
                        help="sequence-parallel strategy: ring (K/V streamed "
                        "over ICI) or ulysses (all-to-all head re-partition)")
    parser.add_argument("--tensor-parallel", type=int, default=-1,
                        help="model axis size for Megatron-TP sharding (BERT)")
    parser.add_argument("--moe-experts", type=int, default=-1,
                        help="switch-MoE FFN with N experts (BERT; 0 = dense FFN)")
    parser.add_argument("--moe-dispatch", default="",
                        choices=["", "replicated", "alltoall", "sharded"],
                        help="MoE dispatch layout: alltoall = capacity-buffer "
                        "exchange over replicated tokens; sharded = the "
                        "production GShard layout (batch sharded over the "
                        "expert axis, zero replicated non-MoE compute)")
    parser.add_argument("--moe-topk", type=int, default=-1,
                        help="routing fan-out: 1 = Switch top-1 (default), "
                        "2 = GShard top-2 (renormalized gates, per-expert "
                        "capacity unchanged)")
    parser.add_argument("--pipeline-parallel", type=int, default=-1,
                        help="pipeline-stage axis size for the BERT encoder "
                        "(GPipe schedule; 0 disables)")
    parser.add_argument("--pipeline-microbatches", type=int, default=0,
                        help="GPipe microbatch count M (default 4x stages)")
    parser.add_argument("--grad-accum", type=int, default=0,
                        help="accumulate gradients over N micro-slices of "
                        "each device's batch inside the compiled step "
                        "(mean of per-slice grads) — train global batches "
                        "whose activations don't fit; composes with --remat")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialise encoder-layer activations during "
                        "backward (jax.checkpoint): ~1 extra fwd pass of "
                        "layer FLOPs for O(num_layers) less activation "
                        "memory — enables longer --seq-len / larger batch "
                        "per chip (BERT)")
    parser.add_argument("--expert-parallel", type=int, default=-1,
                        help="expert axis size for MoE sharding (BERT)")
    parser.add_argument("--bert-layers", type=int, default=0,
                        help="override BERT encoder depth (smoke runs)")
    parser.add_argument("--bert-hidden", type=int, default=0,
                        help="override BERT hidden size (intermediate = 4x)")
    parser.add_argument("--bert-vocab", type=int, default=0,
                        help="override BERT vocab size (smoke runs)")
    parser.add_argument("--staleness", type=int, default=-1)
    parser.add_argument("--lr", type=float, default=0.0)
    parser.add_argument("--lr-schedule", default="",
                        choices=["", "constant", "warmup_cosine", "piecewise"])
    parser.add_argument("--log-every", type=int, default=0)
    parser.add_argument("--data-dir", default="",
                        help="directory with real dataset files (synthetic fallback)")
    parser.add_argument("--no-native-input", action="store_true",
                        help="force the numpy input path (skip the C++ pipeline)")
    parser.add_argument("--device-pool", type=int, default=0,
                        help="pre-place N batches in HBM and cycle them "
                        "(device-rate runs on feed-bound hosts; revisits "
                        "the pool every N steps)")
    parser.add_argument("--prefetch", type=int, default=-1,
                        help="feed lookahead depth: a feeder thread runs "
                        "batch assembly + host->device transfer N batches "
                        "ahead of the step stream (default 2; 0 = "
                        "synchronous feed). Batch streams are bit-identical "
                        "for any N")
    parser.add_argument("--coordinator-address", default="",
                        help="multi-host bootstrap: coordinator ip:port for "
                        "jax.distributed.initialize (detected from the "
                        "launcher's host list on TPU pods; required for "
                        "CPU/GPU clusters / manual launch)")
    parser.add_argument("--num-processes", type=int, default=0,
                        help="multi-host bootstrap: total process count "
                        "(with --coordinator-address)")
    parser.add_argument("--process-id", type=int, default=-1,
                        help="multi-host bootstrap: this process's rank in "
                        "[0, --num-processes)")
    parser.add_argument("--eval-every", type=int, default=0,
                        help="run held-out eval every N steps (0 = off)")
    parser.add_argument("--eval-batches", type=int, default=8,
                        help="number of global batches per eval pass")
    parser.add_argument("--ckpt-dir", default="")
    parser.add_argument("--ckpt-every", type=int, default=0)
    parser.add_argument("--tb-dir", default="")
    parser.add_argument("--metrics-jsonl", default="")
    parser.add_argument("--beacon-dir", default="",
                        help="shared directory for per-host health beacons "
                        "(host_<i>.json, atomically replaced at the log "
                        "cadence): step-time/host-wait windows + straggler "
                        "anomalies, aggregated by obs.fleet.fleet_summary")
    parser.add_argument("--profile-dir", default="",
                        help="capture an xprof trace of the whole run to this dir")
    parser.add_argument("--profile-steps", type=int, default=0,
                        help="arm the --profile-dir window for exactly N "
                        "dispatched steps (starts at the first step, stops "
                        "after the Nth; 0 = trace the whole run)")
    parser.add_argument("--trace-dir", default="",
                        help="record host-side step-phase spans (host_wait/"
                        "dispatch/device/metrics_fetch/checkpoint) and "
                        "write them here as Chrome trace-event JSON "
                        "(Perfetto / chrome://tracing)")
    parser.add_argument("--trace-buffer", type=int, default=4096,
                        help="span ring-buffer size for --trace-dir (the "
                        "export holds the most recent N spans)")
    parser.add_argument("--dump-dir", default="",
                        help="flight-recorder dump directory: an unhandled "
                        "training failure writes one timestamped JSON with "
                        "the event ring + memory/tracer digests (see "
                        "OBS.md \"Flight recorder\"; empty = disabled)")
    parser.add_argument("--resilient", action="store_true",
                        help="preemption-safe supervised training "
                        "(train/resilience.py): SIGTERM/SIGINT triggers a "
                        "final synchronous checkpoint + clean exit; "
                        "transient feeder/checkpoint-IO failures restore "
                        "from the last checkpoint and retry with capped "
                        "exponential backoff; non-finite loss and shape "
                        "errors stay fatal (with a flight-recorder dump "
                        "when --dump-dir is set)")
    parser.add_argument("--max-restarts", type=int, default=3,
                        help="consecutive no-progress restart budget for "
                        "--resilient (a restart that resumes from a newer "
                        "checkpoint resets the count)")
    parser.add_argument("--fault-plan", default="",
                        help="deterministic fault injection "
                        "(train/faultinject.py): either a seeded spec like "
                        "'seed=7,feeder_error=2,ckpt_write_error=1,"
                        "slow_step=1,slow_step_s=0.1' or a path to a JSON "
                        "plan; scheduled events fire in the train loop, "
                        "the feed stage, and the checkpointer, and are "
                        "recorded to the flight recorder and host beacon")
    parser.add_argument("--nonfinite", default="abort",
                        choices=["abort", "skip"],
                        help="NaN/Inf step-loss policy, checked at the log "
                        "cadence: abort (default) raises NonFiniteLossError "
                        "(+ flight-recorder event and forced dump with "
                        "--dump-dir); skip records the event and trains on")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--rng-impl",
        default="auto",
        choices=["auto", "threefry", "rbg"],
        help="PRNG for the per-step rng (dropout etc.). auto = rbg on TPU "
        "(counter-based hardware generator — measured 15%% faster BERT-base "
        "steps than threefry at L=512, docs/PERF.md r5; the semantics class "
        "of the reference's Philox dropout), threefry elsewhere (bit-stable "
        "across versions/backends).",
    )
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s: %(message)s"
    )
    cfg = PRESETS[args.config]
    overrides = {}
    if args.steps:
        overrides["num_steps"] = args.steps
    if args.global_batch:
        overrides["global_batch"] = args.global_batch
    if args.image_size:
        overrides["image_size"] = args.image_size
    if args.seq_parallel >= 0:
        overrides["seq_parallel"] = args.seq_parallel
    if args.sp_impl:
        overrides["sp_impl"] = args.sp_impl
    if args.tensor_parallel >= 0:
        overrides["tensor_parallel"] = args.tensor_parallel
    if args.moe_experts >= 0:
        overrides["moe_experts"] = args.moe_experts
    if args.moe_dispatch:
        overrides["moe_dispatch"] = args.moe_dispatch
    if args.moe_topk == 0:
        raise SystemExit("--moe-topk must be >= 1")
    if args.moe_topk > 0:
        overrides["moe_topk"] = args.moe_topk
    if args.expert_parallel >= 0:
        overrides["expert_parallel"] = args.expert_parallel
    if args.pipeline_parallel >= 0:
        overrides["pipeline_parallel"] = args.pipeline_parallel
    if args.pipeline_microbatches:
        overrides["pipeline_microbatches"] = args.pipeline_microbatches
    if args.remat:
        overrides["remat"] = True
    if args.grad_accum:
        if args.grad_accum < 1:
            raise SystemExit("--grad-accum must be >= 1")
        overrides["grad_accum"] = args.grad_accum
    if args.bert_layers:
        overrides["bert_layers"] = args.bert_layers
    if args.bert_hidden:
        overrides["bert_hidden"] = args.bert_hidden
    if args.bert_vocab:
        overrides["bert_vocab"] = args.bert_vocab
    if args.staleness >= 0:
        overrides["staleness"] = args.staleness
        if args.staleness:
            overrides["mode"] = "stale"
    if args.lr:
        overrides["learning_rate"] = args.lr
    if args.lr_schedule:
        overrides["lr_schedule"] = args.lr_schedule
    if args.log_every:
        overrides["log_every"] = args.log_every
    if args.data_dir:
        overrides["data_dir"] = args.data_dir
    if args.no_native_input:
        overrides["native_input"] = False
    if args.device_pool:
        overrides["device_pool"] = args.device_pool
    if args.prefetch >= 0:
        overrides["prefetch"] = args.prefetch
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    state, last = run(cfg, args)
    if jax.process_index() == 0 and last is not None:
        logging.info("final: %s", last)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
