"""distributed_tensorflow_tpu — a TPU-native distributed training framework.

A ground-up rebuild of the capabilities of ``hwang595/distributed_tensorflow``
(a TF-1.x gRPC parameter-server / NCCL-allreduce data-parallel harness; see
SURVEY.md for the full layer map) as an idiomatic JAX/XLA SPMD framework:

- one pod-level SPMD entrypoint over a ``jax.sharding.Mesh`` (replaces
  ``tf.train.ClusterSpec`` / ``tf.train.Server`` / ``run_ps.py`` +
  ``run_worker.py``, SURVEY.md §1 L1-L2, §3a-3b),
- gradient aggregation as XLA collectives over ICI (``lax.psum``) inside one
  compiled train step (replaces ``SyncReplicasOptimizer`` accumulators and the
  NCCL ring, SURVEY.md §2 native-component table),
- an explicit, deterministic staleness emulator for the reference's async-PS
  stale-gradient flavor (SURVEY.md §3c, §7 hard-part 1),
- five parity workloads: MNIST LeNet-5, CIFAR-10 ResNet-20, ImageNet
  ResNet-50, ImageNet Inception-v3 (async-stale), BERT-base pretraining
  (BASELINE.json "configs"),
- ring-attention sequence/context parallelism over an ICI mesh axis
  (``shard_map`` + ``lax.ppermute``) as a first-class capability.

NOTE on citations: the reference mount ``/root/reference`` was empty in every
session of this build (verified in SURVEY.md "EVIDENCE STATUS"), so docstrings
cite SURVEY.md sections and BASELINE.json lines — the only checkable sources
describing the reference — instead of reference ``file:line``.
"""

__version__ = "0.1.0"

from distributed_tensorflow_tpu.parallel.mesh import (  # noqa: F401
    MeshSpec,
    build_mesh,
    initialize_runtime,
)
