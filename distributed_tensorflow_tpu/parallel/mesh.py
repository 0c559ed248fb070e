"""Device-mesh bootstrap: topology discovery for the single SPMD entrypoint.

Replaces the reference's cluster-topology and launcher layers (SURVEY.md §1
L2/L7, §3a-3b): where the reference declares ``tf.train.ClusterSpec({"ps":
[...], "worker": [...]})`` and spawns one gRPC ``tf.train.Server`` per role
via ``run_ps.py`` / ``run_worker.py``, here every host runs the *same*
program, calls :func:`initialize_runtime` once, and builds a
:class:`jax.sharding.Mesh` over all devices in the slice. Roles (ps/worker/
chief) do not exist; parameters live replicated or sharded on the TPUs
themselves, so the gRPC PS data path is eliminated by construction
(BASELINE.json:5 "zero gRPC PS traffic").

Mesh axis conventions used across the framework:

- ``"data"``  — data parallelism (batch sharded, params replicated).
- ``"model"`` — tensor/model parallelism (params sharded; optional).
- ``"seq"``   — sequence/context parallelism for long-context attention
  (ring attention over ICI neighbors; see ``parallel/ring_attention.py``).
- ``"replica"`` — reserved for a DCN axis across slices (multi-slice DP).

Within a slice, axes map onto ICI; across slices, put the outermost
(pure-DP) axis on DCN — this is the standard multislice recipe.
"""

from __future__ import annotations

import dataclasses
import logging
from collections.abc import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

# Canonical axis names, in the order they should appear in a mesh (outermost
# first: slowest-varying ⇒ DCN/furthest devices, innermost ⇒ ICI neighbors).
AXIS_ORDER = ("replica", "data", "pipeline", "expert", "seq", "model")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape.

    ``axes`` maps axis name -> size. At most one axis may be ``-1``, meaning
    "all remaining devices". Axes of size 1 are kept (they are free and make
    ``PartitionSpec``s uniform across configs).

    Example::

        MeshSpec({"data": -1})                      # pure DP over everything
        MeshSpec({"data": -1, "seq": 4})            # DP x 4-way context parallel
        MeshSpec({"replica": 2, "data": -1})        # 2 slices over DCN
    """

    axes: Mapping[str, int]

    def __post_init__(self):
        unknown = [a for a in self.axes if a not in AXIS_ORDER]
        if unknown:
            raise ValueError(
                f"unknown mesh axes {unknown}; expected a subset of {AXIS_ORDER}"
            )
        wild = [a for a, n in self.axes.items() if n == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")

    def resolve(self, n_devices: int) -> dict[str, int]:
        """Return concrete sizes in canonical axis order, filling the -1 axis."""
        fixed = 1
        for a, n in self.axes.items():
            if n != -1:
                if n <= 0:
                    raise ValueError(f"axis {a!r} must be positive or -1, got {n}")
                fixed *= n
        sizes = dict(self.axes)
        wild = [a for a, n in self.axes.items() if n == -1]
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        else:
            total = fixed
            if total != n_devices:
                raise ValueError(
                    f"mesh {dict(self.axes)} needs {total} devices, have {n_devices}"
                )
        return {a: sizes[a] for a in AXIS_ORDER if a in sizes}


_runtime_initialized = False


def initialize_runtime(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize the multi-host JAX runtime (idempotent).

    This is the entire replacement for the reference's per-role server
    bootstrap (SURVEY.md §3a: ``tf.train.Server(cluster, "ps", k);
    server.join()``): on a TPU pod whose launcher exports the slice's host
    list, jax works out coordinator and process topology itself and zero
    arguments are needed; explicit arguments serve every other launcher and
    CPU/GPU multi-process testing.

    Must be called before anything touches the XLA backend (first ``jit`` /
    ``jax.devices()``), exactly like ``jax.distributed.initialize`` itself.

    ``jax.distributed.initialize`` runs only for a declared cluster (any
    explicit argument) or a detected one (:func:`_cluster_env_present`), and
    whatever it raises propagates: a misconfigured cluster must not degrade
    to N independent single-process jobs. A single process with neither
    makes no call at all — with no arguments jax's cluster detection asks
    the cloud metadata server, which a machine without one answers only
    after minutes of retries.

    There is no ``server.join()`` analog because there are no passive
    processes — every host executes the compiled SPMD program.
    """
    global _runtime_initialized
    if _runtime_initialized:
        return
    explicit = any(
        a is not None for a in (coordinator_address, num_processes, process_id)
    )
    if explicit or _cluster_env_present():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    else:
        logger.info("single-process runtime (no cluster declared or detected)")
    _runtime_initialized = True


def _cluster_env_present() -> bool:
    """True only for genuinely multi-host environment markers.

    Single-host TPU VMs legitimately set ``TPU_WORKER_HOSTNAMES=localhost``
    — a one-entry host list is not a cluster, and needs no coordinator.
    """
    import os

    env = os.environ.get
    if env("COORDINATOR_ADDRESS") or env("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    hostnames = env("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    try:
        # A nonzero worker id means this process is not the only worker even
        # if the launcher didn't propagate the full host list.
        if int(env("TPU_WORKER_ID", "0")) > 0:
            return True
    except ValueError:
        pass
    for count_var in ("SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(env(count_var, "0")) > 1:
                return True
        except ValueError:
            pass
    return False


def build_mesh(
    spec: MeshSpec | Mapping[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` from slice metadata.

    Default is a 1-D ``"data"`` mesh over every addressable-or-not device in
    the job — the SPMD collapse of the reference's whole ps/worker cluster
    (SURVEY.md §1 "Key structural fact").

    Devices are ordered so that the innermost mesh axes land on
    ICI-contiguous neighbors (jax's default device order already follows the
    physical torus for TPU).
    """
    if spec is None:
        spec = MeshSpec({"data": -1})
    elif not isinstance(spec, MeshSpec):
        spec = MeshSpec(dict(spec))
    if devices is None:
        devices = jax.devices()
    sizes = spec.resolve(len(devices))
    names = tuple(sizes)
    shape = tuple(sizes.values())
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names=names)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes over which the global batch is sharded (DP-like axes)."""
    return tuple(a for a in ("replica", "data") if a in mesh.axis_names)


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    """A dp/tp replan onto a surviving device set (see
    :func:`plan_elastic_mesh`). ``axes`` feeds straight into
    :func:`build_mesh` together with the surviving device list;
    ``notes`` records every fallback taken, in order."""

    axes: dict[str, int]
    n_devices: int      # devices the plan actually uses (dp * tp)
    dp: int
    tp: int
    grad_accum: int
    global_batch: int
    notes: tuple[str, ...] = ()


def plan_elastic_mesh(
    surviving: int | Sequence,
    *,
    tp: int = 1,
    global_batch: int = 0,
    grad_accum: int = 1,
    old_dp: int = 0,
) -> ElasticPlan:
    """Replan dp/tp onto the devices that survived a host loss.

    The elastic-resume recipe (docs/DEPLOY.md "Surviving a cluster"): when
    the :class:`~distributed_tensorflow_tpu.obs.fleet.FleetSupervisor`
    declares ``re_mesh``, the relaunch calls this with the surviving
    device set (or count), builds ``build_mesh(plan.axes, devices)``, and
    restores the sharded checkpoint straight into the new layout — orbax/
    tensorstore reshards on read, so no migration step exists.

    Degradation policy, mirroring ``serve.engine.plan_serve_mesh``: never
    refuse a survivable topology, always log what was given up —

    - ``tp`` that no longer divides the survivors falls back to its
      largest divisor that does (worst case 1 = pure DP; params restore
      into any tp width via the template machinery);
    - ``dp`` shrinks to the largest width dividing ``global_batch``
      (loaders require exact divisibility), idling the remainder — a
      smaller mesh that trains beats a bigger one that cannot;
    - ``grad_accum`` is rescaled by ``old_dp / new_dp`` (rounded up to a
      divisor of the per-device rows) so the GLOBAL batch — and with it
      the training trajectory's recipe — is preserved while the
      per-microslice device memory stays bounded at the old level.
    """
    n = surviving if isinstance(surviving, int) else len(surviving)
    if n < 1:
        raise ValueError(f"need at least one surviving device, got {n}")
    notes: list[str] = []
    tp = max(int(tp), 1)
    if tp > 1 and (tp > n or n % tp):
        new_tp = max(d for d in range(1, min(tp, n) + 1) if tp % d == 0 and n % d == 0)
        notes.append(
            f"tp={tp} does not divide {n} surviving devices; falling back "
            f"to tp={new_tp}"
        )
        tp = new_tp
    dp = n // tp
    if global_batch:
        if global_batch % dp:
            new_dp = max(d for d in range(1, dp + 1) if global_batch % d == 0)
            notes.append(
                f"global batch {global_batch} not divisible by dp={dp}; "
                f"shrinking to dp={new_dp} (idling {(dp - new_dp) * tp} "
                "surviving devices)"
            )
            dp = new_dp
    ga = max(int(grad_accum), 1)
    if old_dp and global_batch and old_dp != dp:
        # Preserve the old per-microslice device rows: the activation
        # memory the old layout was sized for.
        scaled = ga * old_dp / dp
        new_ga = max(int(-(-scaled // 1)), 1)  # ceil
        per_dev = global_batch // dp
        while per_dev % new_ga and new_ga < per_dev:
            new_ga += 1
        if new_ga != ga:
            notes.append(
                f"grad_accum {ga} -> {new_ga} (dp {old_dp} -> {dp}; global "
                f"batch {global_batch} preserved)"
            )
            ga = new_ga
    axes = {"data": dp}
    if tp > 1:
        axes["model"] = tp
    for note in notes:
        logger.warning("elastic re-mesh: %s", note)
    return ElasticPlan(
        axes=axes,
        n_devices=dp * tp,
        dp=dp,
        tp=tp,
        grad_accum=ga,
        global_batch=global_batch,
        notes=tuple(notes),
    )


@dataclasses.dataclass(frozen=True)
class DisaggPlan:
    """A prefill/decode role split over one device slice (see
    :func:`plan_disagg_mesh`). ``*_device_ids`` index into the caller's
    device list (``jax.devices()`` order); ``*_axes`` feed straight into
    :func:`build_mesh` together with the corresponding device subset.
    ``fell_back`` means the roles share devices (colocated) because the
    slice was too small to split; ``notes`` records every fallback taken,
    in order."""

    prefill_axes: dict[str, int]
    decode_axes: dict[str, int]
    prefill_device_ids: tuple[int, ...]
    decode_device_ids: tuple[int, ...]
    fell_back: bool = False
    notes: tuple[str, ...] = ()


def plan_disagg_mesh(
    n_devices: int,
    *,
    prefill_devices: int = -1,
    prefill_tp: int = 1,
    decode_tp: int = 1,
) -> DisaggPlan:
    """Plan a prefill/decode engine-role split onto one device slice.

    The serving twin of :func:`plan_elastic_mesh` and the inference rebirth
    of the reference's ps/worker role split (SURVEY.md §1 L2–L3): prefill
    is compute-bound and bursty, decode is memory-bound and steady, so a
    disaggregated fleet plans them onto disjoint device subsets of the same
    slice. Pure arithmetic — no jax import needed at plan time, so the
    shardcheck SC002 sweep can cross it with every layout.

    ``prefill_devices=-1`` means "half the slice, at least one device".
    Degradation policy mirrors ``plan_elastic_mesh``: never refuse a
    plannable topology, always note what was given up —

    - a slice too small to split (``n_devices < 2``) falls back to
      colocated roles sharing every device (``fell_back=True``);
    - an explicit ``prefill_devices`` that would leave the decode role
      empty is shrunk to leave at least one decode device;
    - a role ``tp`` that does not divide its device count falls back to
      the largest divisor that does (worst case 1).

    Genuinely invalid inputs (``n_devices < 1``, non-positive explicit
    ``prefill_devices``, non-positive tp) raise a clean ``ValueError`` —
    the plan-or-clean-ValueError contract the SC002 sweep enforces.
    """
    if n_devices < 1:
        raise ValueError(f"need at least one device, got {n_devices}")
    if prefill_devices != -1 and prefill_devices < 1:
        raise ValueError(
            f"prefill_devices must be -1 (auto) or >= 1, got {prefill_devices}"
        )
    if prefill_tp < 1 or decode_tp < 1:
        raise ValueError(
            f"role tp must be >= 1, got prefill_tp={prefill_tp} "
            f"decode_tp={decode_tp}"
        )
    notes: list[str] = []
    if n_devices < 2:
        notes.append(
            "slice too small to split roles; colocating prefill and decode "
            "on the same device"
        )
        ids = tuple(range(n_devices))
        pre_ids, dec_ids, fell_back = ids, ids, True
    else:
        n_pre = prefill_devices if prefill_devices != -1 else n_devices // 2
        if n_pre >= n_devices:
            notes.append(
                f"prefill_devices={n_pre} would leave no decode devices on "
                f"a {n_devices}-device slice; shrinking to {n_devices - 1}"
            )
            n_pre = n_devices - 1
        pre_ids = tuple(range(n_pre))
        dec_ids = tuple(range(n_pre, n_devices))
        fell_back = False

    def _role_axes(role: str, tp: int, n: int) -> dict[str, int]:
        if tp > 1 and (tp > n or n % tp):
            new_tp = max(
                d for d in range(1, min(tp, n) + 1) if tp % d == 0 and n % d == 0
            )
            notes.append(
                f"{role} tp={tp} does not divide its {n} devices; falling "
                f"back to tp={new_tp}"
            )
            tp = new_tp
        axes = {"data": n // tp}
        if tp > 1:
            axes["model"] = tp
        return axes

    prefill_axes = _role_axes("prefill", prefill_tp, len(pre_ids))
    decode_axes = _role_axes("decode", decode_tp, len(dec_ids))
    for note in notes:
        logger.warning("disagg role plan: %s", note)
    return DisaggPlan(
        prefill_axes=prefill_axes,
        decode_axes=decode_axes,
        prefill_device_ids=pre_ids,
        decode_device_ids=dec_ids,
        fell_back=fell_back,
        notes=tuple(notes),
    )


# Short axis tags for layout labels, keyed by the canonical axis names.
_AXIS_SHORT = {
    "replica": "rep",
    "data": "dp",
    "pipeline": "pp",
    "expert": "ep",
    "seq": "sp",
    "model": "tp",
}


def layout_label(mesh: Mesh) -> str:
    """Compact human/metric-label tag for a mesh layout.

    Size-1 axes are dropped (they change no sharding): ``{"data": 2,
    "model": 4}`` -> ``"dp2-tp4"``; a single-device mesh -> ``"single"``.
    Used as the serving engines' layout identity — it keys the
    layout-labelled ServeMetrics instruments and the serve_bench per-layout
    report, so it must be stable across runs (it is: axis order is the
    mesh's, which ``build_mesh`` derives from ``AXIS_ORDER``).
    """
    parts = [
        f"{_AXIS_SHORT.get(a, a)}{mesh.shape[a]}"
        for a in mesh.axis_names
        if mesh.shape[a] > 1
    ]
    return "-".join(parts) or "single"


def batch_pspec(mesh: Mesh) -> P:
    """The canonical batch PartitionSpec: leading dim over the DP axes.

    Single source of truth for the DP-batch rule — used by the data loader,
    the train/eval steps, and ``batch_sharding``.
    """
    axes = data_axes(mesh)
    return P(axes if axes else None)


def batch_sharding(mesh: Mesh, ndim: int = 0) -> NamedSharding:
    """Sharding for a batch: leading dim split over the DP axes, rest replicated.

    ``ndim`` is accepted for readability at call sites but unused:
    PartitionSpec only needs the leading entry.
    """
    del ndim
    return NamedSharding(mesh, batch_pspec(mesh))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (the SPMD analog of PS-hosted variables —
    except every chip holds a copy and no RecvTensor RPC exists,
    SURVEY.md §2 native-component table row 1)."""
    return NamedSharding(mesh, P())


def local_batch_size(
    global_batch: int, mesh: Mesh, extra_axes: Sequence[str] = ()
) -> int:
    """Per-host slice of the global batch (for building host-local arrays).

    The single rule every loader follows (data/loader.py, data/text.py):
    each of the job's ``jax.process_count()`` hosts materializes an equal
    contiguous slice; ``jax.make_array_from_process_local_data`` assembles
    the global array. Validates divisibility by both the DP world size
    (shard shapes must be static) and the host count. ``extra_axes`` names
    additional mesh axes the batch rows shard over (e.g. ``("expert",)``
    under the token-sharded MoE layout) so the loud divisibility check
    covers the full row partition, not just the DP axes.
    """
    axes = tuple(data_axes(mesh)) + tuple(
        a for a in extra_axes if a in mesh.axis_names
    )
    n_data = int(np.prod([mesh.shape[a] for a in axes], initial=1))
    if global_batch % n_data:
        raise ValueError(f"global batch {global_batch} not divisible by {n_data}")
    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(f"global batch {global_batch} not divisible by {n_proc} hosts")
    if n_proc > 1:
        # The equal-slice-per-process rule assumes every process owns the
        # same number of mesh devices (true on uniform TPU slices). On a
        # job where hosts own unequal shares, each host's slice would no
        # longer match its addressable shards and
        # make_array_from_process_local_data would mis-assemble — fail
        # loudly instead of corrupting batches.
        counts: dict[int, int] = {}
        for d in mesh.devices.flat:
            counts[d.process_index] = counts.get(d.process_index, 0) + 1
        if len(counts) != n_proc or len(set(counts.values())) > 1:
            # len(counts) < n_proc: a process owns ZERO mesh devices but
            # would still be assigned a batch slice — just as mis-assembled
            # as an uneven split.
            raise ValueError(
                "mesh devices are unevenly distributed across processes "
                f"({counts} over {n_proc} processes); equal per-process "
                "batch slices require uniform local device counts"
            )
    return global_batch // n_proc
