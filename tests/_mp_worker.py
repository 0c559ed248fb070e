"""Worker body for the 2-process localhost cluster smoke test.

The SPMD analog of the reference's "launch real ps/workers on localhost
ports" testing idiom (SURVEY.md §4): N identical processes, one coordinator
address, no roles. Run by tests/test_multiprocess.py:

    python tests/_mp_worker.py <process_id> <num_processes> <port> [mode]

mode "dp" (default): LeNet sync-DP over all devices. mode "tp": tiny BERT
on the PRODUCTION cross-host layout — the data axis spans the processes
while the model (tensor-parallel) axis stays inside each process's local
devices, so row-parallel psums ride process-local links and only the DP
pmean crosses the process boundary. Prints one JSON line with a digest of
the final replicated params; the launcher asserts every process converged
to bit-identical replicated state.
"""

import json
import sys


def main() -> int:
    proc_id, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else "dp"

    import jax

    # 4 virtual CPU devices per process -> an 8-device global mesh. Must be
    # set before the first backend touch (same trick as tests/conftest.py).
    # threefry_partitionable matches conftest so the pp/ep rehearsals'
    # trajectories are comparable against the launcher's in-process runs.
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    jax.config.update("jax_threefry_partitionable", True)

    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.data import (
        device_batches,
        synthetic_image_classification,
    )
    from distributed_tensorflow_tpu.models import LeNet5
    from distributed_tensorflow_tpu.parallel.mesh import (
        build_mesh,
        initialize_runtime,
    )
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.objectives import (
        init_model,
        make_classification_loss,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    if mode == "chaos":
        # Like "straggler": beacons/checkpoints/dumps are the coordination-
        # free channels under test, so no JAX cluster — each host trains on
        # its own local mesh.
        return _chaos_body(proc_id, sys.argv[5])

    if mode == "straggler":
        # Beacons are collective-free by design — the processes share only
        # the beacon directory, never a JAX cluster — so this mode skips
        # initialize_runtime and runs each host on its own local mesh.
        return _straggler_body(proc_id, sys.argv[5])

    initialize_runtime(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=proc_id,
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.devices()) == 4 * nproc, len(jax.devices())

    if mode == "tp":
        return _tp_body(proc_id, nproc)
    if mode in ("pp", "ep", "sp_ring", "sp_ulysses"):
        if mode == "pp":
            rec = pp_train()
        elif mode == "ep":
            rec = ep_train()
        else:
            rec = sp_train(impl=mode.removeprefix("sp_"))
        rec["proc"] = proc_id
        rec["n_devices"] = len(jax.devices())
        print(json.dumps(rec))
        return 0

    mesh = build_mesh({"data": -1})
    model = LeNet5()
    params, model_state = init_model(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1), jnp.float32)
    )
    tx = optax.sgd(0.05, momentum=0.9)
    state = place_state(create_train_state(params, tx, model_state), mesh)
    step = make_train_step(make_classification_loss(model), tx, mesh)

    ds = synthetic_image_classification(256, (28, 28, 1), 10, seed=0)
    batches = device_batches(ds, mesh, global_batch=32, seed=1)
    rng = jax.random.key(0)
    loss = None
    for _ in range(3):
        state, metrics = step(state, next(batches), rng)
        loss = float(metrics["loss"])

    # Params are replicated; every process reads its addressable shard and
    # digests it — identical across processes iff training stayed in lockstep.
    leaves = jax.tree.leaves(state.params)
    digest = float(
        sum(np.abs(np.asarray(jax.device_get(x))).sum() for x in leaves)
    )
    print(
        json.dumps(
            {
                "proc": proc_id,
                "digest": round(digest, 6),
                "loss": loss,
                "step": int(state.step),
                "n_devices": len(jax.devices()),
            }
        )
    )
    return 0


def _straggler_body(proc_id: int, beacon_dir: str) -> int:
    """Fleet-health rehearsal: the sync-DP LeNet run driven through the
    real ``fit(timeline=...)`` path, with process 0 seeded 5x slower (a
    per-step sleep — the 'one bad host' failure mode). Each process trains
    on its own local-device mesh (no cross-process cluster: beacons are
    the coordination-free channel under test) and writes its HostBeacon;
    the launcher aggregates the beacon directory and must flag process 0
    and ONLY process 0."""
    import time

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.data import (
        device_batches,
        synthetic_image_classification,
    )
    from distributed_tensorflow_tpu.models import LeNet5
    from distributed_tensorflow_tpu.obs.fleet import HostBeacon, StepTimeline
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import (
        create_train_state,
        make_train_step,
    )
    from distributed_tensorflow_tpu.train.loop import fit
    from distributed_tensorflow_tpu.train.objectives import (
        init_model,
        make_classification_loss,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    mesh = build_mesh({"data": -1})
    model = LeNet5()
    params, model_state = init_model(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1), jnp.float32)
    )
    tx = optax.sgd(0.05, momentum=0.9)
    state = place_state(create_train_state(params, tx, model_state), mesh)
    step = make_train_step(make_classification_loss(model), tx, mesh)

    # 5x seeded degradation, sized to dominate the ~30ms real step compute
    # (0.05 vs 0.01 would land the beacon medians right at the 2.0 detection
    # threshold once compute is added on top).
    delay = 0.25 if proc_id == 0 else 0.05

    def seeded_step(state_, batch_, rng_):
        time.sleep(delay)  # the seeded degradation (sync-DP keeps lockstep)
        return step(state_, batch_, rng_)

    timeline = StepTimeline()
    ds = synthetic_image_classification(256, (28, 28, 1), 10, seed=0)
    batches = device_batches(ds, mesh, global_batch=32, seed=1)
    state, _ = fit(
        state,
        seeded_step,
        batches,
        num_steps=12,
        log_every=0,
        timeline=timeline,
    )
    beacon = HostBeacon(beacon_dir, proc_id, timeline)
    beacon.write()
    summ = timeline.summary()
    print(
        json.dumps(
            {
                "proc": proc_id,
                "last_step": timeline.last_step,
                "median_step_s": summ["step_s"]["p50"],
                "step": int(state.step),
                "n_devices": len(jax.devices()),
            }
        )
    )
    return 0


def _chaos_body(proc_id: int, workdir: str) -> int:
    """ISSUE 15 chaos rehearsal: the sync-DP LeNet run through the REAL
    resilience surfaces — flight recorder, fault injector, async periodic
    checkpoints, health beacons — with process 0 carrying a seeded
    FaultPlan that SIGKILLs it mid-step-11 (``host_drop``: the preemption
    that never says goodbye). The injector force-dumps the flight recorder
    before pulling the trigger, so the launcher can read the injected
    events out of ``dumps_0`` even though the process died without atexit.

    Layout under ``workdir``: ``beacons/`` (shared), ``ckpt_<proc>/``,
    ``dumps_<proc>/``. Process 1 runs the same body fault-free to 16 and
    exits 0 — the survivor whose fresh beacon the FleetSupervisor must
    classify against the dead host's stale one.
    """
    import time
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.ckpt import Checkpointer
    from distributed_tensorflow_tpu.data import (
        device_batches,
        synthetic_image_classification,
    )
    from distributed_tensorflow_tpu.models import LeNet5
    from distributed_tensorflow_tpu.obs.fleet import HostBeacon, StepTimeline
    from distributed_tensorflow_tpu.obs.flightrec import FlightRecorder
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import (
        create_train_state,
        make_train_step,
    )
    from distributed_tensorflow_tpu.train.faultinject import (
        FaultEvent,
        FaultInjector,
        FaultPlan,
    )
    from distributed_tensorflow_tpu.train.loop import fit
    from distributed_tensorflow_tpu.train.objectives import (
        init_model,
        make_classification_loss,
    )
    from distributed_tensorflow_tpu.train.step import place_state

    work = Path(workdir)
    mesh = build_mesh({"data": -1})
    model = LeNet5()
    params, model_state = init_model(
        model, jax.random.key(0), jnp.zeros((1, 28, 28, 1), jnp.float32)
    )
    tx = optax.sgd(0.05, momentum=0.9)
    state = place_state(create_train_state(params, tx, model_state), mesh)
    step = make_train_step(make_classification_loss(model), tx, mesh)

    recorder = FlightRecorder(dump_dir=work / f"dumps_{proc_id}")
    if proc_id == 0:
        # slow_step at 5 proves a non-lethal injection lands in the same
        # dump/beacon channels; host_drop at 11 is the kill. ckpt_every=4
        # queues the async save at step 8 — the ~3 padded steps before
        # death give the tiny write ample time to become durable, so the
        # launcher's resume loses 11-8=3 <= ckpt_every steps.
        plan = FaultPlan(
            (
                FaultEvent("slow_step", 5, duration_s=0.05),
                FaultEvent("host_drop", 11),
            )
        )
    else:
        plan = FaultPlan(())
    injector = FaultInjector(plan, recorder=recorder)

    timeline = StepTimeline()
    beacon = HostBeacon(
        work / "beacons", proc_id, timeline, extras=injector.summary
    )

    def beacon_hook(step_no, state_, metrics_):
        beacon.write()

    def padded_step(state_, batch_, rng_):
        # Real wall-clock per step so the async checkpoint writer gets
        # scheduled between steps (and beacon wall_times order cleanly).
        time.sleep(0.12)
        return step(state_, batch_, rng_)

    ds = synthetic_image_classification(256, (28, 28, 1), 10, seed=0)
    batches = device_batches(ds, mesh, global_batch=32, seed=1)
    with Checkpointer(work / f"ckpt_{proc_id}", fault_injector=injector) as ckpt:
        state, _ = fit(
            state,
            padded_step,
            batches,
            num_steps=16,
            rng=jax.random.key(0),
            log_every=1,
            hooks=(beacon_hook,),
            checkpointer=ckpt,
            ckpt_every=4,
            timeline=timeline,
            recorder=recorder,
            fault_injector=injector,
        )
        ckpt.wait()
        latest = ckpt.latest_step()
    print(
        json.dumps(
            {
                "proc": proc_id,
                "step": int(state.step),
                "latest_ckpt": latest,
                "last_step": timeline.last_step,
            }
        )
    )
    return 0


def _tp_body(proc_id: int, nproc: int) -> int:
    """Tiny BERT, mesh data x model with the model axis inside each process
    (canonical axis order puts "data" outermost, so with 4 local devices
    and model=4 every TP group is process-local). Digests the REPLICATED
    leaves (embeddings, LN, post-psum biases) — identical across processes
    iff cross-process DP and within-process TP both stayed in lockstep."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_tensorflow_tpu.data.text import (
        SyntheticMLM,
        SyntheticMLMConfig,
        bert_batch_specs,
        mlm_device_batches,
    )
    from distributed_tensorflow_tpu.models.bert import (
        BertConfig,
        BertForPreTraining,
        bert_param_specs,
        make_bert_pretraining_loss,
    )
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.step import (
        _spec_axes,
        make_state_specs,
        place_state,
    )

    L = 32
    mesh = build_mesh({"data": nproc, "model": 4})
    cfg = BertConfig(
        vocab_size=96,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        intermediate_size=64,
        max_position=L,
        dropout_rate=0.0,
    )
    variables = BertForPreTraining(cfg).init(
        jax.random.key(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
        jnp.zeros((1, L), jnp.int32),
        train=False,
    )
    params = jax.device_get(variables["params"])
    tp_cfg = dataclasses.replace(cfg, model_axis="model", model_parallel=4)
    tx = optax.adam(1e-3)
    host_state = create_train_state(params, tx)
    specs = make_state_specs(host_state, tx, bert_param_specs(params))
    state = place_state(host_state, mesh, specs)
    step = make_train_step(
        make_bert_pretraining_loss(BertForPreTraining(tp_cfg)),
        tx,
        mesh,
        batch_spec=bert_batch_specs(mesh),
        state_specs=specs,
        clip_norm=0.05,  # active clipping exercises the spec-aware path
    )
    data = SyntheticMLM(SyntheticMLMConfig(vocab_size=96, seq_len=L, seed=0))
    batches = mlm_device_batches(data, mesh, 8 * nproc, seed=3)
    loss = grad_norm = None
    for _ in range(3):
        state, metrics = step(state, next(batches), jax.random.key(1))
        loss = float(metrics["loss"])
        grad_norm = float(metrics["grad_norm"])

    # Replicated leaves are fully addressable on every process; sharded
    # leaves are not, so digest only the replicated subtree.
    from jax.sharding import PartitionSpec as P

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    digest = 0.0
    n_replicated = 0
    for leaf, spec in zip(
        jax.tree.leaves(state.params),
        jax.tree.leaves(specs.params, is_leaf=is_spec),
    ):
        if not _spec_axes(spec):
            digest += float(np.abs(np.asarray(jax.device_get(leaf))).sum())
            n_replicated += 1
    print(
        json.dumps(
            {
                "proc": proc_id,
                "digest": round(digest, 6),
                "loss": loss,
                "grad_norm": grad_norm,
                "n_replicated": n_replicated,
                "step": int(state.step),
                "n_devices": len(jax.devices()),
            }
        )
    )
    return 0


def _digest_replicated(state, specs):
    """Sum-abs digest of the REPLICATED param leaves (fully addressable on
    every process; sharded leaves are not)."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.train.step import _spec_axes

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    digest, n = 0.0, 0
    for leaf, spec in zip(
        jax.tree.leaves(state.params),
        jax.tree.leaves(specs.params, is_leaf=is_spec),
    ):
        if not _spec_axes(spec):
            digest += float(np.abs(np.asarray(jax.device_get(leaf))).sum())
            n += 1
    return round(digest, 6), n


def _bert_train(cfg_init, cfg_run, mesh_axes, *, expert_sharded=False,
                seq_sharded=False, n_steps=3, global_batch=16):
    """Shared body for the pp/ep rehearsals: runnable identically inside a
    2-process cluster (the worker modes) and in-process on the 8-virtual-
    device mesh (the launcher's reference run) — VERDICT r4 #3's
    'trajectory equality with the single-process virtual-mesh run'."""
    import jax
    import optax

    from distributed_tensorflow_tpu.data.text import (
        SyntheticMLM,
        SyntheticMLMConfig,
        bert_batch_specs,
        mlm_device_batches,
    )
    from distributed_tensorflow_tpu.models.bert import (
        BertForPreTraining,
        bert_param_specs,
        make_bert_pretraining_loss,
    )
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh
    from distributed_tensorflow_tpu.train import create_train_state, make_train_step
    from distributed_tensorflow_tpu.train.step import make_state_specs, place_state

    import jax.numpy as jnp

    L = cfg_init.max_position
    variables = BertForPreTraining(cfg_init).init(
        jax.random.key(0),
        jnp.zeros((1, L), jnp.int32),
        jnp.ones((1, L), bool),
        jnp.zeros((1, L), jnp.int32),
        train=False,
    )
    params = jax.device_get(variables["params"])
    mesh = build_mesh(mesh_axes)
    tx = optax.adam(1e-3)
    host_state = create_train_state(params, tx)
    specs = make_state_specs(
        host_state,
        tx,
        bert_param_specs(
            params,
            model_axis=None,
            expert_axis=cfg_run.expert_axis,
            pipeline_axis=cfg_run.pipeline_axis,
        ),
    )
    state = place_state(host_state, mesh, specs)
    step = make_train_step(
        make_bert_pretraining_loss(BertForPreTraining(cfg_run)),
        tx,
        mesh,
        batch_spec=bert_batch_specs(
            mesh, expert_sharded=expert_sharded, seq_sharded=seq_sharded
        ),
        state_specs=specs,
        clip_norm=0.05,
    )
    data = SyntheticMLM(
        SyntheticMLMConfig(vocab_size=cfg_init.vocab_size, seq_len=L, seed=0)
    )
    batches = mlm_device_batches(
        data, mesh, global_batch, expert_sharded=expert_sharded,
        seq_sharded=seq_sharded, seed=3,
    )
    losses = []
    metrics = {}
    for _ in range(n_steps):
        state, metrics = step(state, next(batches), jax.random.key(1))
        losses.append(float(metrics["loss"]))
    digest, n_replicated = _digest_replicated(state, specs)
    return {
        "losses": losses,
        "loss": losses[-1],
        "grad_norm": float(metrics["grad_norm"]),
        "digest": digest,
        "n_replicated": n_replicated,
        "step": int(state.step),
    }


def pp_train(n_steps: int = 3):
    """Pure-pp BERT on mesh {pipeline: 8}: under the 2-process cluster the
    pipeline axis SPANS the process boundary (stages 0-3 on process 0,
    4-7 on process 1), so the GPipe ppermute hand-off crosses it on every
    tick — the rehearsal VERDICT r4 #3 asked for."""
    import dataclasses

    from distributed_tensorflow_tpu.models.bert import BertConfig

    base = BertConfig(
        vocab_size=96, hidden_size=32, num_layers=8, num_heads=4,
        intermediate_size=64, max_position=32, dropout_rate=0.0,
        pipeline_parallel=8,
    )
    run = dataclasses.replace(
        base, pipeline_axis="pipeline", pipeline_microbatches=4
    )
    return _bert_train(base, run, {"pipeline": 8}, n_steps=n_steps)


def ep_train(n_steps: int = 3):
    """Token-sharded (GShard) MoE BERT on mesh {expert: 8}: the dispatch
    all_to_all crosses the process boundary (experts 0-3 on process 0,
    4-7 on process 1)."""
    import dataclasses

    from distributed_tensorflow_tpu.models.bert import BertConfig

    base = BertConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
        intermediate_size=64, max_position=32, dropout_rate=0.0,
        moe_experts=8, moe_capacity_factor=4.0,
    )
    run = dataclasses.replace(
        base, expert_axis="expert", expert_parallel=8, moe_dispatch="sharded"
    )
    return _bert_train(
        base, run, {"expert": 8}, expert_sharded=True, global_batch=16,
        n_steps=n_steps,
    )


def sp_train(n_steps: int = 3, impl: str = "ring"):
    """Pure-sp BERT on mesh {seq: 8}: under the 2-process cluster the
    sequence axis SPANS the process boundary, so the ring's K/V ppermute
    hops (impl="ring") or the Ulysses head<->sequence all_to_alls
    (impl="ulysses") cross it on every layer of every step — the last
    parallelism family without a cross-process rehearsal after r5 added
    pp and ep."""
    import dataclasses

    from distributed_tensorflow_tpu.models.bert import BertConfig

    # Ulysses shards heads over the seq axis -> needs num_heads % 8 == 0;
    # the ring has no such constraint and uses the production head shape.
    heads = 8 if impl == "ulysses" else 4
    base = BertConfig(
        vocab_size=96, hidden_size=32, num_layers=2, num_heads=heads,
        intermediate_size=64, max_position=64, dropout_rate=0.0,
    )
    run = dataclasses.replace(base, seq_axis="seq", sp_impl=impl)
    return _bert_train(
        base, run, {"seq": 8}, seq_sharded=True, n_steps=n_steps
    )


if __name__ == "__main__":
    raise SystemExit(main())
