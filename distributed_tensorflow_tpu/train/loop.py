"""Training driver loop — the replacement for MonitoredTrainingSession.

The reference's L6 (SURVEY.md §1): ``MonitoredTrainingSession`` + hooks +
``while not sess.should_stop(): sess.run(train_op)``. Here the loop is plain
Python around one compiled step; hooks become plain callables; there is no
chief (every host runs the identical loop; host-dependent work like metric
printing is gated on ``jax.process_index() == 0``).

TPU-first details:

- the loop never blocks on device values except at the logging cadence —
  metrics come back as device arrays and are only fetched every
  ``log_every`` steps, keeping the step stream fully async;
- the feed is **pull-ahead**: step ``i`` is dispatched *before* batch
  ``i+1`` is fetched, so host batch assembly/transfer overlaps device
  compute even for unwrapped producers, and composes with
  ``data.prefetch`` (which moves the assembly itself onto a feeder
  thread — in steady state ``next(it)`` is then a queue pop ≈ 0);
- feed stalls are measured, not inferred: every blocking ``next(it)`` is
  timed into ``feed_metrics`` and surfaced as ``host_wait_ms`` at the log
  cadence alongside ``steps_per_sec``.
"""

from __future__ import annotations

import json
import logging
import math
import time
from collections.abc import Callable, Iterable, Iterator
from typing import Any

import jax

from distributed_tensorflow_tpu.obs.flightrec import NULL_RECORDER
from distributed_tensorflow_tpu.obs.memory import default_registry
from distributed_tensorflow_tpu.obs.metrics import FeedMetrics
from distributed_tensorflow_tpu.obs.trace import NULL_TRACER, Tracer

logger = logging.getLogger(__name__)

# hook(step: int, state, metrics: dict[str, float]) -> None, called at log cadence
Hook = Callable[[int, Any, dict], None]


class NonFiniteLossError(RuntimeError):
    """The step loss went NaN/Inf — training state is garbage from here.

    Raised by the loop's non-finite guard (``fit(nonfinite="abort")``, the
    default). Deliberately NOT a transient failure class: restarting from
    the last checkpoint would replay the same divergence, so
    ``train/resilience.py`` classifies it fatal-with-dump.
    """

    def __init__(self, step: int, loss: float):
        super().__init__(
            f"non-finite loss {loss!r} at step {step}; aborting (use "
            "--nonfinite=skip to tolerate)"
        )
        self.step = step
        self.loss = loss


def _batch_layout(batch) -> dict:
    """Per leaf: the global shape, one device's shard of it, and how many
    devices hold a shard — where the batch really lives, not where the
    loader was asked to put it."""
    layout = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(batch):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            layout[jax.tree_util.keystr(path, simple=True, separator="/")] = {
                "shape": list(leaf.shape),
                "shard": list(sharding.shard_shape(leaf.shape)),
                "devices": len(sharding.device_set),
            }
    return layout


def fit(
    state,
    train_step,
    data: Iterable,
    *,
    num_steps: int,
    rng: jax.Array | None = None,
    log_every: int = 100,
    hooks: tuple[Hook, ...] = (),
    checkpointer=None,
    ckpt_every: int = 0,
    evaluate: Callable[[Any], dict] | None = None,
    eval_every: int = 0,
    feed_metrics: FeedMetrics | None = None,
    tracer: Tracer | None = None,
    timeline=None,
    memory=None,
    recorder=None,
    fault_injector=None,
    nonfinite: str = "abort",
    should_stop: Callable[[], bool] | None = None,
):
    """Run the training loop; returns the final state.

    ``data`` yields already-placed global batches (see ``data`` package).
    ``checkpointer``/``ckpt_every`` wire in periodic async checkpointing —
    the analog of the reference chief's periodic ``tf.train.Saver`` writes
    (SURVEY.md §5 checkpoint row), minus the chief: saving is collective.
    ``evaluate(state) -> dict`` runs every ``eval_every`` steps (and at the
    end); its metrics reach the hooks prefixed ``eval_`` — the held-out
    accuracy loop the reference never had (SURVEY.md §4 "do better").

    ``feed_metrics`` collects host-wait observations (every blocking
    ``next(it)`` in the loop is timed into it); when ``data`` carries its
    own bundle (a ``data.prefetch`` wrapper exposes ``.metrics``) that one
    is picked up automatically so feeder- and consumer-side numbers land in
    one place. Logged throughput is **steady-state**: the wall-clock origin
    resets after the first step of the run completes, so step-0
    tracing+compilation never dilutes ``steps_per_sec``.

    ``tracer`` (obs/trace.py) records the per-step phase timeline —
    ``host_wait`` (blocked on the feed) and ``dispatch`` (handing the step
    to the device stream) every step, ``device``/``metrics_fetch`` at the
    log cadence (the only points the loop blocks on device values), plus
    ``checkpoint_save`` and ``eval`` spans — each carrying its ``step``
    correlation key. Disabled (the default) it is a no-op context manager
    per call site, cheap enough to leave in the hot loop. Enabled, each
    span is also a profiler annotation (obs/trace.py), and every dispatch
    sits inside ``tracer.step("train", step)`` (a ``StepTraceAnnotation``),
    so a capture (``--profile-dir`` with ``--trace-dir``) shows the loop's
    phases on the host lines of the same trace as the device ops, grouped
    by step.

    ``timeline`` (obs/fleet.py :class:`StepTimeline`) records every step's
    wall / host-wait / dispatch durations into windowed series and runs the
    in-line straggler detector — the per-host health view the fleet
    beacons publish (cli/train.py ``--beacon-dir``). Three clock reads and
    a histogram insert per step; ``None`` (the default) costs nothing.

    ``memory`` (obs/memory.py :class:`MemoryRegistry`; default the
    process-wide registry) receives the ``params`` / ``opt_state`` /
    ``grad_ring`` byte footprints once at loop entry — shape-derived, so
    the accounting never touches the step stream.

    ``recorder`` (obs/flightrec.py) receives the loop's failure-path
    events (``nonfinite_loss``); ``fault_injector``
    (train/faultinject.py) is consulted once per step before dispatch —
    both default to no-ops and cost nothing in the hot loop.

    ``nonfinite`` is the NaN/Inf-loss policy, checked at the metrics
    cadence (``log_every`` — the loop only ever blocks on device values
    there, so the guard adds ZERO extra syncs; up to ``log_every - 1``
    poisoned steps can run before detection): ``"abort"`` (default)
    raises :class:`NonFiniteLossError`, ``"skip"`` records the event and
    trains on.

    ``should_stop`` is polled once per step; returning True ends the loop
    cleanly with the current state (the preemption path —
    ``train/resilience.py`` wires its SIGTERM/SIGINT flag here and then
    writes the final synchronous checkpoint).
    """
    if rng is None:
        rng = jax.random.key(0)
    if tracer is None:
        tracer = NULL_TRACER
    if recorder is None:
        recorder = NULL_RECORDER
    if nonfinite not in ("abort", "skip"):
        raise ValueError(f"nonfinite must be 'abort' or 'skip', got {nonfinite!r}")
    # HBM accounting (obs/memory.py): shape-derived byte counts, no device
    # sync. ``memory`` defaults to the process-wide registry so a train
    # process's footprints show up anywhere /memz-style tooling looks.
    if memory is None:
        memory = default_registry()
    for component, tree in (
        ("params", getattr(state, "params", None)),
        ("opt_state", getattr(state, "opt_state", None)),
        ("grad_ring", getattr(state, "grad_buffer", None)),
    ):
        if tree is not None:
            memory.register_tree(component, tree)
    it: Iterator = iter(data)
    if feed_metrics is None:
        feed_metrics = getattr(data, "metrics", None) or FeedMetrics()
    pending_metrics = None
    start_step = int(state.step)
    if start_step >= num_steps:
        return state, None  # restored at (or past) the final step
    poison_step = None  # injected nonfinite_loss pending detection
    t0 = time.perf_counter()  # run origin (only used if the run is 1 step)
    t_steady = None           # reset after the first step: excludes compile
    t_fetch = time.perf_counter()
    with tracer.span("host_wait", "train", step=start_step):
        batch = next(it)
    feed_metrics.observe_wait(time.perf_counter() - t_fetch)
    if jax.process_index() == 0:
        logger.info("batch_layout: %s", json.dumps(_batch_layout(batch)))
    for step in range(start_step, num_steps):
        if should_stop is not None and should_stop():
            logger.info("stop requested before step %d; leaving the loop", step)
            break
        poison = (
            fault_injector.on_step(step) if fault_injector is not None else False
        )
        t_iter = time.perf_counter()
        wait_s = 0.0
        # The step marker groups a capture by step; the span inside it is
        # the loop's own dispatch time. Both are no-ops on a disabled tracer.
        with tracer.step("train", step), \
                tracer.span("dispatch", "train", step=step):
            state, metrics = train_step(state, batch, rng)
        if poison and poison_step is None:
            # Injected nonfinite_loss: poison the METRIC (what the guard
            # watches), leaving the state untouched — the guard path is
            # exercised without actually diverging the model. Sticky until
            # the next metrics fetch, which is where the guard runs.
            poison_step = step + 1
        dispatch_s = time.perf_counter() - t_iter
        if t_steady is None:
            # The first call paid tracing + compilation (dispatch itself is
            # async); everything after this point is the steady-state
            # stream the logged throughput should describe.
            t_steady = time.perf_counter()
        if step + 1 < num_steps:
            # Pull-ahead: fetch batch i+1 while the device runs step i.
            t_fetch = time.perf_counter()
            with tracer.span("host_wait", "train", step=step + 1):
                batch = next(it)
            wait_s = time.perf_counter() - t_fetch
            feed_metrics.observe_wait(wait_s)
        if log_every and ((step + 1) % log_every == 0 or step + 1 == num_steps):
            # Fetch (blocks on the step stream only here) — ONE device_get
            # for the whole dict, not a per-leaf float() sync each. The
            # `device` span is the honest device edge: the blocking wait on
            # the dispatched step stream; `metrics_fetch` is the host-side
            # conversion after it.
            with tracer.span("device", "train", step=step + 1):
                fetched_dev = jax.device_get(metrics)
            with tracer.span("metrics_fetch", "train", step=step + 1):
                fetched = {k: float(v) for k, v in fetched_dev.items()}
            if poison_step is not None and "loss" in fetched:
                fetched["loss"] = float("nan")
                poison_step = None
            loss = fetched.get("loss")
            if loss is not None and not math.isfinite(loss):
                # str(), not the float: NaN/Inf are not valid JSON and the
                # recorder's dump must stay strictly parseable.
                recorder.record(
                    "nonfinite_loss", step=step + 1, loss=str(loss),
                    action=nonfinite,
                )
                if nonfinite == "abort":
                    raise NonFiniteLossError(step + 1, loss)
                logger.warning(
                    "non-finite loss %r at step %d (nonfinite=skip: training on)",
                    loss, step + 1,
                )
            now = time.perf_counter()
            steps_done = step - start_step  # steady-state steps completed
            if steps_done > 0:
                dt = now - t_steady
            else:
                # Log fired on the very first step: nothing but the compile
                # step exists, so report the honest compile-inclusive rate.
                dt, steps_done = now - t0, 1
            fetched["steps_per_sec"] = steps_done / dt if dt > 0 else 0.0
            fetched.update(feed_metrics.window())
            if jax.process_index() == 0:
                logger.info(
                    "step %d: %s",
                    step + 1,
                    " ".join(f"{k}={v:.5g}" for k, v in sorted(fetched.items())),
                )
            for hook in hooks:
                hook(step + 1, state, fetched)
            pending_metrics = fetched
        if evaluate is not None and eval_every and (
            (step + 1) % eval_every == 0 or step + 1 == num_steps
        ):
            with tracer.span("eval", "train", step=step + 1):
                ev = {
                    f"eval_{k}": float(v)
                    for k, v in jax.device_get(evaluate(state)).items()
                }
            if jax.process_index() == 0:
                logger.info(
                    "step %d eval: %s",
                    step + 1,
                    " ".join(f"{k}={v:.5g}" for k, v in sorted(ev.items())),
                )
            for hook in hooks:
                hook(step + 1, state, ev)
            pending_metrics = {**(pending_metrics or {}), **ev}
        if checkpointer is not None and ckpt_every and (step + 1) % ckpt_every == 0:
            with tracer.span("checkpoint_save", "train", step=step + 1):
                checkpointer.save(step + 1, state)
        if timeline is not None:
            # Whole-iteration wall time on purpose: a step slowed by eval
            # or a checkpoint save IS slow from the fleet's point of view;
            # the detector's trailing MEDIAN keeps periodic spikes from
            # shifting the baseline.
            timeline.record_step(
                step + 1,
                time.perf_counter() - t_iter,
                host_wait_s=wait_s,
                dispatch_s=dispatch_s,
            )
    return state, pending_metrics
