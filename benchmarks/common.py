"""What both runners share: the device gate, the compile counter, memory
readings, and the run context that ``run.py`` hands a runner."""

from __future__ import annotations

import dataclasses
import json
import sys
import time


@dataclasses.dataclass
class Run:
    """One invocation: the cell's file, its configuration's file, and the
    command line. ``t_start`` is ``time.monotonic()`` at process start."""

    name: str
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    trace_dir: str
    sweep: list | None = None
    describe_trace: str | None = None

    @property
    def rehearsal(self) -> bool:
        return bool(self.workload.get("rehearsal"))

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]


def info(kind: str, **fields) -> None:
    """An earlier line of standard output: never the last one."""
    print(json.dumps({"info": kind, **fields}, default=str), flush=True)


def require_devices(run: Run) -> list:
    """The devices of this process, which must be the TPU chips the cell
    asks for. A rehearsal cell (never listed in BENCHMARK.json) takes what
    is there and says what it was."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not run.rehearsal:
        if platform != "tpu":
            sys.exit(f"cell {run.name!r} runs on a TPU only; jax found {platform!r}")
        if len(devices) != run.workload["chips"]:
            sys.exit(
                f"cell {run.name!r} asks for {run.workload['chips']} chip(s); "
                f"jax found {len(devices)}"
            )
    return devices


def peak_bytes_in_use(devices) -> int:
    """The runtime's ``peak_bytes_in_use`` on the fullest chip. A runner reads
    it when the window closes, before ``correct`` is decided: the comparison
    with the float32 reference holds buffers of its own (0.75 GB of logits
    in the training cell) that no step and no request ever needed."""
    peak = 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — the CPU backend reports nothing
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_report(devices, window_peak_bytes: int, program_temp_bytes: int) -> dict:
    """The device as jax reports it. ``memory_peak_bytes`` is the window's
    ``peak_bytes_in_use`` plus ``program_temp_bytes``: on this installation
    the runtime's counter holds the buffers (weights, optimizer state, KV
    slots, batches) and leaves out the scratch the compiler reserves for a
    running program (``memory_analysis().temp_size_in_bytes`` of the cell's
    main program: 12 GB of activations for the L=512 step against a counter
    of 1.7 GB), so the peak on the chip is their sum."""
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(window_peak_bytes) + int(program_temp_bytes),
    }


class CompileCounter:
    """Counts XLA compilations (cache hits included: a hit still stalls the
    window while the program loads). ``with counter:`` brackets the window."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.total = 0
        self.in_window = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self._EVENT:
            self.total += 1
            if self._open:
                self.in_window += 1

    def __enter__(self):
        self._open = True
        return self

    def __exit__(self, *exc):
        self._open = False


class Stopwatch:
    """Names the parts of set-up: ``lap("init")`` closes the part that
    began at the previous lap."""

    def __init__(self, t_start: float):
        self._last = t_start
        self.parts: dict[str, float] = {}

    def lap(self, name: str) -> None:
        now = time.monotonic()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._last
        self._last = now
