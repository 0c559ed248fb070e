"""Capture a window of the profiler's trace and read the device's events.

The capture is ``jax.profiler.start_trace`` / ``stop_trace`` with the Python
tracer off (it slows the host code that the serving cell measures). The
reading needs nothing but jax: ``ProfileData.from_file`` gives planes, lines
and events with a start and a duration in nanoseconds. Which planes and
lines are the device's is data (``trace_layout.json``).
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
from collections import defaultdict, namedtuple
from pathlib import Path

from . import reduce as R

HERE = Path(__file__).resolve().parent

#: One device event. ``chip`` is the device ordinal, times are seconds from
#: the profile's origin, ``name`` the HLO instruction's name (``fusion.12``),
#: ``meta`` the event's full text as the profiler has it (on a TPU the whole
#: HLO instruction, ``%attention.3 = (...) custom-call(...)``) and its stats.
Event = namedtuple("Event", "chip name start end meta")


class DeviceTrace:
    """The device's side of one captured window: ops and module executions
    per chip. Built by :func:`read`; tests build it by hand."""

    def __init__(self, ops, modules):
        self.ops = sorted(ops, key=lambda e: e.start)
        self.modules = sorted(modules, key=lambda e: e.start)
        self.chips = sorted({e.chip for e in self.ops} | {e.chip for e in self.modules})

    @property
    def first_chip(self):
        return self.chips[0] if self.chips else None

    def select(self, kind: str, pattern: str, chip=None, field: str = "name"):
        events = {"ops": self.ops, "modules": self.modules}[kind]
        rx = re.compile(pattern)
        return [
            e for e in events
            if (chip is None or e.chip == chip) and rx.search(getattr(e, field))
        ]

    def op_intervals(self, chip):
        return [(e.start, e.end) for e in self.ops if e.chip == chip]

    def busy_and_window(self):
        """``(busy_s, window_s)`` averaged over the chips that ran anything.
        The window of a chip is from its first op's start to its last op's
        end, so whole steps are counted and the profiler's start-up is not."""
        busy, window = [], []
        for chip in self.chips:
            ivs = self.op_intervals(chip)
            span = R.span_of(ivs)
            if span is None:
                continue
            busy.append(R.busy_seconds(ivs))
            window.append(span[1] - span[0])
        if not busy:
            return 0.0, 0.0
        return sum(busy) / len(busy), sum(window) / len(window)

    def breakdown(self, n_ops: int = 8, n_gaps: int = 5) -> dict:
        """The device ops with most time on the first chip, and its longest
        idle gaps named by the modules that ran on either side."""
        chip = self.first_chip
        if chip is None:
            return {"device_ops": [], "idle_gaps": []}
        by_name = defaultdict(float)
        for e in self.ops:
            if e.chip == chip:
                by_name[e.name] += e.end - e.start
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n_ops]
        mods = [e for e in self.modules if e.chip == chip]

        def module_at(t, before: bool):
            best = None
            for m in mods:
                if before and m.start <= t:
                    best = m
                if not before and m.end >= t:
                    return m.name
            return best.name if (before and best) else "?"

        longest = sorted(R.gaps(self.op_intervals(chip)), key=lambda g: g[0] - g[1])
        gaps = [
            [f"{module_at(s, True)} -> {module_at(e, False)}", e - s]
            for s, e in longest[:n_gaps]
        ]
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


def start(logdir: str) -> None:
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def _clean(name: str) -> str:
    """An HLO op's name without the text of its operands: '%fusion.3 = ...'
    and 'fusion.3' both give 'fusion.3'."""
    name = name.strip()
    if name.startswith("%"):
        name = name[1:]
    return name.split(" ", 1)[0].split("=", 1)[0]


def read(logdir: str, platform: str) -> DeviceTrace | None:
    """The device events of the newest capture under ``logdir``, or ``None``
    if there is no capture or the platform has no layout entry."""
    import jax

    layout = json.loads((HERE / "trace_layout.json").read_text()).get(platform)
    files = sorted(glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb")))
    if layout is None or not files:
        return None
    data = jax.profiler.ProfileData.from_file(files[-1])
    plane_rx = re.compile(layout["plane"])
    def line_rx(key):
        return re.compile(layout[key]) if layout.get(key) else None

    ops_line, mod_line = line_rx("ops_line"), line_rx("modules_line")
    ops_stat = layout.get("ops_stat")
    ops, modules = [], []
    for plane in data.planes:
        m = plane_rx.search(plane.name)
        if not m:
            continue
        chip = int(m.group(1)) if m.groups() else 0
        for line in plane.lines:
            is_ops = bool(ops_line and ops_line.search(line.name))
            is_mod = bool(mod_line and mod_line.search(line.name))
            if not (is_ops or is_mod or ops_stat):
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                start_s = ev.start_ns * 1e-9
                end_s = start_s + ev.duration_ns * 1e-9
                if is_mod:
                    modules.append(Event(chip, ev.name, start_s, end_s, ""))
                    continue
                stats = dict(ev.stats)
                if ops_stat and not is_ops:
                    if ops_stat not in stats:
                        continue
                    chip = int(stats.get("device_ordinal", chip))
                meta = ev.name + " " + " ".join(f"{k}={v}" for k, v in stats.items())
                ops.append(Event(chip, _clean(ev.name), start_s, end_s, meta))
    return DeviceTrace(ops, modules)


def describe(logdir: str, out_path: str, top: int = 40) -> None:
    """Write what a capture holds — planes, lines, and each line's heaviest
    event names with one event's stats — for looking at a trace by hand
    before writing ``select`` patterns against it."""
    import jax

    files = sorted(glob.glob(os.path.join(logdir, "plugins/profile/*/*.xplane.pb")))
    if not files:
        return
    data = jax.profiler.ProfileData.from_file(files[-1])
    lines_out = []
    for plane in data.planes:
        lines_out.append(f"PLANE {plane.name!r} stats={list(plane.stats)[:8]}")
        for line in plane.lines:
            agg = defaultdict(lambda: [0, 0.0, None])
            n = 0
            for ev in line.events:
                n += 1
                a = agg[ev.name[:160]]
                a[0] += 1
                a[1] += ev.duration_ns * 1e-9
                if a[2] is None:
                    a[2] = [(k, str(v)[:120]) for k, v in ev.stats][:12]
            lines_out.append(f"  LINE {line.name!r} events={n} distinct={len(agg)}")
            for name, (cnt, dur, stats) in sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]:
                lines_out.append(f"    {dur:10.6f}s x{cnt:<6d} {name!r} {stats}")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    Path(out_path).write_text("\n".join(lines_out) + "\n")
