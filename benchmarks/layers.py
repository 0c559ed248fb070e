"""Per-layer metrics as data: one file ``layer_metrics/<name>.json`` a metric,
read in a traced run by every cell whose workload file lists the name under
``layer_metrics``, and by every cell the file itself names under ``cells``.

    {"layer": "...", "unit": "ms", "moves": "train_tokens_per_s",
     "cells": ["<a cell that was there before this metric>", ...],   # optional
     "source": "span" | "trace",
     "select": "<regex>", "reduce": "<name in reduce.REDUCERS>", "args": {...}}

``source: span`` selects host spans by name (the program's ``Tracer`` spans
of the training loop, the per-request ``phases`` of a future) and reduces
their durations. ``source: trace`` selects device events of the profiler's
trace: ``args.on`` says whether ``select`` matches op names (``ops``,
default), an op's full text and stats (``ops_meta``) or module names
(``modules``), on the first chip. A reader that finds nothing returns
nothing, and the metric is left out of the line.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

from . import flops
from .reduce import REDUCERS

HERE = Path(__file__).resolve().parent


def load_for(cell: str, workload: dict) -> dict:
    """The metric files a cell reports: those its workload file lists, and
    those that name the cell under the optional key ``cells``. So a new cell
    takes metrics that are there, and a new metric takes cells that are
    there, and neither edits a file."""
    out = {}
    for path in sorted((HERE / "layer_metrics").glob("*.json")):
        spec = json.loads(path.read_text())
        if path.stem in workload["layer_metrics"] or cell in spec.get("cells", ()):
            out[path.stem] = spec
    missing = set(workload["layer_metrics"]) - set(out)
    if missing:
        raise SystemExit(f"cell {cell!r} lists layer metrics with no file: {sorted(missing)}")
    return out


def _select(spec: dict, spans: dict, trace) -> dict:
    args = spec.get("args", {})
    if spec["source"] == "span":
        rx = re.compile(spec["select"])
        durations = [d for name, ds in spans.items() if rx.search(name) for d in ds]
        return {"durations": durations, "intervals": []}
    if trace is None:
        return {"durations": [], "intervals": []}
    on = args.get("on", "ops")
    chip = trace.first_chip
    kind = "ops" if on == "ops_meta" else on
    field = "meta" if on == "ops_meta" else "name"
    events = trace.select(kind, spec["select"], chip=chip, field=field)
    return {
        "durations": [e.end - e.start for e in events],
        "intervals": [(e.start, e.end) for e in events],
    }


def evaluate(specs: dict, *, spans: dict, trace, config: dict, job: dict,
             peaks: dict | None) -> dict:
    """``{name: {"value": v, "unit": u}}`` for every metric that found
    something to read."""

    def count_modules(pattern):
        if trace is None:
            return 0
        return len(trace.select("modules", pattern, chip=trace.first_chip))

    def kernel_work(kernel):
        return flops.KERNEL_WORK[kernel](config, job["seq_len"], job["per_chip_batch"])

    ctx = {
        "count_modules": count_modules,
        "kernel_work": kernel_work,
        "op_intervals": lambda: trace.op_intervals(trace.first_chip),
        "peaks": peaks,
    }
    out = {}
    for name, spec in specs.items():
        sel = _select(spec, spans, trace)
        if not sel["durations"] and not sel["intervals"]:
            continue
        value = REDUCERS[spec["reduce"]](sel, spec.get("args", {}), ctx)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out
