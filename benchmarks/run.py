"""One run of one cell:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

reads ``workloads/<name>.json``, its ``config`` from ``configs/``, hands both
to ``runners/<runner>.py`` and prints, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics: the
names its workload file lists under ``end_to_end`` and ``layer_metrics``.
See README.md.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmarks import common, flops, layers  # noqa: E402
from benchmarks import trace as tracelib  # noqa: E402


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        sys.exit(f"no {kind[:-1]} named {name!r}: {path} is missing")
    return json.loads(path.read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="serving cells: comma-separated rates (req/s) to try "
                         "in one process; prints a table, not a result")
    ap.add_argument("--describe-trace", default="",
                    help="with --trace 1: also write what the capture holds "
                         "(planes, lines, heaviest events) to this file")
    args = ap.parse_args(argv)
    # The program's own log (fit's step lines, the engine's grid) goes to
    # standard error with the time, so a slow set-up can be read off it.
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s: %(message)s")

    workload = load_json("workloads", args.workload)
    config = load_json("configs", workload["config"])
    run = common.Run(
        name=args.workload, workload=workload, config=config, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        trace_dir=str(HERE / "_trace" / args.workload),
        sweep=[float(r) for r in args.sweep.split(",") if r] or None,
        describe_trace=args.describe_trace or None,
    )
    runner = importlib.import_module(f"benchmarks.runners.{workload['runner']}")
    result = runner.run(run)
    if result is None:  # a sweep prints its own table
        return 0

    devices = result["devices"]
    device = common.device_report(
        devices, result["window_peak_bytes"], result["program_temp_bytes"])
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    if run.trace:
        dtrace = tracelib.read(run.trace_dir, device["platform"])
        if run.describe_trace:
            tracelib.describe(run.trace_dir, run.describe_trace)
        peaks = None if run.rehearsal else flops.chip_peaks(device["kind"])
        line["metrics"] = layers.evaluate(
            layers.load_for(run.name, workload), spans=result["spans"], trace=dtrace,
            config=config, job=result.get("job", {}), peaks=peaks,
        )
        busy_s, window_s = dtrace.busy_and_window() if dtrace else (0.0, 0.0)
        device["busy_s"], device["window_s"] = busy_s, window_s
        line["device"] = device
        line["breakdown"] = dtrace.breakdown() if dtrace else {"device_ops": [], "idle_gaps": []}
    else:
        # The runner measures what it can; the cell's file says which of it
        # the cell reports (BENCHMARK.json's end_to_end lists the same cells).
        line["metrics"] = {
            name: dict(zip(("value", "unit"), result["end_to_end"][name]))
            for name in workload["end_to_end"]
        }
        line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
