"""Why ``serve_tokens_per_s`` of ``phi4_mini_flash.reason_steady`` moves 3-5% with
the seed and nothing in the program does (PERF.md section 6, PR 35).

A model of the continuous batcher on the CPU, from the cell's own traffic
(``benchmarks/traffic.py``) and three numbers measured on the chip: a decode
step of 34.2 ms whatever is live, a prefill of 13 / 16 / 23 ms at buckets
128 / 256 / 512, times 1 / 1.7 / 3 for an admission of 1 / 2 / up to 4 rows.
Prints, a seed, what ``runners/serve.py::_reduce`` would report: the window
(first due -> last completion), ``serve_tokens_per_s``, ``tpot_p95_ms``, the
median queue wait + prefill of the first and the last quarter.

    python scripts/sambay_window_model.py --seeds 2147485101,2147485102 [--rate 4.8] [--seconds 40]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

STEP_S = 0.0342
PREFILL_S = {128: 0.013, 256: 0.016, 512: 0.023}
TIER_FACTOR = {1: 1.0, 2: 1.7, 3: 3.0, 4: 3.0}
SLOTS, MAX_BATCH = 128, 4


def model(traffic_spec: dict, seed: int, seconds: float, rate: float) -> dict:
    from benchmarks import traffic

    reqs = traffic.generate(traffic_spec, seed, seconds, 200064, rate_rps=rate)
    due = [r.due_s for r in reqs]
    out = [r.max_new_tokens for r in reqs]
    bucket = [min(b for b in PREFILL_S if len(r.prompt) <= b) for r in reqs]
    t, nxt, queue, live, first, end = 0.0, 0, [], {}, {}, {}
    while len(end) < len(reqs):
        while nxt < len(reqs) and due[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        if not live and not queue:
            t = due[nxt]
            continue
        admitted = []
        while queue and len(admitted) < MAX_BATCH and len(live) + len(admitted) < SLOTS:
            admitted.append(queue.pop(0))
        if admitted:
            t += PREFILL_S[max(bucket[i] for i in admitted)] * TIER_FACTOR[len(admitted)]
            for i in admitted:
                live[i], first[i] = 1, t
        if live:
            t += STEP_S
            for i in list(live):
                live[i] += 1
                if live[i] >= out[i]:
                    end[i] = t
                    del live[i]
    n = len(reqs)
    tpot = [(end[i] - first[i]) / (out[i] - 1) for i in range(n)]
    wait = [first[i] - due[i] for i in range(n)]
    q = max(1, n // 4)
    window = max(end.values())
    return {"seed": seed, "requests": n, "window_s": window,
            "serve_tokens_per_s": sum(out) / window,
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "wait_first_quarter_ms": 1e3 * float(np.median(wait[:q])),
            "wait_last_quarter_ms": 1e3 * float(np.median(wait[-q:]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    cell = json.loads(
        (ROOT / "benchmarks/workloads/phi4_mini_flash.reason_steady.json").read_text()
    )["traffic"]
    for seed in args.seeds.split(","):
        print(json.dumps(model(cell, int(seed), args.seconds,
                               args.rate or cell["rate_rps"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
