"""Why ``tpot_p95_ms`` of ``olmo_hybrid_7b.longdoc_steady`` moved 5-10% with
the seed while nothing in the program did (PERF.md section 6, PR 37), and why
the cell's runner now takes the ORDER of its requests from ``shape_seed``
(``benchmarks/runners/serve_olmo_hybrid.py::requests``): the order a seed
gives is all this model knows of it. ``--seeds <the cell's shape_seed>`` is the
order every run of the cell offers.

A model of the continuous batcher with chunked prefill on the CPU, from the
cell's own traffic (``benchmarks/traffic.py``) and two numbers measured on the
chip: a decode step of 19.45 ms whatever is live, and a chunk of 43.3 ms
whatever part of its 512 lanes is real. A pass of the batcher's loop admits
at most one request into a free slot, runs one chunk of the first slot that
is still taking its prompt, then one decode step over the slots that are
past theirs; a request's ``tpot`` is its decode phase over its tokens less
one, as ``runners/serve.py::_reduce`` has it. Prints, a seed, the modelled
``tpot_p95_ms`` / ``tpot_p50_ms`` / ``ttft_p50_ms``; with ``--spread N`` the
spread (interquartile range over the median, as the driver takes it) of N
seeds in sets of six.

    python scripts/olmo_hybrid_window_model.py --seeds 2147484101,2147484102
    python scripts/olmo_hybrid_window_model.py --spread 24 [--rate 1.4] [--chunk-tokens 512 --chunk-ms 43.3]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL = ROOT / "benchmarks/workloads/olmo_hybrid_7b.longdoc_steady.json"


def model(spec: dict, seed: int, seconds: float, rate: float, *, slots: int,
          step_s: float, chunk_s: float, chunk_tokens: int) -> dict:
    from benchmarks import traffic

    reqs = traffic.generate(spec, seed, seconds, 100352, rate_rps=rate)
    due = [r.due_s for r in reqs]
    out = [r.max_new_tokens for r in reqs]
    prompt = [len(r.prompt) for r in reqs]
    t, nxt, queue, first, end = 0.0, 0, [], {}, {}
    table = [None] * slots  # [request, prompt positions in, tokens out]
    while len(end) < len(reqs):
        while nxt < len(reqs) and due[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        if not queue and not any(table):
            t = due[nxt]
            continue
        free = [k for k, s in enumerate(table) if s is None]
        if queue and free:
            table[free[0]] = [queue.pop(0), 0, 0]
        for s in table:  # one chunk, of the first slot still in its prompt
            if s is not None and s[1] < prompt[s[0]]:
                s[1] = min(prompt[s[0]], s[1] + chunk_tokens)
                t += chunk_s
                if s[1] >= prompt[s[0]]:
                    first[s[0]], s[2] = t, 1
                break
        if any(s is not None and s[2] for s in table):
            t += step_s
            for k, s in enumerate(table):
                if s is not None and s[2]:
                    s[2] += 1
                    if s[2] >= out[s[0]]:
                        end[s[0]], table[k] = t, None
    n = range(len(reqs))
    tpot = [(end[i] - first[i]) / (out[i] - 1) for i in n]
    return {"seed": seed, "requests": len(reqs),
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "tpot_p50_ms": 1e3 * float(np.percentile(tpot, 50)),
            "ttft_p50_ms": 1e3 * float(np.percentile(
                [first[i] - due[i] for i in n], 50))}


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="", help="comma-separated")
    ap.add_argument("--spread", type=int, default=0,
                    help="this many seeds from 2147485000, in sets of six")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--step-ms", type=float, default=19.45)
    ap.add_argument("--chunk-ms", type=float, default=43.3)
    ap.add_argument("--chunk-tokens", type=int, default=512)
    args = ap.parse_args(argv)
    cell = json.loads(CELL.read_text())
    run = lambda seed: model(  # noqa: E731
        cell["traffic"], seed, args.seconds,
        args.rate or cell["traffic"]["rate_rps"], slots=cell["slots"],
        step_s=args.step_ms / 1e3, chunk_s=args.chunk_ms / 1e3,
        chunk_tokens=args.chunk_tokens,
    )
    for seed in filter(None, args.seeds.split(",")):
        print(json.dumps(run(int(seed))), flush=True)
    if args.spread:
        rows = [run(2147485000 + i) for i in range(args.spread)]
        for name in ("tpot_p95_ms", "tpot_p50_ms"):
            values = [r[name] for r in rows]
            print(json.dumps({
                "metric": name, "median": statistics.median(values),
                "spread_pct": 100 * spread(values),
                "sets_of_six_pct": [
                    100 * spread(values[i:i + 6])
                    for i in range(0, len(values) - 5, 6)
                ],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
