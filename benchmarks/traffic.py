"""One general generator of request traffic, driven by a cell's ``traffic``
parameters and the run's seed.

    {"rate_rps": 100,
     "prompt_len": {"median": 64, "sigma": 0.8, "min": 8, "max": 256},
     "output_len": {"median": 48, "sigma": 0.7, "min": 4, "max": 128},
     "token_ids": {"low": 5}, "shape_seed": 20260928}

Arrivals are a Poisson process at ``rate_rps`` and lengths are lognormal,
clipped: the one mix the listed cells run. Another arrival process or length
distribution is new code, which a ``benchmark`` PR adds with the cell that
needs it. The *set* of gaps and of (prompt, output) sizes is drawn from ``shape_seed``,
which the cell fixes; the run's ``--seed`` only shuffles their order and
draws the token ids. So every seed offers the same work — the same number
of requests, prompt tokens and output tokens over the same time — in another
order, and runs with different seeds differ no more than runs of one seed.
"""

from __future__ import annotations

from collections import namedtuple

import numpy as np

#: ``due_s`` is seconds from the start of the window.
Request = namedtuple("Request", "index due_s prompt max_new_tokens")

_SEED_MASK = (1 << 63) - 1


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lognormal lengths around ``median``, rounded, clipped to
    ``min``..``max``."""
    out = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(out).astype(np.int64), spec["min"], spec["max"])


def draw_gaps(seconds: float, rate: float, rng) -> np.ndarray:
    """``rate x seconds`` exponential inter-arrival gaps in seconds, scaled
    so that the set fills the window exactly: the offered load is ``rate``,
    not ``rate`` give or take a draw."""
    gaps = rng.exponential(1.0 / rate, max(1, int(round(rate * seconds))))
    return gaps * (seconds / gaps.sum())


def generate(traffic: dict, seed: int, seconds: float, vocab_size: int,
             rate_rps: float | None = None) -> list[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    rate = float(rate_rps if rate_rps is not None else traffic["rate_rps"])
    shape_rng = np.random.default_rng(int(traffic["shape_seed"]) & _SEED_MASK)
    gaps = draw_gaps(seconds, rate, shape_rng)
    n = len(gaps)
    prompt_len = draw_lengths(traffic["prompt_len"], n, shape_rng)
    output_len = draw_lengths(traffic["output_len"], n, shape_rng)

    rng = np.random.default_rng(int(seed) & _SEED_MASK)
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)  # sizes stay paired, their order moves
    prompt_len, output_len = prompt_len[order], output_len[order]
    due = np.cumsum(gaps) - gaps[0]
    low = int(traffic["token_ids"]["low"])
    out = []
    for i in range(n):
        ids = rng.integers(low, vocab_size, int(prompt_len[i]), dtype=np.int64)
        out.append(Request(i, float(due[i]), ids.astype(np.int32), int(output_len[i])))
    return out


def offered(requests: list[Request]) -> dict:
    return {
        "requests": len(requests),
        "prompt_tokens": int(sum(len(r.prompt) for r in requests)),
        "output_tokens": int(sum(r.max_new_tokens for r in requests)),
    }
