"""The traffic generator and the due-time arithmetic of the serving runner."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import traffic
from benchmarks.runners import serve

ROOT = Path(__file__).resolve().parents[1]
MIX = json.loads((ROOT / "workloads" / "lm_base.chat_steady.json").read_text())["traffic"]


def _key(reqs):
    return [(r.due_s, tuple(r.prompt.tolist()), r.max_new_tokens) for r in reqs]


def test_same_seed_same_requests_other_seed_other_requests():
    a = traffic.generate(MIX, 3_000_000_011, 5.0, 40478)
    b = traffic.generate(MIX, 3_000_000_011, 5.0, 40478)
    c = traffic.generate(MIX, 3_000_000_012, 5.0, 40478)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.generate(MIX, 1, 5.0, 40478)
    c = traffic.generate(MIX, 2**31 + 5, 5.0, 40478)
    assert traffic.offered(a) == traffic.offered(c)
    sizes = lambda reqs: sorted((len(r.prompt), r.max_new_tokens) for r in reqs)  # noqa: E731
    assert sizes(a) == sizes(c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    # the same set of gaps, but for the one that falls before the first request
    gaps = lambda reqs: set(np.diff([r.due_s for r in reqs]).round(9))  # noqa: E731
    assert len(gaps(a) ^ gaps(c)) <= 2


def test_lengths_are_clipped_and_arrivals_fill_the_window():
    reqs = traffic.generate(MIX, 9, 10.0, 40478)
    n = round(MIX["rate_rps"] * 10.0)
    assert len(reqs) == n
    assert reqs[0].due_s == 0.0 and reqs[-1].due_s < 10.0
    assert all(a.due_s <= b.due_s for a, b in zip(reqs, reqs[1:]))
    p = [len(r.prompt) for r in reqs]
    o = [r.max_new_tokens for r in reqs]
    assert min(p) >= MIX["prompt_len"]["min"] and max(p) <= MIX["prompt_len"]["max"]
    assert min(o) >= MIX["output_len"]["min"] and max(o) <= MIX["output_len"]["max"]
    assert all(r.prompt.min() >= MIX["token_ids"]["low"] and r.prompt.max() < 40478 for r in reqs)
    assert 50 < np.median(p) < 80 and 38 < np.median(o) < 60


def _record(due, t_submit, queue_wait, prefill, decode, n_tokens, latency):
    fut = SimpleNamespace(
        phases={"queue_wait": queue_wait, "prefill": prefill, "decode": decode},
        latency_s=latency,
    )
    req = SimpleNamespace(prompt=[7] * 20)
    return {"req": req, "due": due, "t_submit": t_submit, "future": fut,
            "refused": None, "result": {"n_tokens": n_tokens}}


def test_ttft_is_timed_from_when_the_request_was_due():
    # due at t=10, sent 4 ms late, waited 6 ms, prefilled 10 ms: 20 ms.
    recs = [_record(10.0, 10.004, 0.006, 0.010, 0.5, 51, 0.516)]
    red = serve._reduce(recs, t0=10.0, seconds=1.0)
    assert red["ttft_p95_ms"] == pytest.approx(20.0)
    assert red["tpot_p95_ms"] == pytest.approx(10.0)  # 0.5 s over 50 gaps
    assert red["lateness_p95_ms"] == pytest.approx(4.0)
    # tokens over first due .. last completion: 51 / (10.004 + 0.516 - 10.0)
    assert red["serve_tokens_per_s"] == pytest.approx(51 / 0.52)
    assert red["spans"]["ttft"] == pytest.approx([0.020])
    # 20 prompt tokens + half of 51, held for 0.51 s of a 0.52 s window
    assert red["kv_tokens_held_mean"] == pytest.approx(45.5 * 0.51 / 0.52)
    assert red["failed"] == 0 and red["sustained"]


def test_a_refused_request_counts_as_failed_and_misses_every_limit():
    recs = [_record(0.0, 0.0, 0.001, 0.01, 0.1, 11, 0.111) for _ in range(9)]
    recs.append({"req": None, "due": 0.0, "t_submit": 0.0, "future": None,
                 "refused": "Backpressure"})
    red = serve._reduce(recs, t0=0.0, seconds=1.0)
    assert red["failed"] == 1 and red["attempted"] == 10
    assert red["ttft_p95_ms"] > 1e3  # the tail holds the miss
    assert not red["sustained"]


def test_growing_queue_wait_is_not_sustained():
    recs = [_record(i, i, 0.002 + (0.05 * i if i >= 30 else 0), 0.01, 0.1, 11, 0.2)
            for i in range(40)]
    assert not serve._reduce(recs, t0=0.0, seconds=40.0)["sustained"]
