"""Reducers: from samples, spans and device-trace intervals to one number.

Everything here is plain arithmetic on lists, so ``benchmarks/tests`` checks
it on hand-built traces with no device. An interval is ``(start_s, end_s)``.
A reducer that has nothing to read returns ``None`` and the harness leaves
the metric out of the line.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float):
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks — numpy's default, written out so that the yardstick does
    not move with a library."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def median(values):
    vals = list(values)
    return float(statistics.median(vals)) if vals else None


def mean(values):
    vals = list(values)
    return float(sum(vals) / len(vals)) if vals else None


# ------------------------------------------------------------------ intervals


def union(intervals):
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def busy_seconds(intervals) -> float:
    """Seconds in which at least one of ``intervals`` is open."""
    return total(union(intervals))


def span_of(intervals):
    """``(first start, last end)`` of the intervals, or ``None``."""
    ivs = [(s, e) for s, e in intervals if e > s]
    if not ivs:
        return None
    return (min(s for s, _ in ivs), max(e for _, e in ivs))


def idle_share(intervals, window=None):
    """1 - busy / window. ``window`` defaults to the span the intervals
    cover (first start to last end), so whole steps are counted and the
    profiler's own start-up is not."""
    window = window or span_of(intervals)
    if window is None or window[1] <= window[0]:
        return None
    clipped = clip(intervals, window)
    return 1.0 - busy_seconds(clipped) / (window[1] - window[0])


def clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def gaps(intervals):
    """The idle gaps between the merged intervals: ``[(start, end), ...]``."""
    merged = union(intervals)
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def consecutive_gaps(intervals):
    """Idle time between each execution and the next, in start order:
    ``max(0, next.start - this.end)``. For repeated executions of one
    module (decode steps) this is the time the device waited for the host."""
    ivs = sorted(intervals)
    return [max(0.0, b[0] - a[1]) for a, b in zip(ivs, ivs[1:])]


def busy_inside(outer, inner):
    """For each interval of ``outer``: the seconds of it that the union of
    ``inner`` covers (busy time inside one execution of a module)."""
    cover = union(inner)
    out = []
    for s, e in outer:
        out.append(total(clip(cover, (s, e))))
    return out


# --------------------------------------------------------- named reducers
# A layer-metric file names one of these under ``reduce``. Each takes the
# selected samples (seconds) or intervals plus the file's ``args`` and the
# context the harness built, and returns a value in the metric's unit.


def _ms(x):
    return None if x is None else 1e3 * x


def r_mean_ms(sel, args, ctx):
    return _ms(mean(sel["durations"]))


def r_median_ms(sel, args, ctx):
    return _ms(median(sel["durations"]))


def r_p95_ms(sel, args, ctx):
    return _ms(percentile(sel["durations"], 95))


def r_sum_per_step_ms(sel, args, ctx):
    """Summed duration of the selected device ops on the first chip, per
    execution of the module ``args["per"]`` names."""
    steps = ctx["count_modules"](args["per"])
    if not steps or not sel["durations"]:
        return None
    return _ms(sum(sel["durations"]) / steps)


def r_median_gap_ms(sel, args, ctx):
    return _ms(median(consecutive_gaps(sel["intervals"])))


def r_module_busy_median_ms(sel, args, ctx):
    """Median over executions of the selected module of the busy time of
    the chip's ops inside it."""
    if not sel["intervals"]:
        return None
    return _ms(median(busy_inside(sel["intervals"], ctx["op_intervals"]())))


def r_idle_share_pct(sel, args, ctx):
    share = idle_share(sel["intervals"])
    return None if share is None else 100.0 * share


def r_roofline_pct(sel, args, ctx):
    """The least time the chip could take for the selected kernel's work in
    one step — max(ops / peak FLOP/s, bytes / peak B/s), with ops and bytes
    from ``benchmarks/flops.py`` by shape — over the time it took."""
    steps = ctx["count_modules"](args["per"])
    if not steps or not sel["durations"]:
        return None
    per_step_s = sum(sel["durations"]) / steps
    work = ctx["kernel_work"](args["kernel"])
    peaks = ctx["peaks"]
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_step_s


REDUCERS = {
    "mean_ms": r_mean_ms,
    "median_ms": r_median_ms,
    "p95_ms": r_p95_ms,
    "sum_per_step_ms": r_sum_per_step_ms,
    "median_gap_ms": r_median_gap_ms,
    "module_busy_median_ms": r_module_busy_median_ms,
    "idle_share_pct": r_idle_share_pct,
    "roofline_pct": r_roofline_pct,
}
