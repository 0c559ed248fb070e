"""Device time by named scope, from a profiler capture.

The program names its device work with ``jax.named_scope`` (``flash_fwd``,
``flash_dq``, ``flash_dkv``, ``mlm_head``, ``grad_reduce``, ``clip``,
``optimizer`` in the training step; ``kv_write``, ``cached_attention``,
``lm_head``, ``sample`` in the decode and prefill cells, and a hybrid model's
``ssm_scan``, ``ssm_step``, ``gmu``, ``window_attention``, ``full_attention``,
or ``delta_chunk``, ``delta_step``, ``short_conv``, and DeepSeek-V2's
``mla_project``, ``latent_attention``, ``mla_chunk_attention``, ``moe_route``,
``moe_experts``, ``shared_experts``, ``dense_mlp``).
The TPU profiler
keeps an op's ``op_name`` in the *metadata* of its events, which
``jax.profiler.ProfileData`` does not expose (it gives an event's own stats
only), so this reads the ``*.xplane.pb`` with the xplane proto itself
(tensorflow ships it). For a capture of ``POST /profilez``,
``cli.train --profile-dir`` or ``benchmarks/run.py --trace 1``:

    python scripts/trace_scopes.py <dir or .xplane.pb> [--per '^jit_decode_fn']

prints, per scope, the summed duration of the first chip's ops that carry it
and the same per execution of the module ``--per`` names; with ``--stats`` also
which metadata stats the ops carry and one example, and with ``--host`` the
totals of the host lines' events whose names match (the loops' spans). With
``--host`` a span's integer keys are summed too (``engine.decode_dispatch``'s
``rows``, ``full_rows_written``, ``full_blocks_read``, ``full_blocks_total``:
what the capture's steps moved, and the share of the table they touched). With
``--per`` and ``--host`` together it also says what the host did in the idle
gaps between consecutive executions of that module: for each matching host
event, the part of it that lies inside a gap, summed and per gap. Host and
device lines share the profiler's clock (line ``timestamp_ns`` + event
``offset_ps``), which is what makes the overlap meaningful.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import sys
from collections import defaultdict

SCOPES = ("flash_fwd", "flash_dq", "flash_dkv", "mlm_head", "grad_reduce", "clip",
          "optimizer", "kv_write", "cached_attention", "lm_head", "sample",
          # models/sambay.py: the prompt's scan, the step's recurrence, the gated
          # memory units, the ring readers and the readers of the one full table
          "ssm_scan", "ssm_step", "gmu", "window_attention", "full_attention",
          # models/olmo_hybrid.py: the delta rule over a prompt chunk's blocks
          # and one token a slot, and the convolution before both
          "delta_chunk", "delta_step", "short_conv",
          # models/deepseek_v2.py: latent attention's projections, its absorbed
          # decode read and a chunk's decompressed read; the router, the routed
          # experts' grouped matmuls, the shared experts, the dense layer
          "mla_project", "latent_attention", "mla_chunk_attention", "moe_route",
          "moe_experts", "shared_experts", "dense_mlp")
SCOPE_RX = re.compile(r"[/(](" + "|".join(SCOPES) + r")[/)]")


def _stat_text(stat, stat_names) -> str | None:
    kind = stat.WhichOneof("value")
    if kind == "str_value":
        return stat.str_value
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return None


def _events(plane, line_name=None):
    """``(event name, line name, start_ps, duration_ps, metadata id)`` of a
    plane's events; picoseconds on the profiler's clock, as integers."""
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for e in line.events:
                yield (plane.event_metadata[e.metadata_id].name, line.name,
                       line.timestamp_ns * 1000 + e.offset_ps, e.duration_ps, e.metadata_id)


def _device_report(device, per, want_stats: bool) -> list:
    """Prints the device's time by scope; returns the idle gaps (ps) between
    consecutive executions of the modules ``per`` matches."""
    stat_names = {k: v.name for k, v in device.stat_metadata.items()}
    scope_of, seen_stats, example = {}, defaultdict(int), None
    for mid, meta in device.event_metadata.items():
        texts = [t for t in (_stat_text(st, stat_names) for st in meta.stats) if t]
        for st in meta.stats:
            seen_stats[stat_names.get(st.metadata_id, "?")] += 1
        found = SCOPE_RX.search(" ".join(texts))
        if found:
            scope_of[mid] = found.group(1)
            example = example or (meta.name[:80], texts)
    runs = sorted((start, dur) for name, _, start, dur, _ in _events(device, "XLA Modules")
                  if per is not None and per.search(name))
    by_scope, busy = defaultdict(lambda: [0, 0]), 0
    for _, _, _, dur, mid in _events(device, "XLA Ops"):
        busy += dur
        if mid in scope_of:
            by_scope[scope_of[mid]][0] += 1
            by_scope[scope_of[mid]][1] += dur
    print(f"plane {device.name}, ops' summed time {busy * 1e-12:.4f} s"
          + (f", {len(runs)} executions of {per.pattern}" if per else ""))
    for scope in SCOPES:
        if scope in by_scope:
            n, dur = by_scope[scope]
            per_step = f"  {dur * 1e-9 / len(runs):9.3f} ms a step" if runs else ""
            print(f"  {scope:18s} {dur * 1e-12:9.4f} s in {n:6d} ops{per_step}")
    if want_stats:
        print("  metadata stats of the ops:", dict(seen_stats))
        print("  example:", example)
    return [(a + d, b) for (a, d), (b, _) in zip(runs, runs[1:]) if b > a + d]


def _host_report(plane, rx, gaps, per) -> None:
    """Prints the totals of the plane's events ``rx`` matches and, given
    gaps, how much of each lies inside them."""
    totals = defaultdict(lambda: [0, 0, "", 0])
    for name, line, start, dur, _ in _events(plane):
        if rx.search(name):
            t = totals[name]
            t[0] += 1
            t[1] += dur
            t[2] = line
            t[3] += sum(max(0, min(start + dur, g1) - max(start, g0)) for g0, g1 in gaps)
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    counters = defaultdict(lambda: defaultdict(int))  # a span's integer keys
    for line in plane.lines:
        for e in line.events:
            name = plane.event_metadata[e.metadata_id].name
            if rx.search(name):
                for st in e.stats:
                    if st.WhichOneof("value") in ("int64_value", "uint64_value"):
                        counters[name][stat_names.get(st.metadata_id, "?")] += (
                            st.int64_value or st.uint64_value)
    for name, (n, dur, line, in_gaps) in sorted(totals.items(), key=lambda kv: -kv[1][1]):
        gap_part = f"  in the gaps {in_gaps * 1e-9 / len(gaps):7.3f} ms a gap" if gaps else ""
        print(f"  host {name:26s} x{n:<6d} {dur * 1e-12:9.4f} s  mean {dur * 1e-9 / n:8.3f} ms"
              f"{gap_part}  (line {line!r})")
        if counters[name]:
            print("       summed keys: " + ", ".join(
                f"{k}={v}" for k, v in sorted(counters[name].items())))
    if gaps and totals:
        print(f"  {len(gaps)} gaps between executions of {per.pattern}, "
              f"mean {sum(b - a for a, b in gaps) * 1e-9 / len(gaps):.3f} ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture")
    ap.add_argument("--per", default="", help="regex on module names: report per execution")
    ap.add_argument("--host", default="", help="regex on host event names to total")
    ap.add_argument("--stats", action="store_true")
    args = ap.parse_args()
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    path = args.capture
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not files:
            sys.exit(f"no *.xplane.pb under {path}")
        path = files[-1]
    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    print(path)
    per = re.compile(args.per) if args.per else None
    device = next((p for p in sorted(space.planes, key=lambda p: p.name)
                   if re.match(r"^/device:TPU:\d+$", p.name)), None)
    gaps = _device_report(device, per, args.stats) if device is not None else []
    if device is None:
        print("no /device:TPU plane")
    if args.host:
        for plane in space.planes:
            if plane.name.startswith("/host:"):
                _host_report(plane, re.compile(args.host), gaps, per)
    return 0


if __name__ == "__main__":
    sys.exit(main())
