#!/usr/bin/env python3
"""Prove that the system starts on the chip: trainer, flash kernel, decode server.

    python chip_smoke.py               # one TPU chip: train, kernel, serve
    python chip_smoke.py --multichip   # four chips: data-parallel vs one chip

This process never imports jax (nor the package, which does): a parent that
has touched JAX holds the chip and its children cannot get it. Every phase
runs in child processes, strictly one after another; each child's output is
copied to a log under ``chiprun_out/chip_smoke/`` and to this process's
stderr. The run fails — nonzero exit, no ``"ok": true`` — as soon as a child
fails or a check does not hold. There is no CPU mode: ``main`` fixes the
published widths and demands a TPU, and the first child's device report
settles it within seconds of its start-up.

The phases are functions that take their sizes as arguments, so that
tests/test_chip_smoke.py can drive them at toy sizes on the CPU.

The last line of standard output is the one the driver reads::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Earlier lines are one JSON object per phase: seconds, compile seconds,
steps/s, tokens/s, HBM in use. They are smoke output, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
# Bulky and short-lived (the lm_base checkpoint is 1.3 GB): kept apart from
# OUT, which the chip tool copies back, and removed when the run ends.
WORK = ROOT / ".chip_smoke_work"

# The r5 production geometry of the flash kernel: BERT-base heads at L=512,
# the batch that scripts/bench_bert.py benches.
KERNEL_SHAPE = (24, 512, 12, 64)
# max|flash - dense| / max|dense| for the output and each gradient, both
# sides in bf16 with f32 accumulation: four bf16 ulps (2^-8 each) at the
# tensor's scale. The two paths round P, dS and the outputs to bf16 at
# different points, so they agree to a few ulps and no closer.
KERNEL_TOL = 2.0**-6
# Per step, |x(4 chips) - x(1 chip)| / x(1 chip) for the loss and for the
# gradient norm. The two runs are the same program up to (a) the order of
# bf16 roundings and f32 sums, which moves with the per-device batch, and
# (b) the data-parallel step averaging per-shard token-weighted means where
# one chip takes one mean over the whole batch (rows are 64..128 tokens
# long, so the shards' weights differ by a few percent). A rehearsal on four
# virtual CPU devices (2 layers, hidden 96, same batch and rate) differed by
# 4e-5 in the loss and 4.5e-3 in the gradient norm. The norm is what a
# missing or mis-scaled reduction moves: a quarter of the batch has about
# twice the gradient noise.
MULTICHIP_LOSS_TOL = 2e-3
MULTICHIP_GRAD_NORM_TOL = 5e-2
# The preset's schedule warms up over 1000 steps, so eight steps at it move
# no parameter and would compare forward passes only. A constant 3e-4 makes
# every step depend on the reduced gradient of the one before.
MULTICHIP_LR_ARGS = ("--lr", "3e-4", "--lr-schedule", "constant")


class SmokeFailure(Exception):
    """A child failed or a check did not hold."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ children


def child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT), env.get("PYTHONPATH", "")) if p
    )
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def run_child(cmd, log_path: Path, *, env=None, on_line=None,
              timeout: float = 1100.0) -> float:
    """Run one child to its end: copy its output to ``log_path`` and to
    stderr, hand each line to ``on_line`` (which may raise SmokeFailure to
    stop the child at once), require exit code 0. Returns wall seconds."""
    log_path.parent.mkdir(parents=True, exist_ok=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, cwd=ROOT, env=child_env(env), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, errors="replace", bufsize=1,
    )
    watchdog = threading.Timer(timeout, proc.kill)
    watchdog.start()
    try:
        with log_path.open("w") as log:
            for line in proc.stdout:
                log.write(line)
                sys.stderr.write(line)
                if on_line is not None:
                    on_line(line.rstrip("\n"))
        rc = proc.wait()
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    seconds = time.monotonic() - t0
    check(rc == 0, f"{cmd[1:4]} exited {rc} after {seconds:.0f}s "
                   f"(log: {log_path})")
    return seconds


def tagged_json(line: str, tag: str):
    """The JSON value a child logged after ``<tag>: ``, else None."""
    marker = f"{tag}: "
    at = line.find(marker)
    if at < 0 or (at > 0 and line[at - 1] != " "):
        return None
    rest = line[at + len(marker):]
    return json.loads(rest) if rest.startswith(("{", "[")) else None


def check_runtime(runtime: dict, platform: str, count: int) -> None:
    check(
        runtime["platform"] == platform and runtime["device_count"] == count,
        f"child runs on {runtime['device_count']} x {runtime['platform']} "
        f"({runtime['device_kind']}), expected {count} x {platform}",
    )


# --------------------------------------------------------------------- train


def train_phase(label: str, out: Path, *, config: str, steps: int,
                global_batch: int, platform: str, devices: int = 1,
                extra_args=(), env=None, timeout: float = 1100.0) -> dict:
    """``cli.train --config <config>`` for ``steps`` steps on synthetic data.

    Checks: the child reports ``devices`` x ``platform`` (settled at its
    first log lines — a wrong device stops the child there), exits 0, and
    leaves ``steps`` finite losses in its metrics JSONL."""
    jsonl = out / f"{label}.jsonl"
    jsonl.unlink(missing_ok=True)
    cmd = [
        sys.executable, "-m", "distributed_tensorflow_tpu.cli.train",
        "--config", config, "--steps", str(steps),
        "--global-batch", str(global_batch), "--log-every", "1",
        "--metrics-jsonl", str(jsonl), *extra_args,
    ]
    seen: dict = {}
    t0 = time.monotonic()

    def on_line(line: str) -> None:
        for tag in ("runtime", "batch_layout", "device_memory"):
            if tag not in seen and (obj := tagged_json(line, tag)) is not None:
                seen[tag] = obj
                if tag == "runtime":
                    check_runtime(obj, platform, devices)
        if "first_step_seconds" not in seen and " step 1: " in line:
            seen["first_step_seconds"] = time.monotonic() - t0

    seconds = run_child(cmd, out / f"{label}.log", env=env, on_line=on_line,
                        timeout=timeout)
    for tag in ("runtime", "batch_layout", "device_memory"):
        check(tag in seen, f"{label}: the child logged no '{tag}:' line")
    records = [json.loads(l) for l in jsonl.read_text().splitlines()]
    losses = [r["loss"] for r in records if "loss" in r]
    check(
        len(losses) == steps and all(math.isfinite(x) for x in losses),
        f"{label}: expected {steps} finite losses, got {losses}",
    )
    return {
        "seconds": round(seconds, 1),
        "first_step_seconds": round(seen.get("first_step_seconds", 0.0), 1),
        "steps_per_sec": records[-1].get("steps_per_sec"),
        "global_batch": global_batch,
        "losses": losses,
        "grad_norms": [r["grad_norm"] for r in records if "grad_norm" in r],
        "runtime": seen["runtime"],
        "batch_layout": seen["batch_layout"],
        "device_memory": seen["device_memory"],
    }


# -------------------------------------------------------------------- kernel


def kernel_phase(out: Path, *, shape, train_steps: int, platform: str,
                 timeout: float = 1100.0) -> dict:
    """The Pallas flash-attention kernel against ``dense_attention`` at
    ``shape`` = (B, L, H, D) bf16 with a padding mask — output and all three
    gradients within KERNEL_TOL — then ``train_steps`` steps of the BERT-base
    L=512 b=24 step that scripts/bench_bert.py benches. On a TPU the lowered
    programs must contain the Mosaic custom call: a kernel that ran
    interpreted fails the phase."""
    spec = {"shape": list(shape), "train_steps": train_steps, "seed": 0}
    seen: dict = {}

    # The child only reports; every check is made here, as its lines come
    # in, so a failed one stops it before the next stage.
    def on_line(line: str) -> None:
        if (obj := tagged_json(line, "runtime")) is not None:
            check_runtime(obj, platform, 1)
            seen["runtime"] = obj
        elif (obj := tagged_json(line, "kernel_parity")) is not None:
            check(obj["compiled"] == (platform == "tpu"),
                  f"kernel: compiled={obj['compiled']} on platform {platform}")
            for name, err in obj["rel_err"].items():
                check(err <= KERNEL_TOL,
                      f"kernel: {name} differs from dense attention by "
                      f"{err:.3g} of its scale (tolerance {KERNEL_TOL:.3g})")
            seen["parity"] = {**obj, "tol": KERNEL_TOL}
        elif (obj := tagged_json(line, "kernel_train")) is not None:
            check(obj["custom_calls"] > 0,
                  "kernel: the L=512 train step holds no Mosaic custom call")
            check(len(obj["losses"]) == train_steps
                  and all(math.isfinite(x) for x in obj["losses"]),
                  f"kernel: expected {train_steps} finite losses, got "
                  f"{obj['losses']}")
            seen["train"] = obj

    seconds = run_child(
        [sys.executable, str(Path(__file__).resolve()), "--kernel-child",
         json.dumps(spec)],
        out / "kernel.log", on_line=on_line, timeout=timeout,
    )
    for key in ("runtime", "parity", *(("train",) if train_steps else ())):
        check(key in seen, f"kernel: the child reported no {key} result")
    return {"seconds": round(seconds, 1), **seen}


def _kernel_child(spec: dict) -> int:
    """Body of the kernel phase's child process (holds the device). It
    measures and reports; kernel_phase judges."""
    from distributed_tensorflow_tpu.runtime import (
        describe_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.ops import flash_attention
    from distributed_tensorflow_tpu.parallel.ring_attention import (
        dense_attention,
    )

    def emit(tag: str, obj) -> None:
        print(f"{tag}: {json.dumps(obj)}", flush=True)

    emit("runtime", describe_devices())

    b, l, h, d = spec["shape"]
    kq, kk, kv, kw = jax.random.split(jax.random.key(spec["seed"]), 4)
    q, k, v, w = (
        jax.random.normal(key, (b, l, h, d), jnp.bfloat16)
        for key in (kq, kk, kv, kw)
    )
    # Padding mask: row i keeps its first l - i*(l/2)/b keys (row 0 is full,
    # the last row about half), as a batch of unequal sentences would.
    lengths = l - (np.arange(b) * (l // 2)) // b
    mask = jnp.asarray(np.arange(l)[None, :] < lengths[:, None])

    # Everything large is an argument: a closed-over array would be baked
    # into the program as a constant.
    def fwd_and_grads(attention):
        def loss(q, k, v, w, mask):
            o = attention(q, k, v, mask)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

        def run(q, k, v, w, mask):
            grads = jax.grad(loss, (0, 1, 2))(q, k, v, w, mask)
            return attention(q, k, v, mask), *grads

        return jax.jit(run)

    lowered = fwd_and_grads(flash_attention).lower(q, k, v, w, mask)
    custom_calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.monotonic()
    flash_exe = lowered.compile()
    compile_s = time.monotonic() - t0
    got = jax.block_until_ready(flash_exe(q, k, v, w, mask))
    want = jax.block_until_ready(
        fwd_and_grads(dense_attention)(q, k, v, w, mask)
    )
    rel_err = {}
    for name, a, ref in zip(("o", "dq", "dk", "dv"), got, want):
        a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
        rel_err[name] = float(np.abs(a - ref).max() / np.abs(ref).max())
    emit("kernel_parity", {
        "shape": spec["shape"], "dtype": "bfloat16",
        "compiled": custom_calls > 0, "custom_calls": custom_calls,
        "compile_seconds": round(compile_s, 1), "rel_err": rel_err,
    })

    if spec["train_steps"]:
        sys.path.insert(0, str(ROOT / "scripts"))
        import bench_bert

        step, state, batch, rng, _ = bench_bert.build_step(
            512, 24, attn_impl="auto"
        )
        lowered = step.lower(state, batch, rng)
        step_calls = lowered.as_text().count("tpu_custom_call")
        t0 = time.monotonic()
        exe = lowered.compile()
        compile_s = time.monotonic() - t0
        losses, step_s = [], []
        for _ in range(spec["train_steps"]):
            t0 = time.monotonic()
            state, metrics = exe(state, batch, rng)
            losses.append(float(metrics["loss"]))
            step_s.append(time.monotonic() - t0)
        stats = jax.devices()[0].memory_stats() or {}
        emit("kernel_train", {
            "L": 512, "per_chip_batch": 24, "custom_calls": step_calls,
            "compile_seconds": round(compile_s, 1), "losses": losses,
            "step_seconds": [round(s, 4) for s in step_s],
            "tokens_per_sec": round(24 * 512 / min(step_s)),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return 0


# --------------------------------------------------------------------- serve


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(method: str, url: str, body=None, timeout: float = 120.0):
    """(status, parsed body) — HTTP error statuses are returned, not raised."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read() or b"null")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def wait_ready(base: str, proc: subprocess.Popen, timeout: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        check(proc.poll() is None,
              f"serve: the server exited {proc.returncode} before it was ready")
        try:
            status, body = http_json("GET", base + "/healthz", timeout=5)
            if status == 200 and body.get("status") == "ready":
                return time.monotonic() - t0
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(1.0)
    raise SmokeFailure(f"serve: not ready after {timeout:.0f}s")


def serve_phase(out: Path, work: Path, *, train_steps: int, global_batch: int,
                platform: str, model_args=(), buckets=(64, 128),
                max_batch: int = 4, slots: int = 8, max_new_tokens: int = 32,
                prompt_len: int = 24, burst: int = 6,
                timeout: float = 1100.0) -> dict:
    """Train ``lm_base`` for a few steps into a checkpoint, serve that
    checkpoint with ``cli.serve``, and walk the HTTP surface: a greedy
    generate, the same prompt again (identical tokens), ``burst`` concurrent
    generates (all 200), /statusz platform, /memz device rows, /compilez
    fully warm, /drainz. The server is then interrupted by PID, waited for,
    and must exit 0."""
    ckpt = work / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    trained = train_phase(
        "serve_train", out, config="lm_base", steps=train_steps,
        global_batch=global_batch, platform=platform,
        extra_args=(*model_args, "--ckpt-dir", str(ckpt)), timeout=timeout,
    )

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [
        sys.executable, "-m", "distributed_tensorflow_tpu.cli.serve",
        "--config", "lm_base", "--ckpt-dir", str(ckpt), "--port", str(port),
        "--buckets", *map(str, buckets), "--max-batch", str(max_batch),
        "--slots", str(slots), "--max-new-tokens", str(max_new_tokens),
        *model_args,
    ]
    t0 = time.monotonic()
    log = (out / "serve.log").open("w")
    proc = subprocess.Popen(cmd, cwd=ROOT, env=child_env(), stdout=log,
                            stderr=subprocess.STDOUT)
    try:
        ready_s = wait_ready(base, proc, timeout)

        status, statusz = http_json("GET", base + "/statusz")
        check(status == 200 and statusz["mesh"]["platform"] == platform,
              f"serve: /statusz reports {statusz.get('mesh')}, expected "
              f"platform {platform}")

        # Token ids above the specials, below every vocab the phase runs at.
        prompt = [5 + (7 * i) % 100 for i in range(prompt_len)]
        payload = {"input_ids": prompt, "max_new_tokens": max_new_tokens}
        t_gen = time.monotonic()
        status, first = http_json("POST", base + "/v1/generate", payload)
        gen_s = time.monotonic() - t_gen
        check(status == 200 and len(first["tokens"]) == max_new_tokens,
              f"serve: generate answered {status}: {first}")
        status, again = http_json("POST", base + "/v1/generate", payload)
        check(status == 200 and again["tokens"] == first["tokens"],
              f"serve: the same greedy prompt gave {again.get('tokens')} "
              f"after {first['tokens']}")

        answers: list = [None] * burst

        def one(i: int) -> None:
            body = {"input_ids": [5 + (11 * i + j) % 100
                                  for j in range(prompt_len)],
                    "max_new_tokens": max_new_tokens}
            answers[i] = http_json("POST", base + "/v1/generate", body)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(burst)]
        t_burst = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        burst_s = time.monotonic() - t_burst
        check(all(a is not None and a[0] == 200
                  and len(a[1]["tokens"]) == max_new_tokens for a in answers),
              f"serve: burst answers {[a and a[0] for a in answers]}")

        status, memz = http_json("GET", base + "/memz")
        check(status == 200 and memz["devices"], f"serve: /memz {status}")
        row = memz["devices"][0]
        if platform == "tpu":
            check(row["reported"] and row["bytes_in_use"] > 0,
                  f"serve: /memz device row {row}: the TPU reports memory")
        status, compilez = http_json("GET", base + "/compilez")
        check(status == 200 and compilez["warm_fraction"] == 1.0,
              f"serve: /compilez warm_fraction "
              f"{compilez.get('warm_fraction')}")
        status, _ = http_json("POST", base + "/drainz", {})
        check(status == 200, f"serve: /drainz answered {status}")
        status, health = http_json("GET", base + "/healthz")
        check(status == 503 and health.get("status") == "draining",
              f"serve: /healthz after drain: {status} {health}")
    finally:
        # By PID, with the signal cli.serve turns into an orderly shutdown
        # (batcher joined, device released); nothing else starts before the
        # server is gone.
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        log.close()
        sys.stderr.write((out / "serve.log").read_text(errors="replace")[-6000:])
        shutil.rmtree(ckpt, ignore_errors=True)
    check(proc.returncode == 0,
          f"serve: the server exited {proc.returncode} on SIGINT")
    return {
        "seconds": round(trained["seconds"] + time.monotonic() - t0, 1),
        "train": {k: trained[k] for k in
                  ("seconds", "first_step_seconds", "steps_per_sec",
                   "global_batch", "losses")},
        "ready_seconds": round(ready_s, 1),
        "grid_cells": compilez["cells_total"],
        "compile_seconds": round(compilez["compile_seconds_total"], 1),
        "generate_tokens_per_sec": round(max_new_tokens / gen_s, 1),
        "burst_tokens_per_sec": round(burst * max_new_tokens / burst_s, 1),
        "first_tokens": first["tokens"][:8],
        "memz_device": row,
        "platform": statusz["mesh"]["platform"],
    }


# ----------------------------------------------------------------- multichip


def multichip_phase(out: Path, *, steps: int, global_batch: int, seed: int,
                    n_devices: int, platform: str, env_all: dict,
                    env_one: dict, model_args=(),
                    timeout: float = 1100.0) -> dict:
    """``cli.train --config lm_base`` twice — once confined to one device
    from outside the program (``env_one``), once on all ``n_devices``
    (``env_all``) — same global batch, seed and learning rate.

    Checks: the wide child reports ``n_devices`` devices and a
    ``data=n_devices`` mesh, every device holds memory (where the backend
    reports it) and a 1/n_devices shard of each batch leaf, and the two
    runs' per-step losses and gradient norms agree within
    MULTICHIP_LOSS_TOL / MULTICHIP_GRAD_NORM_TOL."""
    common = dict(
        config="lm_base", steps=steps, global_batch=global_batch,
        platform=platform, timeout=timeout,
        extra_args=(*model_args, *MULTICHIP_LR_ARGS, "--seed", str(seed)),
    )
    one = train_phase("multichip_1", out, devices=1, env=env_one, **common)
    wide = train_phase(f"multichip_{n_devices}", out, devices=n_devices,
                       env=env_all, **common)

    check(wide["runtime"]["mesh"] == {"data": n_devices},
          f"multichip: mesh {wide['runtime']['mesh']}, expected "
          f"data={n_devices}")
    for name, leaf in wide["batch_layout"].items():
        check(
            leaf["devices"] == n_devices
            and leaf["shard"][0] * n_devices == leaf["shape"][0]
            and leaf["shape"][0] == global_batch,
            f"multichip: batch leaf {name} is laid out as {leaf}",
        )
    rows = wide["device_memory"]
    check(len(rows) == n_devices,
          f"multichip: {len(rows)} device memory rows")
    if platform == "tpu":
        check(all(r["reported"] and r["bytes_in_use"] > 0 for r in rows),
              f"multichip: not every chip holds memory: {rows}")
    worst = {}
    for key, tol in (("losses", MULTICHIP_LOSS_TOL),
                     ("grad_norms", MULTICHIP_GRAD_NORM_TOL)):
        worst[key] = max(abs(a - b) / abs(b)
                         for a, b in zip(wide[key], one[key]))
        check(worst[key] <= tol,
              f"multichip: per-step {key} differ by up to {worst[key]:.3g} "
              f"(tolerance {tol}): {wide[key]} on {n_devices} devices, "
              f"{one[key]} on one")
    return {
        "one": one, "wide": wide,
        "max_rel_loss_diff": worst["losses"],
        "max_rel_grad_norm_diff": worst["grad_norms"],
        "tol": {"loss": MULTICHIP_LOSS_TOL,
                "grad_norm": MULTICHIP_GRAD_NORM_TOL},
    }


# ---------------------------------------------------------------------- main


def device_fields(runtime: dict) -> dict:
    return {"platform": runtime["platform"], "kind": runtime["device_kind"],
            "count": runtime["device_count"]}


def run_default() -> dict:
    """One chip, three phases, published widths."""
    train = train_phase(
        "train", OUT, config="bert_base", steps=8, global_batch=64,
        platform="tpu",
    )
    report("train", config="bert_base (12 layers, hidden 768, L=128, bf16, "
           "AdamW + clip 1.0)", **{k: train[k] for k in (
               "seconds", "first_step_seconds", "steps_per_sec",
               "global_batch", "losses", "device_memory")})
    kernel = kernel_phase(OUT, shape=KERNEL_SHAPE, train_steps=5,
                          platform="tpu")
    report("kernel", **{k: kernel[k] for k in ("seconds", "parity", "train")})
    serve = serve_phase(OUT, WORK, train_steps=4, global_batch=64,
                        platform="tpu")
    report("serve", config="lm_base (12 layers, hidden 768, L=128, bf16)",
           **serve)
    device = device_fields(train["runtime"])
    check(device_fields(kernel["runtime"]) == device,
          f"children disagree on the device: {train['runtime']} vs "
          f"{kernel['runtime']}")
    return device


def run_multichip() -> dict:
    """Four chips: data-parallel lm_base against the same run on one chip."""
    result = multichip_phase(
        OUT, steps=8, global_batch=64, seed=0, n_devices=4, platform="tpu",
        env_all={},
        # One chip of the host, set from outside the program.
        env_one={"TPU_VISIBLE_CHIPS": "0",
                 "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                 "TPU_PROCESS_BOUNDS": "1,1,1"},
    )
    for key in ("one", "wide"):
        report(f"multichip_{key}", **{k: result[key][k] for k in (
            "seconds", "first_step_seconds", "steps_per_sec", "global_batch",
            "losses", "grad_norms", "runtime", "batch_layout",
            "device_memory")})
    report("multichip", max_rel_loss_diff=result["max_rel_loss_diff"],
           max_rel_grad_norm_diff=result["max_rel_grad_norm_diff"],
           tol=result["tol"])
    return device_fields(result["wide"]["runtime"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: the data-parallel trainer against "
                        "the same run on one chip, and nothing else")
    parser.add_argument("--kernel-child", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.kernel_child:
        return _kernel_child(json.loads(args.kernel_child))
    t0 = time.monotonic()
    try:
        device = run_multichip() if args.multichip else run_default()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    report("total", seconds=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
