"""Process-level set-up: the compile cache, one process per chip, and a
runtime bootstrap that waits on no network (runtime.py, parallel/mesh.py),
plus the benchmark scripts' refusal to run anywhere but on a known TPU."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from distributed_tensorflow_tpu import runtime
from distributed_tensorflow_tpu.parallel import mesh as mesh_mod
from distributed_tensorflow_tpu.serve.router import Router

REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ compile cache


def test_compile_cache_is_left_alone_on_the_cpu():
    """conftest holds the suite to the CPU: the helper must set nothing, or
    tests that call cli.train.main() would write a cache into the checkout."""
    assert jax.config.jax_platforms == "cpu"
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    assert runtime.enable_compile_cache() is None
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before


def _cache_dir_in_child(env_overrides: dict) -> tuple[str, str, float]:
    """What a process not held to the CPU ends up with (no backend touch)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = str(REPO)
    env.update(env_overrides)
    code = (
        "import jax\n"
        "from distributed_tensorflow_tpu.runtime import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, check=True, timeout=120,
        capture_output=True, text=True,
    ).stdout.split()
    return out[0], out[1], float(out[2])


def test_compile_cache_defaults_to_one_fixed_directory_in_the_checkout():
    returned, configured, min_secs = _cache_dir_in_child({})
    assert returned == configured == str(REPO / ".jax_cache")
    assert min_secs == 0.0


def test_compile_cache_follows_the_environment_and_sets_no_other(tmp_path):
    returned, configured, min_secs = _cache_dir_in_child(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    )
    assert returned == configured == str(tmp_path)
    assert min_secs == 0.0


# ------------------------------------------------------ one process per chip


@pytest.mark.parametrize(
    "chips, platforms, n, refused",
    [
        (1, "", 2, True),       # the one-chip machine, two replicas
        (1, "tpu", 2, True),
        (1, "cpu", 2, False),   # replicas held to the CPU share nothing
        (4, "", 4, False),
        (4, "", 5, True),
        (0, "", 8, False),      # no TPU on this host: nothing to guard
        (1, "", 1, False),
    ],
)
def test_require_chip_per_process(monkeypatch, chips, platforms, n, refused):
    monkeypatch.setattr(runtime, "tpu_chips_on_host", lambda: chips)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if refused:
        with pytest.raises(RuntimeError, match=f"has {chips} TPU chip"):
            runtime.require_chip_per_process(n, "a test")
    else:
        runtime.require_chip_per_process(n, "a test")


def test_tpu_chips_on_host_reads_sysfs_without_a_backend():
    assert runtime.tpu_chips_on_host() == 0  # the sandbox has no accelerator


def test_router_refuses_more_owned_replicas_than_chips(monkeypatch):
    monkeypatch.setattr(
        "distributed_tensorflow_tpu.runtime.tpu_chips_on_host", lambda: 1
    )
    monkeypatch.setenv("JAX_PLATFORMS", "")
    cmd = [sys.executable, "-c", "raise SystemExit('must not be started')"]
    router = Router([
        ("r0", "http://127.0.0.1:1", cmd), ("r1", "http://127.0.0.1:2", cmd),
    ])
    with pytest.raises(RuntimeError, match="owns 2 replica processes"):
        router.start()
    assert all(r.proc is None for r in router.replicas)


# --------------------------------------------------------- runtime bootstrap


@pytest.fixture()
def fresh_runtime(monkeypatch):
    calls = []
    monkeypatch.setattr(mesh_mod, "_runtime_initialized", False)
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.append(kw)
    )
    for var in ("COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
                "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID",
                "SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
        monkeypatch.delenv(var, raising=False)
    return calls


def test_single_process_start_calls_nothing_that_waits(fresh_runtime, monkeypatch):
    # A single-host TPU VM's one-entry host list is not a cluster.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    mesh_mod.initialize_runtime()
    assert fresh_runtime == []


def test_detected_cluster_initializes_and_lets_it_raise(fresh_runtime, monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-a,host-b")
    mesh_mod.initialize_runtime()
    assert fresh_runtime == [
        {"coordinator_address": None, "num_processes": None,
         "process_id": None}
    ]

    def boom(**kw):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(mesh_mod, "_runtime_initialized", False)
    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        mesh_mod.initialize_runtime()


def test_explicit_flags_initialize(fresh_runtime):
    mesh_mod.initialize_runtime("10.0.0.1:8476", 2, 1)
    assert fresh_runtime == [
        {"coordinator_address": "10.0.0.1:8476", "num_processes": 2,
         "process_id": 1}
    ]


# ------------------------------------------------------- benchmark scripts


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_bench_needs_a_tpu(bench):
    with pytest.raises(SystemExit, match="TPU only"):
        bench.require_tpu()


def test_bench_peak_table_has_no_default(bench):
    assert bench.chip_peak_flops(
        SimpleNamespace(device_kind="TPU v5 lite")
    ) == 197e12
    with pytest.raises(SystemExit, match="no peak FLOP/s known"):
        bench.chip_peak_flops(SimpleNamespace(device_kind="TPU v9 mega"))


@pytest.mark.parametrize("script", ["bench.py", "scripts/bench_bert.py"])
def test_bench_scripts_exit_nonzero_without_a_tpu(script):
    proc = subprocess.run(
        [sys.executable, str(REPO / script)], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "TPU only" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line under a device metric
