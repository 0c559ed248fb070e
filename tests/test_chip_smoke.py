"""chip_smoke.py's phases, driven at toy sizes on the CPU.

The smoke itself runs at published widths on a TPU and nowhere else; what
can be checked here is that every phase function starts its children, reads
what they report, and applies its checks — including the train -> checkpoint
-> ``cli.serve`` -> HTTP chain and the four-device data-parallel run against
the one-device run, on virtual CPU devices. The kernel phase's five L=512
steps are full-width by construction and run on the chip only.
"""

import importlib.util
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
# 1 layer, hidden 48 (12 heads x 4), vocab 128: the smallest geometry both
# presets' models accept.
TINY = ("--bert-layers", "1", "--bert-hidden", "48", "--bert-vocab", "128")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(autouse=True)
def children_on_the_cpu(monkeypatch):
    # conftest holds THIS process to the CPU through jax's config; the
    # children read the environment.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)


def test_parent_stays_off_jax():
    """Importing the script must not import jax or the package: a parent
    that has touched JAX holds the chip."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(REPO / 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "bad = [n for n in sys.modules if n == 'jax' or n.startswith(('jax.', 'distributed_tensorflow_tpu'))]\n"
        "assert not bad, bad\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_smoke_refuses_the_cpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "expected 1 x tpu" in proc.stderr
    # The first child's device report settles it: no full-width model is
    # compiled for the CPU first.
    assert time.monotonic() - t0 < 120


def test_tagged_json(smoke):
    line = '2026-01-01 00:00:00,000 root: runtime: {"platform": "cpu"}'
    assert smoke.tagged_json(line, "runtime") == {"platform": "cpu"}
    assert smoke.tagged_json('runtime: {"a": 1}', "runtime") == {"a": 1}
    assert smoke.tagged_json("single-process runtime (none)", "runtime") is None
    assert smoke.tagged_json("x: device_memory: [1]", "memory") is None


def test_train_phase_tiny(smoke, tmp_path):
    r = smoke.train_phase(
        "train", tmp_path, config="bert_base", steps=3, global_batch=8,
        platform="cpu", extra_args=TINY,
    )
    assert len(r["losses"]) == 3
    assert r["runtime"]["mesh"] == {"data": 1}
    assert r["batch_layout"]["input_ids"]["shape"] == [8, 128]
    assert (tmp_path / "train.log").read_text()


def test_train_phase_stops_a_child_on_the_wrong_device(smoke, tmp_path):
    with pytest.raises(smoke.SmokeFailure, match="expected 1 x tpu"):
        smoke.train_phase(
            "train", tmp_path, config="bert_base", steps=3, global_batch=8,
            platform="tpu", extra_args=TINY,
        )
    # Stopped at the device report: no step ran.
    assert " step 1: " not in (tmp_path / "train.log").read_text()


def test_kernel_phase_tiny(smoke, tmp_path):
    r = smoke.kernel_phase(
        tmp_path, shape=(2, 32, 2, 16), train_steps=0, platform="cpu"
    )
    # Interpreted here, and said so; on a TPU the phase demands compiled.
    assert r["parity"]["compiled"] is False
    assert set(r["parity"]["rel_err"]) == {"o", "dq", "dk", "dv"}
    assert max(r["parity"]["rel_err"].values()) <= smoke.KERNEL_TOL


def test_serve_phase_tiny(smoke, tmp_path):
    r = smoke.serve_phase(
        tmp_path / "out", tmp_path / "work", train_steps=3, global_batch=8,
        platform="cpu", model_args=TINY, buckets=(16, 32), max_batch=2,
        slots=4, max_new_tokens=8, prompt_len=8, burst=6, timeout=300,
    )
    assert r["platform"] == "cpu"
    assert len(r["train"]["losses"]) == 3
    assert r["grid_cells"] >= 3  # 2 tiers x 2 buckets of prefill + decode
    assert len(r["first_tokens"]) == 8
    assert not (tmp_path / "work" / "ckpt").exists()  # removed with the phase


def test_multichip_phase_on_four_virtual_devices(smoke, tmp_path):
    flag = "--xla_force_host_platform_device_count="
    r = smoke.multichip_phase(
        tmp_path, steps=4, global_batch=8, seed=3, n_devices=4,
        platform="cpu", env_all={"XLA_FLAGS": flag + "4"},
        env_one={"XLA_FLAGS": flag + "1"}, model_args=TINY, timeout=300,
    )
    assert r["wide"]["runtime"]["device_count"] == 4
    assert r["one"]["runtime"]["device_count"] == 1
    assert r["wide"]["batch_layout"]["input_ids"]["shard"] == [2, 128]
    assert r["max_rel_loss_diff"] <= smoke.MULTICHIP_LOSS_TOL


def test_multichip_phase_fails_when_one_device_is_not_one(smoke, tmp_path):
    """Confinement comes from the child's environment; where the runtime
    does not honour it, the phase says so at the first device report."""
    flag = "--xla_force_host_platform_device_count="
    with pytest.raises(smoke.SmokeFailure, match="expected 1 x cpu"):
        smoke.multichip_phase(
            tmp_path, steps=2, global_batch=8, seed=3, n_devices=4,
            platform="cpu", env_all={"XLA_FLAGS": flag + "4"},
            env_one={"XLA_FLAGS": flag + "2"}, model_args=TINY, timeout=300,
        )
