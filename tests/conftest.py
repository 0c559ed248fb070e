"""Test harness: 8 simulated devices on CPU, no TPU required.

This is the rebuild's answer to the reference's "launch real ps/worker
processes on localhost ports" testing idiom (SURVEY.md §4): JAX simulates an
8-device mesh in-process (``jax_num_cpu_devices``), so every
collective/sharding test runs in CI on CPU.

Must run before the first backend touch of the test session, hence conftest.
"""

import jax

# Every test runs on the CPU with eight virtual devices, whatever the
# environment says: set through the config, before the first backend touch.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def data_mesh():
    """8-way pure data-parallel mesh."""
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh

    return build_mesh({"data": -1})


@pytest.fixture()
def data_seq_mesh():
    """2-way DP x 4-way sequence-parallel mesh."""
    from distributed_tensorflow_tpu.parallel.mesh import build_mesh

    return build_mesh({"data": 2, "seq": 4})
