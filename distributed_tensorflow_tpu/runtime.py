"""Process-level set-up shared by every entry point: where compiled
programs are kept, and how many accelerator processes a host can carry.

Only :func:`describe_devices` touches the XLA backend: the compile cache is
set up before a process's first ``jax.devices()``, and
:func:`require_chip_per_process` runs in parents that must stay off the
device altogether (jax is imported only by the functions that configure or
query it).
"""

from __future__ import annotations

import glob
import os
from pathlib import Path

#: Compiled programs of a checkout live here unless the environment names a
#: directory. The path is part of every cache key, so it never moves.
REPO_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Keep this process's compiled programs for the next process.

    BERT-base's train step and every full-width cell of the serving grid
    take from seconds to minutes to compile; without a persistent cache each
    process pays that from cold. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    jax reads it itself and no directory is set here; otherwise the cache is
    :data:`REPO_CACHE_DIR`. The minimum compile time for an entry drops to 0
    so that the grid's small cells (insert / export / import) are kept too.

    A process held to the CPU (``JAX_PLATFORMS=cpu``, or the tests'
    ``jax_platforms`` config) is left alone: its toy-sized compiles are not
    worth a cache, and the test suite must not write one into the checkout.

    Returns the directory in use, or ``None`` when nothing was enabled.
    Call before the first compilation.
    """
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def describe_devices() -> dict:
    """The devices this process runs on, as jax reports them. Initializes
    the backend, so only a process that is meant to hold the device asks."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "process_count": jax.process_count(),
    }


# PCI identity of a TPU chip: Google's vendor id and the device ids of the
# generations this JAX supports (the table jax._src.hardware_utils keeps).
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"}
)


def tpu_chips_on_host() -> int:
    """TPU chips attached to this host, read from sysfs — no backend, no
    libtpu, so a parent that must leave the chip to its children can ask."""
    chips = 0
    for vendor_path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            if Path(vendor_path).read_text().strip() != _GOOGLE_PCI_VENDOR:
                continue
            device = Path(vendor_path).with_name("device").read_text().strip()
        except OSError:
            continue
        chips += device in _TPU_PCI_DEVICES
    return chips


def require_chip_per_process(n_processes: int, what: str) -> None:
    """Refuse to start ``n_processes`` JAX processes on a TPU host that has
    fewer chips than that.

    A chip belongs to one process at a time: the second process fails or
    hangs at device init, and a supervisor then burns its restart budget
    against a cause it cannot see. Processes held to the CPU
    (``JAX_PLATFORMS`` without ``tpu``) share nothing and pass. Giving each
    process its own chip when the host has enough is an open item
    (ROADMAP.md, Reach 6).
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    chips = tpu_chips_on_host()
    if 0 < chips < n_processes:
        raise RuntimeError(
            f"{what} needs {n_processes} processes on the device, and this "
            f"host has {chips} TPU chip(s): a chip belongs to one process at "
            "a time, so the others would fail or hang at device init. Run "
            "them on the CPU (JAX_PLATFORMS=cpu) or on a host with a chip "
            "for each."
        )
