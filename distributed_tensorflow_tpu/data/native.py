"""ctypes bindings for the native (C++) input pipeline.

The reference rides tf.data's C++ threadpool for its input pipelines
(SURVEY.md §2); this is the rebuild's own native layer: a pthread worker
pool in ``native/data_pipeline.cpp`` that samples a per-epoch permutation
(without replacement, via an O(1) Feistel index permutation), augments
(pad-crop / flip / per-image standardization for CIFAR; random-resized-crop
+ per-channel normalization for ImageNet), and stages batches in a bounded
ring — deterministic by construction (per-ticket RNG, in-order staging),
unlike the reference's racy async readers.

``NativePipeline`` builds the shared library on first use (g++ is in the
image) and again whenever ``native/data_pipeline.cpp`` or its Makefile
differ from what the library on disk was built from; if the toolchain is
unavailable the caller falls back to the numpy path (``native_available()``
gates it, and cli/train.py logs which pipeline a run used).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libdata_pipeline.so"
# Digest of the sources the library on disk was built from. File times say
# nothing after a copy or a checkout, so staleness is decided by content.
_STAMP_PATH = _NATIVE_DIR / "libdata_pipeline.so.sha256"
# _load() is reached both from the main thread (native_available probes)
# and from prefetch feeder threads first touching a NativePipeline; the
# lock keeps the lazy check-then-build-then-publish atomic so two threads
# can never race concurrent `make -B` builds of the same .so.
_LOAD_LOCK = threading.Lock()
_lib = None
_build_failed = False


def _source_digest() -> str:
    h = hashlib.sha256()
    for name in ("data_pipeline.cpp", "Makefile"):
        h.update((_NATIVE_DIR / name).read_bytes())
    return h.hexdigest()


def _build(digest: str) -> None:
    """Compile next to the target and rename into place: another process
    (an xdist worker, a second trainer) may be loading the library now."""
    tmp = _LIB_PATH.with_name(f".{_LIB_PATH.name}.{os.getpid()}")
    try:
        subprocess.run(
            ["make", "-C", str(_NATIVE_DIR), "-B", f"OUT={tmp.name}"],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, _LIB_PATH)
    finally:
        tmp.unlink(missing_ok=True)
    stamp_tmp = _STAMP_PATH.with_name(f".{_STAMP_PATH.name}.{os.getpid()}")
    stamp_tmp.write_text(digest + "\n")
    os.replace(stamp_tmp, _STAMP_PATH)


def _load() -> ctypes.CDLL | None:
    global _lib, _build_failed
    with _LOAD_LOCK:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        digest = _source_digest()
        built_from = (
            _STAMP_PATH.read_text().strip() if _STAMP_PATH.exists() else ""
        )
        if not _LIB_PATH.exists() or built_from != digest:
            try:
                _build(digest)
            except (subprocess.CalledProcessError, FileNotFoundError) as e:
                logger.warning(
                    "native pipeline build failed, using numpy path: %s\n%s",
                    e, getattr(e, "stderr", "") or "",
                )
                _build_failed = True
                return None
        return _bind(ctypes.CDLL(str(_LIB_PATH)))


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.dp_create.restype = ctypes.c_void_p
    lib.dp_create.argtypes = [
        ctypes.c_void_p,  # images
        ctypes.c_void_p,  # labels
        ctypes.c_int64,   # n
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # h, w, c
        ctypes.c_int, ctypes.c_int,  # out_h, out_w
        ctypes.c_int,     # batch
        ctypes.c_int, ctypes.c_int, ctypes.c_int,  # pad, flip, standardize
        ctypes.c_int, ctypes.c_float,  # rrc, rrc_min_area
        ctypes.c_int,     # src_u8
        ctypes.c_void_p, ctypes.c_void_p,  # mean, stddev
        ctypes.c_uint64,  # seed
        ctypes.c_uint64, ctypes.c_uint64,  # stream_offset, stream_stride
        ctypes.c_uint64,  # start_ticket
        ctypes.c_int, ctypes.c_int,  # n_threads, queue_cap
    ]
    lib.dp_next.restype = ctypes.c_int
    lib.dp_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.dp_destroy.argtypes = [ctypes.c_void_p]
    global _lib
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def resolve_input_dtype(name) -> np.dtype:
    """Normalize an input-batch dtype knob to a numpy dtype.

    ``bfloat16`` resolves through ``ml_dtypes`` (numpy has no native
    bf16); only float32 and bfloat16 are supported — images narrower
    than bf16 lose augmentation precision for no transfer win the
    roofline credits.
    """
    s = str(name).lower()
    if s in ("bfloat16", "bf16"):
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    if s in ("float32", "f32", "fp32"):
        return np.dtype(np.float32)
    raise ValueError(
        f"input dtype {name!r} not supported: pick float32 or bfloat16"
    )


class NativePipeline:
    """Threaded batch producer over an in-memory (or memory-mapped) dataset.

    Yields ``(images [B,out_H,out_W,C] f32, labels [B] i32)`` numpy batches
    with augmentation done by the C++ worker pool. Deterministic for a fixed
    ``seed`` independent of ``n_threads``. Sampling is per-epoch permutation
    without replacement; ``start_ticket`` resumes the stream at batch N
    (checkpoint-resume without replaying data).

    ``images`` may be float32 or uint8 (uint8 pixels are scaled by 1/255 —
    pass an np.memmap for datasets that don't fit RAM). When
    ``out_size != (H, W)`` or ``rrc=True``, images are (random-resized-)
    cropped and bilinearly resampled to ``out_size``.

    Multi-host: pass ``stream_offset = host_index * batch`` and
    ``stream_stride = num_hosts * batch`` with the SAME seed everywhere —
    all hosts then share each epoch's permutation and read disjoint slices
    (the explicit form of tf.data's ``shard(num_hosts, host_id)``).

    The C++ pool overlaps *augmentation* with Python; ``next()`` still
    copies the staged batch out and the caller still pays the
    host→device transfer. Wrapping the consuming stream in
    ``data.prefetch`` moves both off the step stream — the two queues
    compose (C++ ring feeds the Python feeder thread). ``close()`` (or
    exiting the ``with`` block) unblocks any thread waiting in ``next()``,
    which then raises instead of returning garbage.

    ``out_dtype="bfloat16"`` converts batches at the Python copy-out
    (the C++ ring itself stays float32 — augmentation arithmetic keeps
    full precision; only the staged result narrows). Halving the batch
    bytes halves the host→device transfer the roofline charges to input
    (docs/PERF.md r19) and matmul inputs arrive in the accelerator's
    native compute dtype.
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        batch: int,
        *,
        out_size: tuple[int, int] | None = None,
        pad: int = 0,
        flip: bool = False,
        standardize: bool = False,
        rrc: bool = False,
        rrc_min_area: float = 0.08,
        mean: np.ndarray | None = None,
        stddev: np.ndarray | None = None,
        seed: int = 0,
        stream_offset: int = 0,
        stream_stride: int = 0,
        start_ticket: int = 0,
        n_threads: int = 4,
        queue_cap: int = 8,
        out_dtype: str = "float32",
    ):
        self._out_dtype = resolve_input_dtype(out_dtype)
        lib = _load()
        if lib is None:
            raise RuntimeError("native pipeline library unavailable")
        # Own contiguous arrays: the C++ side keeps raw pointers to these.
        # uint8 sources stay uint8 (4x smaller; memmaps pass through without
        # materializing), anything else becomes float32.
        if images.dtype == np.uint8:
            self._images = images if images.flags["C_CONTIGUOUS"] else np.ascontiguousarray(images)
            src_u8 = 1
        else:
            self._images = np.ascontiguousarray(images, np.float32)
            src_u8 = 0
        self._labels = np.ascontiguousarray(labels, np.int32)
        n, h, w, c = self._images.shape
        oh, ow = out_size if out_size is not None else (h, w)
        self._shape = (batch, oh, ow, c)
        self._batch = batch
        self._lib = lib
        self._mean = (
            np.ascontiguousarray(mean, np.float32) if mean is not None else None
        )
        self._std = (
            np.ascontiguousarray(stddev, np.float32) if stddev is not None else None
        )
        if (self._mean is None) != (self._std is None):
            raise ValueError("mean and stddev must be given together")
        self._handle = lib.dp_create(
            self._images.ctypes.data_as(ctypes.c_void_p),
            self._labels.ctypes.data_as(ctypes.c_void_p),
            n, h, w, c, oh, ow, batch,
            pad, int(flip), int(standardize),
            int(rrc), float(rrc_min_area), src_u8,
            self._mean.ctypes.data_as(ctypes.c_void_p) if self._mean is not None else None,
            self._std.ctypes.data_as(ctypes.c_void_p) if self._std is not None else None,
            seed, stream_offset, stream_stride, start_ticket,
            n_threads, queue_cap,
        )

    def next(self) -> tuple[np.ndarray, np.ndarray]:
        if self._handle is None:
            raise RuntimeError("pipeline is closed")
        out_images = np.empty(self._shape, np.float32)
        out_labels = np.empty((self._batch,), np.int32)
        ok = self._lib.dp_next(
            self._handle,
            out_images.ctypes.data_as(ctypes.c_void_p),
            out_labels.ctypes.data_as(ctypes.c_void_p),
        )
        if not ok:
            # Racing close()/destruction: never hand back uninitialized
            # buffers as if they were data.
            raise RuntimeError("pipeline stopped while waiting for a batch")
        if self._out_dtype != np.float32:
            out_images = out_images.astype(self._out_dtype)
        return out_images, out_labels

    def __iter__(self):
        while True:
            yield self.next()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        if getattr(self, "_handle", None):
            self._lib.dp_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
