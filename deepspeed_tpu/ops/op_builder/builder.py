"""Native-op build system: g++ JIT compile + ctypes binding.

TPU equivalent of the reference's ``op_builder/builder.py`` (``OpBuilder``
ABC :107 with ``sources()/include_paths()/is_compatible()`` and ``load()``
:453 that either imports a prebuilt module or ``jit_load``s it via
``torch.utils.cpp_extension``).  Here the accelerator ops are Pallas/XLA —
the only native code left is host-side (AIO for the NVMe tier, CPU
optimizers for the offload tier), so ``load()`` compiles the C++ sources
with g++ into a content-hashed shared library under ``.ds_build/`` and
binds it with ctypes (no pybind11 in this image).
"""

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading

from ...utils.logging import logger

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                          "..", "..", ".."))
CSRC_DIR = os.path.join(_REPO_ROOT, "csrc")
BUILD_DIR = os.environ.get("DS_BUILD_DIR",
                           os.path.join(_REPO_ROOT, ".ds_build"))

_build_lock = threading.Lock()


def _host_isa():
    """What ``-march=native`` resolves against on this host: the machine
    type plus the CPU feature flags the kernel reports."""
    import platform
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.split(":")[0].strip() in ("flags", "Features"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        flags = platform.processor()
    return f"{platform.machine()} {flags}"


class OpBuilder:
    """One native op: sources under csrc/, compiled once, loaded via ctypes."""

    NAME = None
    SOURCES = ()            # paths relative to csrc/
    EXTRA_CFLAGS = ()

    def __init__(self):
        self._lib = None

    def name(self):
        return self.NAME

    def sources(self):
        return [os.path.join(CSRC_DIR, s) for s in self.SOURCES]

    def include_paths(self):
        return [os.path.join(CSRC_DIR, "includes")]

    def cflags(self):
        return ["-O3", "-std=c++17", "-fPIC", "-shared", "-fopenmp",
                "-march=native", *self.EXTRA_CFLAGS]

    def is_compatible(self, verbose=False):
        """Host toolchain + sources present (the reference checks CUDA arch
        compatibility here; host ops only need g++)."""
        if shutil.which("g++") is None:
            if verbose:
                logger.warning(f"{self.NAME}: g++ not found")
            return False
        missing = [s for s in self.sources() if not os.path.isfile(s)]
        if missing:
            if verbose:
                logger.warning(f"{self.NAME}: missing sources {missing}")
            return False
        return True

    def _source_hash(self):
        h = hashlib.sha256()
        for s in self.sources():
            with open(s, "rb") as f:
                h.update(f.read())
        h.update(" ".join(self.cflags()).encode())
        # -march=native: the artifact is only valid on a CPU with the
        # build host's instruction set — a build dir carried to another
        # machine (a copied checkout) must miss, not load
        h.update(_host_isa().encode())
        return h.hexdigest()[:16]

    # Library cache name: ops sharing a translation unit (cpu_adam /
    # cpu_adagrad / utils) share one artifact via LIB_NAME.
    LIB_NAME = None

    def lib_path(self):
        lib = self.LIB_NAME or self.NAME
        return os.path.join(BUILD_DIR, f"{lib}-{self._source_hash()}.so")

    def jit_build(self, verbose=True):
        """Compile the sources into the cached .so (parity: reference
        ``builder.py:465 jit_load``)."""
        out = self.lib_path()
        with _build_lock:
            if os.path.isfile(out):
                return out
            os.makedirs(BUILD_DIR, exist_ok=True)
            # pid-suffixed tmp + atomic rename: concurrent launcher ranks on
            # one host each build privately; last rename wins with identical
            # bytes (the reference relies on torch cpp_extension's file lock)
            tmp = f"{out}.tmp.{os.getpid()}"
            cmd = ["g++", *self.cflags(),
                   *[f"-I{p}" for p in self.include_paths() if os.path.isdir(p)],
                   *self.sources(), "-o", tmp]
            if verbose:
                logger.info(f"building native op {self.NAME}: {' '.join(cmd)}")
            try:
                subprocess.run(cmd, check=True, capture_output=True, text=True)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"native build of {self.NAME} failed:\n{e.stderr}") from e
            os.replace(tmp, out)
        return out

    def load(self, verbose=True):
        """Build if needed and return the ctypes library with typed symbols."""
        if self._lib is None:
            lib = ctypes.CDLL(self.jit_build(verbose=verbose))
            self._declare(lib)
            self._lib = lib
        return self._lib

    def _declare(self, lib):
        """Subclasses set argtypes/restype on the C symbols."""
        raise NotImplementedError


c_i64 = ctypes.c_int64
c_int = ctypes.c_int
c_f32 = ctypes.c_float
c_void = ctypes.c_void_p
c_str = ctypes.c_char_p


class AsyncIOBuilder(OpBuilder):
    """Reference ``op_builder/async_io.py`` (libaio) → thread-pool POSIX I/O."""

    NAME = "async_io"
    SOURCES = ("aio/ds_aio.cpp",)
    EXTRA_CFLAGS = ("-pthread",)

    def _declare(self, lib):
        lib.dsaio_create.argtypes = [c_i64, c_int, c_int, c_int, c_int]
        lib.dsaio_create.restype = c_void
        lib.dsaio_destroy.argtypes = [c_void]
        for sym in ("dsaio_sync_pread", "dsaio_sync_pwrite"):
            fn = getattr(lib, sym)
            fn.argtypes = [c_void, c_str, c_void, c_i64, c_i64]
            fn.restype = c_i64
        for sym in ("dsaio_async_pread", "dsaio_async_pwrite"):
            fn = getattr(lib, sym)
            fn.argtypes = [c_void, c_str, c_void, c_i64, c_i64]
            fn.restype = c_int
        lib.dsaio_wait.argtypes = [c_void]
        lib.dsaio_wait.restype = c_i64
        lib.dsaio_block_size.argtypes = [c_void]
        lib.dsaio_block_size.restype = c_i64
        for sym in ("dsaio_queue_depth", "dsaio_single_submit",
                    "dsaio_overlap_events", "dsaio_thread_count"):
            fn = getattr(lib, sym)
            fn.argtypes = [c_void]
            fn.restype = c_int
        lib.dsaio_pending_count.argtypes = [c_void]
        lib.dsaio_pending_count.restype = c_i64


class CPUAdamBuilder(OpBuilder):
    """Reference ``op_builder/cpu_adam.py`` (AVX SIMD) → auto-vectorized C++."""

    NAME = "cpu_adam"
    SOURCES = ("adam/ds_cpu_adam.cpp",)

    def _declare(self, lib):
        f32p = ctypes.POINTER(ctypes.c_float)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.ds_adam_step.argtypes = [f32p, f32p, f32p, f32p, c_i64, c_i64,
                                     c_f32, c_f32, c_f32, c_f32, c_f32,
                                     c_int, c_int, u16p, c_int]
        lib.ds_adam_step.restype = c_int
        lib.ds_adagrad_step.argtypes = [f32p, f32p, f32p, c_i64, c_f32, c_f32,
                                        c_f32, u16p, c_int]
        lib.ds_adagrad_step.restype = c_int
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.ds_adagrad_step_sparse.argtypes = [f32p, i64p, f32p, f32p, c_i64,
                                               c_i64, c_f32, c_f32, c_f32,
                                               u16p, c_int]
        lib.ds_adagrad_step_sparse.restype = c_int
        lib.ds_memcpy.argtypes = [c_void, c_void, c_i64]
        lib.ds_memcpy.restype = c_int
        lib.ds_fp32_to_bf16.argtypes = [f32p, u16p, c_i64]
        lib.ds_fp32_to_bf16.restype = c_int
        lib.ds_bf16_to_fp32.argtypes = [u16p, f32p, c_i64]
        lib.ds_bf16_to_fp32.restype = c_int


# CPU Adagrad and the memcpy/flatten utils live in the same translation unit
# as Adam (one elementwise-sweep library); these builders exist for the
# reference's one-builder-per-op surface (op_builder/{cpu_adagrad,utils}.py).
class CPUAdagradBuilder(CPUAdamBuilder):
    NAME = "cpu_adagrad"
    LIB_NAME = "cpu_adam"


class UtilsBuilder(CPUAdamBuilder):
    NAME = "utils"
    LIB_NAME = "cpu_adam"


ALL_OPS = {b.NAME: b for b in (AsyncIOBuilder, CPUAdamBuilder,
                               CPUAdagradBuilder, UtilsBuilder)}


def get_builder(name):
    return ALL_OPS[name]()
