"""JIT builder for native (C++) ops — the reference op_builder analog.

Reference behavior: op_builder/builder.py:78-286 (JIT ninja compile via
torch cpp_extension, AVX capability autodetect, compatibility checks).
Here: direct g++ -shared compile of C sources into a .so under the
checkout's `.op_build/`, named by a hash of the sources and flags and loaded
with ctypes (no pybind11/torch in the loop), with the same per-op
builder-class shape so `ds_report` can enumerate ops and their compatibility.
"""
import ctypes
import hashlib
import os
import subprocess

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BUILD_DIR = os.path.join(_REPO_ROOT, ".op_build")


class OpBuilder:
    NAME = "base"
    SOURCES = []           # repo-relative .cpp paths
    EXTRA_FLAGS = []

    def absolute_sources(self):
        return [os.path.join(_REPO_ROOT, s) for s in self.SOURCES]

    def is_compatible(self):
        if not all(os.path.exists(s) for s in self.absolute_sources()):
            return False
        try:
            subprocess.run(["g++", "--version"], capture_output=True,
                           check=True)
            return True
        except (OSError, subprocess.CalledProcessError):
            return False

    @staticmethod
    def _cpu_features():
        """The first `flags` line of /proc/cpuinfo ("" where unreadable)."""
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    if line.startswith("flags"):
                        return line
        except OSError:
            pass
        return ""

    def cpu_arch_flags(self):
        """March autodetect (reference op_builder/cpu_adam.py:24-40)."""
        feats = self._cpu_features().split()
        if "avx512f" in feats or "avx2" in feats:
            return ["-march=native"]
        return []

    def compile_flags(self):
        return (["-O3", "-shared", "-fPIC", "-fopenmp"]
                + self.cpu_arch_flags() + self.EXTRA_FLAGS)

    def so_path(self):
        """The library is named by the content of its sources, its flags
        and (as -march=native means this CPU) the CPU's features, so one
        built from other sources or for another CPU is never loaded."""
        h = hashlib.sha256(
            (" ".join(self.compile_flags()) + self._cpu_features()).encode())
        for s in self.absolute_sources():
            with open(s, "rb") as f:
                h.update(f.read())
        return os.path.join(_BUILD_DIR,
                            f"{self.NAME}-{h.hexdigest()[:16]}.so")

    def jit_load(self):
        """Compile (if not built yet) and dlopen. Returns a ctypes.CDLL or
        None on failure (callers fall back to the numpy path)."""
        if not self.is_compatible():
            logger.warning(f"op '{self.NAME}': no compatible toolchain; "
                           f"using fallback implementation")
            return None
        sources = self.absolute_sources()
        so = self.so_path()
        if not os.path.exists(so):
            os.makedirs(_BUILD_DIR, exist_ok=True)
            # unique temp per process: concurrent builders (parallel
            # pytest, hosts sharing a checkout) must not interleave
            # writes; os.replace promotes atomically, last writer wins
            tmp = f"{so}.tmp.{os.getpid()}"
            cmd = ["g++"] + self.compile_flags() + sources + ["-o", tmp]
            try:
                subprocess.run(cmd, capture_output=True, check=True, text=True)
                os.replace(tmp, so)
                logger.info(f"op '{self.NAME}': compiled {so}")
            except subprocess.CalledProcessError as e:
                logger.warning(f"op '{self.NAME}': compile failed "
                               f"({e.stderr[-500:] if e.stderr else e}); "
                               f"using fallback implementation")
                return None
        try:
            return ctypes.CDLL(so)
        except OSError as e:
            logger.warning(f"op '{self.NAME}': dlopen failed ({e})")
            return None


class CPUAdamBuilder(OpBuilder):
    NAME = "cpu_adam"
    SOURCES = ["csrc/adam/cpu_adam.cpp"]

    def load(self):
        lib = self.jit_load()
        if lib is None:
            return None
        lib.ds_adam_step.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float, ctypes.c_float, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64, ctypes.c_float]
        lib.ds_fp32_to_bf16.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_int64]
        lib.ds_fp32_to_fp16.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_int64]
        lib.ds_simd_width.restype = ctypes.c_int
        return lib


ALL_OPS = {"cpu_adam": CPUAdamBuilder}
