"""The device a run is measured on: peaks, and the refusal of anything else.

Peaks are keyed by the exact ``device_kind`` JAX reports.  A kind that is
not in the table is an error, never a default, and so is a platform that is
not ``tpu``: no measurement continues on a CPU.
"""
import jax

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


class NoDevice(RuntimeError):
    """The machine does not hold what the cell asks for."""


def require_tpu(chips):
    """The ``chips`` devices the cell runs on, or NoDevice."""
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoDevice(f"jax.devices() reports platform {d0.platform!r}: "
                       f"the benchmark measures on a TPU only")
    if d0.device_kind not in PEAKS:
        raise NoDevice(f"device_kind {d0.device_kind!r} has no row in the "
                       f"benchmark's peaks table ({sorted(PEAKS)})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, jax.devices() "
                       f"has {len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def bytes_in_use(devices):
    """Bytes in use now on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def describe(devices, peak_bytes):
    """The ``device`` key of a result line, as JAX reports it;
    ``peak_bytes`` as the driver read it when the window had closed,
    before the reference ran."""
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}
