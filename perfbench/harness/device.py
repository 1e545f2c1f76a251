"""Which device a run is on, and that device's published peaks.

The peaks are the yardstick's own table, keyed by the ``device_kind`` JAX
reports.  A device that is not in the table is an error, never a default,
and a run that finds no TPU, or fewer chips than its cell asks for, stops
before it prints a result.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip,
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud TPU documentation, TPU v5e",
    },
}


class NoDevice(RuntimeError):
    """The run has no accelerator of a kind the benchmark knows."""


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoDevice(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def check(chips: int) -> dict:
    """The devices the cell runs on; raises :class:`NoDevice` unless JAX
    finds at least ``chips`` TPUs of a known kind."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    kind = devs[0].device_kind
    peaks(kind)
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.devices()[:chips])


def free_bytes() -> int:
    """Device memory not in use on the first chip.  Backends that keep no
    statistics (the CPU, in tests) report 4 GiB."""
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return 2**32
    return stats["bytes_limit"] - stats["bytes_in_use"]
