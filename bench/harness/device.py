"""The device a run is on, and the table of its published peaks."""
from __future__ import annotations

# One chip's published peaks, keyed by JAX's ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """The peaks of ``kind``; a kind not in the table is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]


def info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(dev: dict, chips: int) -> None:
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{dev['count']}")
    peaks(dev["kind"])


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
