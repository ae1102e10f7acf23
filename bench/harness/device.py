"""The device the run is on: the look for the chip, the peaks table, the
peak memory."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class NoDevice(RuntimeError):
    """The run cannot measure here: no accelerator, too few chips, or
    interpreted kernels."""


def use_checkout_cache(root: str) -> str:
    """Point JAX's persistent compile cache at the fixed directory
    ``<root>/.jax_cache`` of the checkout, whatever the environment says,
    before JAX is imported: only a run's first start in a checkout then
    compiles, and two checkouts share nothing."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; a kind that is not in
    the table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def require_chips(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoDevice`. The kernels
    must be the compiled Pallas ones the platform selects."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX reports platform "
                       f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), JAX finds "
                       f"{len(devices)}")
    from repro.kernels.backends import interpret_mode
    from repro.kernels.registry import get_backend
    if get_backend().name != "pallas" or interpret_mode():
        raise NoDevice("the platform must select the compiled pallas "
                       "kernels (is REPRO_KERNEL_BACKEND set?)")
    return devices[:chips]


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks_seen = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_seen) if peaks_seen else None


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
