"""The device a run is on, its published peaks, and its memory peak."""
from __future__ import annotations

import json

from harness.spec import ROOT


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """Peaks of ``device_kind`` from ``peaks.json``; an unknown kind is an
    error, never a default."""
    with open(ROOT / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"have {sorted(table)}")
    return table[kind]


def require_chips(chips: int) -> list:
    """The first ``chips`` accelerator devices, or `NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoChip(f"JAX found no accelerator, only {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devices: list) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices: list) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
