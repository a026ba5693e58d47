"""The chip: refuse to run without it, its peaks, its memory peak."""
from __future__ import annotations

import json
import os
from typing import Dict

from harness.spec import BENCH


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require(chips: int):
    """The first ``chips`` TPU devices; NoChip otherwise.  Imports JAX,
    so nothing touches a device before the arguments are read."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``.  A kind that is not in
    ``bench/peaks.json`` is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
