"""The device a run is on: the check that refuses anything but a TPU of
the cell's width, peak memory, and the table of published peaks."""

from __future__ import annotations

import json
import os
from typing import Optional


class NoChip(Exception):
    """The backend is not the TPU the cell asks for: nothing is run."""


def describe() -> dict:
    """Platform, kind and count as JAX reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(chips: int) -> dict:
    """The device block of the result line, or :class:`NoChip`.  No CPU
    stand-in: a number from a CPU run is never a device metric."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoChip(f"the default backend is {dev['platform']!r} "
                     f"({dev['kind']}), not a TPU")
    if dev["count"] != chips:
        raise NoChip(f"the cell asks for {chips} chip(s) and JAX sees "
                     f"{dev['count']}")
    return dev


def peak_bytes() -> Optional[int]:
    """``memory_stats()["peak_bytes_in_use"]`` on the fullest device;
    None where the backend reports none (the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks(bench_dir: str, kind: str) -> dict:
    """Published peaks of ``kind`` from ``peaks.json``.  A device that
    is not in the table is an error, not a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {[k for k in table if k[0] != '_']})")
    return table[kind]
