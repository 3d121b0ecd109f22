"""The device the run is on, and its published peaks."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_devices(chips: int) -> dict:
    """The devices as JAX reports them; anything but ``chips`` or more TPU
    chips raises ``NoChip`` (a run never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return info


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to {PEAKS.name} with their source")
    return table[kind]
