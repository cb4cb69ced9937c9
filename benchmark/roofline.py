"""Peaks of the devices this benchmark knows, and the least bytes one wave
of the chunk program has to move. The yardstick's arithmetic lives here,
not in the program, so no PR that claims a gain can change it."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

def peaks(device_kind: str) -> dict:
    """The peak table's row for a ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


def wave_bytes(scenarios: int, nodes: int, resources: int, wave_width: int,
               planes: int) -> float:
    """Bytes one wave needs on one chip: per scenario ``used`` and
    ``allocatable`` ([N, resources]) and the configuration's ``planes``
    further [N] planes (``scheduler.planes`` in its file) read once (the
    ``wave_width`` slots of a wave see the same planes plus each other's
    binds, which fit on chip), and the committed rows written: one
    [resources] row of ``used`` per slot. f32 throughout."""
    read = scenarios * nodes * (2 * resources + planes) * 4
    written = scenarios * wave_width * resources * 4
    return float(read + written)


def wave_min_ms(device_kind: str, **shape) -> float:
    """Least time for one wave: memory-bound (a filter/score fold does a
    few flops per byte, far under the chip's 240 flop/byte ridge)."""
    return wave_bytes(**shape) / peaks(device_kind)["hbm_bytes_per_s"] * 1e3
