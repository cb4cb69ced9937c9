"""The least bytes the arrivals-only hand-back program has to move on one
chip, for ``whatif_arrivals_handback_roofline``. The yardstick's arithmetic,
kept with the benchmark and out of the program."""

from __future__ import annotations

import roofline


def handback_bytes(scenarios: int, slots: int, nodes: int) -> float:
    """Per scenario every wave slot's choice read once, as the chunk program
    left it (2 bytes where a node id fits 15 bits, else 4), and every pod's
    node written once, 4 bytes. A slot is counted as a pod: the cell's chunk
    divides its waves and its waves are full, so no slot is padding."""
    stored = 2 if nodes < 2**15 - 1 else 4
    return float(scenarios * slots * (stored + 4))


def handback_min_ms(device_kind: str, **shape) -> float:
    """Least time for one hand-back: memory-bound (a copy)."""
    return (handback_bytes(**shape)
            / roofline.peaks(device_kind)["hbm_bytes_per_s"] * 1e3)
