"""The least bytes one boundary's release has to move on one chip, for
``whatif_release_roofline``. The yardstick's arithmetic, kept with the
benchmark and out of the program."""

from __future__ import annotations

import roofline


def release_bytes(scenarios: int, nodes: int, resources: int, rows: int) -> float:
    """Per scenario the state plane a release reads and writes once,
    ``used`` ([resources, N]), and one placement read per released row;
    and, shared by all scenarios, the release rows themselves (position,
    the request row and one matched-group id a row). 4 bytes each. The
    spread count plane ([groups, zones], under 100 values a scenario) is
    left out."""
    per_scenario = 2 * resources * nodes + rows
    shared = rows * (1 + resources + 1)
    return 4.0 * (scenarios * per_scenario + shared)


def release_min_ms(device_kind: str, **shape) -> float:
    """Least time for one boundary: memory-bound (a subtraction a byte)."""
    return (release_bytes(**shape)
            / roofline.peaks(device_kind)["hbm_bytes_per_s"] * 1e3)
