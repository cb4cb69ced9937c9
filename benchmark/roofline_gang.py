"""The least bytes one closing wave's rollback of a wide pod group has to move
on one chip, for ``gang_rollback_roofline``. The yardstick's arithmetic, kept
with the benchmark and out of the program."""

from __future__ import annotations

import roofline


def rollback_bytes(scenarios: int, nodes: int, resources: int) -> float:
    """Per scenario, at the wave that closes a wide group: ``used``
    ([resources, N] f32) read once and written once, and the group's carried
    plane (what its tentative binds took, the same shape) read once and
    written once (it starts from zero for the next group). The shipped form
    (``rollback_form`` ``"txn_plane"``) carries no list of binds."""
    return float(scenarios * 4 * resources * nodes * 4)


def rollback_min_ms(device_kind: str, **shape) -> float:
    """Least time for one closing wave's rollback: memory-bound (a select and
    a subtraction a byte pair, far under the chip's ridge)."""
    return (rollback_bytes(**shape)
            / roofline.peaks(device_kind)["hbm_bytes_per_s"] * 1e3)
