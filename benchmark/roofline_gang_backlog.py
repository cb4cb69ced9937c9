"""The least time a boundary's retry pass over a queue of whole JOBS needs on
one chip, for ``gangq_retry_roofline``. The yardstick's arithmetic, kept with
the benchmark and out of the program.

The pass is the wave step walked over the queue, every job from a fresh wave,
so its length is the queue's, not the buffer's: the bytes are those of the
wave steps a pass EXECUTED (``summary()["retry"]["pass_waves"]`` over the
passes made: counting compiled waves, the buffer's, would read a short queue
as a slow pass, PERF.md §7), with the open transaction's own ``[resources,
nodes]`` plane read and written beside ``used`` in every one of them, and the
queue's upkeep once a boundary."""

from __future__ import annotations

import roofline
import roofline_backlog


def pass_wave_bytes(scenarios: int, nodes: int, resources: int, wave_width: int,
                    planes: int) -> float:
    """One EXECUTED pass wave: ``roofline.wave_bytes`` and the transaction's
    plane, read once and written once (f32)."""
    return roofline.wave_bytes(scenarios, nodes, resources, wave_width, planes) \
        + float(2 * scenarios * resources * nodes * 4)


def retry_min_ms(device_kind: str, *, waves_per_pass: float, scenarios: int,
                 nodes: int, resources: int, wave_width: int, planes: int,
                 buffer: int, chunk_slots: int) -> float:
    """Least time for one boundary's pass of ``waves_per_pass`` executed wave
    steps, and the queue's upkeep beside them, at the chip's HBM peak."""
    moved = waves_per_pass * pass_wave_bytes(
        scenarios, nodes, resources, wave_width, planes)
    moved += roofline_backlog.upkeep_bytes(scenarios, buffer, chunk_slots, resources)
    return moved / roofline.peaks(device_kind)["hbm_bytes_per_s"] * 1e3
