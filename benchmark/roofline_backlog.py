"""The least time a boundary's retry pass needs on one chip, for
``backlog_retry_roofline``. The yardstick's arithmetic, kept with the
benchmark and out of the program."""

from __future__ import annotations

import roofline


def upkeep_bytes(scenarios: int, buffer: int, chunk_slots: int,
                 resources: int, record_words: int = 4) -> float:
    """Per scenario, f32 / i32 throughout. The queue's one sort of a
    boundary: the ``buffer`` queued tasks and the chunk's ``chunk_slots``
    slots, four words each (key, task, priority, duration), read once and
    written once. The pass's record: a row of ``buffer`` binds written once,
    ``record_words`` words (task, node, release boundary, matched group) and
    the ``resources`` requests each."""
    sort = 2 * (buffer + chunk_slots) * 4 * 4
    record = buffer * (record_words + resources) * 4
    return float(scenarios * (sort + record))


def retry_min_ms(device_kind: str, *, scenarios: int, nodes: int,
                 resources: int, wave_width: int, planes: int, buffer: int,
                 chunk_slots: int) -> float:
    """Least time for one boundary's retry pass: ``buffer / wave_width`` wave
    steps of ``roofline.wave_min_ms`` at the cell's shape (the pass IS the
    wave step scanned over the queue: memory-bound, as a wave is) and the
    queue's upkeep bytes beside them at the chip's HBM peak."""
    waves = buffer / wave_width * roofline.wave_min_ms(
        device_kind, scenarios=scenarios, nodes=nodes, resources=resources,
        wave_width=wave_width, planes=planes)
    upkeep = upkeep_bytes(scenarios, buffer, chunk_slots, resources)
    return waves + upkeep / roofline.peaks(device_kind)["hbm_bytes_per_s"] * 1e3
