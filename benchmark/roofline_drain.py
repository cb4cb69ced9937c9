"""The least time a boundary's eviction needs on one chip, for
``drain_evict_roofline``. The yardstick's arithmetic, kept with the benchmark
and out of the program: what an eviction has to read and write, whatever
implements it."""

from __future__ import annotations

import roofline


def evict_bytes(scenarios: int, nodes: int, resources: int, tasks: int,
                buffer: int, boundaries: int, victims: int,
                planes: int) -> float:
    """Per scenario, f32 / i32 throughout, once each:

    * the placement buffer, read (``tasks`` places: which binds stand on a
      leaving node) and written where a victim stood (``victims`` words);
    * the record's node rows and their release boundaries, read
      (``boundaries x buffer`` x 2), and a victim's row written (3 words);
    * the node mask the chunk call reads allocatable through, read and
      written (``nodes`` bytes each way);
    * the planes the rewind touches: ``used`` (``resources x nodes``) and the
      ``planes`` node-wide count / taint planes, read and written;
    * per victim what the rewind and the queue need of it: its requests, its
      matched group, priority and duration read (``resources + 3`` words),
      and its log row written (4 words);
    * the queue: ``buffer`` slots of four words (task, priority, duration,
      evicted-at), read and written."""
    place = 4 * tasks + 4 * victims
    record = 2 * 4 * boundaries * buffer + 3 * 4 * victims
    mask = 2 * nodes
    rewind = 2 * 4 * (resources + planes) * nodes
    per_victim = 4 * (resources + 3 + 4) * victims
    queue = 2 * 4 * 4 * buffer
    return float(scenarios * (place + record + mask + rewind + per_victim + queue))


def evict_ops(scenarios: int, victims: int, resources: int) -> float:
    """One subtraction a victim and resource, one a victim for its count."""
    return float(scenarios * victims * (resources + 1))


def evict_min_ms(device_kind: str, **shape) -> float:
    """Least time for one boundary's eviction: its bytes at the chip's HBM
    peak, or its operations at the chip's peak rate if that is longer (it is
    not: a few operations a victim against megabytes of places)."""
    p = roofline.peaks(device_kind)
    ops = evict_ops(shape["scenarios"], shape["victims"], shape["resources"])
    return 1e3 * max(evict_bytes(**shape) / p["hbm_bytes_per_s"],
                     ops / p["bf16_flops_per_s"])
