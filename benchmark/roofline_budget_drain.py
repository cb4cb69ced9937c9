"""The least time a boundary's admission under disruption budgets needs on one
chip, for ``budget_admit_roofline``. The yardstick's arithmetic, kept with the
benchmark and out of the program: what an admission has to read and write,
whatever implements it."""

from __future__ import annotations

import roofline


def admit_bytes(scenarios: int, candidates: float, apps: int) -> float:
    """Per scenario, i32 throughout, once each: a candidate's task, its place
    in the walk and its application read (3 words), its verdict written (1);
    the per-application counters (``down`` and ``maxUnavailable``) read and
    ``down`` written (3 x ``apps``)."""
    return float(scenarios * 4 * (4 * candidates + 3 * apps))


def admit_ops(scenarios: int, candidates: float) -> float:
    """One compare and one add a candidate."""
    return float(scenarios * 2 * candidates)


def admit_min_ms(device_kind: str, scenarios: int, candidates: float,
                 apps: int) -> float:
    p = roofline.peaks(device_kind)
    return 1e3 * max(admit_bytes(scenarios, candidates, apps) / p["hbm_bytes_per_s"],
                     admit_ops(scenarios, candidates) / p["bf16_flops_per_s"])
