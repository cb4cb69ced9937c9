"""A Borg cell that is full, under a rolling maintenance drain: the trace is
``generators/borg_backlog.py``'s (imported, not copied: the same cluster,
resident set, arrival window, gang layout and multiset of arriving tasks).
What this configuration adds is not in the trace: the rollout plans
(``benchmark/drain_plans.py``), which the engine adapter and the plain
reference both make from the configuration's file.

One difference, the configuration's ``deal``: every ``--seed`` gets
``baseSeed``'s own deal of the arriving tasks up to arrival slot ``deal.from``
(of ``deal.of`` a batch; a smaller trace, a rehearsal, scales it) and deals
the tasks BEHIND that slot among themselves. Dealt over the whole window
(``borg_backlog.py``'s rule) five seeds spread this cell's
``placements_per_s`` by 0.68% against an admission at half the 1% bound: what
is dealt decides which tasks queue, how deep, and so how many wave steps the
retry passes take (PERF.md §6, PR 45; ``generators/gang_jobs.py`` has the
same rule for the same reason)."""

from __future__ import annotations

import numpy as np

from generators import borg, borg_backlog

to_program = borg_backlog.to_program


def generate(config: dict, nodes: int, tasks: int, seed: int) -> dict:
    trace = borg_backlog.generate(config, nodes, tasks, config["workload"]["baseSeed"])
    first = trace["resident"] + config["deal"]["from"] * tasks // config["deal"]["of"]
    cols = trace["tasks"]
    behind = first + np.random.default_rng(seed).permutation(len(cols["cpu"]) - first)
    for k in borg.DEALT:
        cols[k][first:] = cols[k][behind]
    return trace
