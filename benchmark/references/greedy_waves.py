"""The plain reference for the greedy wave scheduler: numpy only, nothing of
the program imported, nothing the program made taken but its answers.

The semantics checked (README.md of the repo; ``guarantees`` in the
configuration's file): tasks are scheduled in arrival order in waves of
``waveWidth`` slots, a gang never split over waves; each slot sees the
binds of the slots before it; a gang with an unplaced member is rolled back
at the end of its wave; before each chunk of ``chunkWaves`` waves, tasks
whose arrival + duration is at or before the chunk's start time, and that
were bound two chunks back or earlier, give their resources back. A task
goes to the feasible node (resources fit, taint tolerated) of the highest
score, the lowest index among equals. The score is the default profile's:
NodeResourcesFit LeastAllocated over cpu and memory, integer
(``floor(mean_r floor(100 * free_r / allocatable_r))``), plus twice the
PodTopologySpread score for tasks with a zone constraint
(``floor(count_in_zone * log(zones + 2) + maxSkew - 1 + 0.5)`` turned
round by ``100 * (max + min - raw) // max`` over the feasible nodes).

The check is teacher-forced, as a served model's is: for a sample of tasks
drawn from the seed (the last one always among them) the cluster's state
just before the task is rebuilt from the program's own answers for the
tasks before it, the reference scores every node on that state, and the
program's node has to be the one the reference picks. Scores are integers
cut by ``floor``; where a value lies within ``EDGE`` of a whole number the
program's float32 running sums may fall on either side, so such a node's
score is an interval, and a choice is sound if some scores within the
intervals make it the pick.

``control="bf16"`` puts the reference in bfloat16 in the program's place:
at each sampled task the node that bfloat16 arithmetic picks on the same
state is compared as if the program had answered it.
"""

from __future__ import annotations

import numpy as np

PAD = -1
EDGE = 2e-3  # in score points; float32 sums of a day's binds and releases
# at one node are off by under 5e-4 points (PERF.md §2)
FIT_EDGE = 1e-5  # of a node's allocatable
RESOURCES = ("cpu", "mem", "pods")


def pack_waves(arrival, group_id, width: int) -> np.ndarray:
    """[waves, width] task ids in arrival order (stable), PAD-filled; a
    gang is never split over waves."""
    order = np.argsort(arrival, kind="stable")
    members = {}
    for p in order[group_id[order] != PAD]:
        members.setdefault(int(group_id[p]), []).append(int(p))
    waves, current, consumed = [], [], set()
    for p in order.tolist():
        if p in consumed:
            continue
        g = int(group_id[p])
        batch = [p] if g == PAD else members[g]
        if len(batch) > width:
            raise ValueError(f"gang of {len(batch)} exceeds the wave width {width}")
        if len(current) + len(batch) > width:
            waves.append(current)
            current = []
        current = current + batch
        if g != PAD:
            consumed.update(batch)
    if current:
        waves.append(current)
    idx = np.full((max(len(waves), 1), width), PAD, np.int64)
    for i, w in enumerate(waves):
        idx[i, :len(w)] = w
    return idx


def schedule(tasks: dict, width: int, chunk_waves: int) -> dict:
    """What is static in a replay: the order tasks are tried in, the chunk
    each is tried in, and the chunk boundary at which each, once bound,
    gives its resources back (``never`` = the number of chunks)."""
    idx = pack_waves(tasks["arrival"], tasks["group_id"], width)
    flat = idx.reshape(-1)
    seq = flat[flat >= 0]
    P = len(tasks["arrival"])
    slot = np.full(P, -1, np.int64)
    slot[seq] = np.nonzero(flat >= 0)[0]
    chunk = slot // (width * chunk_waves)
    starts = tasks["arrival"][idx[0::chunk_waves, 0]]  # [chunks] f64
    end = tasks["arrival"] + tasks["duration"].astype(np.float64)
    release = np.maximum(np.searchsorted(starts, end, side="left"), chunk + 2)
    rank = np.empty(P, np.int64)
    rank[seq] = np.arange(len(seq))
    return {"seq": seq, "slot": slot, "wave": slot // width, "chunk": chunk,
            "release": release, "rank": rank}


def _edges(x):
    """floor(x) as an interval: x within EDGE of a whole number may have
    been cut to either side of it."""
    f = np.floor(x)
    lo = np.where(x - f < EDGE, f - 1, f)
    hi = np.where(f + 1 - x < EDGE, f + 1, f)
    return np.maximum(lo, 0), np.minimum(hi, 100)


def _bf16(a):
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16)


class State:
    """The cluster just before one task is tried, rebuilt from answers."""

    def __init__(self, nodes, tasks, sched, assign, k):
        before = sched["seq"][:sched["rank"][k]]
        before = before[(assign[before] >= 0)
                        & (sched["release"][before] > sched["chunk"][k])]
        at = assign[before]
        N = len(nodes["cpu"])
        self.used = {
            "cpu": np.bincount(at, tasks["cpu"][before].astype(np.float64), N),
            "mem": np.bincount(at, tasks["mem"][before].astype(np.float64), N),
            "pods": np.bincount(at, minlength=N).astype(np.float64),
        }
        same = before[tasks["app_id"][before] == tasks["app_id"][k]]
        self.in_zone = np.bincount(nodes["zone"][assign[same]],
                                   minlength=nodes["zones"]).astype(np.float64)


def pick(nodes, tasks, trace, st, k, weights):
    """(lo, hi, sure, maybe): each node's total score as an interval, the
    nodes that are feasible for certain, and those feasible or not by a
    rounding. None for lo, hi where the zone score itself is on an edge."""
    req = {"cpu": float(tasks["cpu"][k]), "mem": float(tasks["mem"][k]), "pods": 1.0}
    free = {r: nodes[r].astype(np.float64) - st.used[r] - req[r] for r in RESOURCES}
    ok = ~nodes["tainted"] | bool(tasks["tolerates"][k])
    sure = ok & np.all([free[r] > FIT_EDGE * nodes[r] for r in RESOURCES], axis=0)
    maybe = ok & ~sure & np.all(
        [free[r] >= -FIT_EDGE * nodes[r] for r in RESOURCES], axis=0)
    lo = hi = 0.0
    for r in ("cpu", "mem"):
        l, h = _edges(100.0 * np.clip(free[r] / nodes[r], 0.0, 1.0))
        lo, hi = lo + l, hi + h
    lo, hi = np.floor(lo / 2) * weights["fit"], np.floor(hi / 2) * weights["fit"]
    if tasks["app_id"][k] < trace["spread_apps"]:
        x = (st.in_zone * float(np.float32(np.log(nodes["zones"] + 2.0)))
             + (trace["spread_max_skew"] - 1) + 0.5)
        raw = np.floor(x)
        if np.any((x - raw < 1e-3) | (raw + 1 - x < 1e-3)):
            return None, None, sure, maybe
        zones = np.unique(nodes["zone"][sure | maybe])
        if zones.size:
            top, low = raw[zones].max(), raw[zones].min()
            zone_score = (100 * (top + low - raw)) // top if top > 0 else raw * 0 + 100
            lo = lo + weights["spread"] * zone_score[nodes["zone"]]
            hi = hi + weights["spread"] * zone_score[nodes["zone"]]
    return lo, hi, sure, maybe


def pick_bf16(nodes, tasks, trace, st, k, weights) -> int:
    """The node the same rule picks with every value and every operation
    in bfloat16 (numpy rounds each result to the array's type)."""
    b = _bf16
    req = {"cpu": b(tasks["cpu"][k]), "mem": b(tasks["mem"][k]), "pods": b(1.0)}
    alloc = {r: b(nodes[r]) for r in RESOURCES}
    used = {r: b(st.used[r]) for r in RESOURCES}
    ok = ~nodes["tainted"] | bool(tasks["tolerates"][k])
    for r in RESOURCES:
        ok = ok & ((used[r] + req[r]) <= alloc[r] + b(1e-6))
    if not ok.any():
        return PAD
    total = b(np.zeros(len(ok)))
    for r in ("cpu", "mem"):
        frac = (alloc[r] - used[r] - req[r]) / alloc[r]
        total = total + np.floor(np.clip(frac, b(0), b(1)) * b(100))
    total = np.floor(total / b(2)) * b(weights["fit"])
    if tasks["app_id"][k] < trace["spread_apps"]:
        raw = np.floor(b(st.in_zone) * b(np.log(nodes["zones"] + 2.0))
                       + b(trace["spread_max_skew"] - 1) + b(0.5))
        zones = np.unique(nodes["zone"][ok])
        top, low = raw[zones].max(), raw[zones].min()
        zone_score = (np.floor(b(100) * (top + low - raw) / top) if top > 0
                      else raw * b(0) + b(100))
        total = total + b(weights["spread"]) * zone_score[nodes["zone"]]
    return int(np.argmax(np.where(ok, total.astype(np.float32), -np.inf)))


def judge(choice: int, lo, hi, sure, maybe) -> float:
    """0.0 where ``choice`` can be the pick, else by how many score points
    it falls short (100.0 for an infeasible node, or for none where one
    fits for certain)."""
    if choice == PAD:
        return 100.0 if sure.any() else 0.0
    if not (sure[choice] or maybe[choice]):
        return 100.0
    # Every other node at its lowest, the choice at its highest: it has to
    # beat the nodes before it and at least equal those after.
    rival = np.where(sure, lo, -np.inf)
    rival[choice] = -np.inf
    short = max(float(rival[:choice].max(initial=-np.inf)) + 1.0,
                float(rival[choice:].max(initial=-np.inf))) - float(hi[choice])
    return max(short, 0.0)


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit) of the comparison; ``limit`` None is a
    number printed for the record and held to nothing."""
    nodes, tasks, eng = trace["nodes"], trace["tasks"], config["engine"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    sched = schedule(tasks, eng["waveWidth"], eng["chunkWaves"])
    P = len(tasks["arrival"])
    rng = np.random.default_rng(seed)
    rows = []
    for s, assign in enumerate(np.atleast_2d(answers["assignments"])):
        assign = np.asarray(assign, np.int64)
        unplaced = assign < 0
        gang = tasks["group_id"]
        broken = np.unique(gang[unplaced & (gang != PAD)])
        in_broken = np.isin(gang, broken) & (gang != PAD)
        # a rolled-back gang's binds were seen by the slots after it in
        # its wave and are in no answer: those slots cannot be rebuilt
        first_broken = np.full(int(sched["wave"].max()) + 1, np.iinfo(np.int64).max)
        np.minimum.at(first_broken, sched["wave"][in_broken], sched["slot"][in_broken])
        usable = ~in_broken & (sched["slot"] < first_broken[sched["wave"]])
        drawn = rng.choice(P, size=min(samples, P), replace=False)
        drawn = np.union1d(drawn, sched["seq"][-1:])
        picked = drawn[usable[drawn]]
        short, edge = [], 0
        for k in picked.tolist():
            st = State(nodes, tasks, sched, assign, k)
            lo, hi, sure, maybe = pick(nodes, tasks, trace, st, k, weights)
            if lo is None:
                edge += 1
                continue
            choice = int(assign[k])
            if control == "bf16":
                choice = pick_bf16(nodes, tasks, trace, st, k, weights)
            elif control:
                raise ValueError(f"unknown control {control!r}")
            short.append(judge(choice, lo, hi, sure, maybe))
        short = np.asarray(short or [100.0])
        tag = f"ref.s{s}." if np.ndim(answers["assignments"]) > 1 else "ref."
        rows += [
            (tag + "choices_not_the_references_share",
             float((short > 0).mean()), limits["choices_not_the_references_share"]),
            (tag + "choices_compared_short_of_min",
             float(max(0, limits["choices_compared_min"] - len(short))), 0),
            (tag + "choice_short_by_points_max", float(short.max()), None),
            (tag + "samples_on_a_zone_score_edge", float(edge), None),
            (tag + "samples_behind_a_rolled_back_gang",
             float(len(drawn) - len(picked)), None),
            (tag + "placed_differs_from_answers",
             float(abs(int((~unplaced).sum()) - int(np.ravel(answers["placed"])[s]))), 0),
        ]
    return rows
