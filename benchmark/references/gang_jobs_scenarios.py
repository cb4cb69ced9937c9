"""The plain reference for the gang-scheduled training deployment (jobs of 1 to
64 workers that bind whole or not at all on a shared GPU cluster), held against
every scenario of a what-if batch on that scenario's OWN cluster: numpy only,
nothing of the program imported, nothing the program made taken but its
answers. The judgement of one choice on score intervals is
``default_plugins_scenarios``', a plain reference of this benchmark.

The semantics checked (``guarantees`` in the configuration's file):
- Pods are tried once each, in arrival order (stable), in waves of
  ``waveWidth`` slots; the members of a pod group arrive consecutively. A
  group of at most ``waveWidth`` members is never split over waves, so a wave
  may close with empty slots. A group WIDER than the wave starts on a wave's
  first slot and fills ceil(size / waveWidth) consecutive waves; the rest of
  its closing wave is open to the pods that follow. Each slot sees the binds
  of every slot before it, those of its own wave and the tentative binds of an
  open group included. Nothing is released.
- Filter. NodeResourcesFit over ALL the configuration's resources: used +
  request <= allocatable for cpu, memory, pods and ``nvidia.com/gpu``; a node
  without the device plugin has 0 of it (upstream: extended resources are
  integers, never overcommitted). TaintToleration: the cluster has no taint of
  its own, a scenario's injected ``NoSchedule`` taint is tolerated by no pod.
  A down node has allocatable 0 in every resource.
- Score, the default plugin set's as it comes out for this workload, integers:
  NodeResourcesFit LeastAllocated over cpu and memory only:
  ``floor((floor(100 * free_cpu / alloc_cpu) + floor(100 * free_mem /
  alloc_mem)) / 2)``, free after the pod, weight 1; every other plugin finds
  no term or gives a constant. The feasible node of the highest score wins,
  the lowest index among equals; with no feasible node the pod is
  unschedulable and is not tried again.
- Pod groups, all or none. A member that fits nowhere fails its group. The
  members AFTER a failed one are still tried and still bind tentatively
  (the builder's choice; where a group ends on a wave's last slot, as every
  size drawn here does, the answers are the same either way). The verdict
  falls at the end of the wave that holds the group's last member: every
  member of a failed group is handed back unplaced, and what the members took
  is given back before the first pod of the next wave is scheduled; the pods
  behind the group IN its closing wave saw the tentative binds. For a group
  of at most ``waveWidth`` members that wave is its only one.

Departures from upstream, all the repo's own: the group rule is the program's
transaction in arrival order, not the coscheduling plugin's Permit wait with a
timeout and a retry (scheduler-plugins ``PodGroup`` ``minMember``, Volcano
``minAvailable``): a failed group is not tried again; waves of ``waveWidth``
are the program's batching and change no answer but where the rollback falls;
scores are float32 values cut by ``floor`` where upstream divides int64s; one
profile, no preemption (PostFilter off), no durations.

Requests and capacities are multiples of 0.25 cpu and 0.5 GiB and whole
devices, so a node's sums are exact in float32 (adding a bind and taking it
out again gives the sum back to the bit) and the fit test has no edge. The fit
score has: within ``EDGE`` of a whole number the program's float32 quotient may
fall on the other side of the ``floor`` (PERF.md §2, PR 24's rule): such a
node's score is an interval, and a choice is sound if some scores within the
intervals make it the pick.

``schedule`` runs the rule over a whole trace on one cluster (tests hold the
program to it pod for pod). ``check`` is teacher-forced: for (scenario, pod)
pairs drawn from the seed, the scenario's state at the start of the pod's wave
is rebuilt from the program's own answers, the wave's earlier slots are bound
as answered, every node of the scenario's table is scored, and the program's
node has to be the pick. The members of a group that was rolled back are in no
answer: where the pod's wave lies in or behind such a group, the state is
rebuilt from the start of the group's FIRST wave and the group's tentative
binds are the reference's own picks, member for member; a member's "unplaced"
is sound where the reference too finds a member of that group with no feasible
node. (Whether a member finds one does not hang on where the members before
it went: all of a job's workers ask the same GPU count, a node takes
floor(free / count) of them whatever the order, and cpu, memory and pods do
not run out. A rebuilt pick on a score edge is counted for the record.)

Every scenario gives its last pod and ``PER_STRATUM`` pods from each stratum:
members of wide groups in their first, a middle and their last wave, members
of rolled-back wide groups, GPU pods, the pod right after a rolled-back wide
group (it must see the usage given back), and ``PER_SCENARIO_ALL`` of all
pods; and ``PER_WHOLE_WAVE`` members of rolled-back wide groups from the
waves of the group that held no failure (``whole_waves_bound``): the binds
that only a transaction carried across waves gives back. Nothing is released,
so the extended resource runs out once, and only the few groups that arrive
while some of it is left and not enough bind a whole wave before they fail (72
such members of 32,768 pods in the deployment's scenario 0): drawn over all
pods alike a sample would hold none of them.

Rows over EVERY placement of every scenario, limit 0: no pod on a down or
injected-taint node; no node's use of a resource over its allocatable at the
end of the batch (nothing is released, so the end state is the sum of the
binds), the three core resources and the extended one apart; no pod group
partly bound; ``placed`` equals the placements handed back.

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place), ``unperturbed`` (every scenario judged on
the base table), ``no-gang`` (a member stands or falls alone) and
``wave-local-gang`` (a wide group judged wave by wave: only the members of a
wave in which one failed are rolled back), which is the program this
deployment must not be run by.
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from references import default_plugins_scenarios as plugins

PAD = -1
_edges, _bf16, judge, takes_pods = (
    plugins._edges, plugins._bf16, plugins.judge, plugins.takes_pods)
PER_STRATUM = 1
PER_SCENARIO_ALL = 2
PER_WHOLE_WAVE = 4
CORE = ("cpu", "mem", "pods")
RESOURCES = CORE + ("gpu",)
SCORED = ("cpu", "mem")
CONTROLS = (None, "bf16", "unperturbed", "no-gang", "wave-local-gang")
F = np.float32


def node_table(base: dict, sc: dict) -> dict:
    """The scenario's cluster: ``base`` (the trace's node table) under the
    perturbations ``sc``: a down node has allocatable 0 in every resource,
    a scaled node its cpu capacity times the factor (float32, as the cluster
    is; the extended resource is not scaled), a node with the injected taint
    is ``injected``."""
    nodes = {k: (v.copy() if isinstance(v, np.ndarray) else v)
             for k, v in base.items()}
    nodes["cpu"][sc["scaled"]] *= F(sc["factor"])
    for r in RESOURCES:
        nodes[r][sc["down"]] = 0.0
    nodes["injected"] = np.zeros(len(nodes["cpu"]), bool)
    nodes["injected"][sc["tainted"]] = True
    return nodes


def pack_waves(arrival, gang, width: int) -> np.ndarray:
    """[waves, width] pod ids in arrival order (stable), PAD-filled. A group
    of at most ``width`` is never split over waves; a wider one starts on a
    wave's first slot and fills consecutive waves."""
    order = np.argsort(arrival, kind="stable")
    members = {}
    for p in order[gang[order] != PAD]:
        members.setdefault(int(gang[p]), []).append(int(p))
    waves, current, consumed = [], [], set()
    for p in order.tolist():
        if p in consumed:
            continue
        g = int(gang[p])
        batch = [p] if g == PAD else members[g]
        if current and len(current) + len(batch) > width:
            waves.append(current)
            current = []
        while len(batch) > width:
            waves.append(batch[:width])
            batch = batch[width:]
        current = current + batch
        if g != PAD:
            consumed.update(members[g])
    if current:
        waves.append(current)
    idx = np.full((max(len(waves), 1), width), PAD, np.int64)
    for i, w in enumerate(waves):
        idx[i, :len(w)] = w
    return idx


def order_tried(pods: dict, width: int) -> dict:
    """What is static in a batch: the waves, each pod's wave and its place in
    the order tried; per group its size, first and closing wave and its last
    member's place in the order; which groups are wider than the wave."""
    idx = pack_waves(pods["arrival"], pods["gang"], width)
    flat = idx.reshape(-1)
    seq = flat[flat >= 0]
    slot = np.full(len(pods["arrival"]), -1, np.int64)
    slot[seq] = np.nonzero(flat >= 0)[0]
    wave = slot // width
    g = pods["gang"]
    in_gang = g != PAD
    G = int(g.max()) + 1 if in_gang.any() else 0
    size = np.bincount(g[in_gang], minlength=G)
    first = np.full(G, idx.shape[0], np.int64)
    last = np.full(G, -1, np.int64)
    last_rank = np.full(G, -1, np.int64)
    rank = np.empty(len(seq), np.int64)
    rank[seq] = np.arange(len(seq))
    np.minimum.at(first, g[in_gang], wave[in_gang])
    np.maximum.at(last, g[in_gang], wave[in_gang])
    np.maximum.at(last_rank, g[in_gang], rank[in_gang])
    return {"idx": idx, "seq": seq, "wave": wave, "width": width,
            "size": size, "first": first, "last": last, "wide": size > width,
            "last_rank": last_rank}


def request(pods: dict, k: int) -> dict:
    return {"cpu": F(pods["cpu"][k]), "mem": F(pods["mem"][k]), "pods": F(1),
            "gpu": F(pods["gpu"][k])}


def used_by(nodes: dict, pods: dict, bound, at) -> dict:
    """Usage per node of the pods ``bound`` on the nodes ``at``."""
    N = len(nodes["cpu"])
    return {
        "cpu": np.bincount(at, pods["cpu"][bound], N).astype(F),
        "mem": np.bincount(at, pods["mem"][bound], N).astype(F),
        "pods": np.bincount(at, minlength=N).astype(F),
        "gpu": np.bincount(at, pods["gpu"][bound], N).astype(F),
    }


def bind(used: dict, pods: dict, k: int, n: int, sign=1) -> None:
    for r, q in request(pods, k).items():
        used[r][n] += F(sign) * q


def pick(nodes, pods, used, k: int, weights: dict):
    """(score, lo, hi, ok): each node's score as the float32 chain gives it
    and as an interval, and the feasible nodes."""
    req = request(pods, k)
    ok = ~nodes["injected"]
    for r in RESOURCES:
        ok = ok & (used[r] + req[r] <= nodes[r])
    fit = lo = hi = 0.0
    for r in SCORED:
        alloc = nodes[r]
        frac = np.where(alloc > 0, (alloc - used[r] - req[r])
                        / np.where(alloc > 0, alloc, F(1)), F(0))
        x = np.clip(frac, F(0), F(1)) * F(100)
        f = np.floor(x)
        l, h = _edges(x, f)
        fit, lo, hi = fit + f, lo + l, hi + h
    w = weights["fit"]
    return (np.floor(fit / 2) * w, np.floor(lo / 2) * w, np.floor(hi / 2) * w,
            ok)


def pick_bf16(nodes, pods, used, k: int, weights: dict) -> int:
    """The node the same rule picks with every value and every operation in
    bfloat16 (numpy rounds each result to the array's type)."""
    b = _bf16
    req = {r: b(v) for r, v in request(pods, k).items()}
    alloc = {r: b(nodes[r]) for r in RESOURCES}
    use = {r: b(used[r]) for r in RESOURCES}
    ok = ~nodes["injected"]
    for r in RESOURCES:
        ok = ok & ((use[r] + req[r]) <= alloc[r])
    if not ok.any():
        return PAD
    total = b(np.zeros(len(ok)))
    for r in SCORED:
        safe = np.where(alloc[r] > 0, alloc[r], b(1))
        frac = np.where(alloc[r] > 0, (alloc[r] - use[r] - req[r]) / safe, b(0))
        total = total + np.floor(np.clip(frac, b(0), b(1)) * b(100))
    total = np.floor(total / b(2)) * b(weights["fit"])
    return int(np.argmax(np.where(ok, total.astype(F), -np.inf)))


def the_pick(score, lo, hi, ok):
    """(node, certain): the node the float32 chain picks (PAD = none
    feasible), and whether no score edge could make it another."""
    if not ok.any():
        return PAD, True
    n = int(np.argmax(np.where(ok, score, -np.inf)))
    rival = np.where(ok, hi, -np.inf)
    rival[n] = -np.inf
    certain = (float(rival[:n].max(initial=-np.inf)) < float(lo[n])
               and float(rival[n:].max(initial=-np.inf)) <= float(lo[n]))
    return n, bool(certain)


def schedule(nodes: dict, pods: dict, width: int, weights: dict,
             gang="carried", stats=None) -> np.ndarray:
    """[P] every pod's node (PAD = unschedulable or rolled back) on one
    cluster: the rule run over the whole trace, the float32 chain's scores as
    they come. ``gang``: ``"carried"`` (the rule), ``"wave-local"`` (a wide
    group judged wave by wave) or ``"none"`` (no rollback). ``stats``, a
    dict, takes the counts of the wide groups: ``rolled_back`` and
    ``rolled_back_after_a_bind`` (groups), ``binds_undone`` (pods) and
    ``undone_in_waves_without_a_failure`` (the pods among them that a rule
    judging a wide group wave by wave would have left bound)."""
    N = len(nodes["cpu"])
    order = order_tried(pods, width)
    used = {r: np.zeros(N, F) for r in RESOURCES}
    assign = np.full(len(pods["arrival"]), PAD, np.int64)
    g_all, wide = pods["gang"], order["wide"]
    txn, txn_failed = [], False  # the open wide group's members bound so far
    count = {"rolled_back": 0, "rolled_back_after_a_bind": 0, "binds_undone": 0,
             "undone_in_waves_without_a_failure": 0}

    def undo(members):
        for k in members:
            bind(used, pods, k, int(assign[k]), -1)
            assign[k] = PAD

    for w, wave in enumerate(order["idx"]):
        wave = wave[wave >= 0]
        for k in wave.tolist():
            score, _, _, ok = pick(nodes, pods, used, k, weights)
            if ok.any():
                assign[k] = int(np.argmax(np.where(ok, score, -np.inf)))
                bind(used, pods, k, int(assign[k]))
        if gang == "none":
            continue
        g = g_all[wave]
        carried = (g != PAD) & wide[np.clip(g, 0, None)] & (gang == "carried")
        local = (g != PAD) & ~carried
        failed = np.unique(g[local & (assign[wave] < 0)])
        undo(wave[local & np.isin(g, failed) & (assign[wave] >= 0)].tolist())
        if carried.any():
            mine = wave[carried]
            txn += mine[assign[mine] >= 0].tolist()
            txn_failed |= bool((assign[mine] < 0).any())
            if order["last"][g_all[mine[0]]] == w:
                if txn_failed:
                    count["rolled_back"] += 1
                    count["rolled_back_after_a_bind"] += bool(txn)
                    count["binds_undone"] += len(txn)
                    # every member after the first failed one fails too (a
                    # job's workers ask alike): its whole waves before that
                    count["undone_in_waves_without_a_failure"] += (
                        len(txn) // width * width)
                    undo(txn)
                txn, txn_failed = [], False
    if stats is not None:
        stats.update(count)
    return assign


def over_allocatable(nodes: dict, pods: dict, assign) -> tuple:
    """(core, extended): nodes whose use of cpu, memory or pods, and of the
    extended resource, is over their allocatable once every placement of the
    batch is bound (nothing is released)."""
    bound = np.nonzero(assign >= 0)[0]
    used = used_by(nodes, pods, bound, assign[bound])
    over = {r: used[r] > nodes[r] for r in RESOURCES}
    return (int(np.any([over[r] for r in CORE], axis=0).sum()),
            int(over["gpu"].sum()))


def groups_bound(pods: dict, assign) -> tuple:
    """(members, bound) per pod group: its size and how many are bound."""
    g = pods["gang"]
    in_gang = g != PAD
    members = np.bincount(g[in_gang])
    bound = np.bincount(g[in_gang & (assign >= 0)], minlength=len(members))
    return members, bound


def gangs_partly_bound(pods: dict, assign) -> int:
    """Pod groups with a member bound and a member not."""
    members, bound = groups_bound(pods, assign)
    return int(((bound > 0) & (bound < members)).sum())


def deployment_counts(nodes: dict, pods: dict, width: int, weights: dict) -> dict:
    """The counts the configuration's file records for scenario 0 (its (a) to
    (d)), from ``schedule`` on the unperturbed table."""
    order = order_tried(pods, width)
    stats = {}
    assign = schedule(nodes, pods, width, weights, stats=stats)
    g = pods["gang"]
    in_wide = (g != PAD) & order["wide"][np.clip(g, 0, None)]
    return {
        "pods": int(len(g)), "jobs": int((g == PAD).sum() + len(order["size"])),
        "waves": int(order["idx"].shape[0]),
        "wide_groups": int(order["wide"].sum()),
        "closing_waves": int(order["wide"].sum()),
        "pods_in_wide_groups": int(in_wide.sum()),
        "gpus_held": float(nodes["gpu"].sum()),
        "gpus_asked": float(pods["gpu"].sum()),
        "wide_rolled_back": stats["rolled_back"],
        "wide_rolled_back_after_a_bind": stats["rolled_back_after_a_bind"],
        "binds_undone": stats["binds_undone"],
        "undone_in_waves_without_a_failure":
            stats["undone_in_waves_without_a_failure"],
        "placed": int((assign >= 0).sum()),
    }


def whole_waves_bound(nodes: dict, pods: dict, order: dict, assign,
                      rolled_g) -> np.ndarray:
    """[P] bool: the members of the rolled-back wide groups that sit in a wave
    of their group in which no member failed. Only where to LOOK (a stratum of
    ``draw``); ``judge_at`` rebuilds such a group by its own picks whatever
    this says. A group's members ask the same GPU count and cpu, memory and
    pods do not run out, so on the state the answers give at the group's first
    wave (a rolled-back group's own binds are in no answer, and were given
    back) it binds ``sum(floor(free_gpu / count))`` members over the nodes
    that take pods before one fails, its first ``bound // width`` waves whole."""
    seq, g, width = order["seq"], pods["gang"], order["width"]
    rank = np.empty(len(g), np.int64)
    rank[seq] = np.arange(len(seq))
    out = np.zeros(len(g), bool)
    groups = np.nonzero(rolled_g & order["wide"])[0]
    # a wide group starts on its first wave's first slot
    firsts = rank[order["idx"][order["first"][groups], 0]]
    free = np.where(nodes["injected"], F(0), nodes["gpu"]).astype(np.float64)
    done = 0
    for q, at in sorted(zip(groups.tolist(), firsts.tolist()), key=lambda x: x[1]):
        ks = seq[done:at]
        ks = ks[assign[ks] >= 0]
        np.subtract.at(free, assign[ks], pods["gpu"][ks])
        done = at
        count = float(pods["gpu"][seq[at]])
        if count > 0:
            whole = int(np.floor(free / count).sum()) // width
            out[(g == q) & (order["wave"] - order["first"][q] < whole)] = True
    return out


def draw(rng, order: dict, pods: dict, assigns, samples: int,
         wholes) -> np.ndarray:
    """[n, 2] (scenario, pod) pairs, sorted, without repeats: every scenario's
    strata first (those of its own answers among them; ``wholes[s]`` is its
    ``whole_waves_bound``), then pairs over all scenarios alike up to
    ``samples``."""
    seq, g = order["seq"], pods["gang"]
    P, S = len(seq), len(assigns)
    gi = np.clip(g, 0, None)
    in_wide = (g != PAD) & order["wide"][gi]
    w, a, b = order["wave"], order["first"][gi], order["last"][gi]
    static = [np.nonzero(in_wide & (w == a))[0],
              np.nonzero(in_wide & (w > a) & (w < b))[0],
              np.nonzero(in_wide & (w == b))[0],
              np.nonzero(pods["gpu"] > 0)[0]]
    pairs = []
    for s in range(S):
        _, bound = groups_bound(pods, assigns[s])
        rolled = in_wide & (bound[gi] == 0)
        # the pod tried right after a rolled-back wide group's last member
        nxt = order["last_rank"][order["wide"] & (bound == 0)] + 1
        after = seq[nxt[nxt < P]]
        pools = static + [np.nonzero(rolled)[0], after[~rolled[after]]]
        ks = [rng.choice(pool, size=min(PER_STRATUM, len(pool)), replace=False)
              for pool in pools]
        whole = np.nonzero(wholes[s])[0]
        ks.append(rng.choice(whole, size=min(PER_WHOLE_WAVE, len(whole)),
                             replace=False))
        ks.append(rng.choice(P, size=min(PER_SCENARIO_ALL, P), replace=False))
        pairs += [(s, int(k)) for k in np.append(np.concatenate(ks), seq[-1])]
    rest = max(0, samples - len(pairs))
    flat = rng.choice(S * P, size=min(rest, S * P), replace=False)
    pairs += [(int(f // P), int(f % P)) for f in flat]
    return np.unique(np.asarray(pairs, np.int64), axis=0)


def judge_at(nodes, pods, order, assign, rolled_g, k: int, weights: dict,
             control=None, edges=None):
    """By how many points the answer for pod ``k`` falls short of the
    reference's pick on the state the answers give (0.0 = sound).

    ``rolled_g`` [G] marks the groups no member of which is bound in the
    answers. The replay starts at ``k``'s wave, or, where that wave lies in
    the span of a rolled-back WIDE group, at that group's first wave; the
    state there is every bind of the waves before it. From there on a slot
    whose answer is a node binds there, and the members of a rolled-back
    group bind where the reference picks (``edges`` counts such picks that
    lie on a score edge). ``k`` is judged at its slot: its node against the
    pick; if its group was rolled back, its "unplaced" is sound where the
    reference finds a member of the group with no feasible node (``no-gang``:
    where ``k`` itself has none; ``wave-local-gang``: where a member in
    ``k``'s own wave has none)."""
    g_all, wide, wave_of = pods["gang"], order["wide"], order["wave"]
    w = int(wave_of[k])
    mine = int(g_all[k])
    mine_rolled = mine != PAD and bool(rolled_g[mine])
    # a rolled-back wide group whose span holds k's wave: k's own, or the one
    # whose closing wave k stands in behind it
    start, stop = w, w
    for j in order["idx"][w]:
        gj = int(g_all[j]) if j >= 0 else PAD
        if gj != PAD and wide[gj] and rolled_g[gj]:
            start = int(order["first"][gj])
            if gj == mine:
                stop = int(order["last"][gj])
            break
    earlier = order["seq"][wave_of[order["seq"]] < start]
    earlier = earlier[assign[earlier] >= 0]
    used = used_by(nodes, pods, earlier, assign[earlier])
    local_only = control == "wave-local-gang" and mine_rolled and wide[mine]
    for v in range(start, stop + 1):
        row = order["idx"][v]
        row = row[row >= 0]
        rebuilt_here = []
        for j in row.tolist():
            gj = int(g_all[j])
            rebuilt = gj != PAD and bool(rolled_g[gj])
            if rebuilt or j == k:
                score, lo, hi, ok = pick(nodes, pods, used, j, weights)
            if j == k and not mine_rolled:
                choice = int(assign[k])
                if control == "bf16":
                    choice = pick_bf16(nodes, pods, used, k, weights)
                return judge(choice, lo, hi, ok)
            if rebuilt:
                n, certain = the_pick(score, lo, hi, ok)
                if edges is not None and not certain:
                    edges[0] += 1
                if j == k and control == "no-gang":
                    return 100.0 if n != PAD else 0.0
                if n == PAD and gj == mine and (not local_only or v == w):
                    return 0.0  # the group has a member that fits nowhere
                if n != PAD:
                    bind(used, pods, j, n)
                    rebuilt_here.append((j, n, gj))
            elif assign[j] >= 0:
                bind(used, pods, j, int(assign[j]))
        if local_only and v == w:
            return 100.0  # no member of k's own wave failed
        # a wave-local group rebuilt in this wave is gone from the next on
        for j, n, gj in rebuilt_here:
            if not wide[gj]:
                bind(used, pods, j, n, -1)
    # every member of k's group had a feasible node: nothing to roll back
    return 100.0


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit); ``limit`` None is printed for the record."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    base, pods = trace["nodes"], trace["tasks"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    assigns = np.asarray(answers["assignments"], np.int64)
    S, P = assigns.shape
    scen = whatif_scenarios.sample(config, len(base["cpu"]), S)
    order = order_tried(pods, config["engine"]["waveWidth"])
    tables = [node_table(base, sc) for sc in scen]
    wholes = [whole_waves_bound(tables[s], pods, order, assigns[s],
                                groups_bound(pods, assigns[s])[1] == 0)
              for s in range(S)]
    pairs = draw(np.random.default_rng(seed), order, pods, assigns, samples,
                 wholes)
    short = [[] for _ in range(S)]
    edges = [0]
    on_blocked = placed_off = over_core = over_ext = partly = 0
    wide_rolled = wide_members_judged = whole_judged = 0
    in_wide = (pods["gang"] != PAD) & order["wide"][np.clip(pods["gang"], 0, None)]
    for s in range(S):
        own = tables[s]
        assign = assigns[s]
        on_blocked += int((~takes_pods(own))[assign[assign >= 0]].sum())
        placed_off = max(placed_off, abs(int((assign >= 0).sum())
                                         - int(answers["placed"][s])))
        core, ext = over_allocatable(own, pods, assign)
        over_core, over_ext = over_core + core, over_ext + ext
        members, bound = groups_bound(pods, assign)
        partly += int(((bound > 0) & (bound < members)).sum())
        rolled_g = bound == 0
        wide_rolled += int((rolled_g & order["wide"]).sum())
        nodes = node_table(base, scen[0]) if control == "unperturbed" else own
        for k in pairs[pairs[:, 0] == s, 1].tolist():
            wide_members_judged += bool(in_wide[k])
            whole_judged += bool(wholes[s][k])
            short[s].append(judge_at(nodes, pods, order, assign, rolled_g, k,
                                     weights, control, edges))
    per = [np.asarray(x) for x in short]
    pooled = np.concatenate(per) if sum(map(len, per)) else np.asarray([100.0])
    worst = max((float((x > 0).mean()) if len(x) else 1.0) for x in per)
    return [
        ("ref.choices_not_the_references_share",
         float((pooled > 0).mean()), limits["choices_not_the_references_share"]),
        ("ref.choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min"] - len(pooled))), 0),
        ("ref.scenario_choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min_per_scenario"]
                   - min(map(len, per)))), 0),
        ("ref.placements_on_down_or_injected_taint_nodes", float(on_blocked), 0),
        ("ref.nodes_over_allocatable_cpu_memory_pods", float(over_core), 0),
        ("ref.nodes_over_allocatable_extended_resource", float(over_ext), 0),
        ("ref.pod_groups_partly_bound", float(partly), 0),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.wide_groups_rolled_back", float(wide_rolled), None),
        ("ref.wide_group_members_compared", float(wide_members_judged), None),
        ("ref.whole_wave_members_compared", float(whole_judged), None),
        ("ref.rebuilt_picks_on_a_score_edge", float(edges[0]), None),
    ]
