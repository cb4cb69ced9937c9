"""The plain reference for the multi-tenant accelerator deployment (extended
resources and pod groups), held against every scenario of a what-if batch on
that scenario's OWN cluster: numpy only, nothing of the program imported,
nothing the program made taken but its answers. The wave packing is
``greedy_waves``' and the judgement of one choice on score intervals
``default_plugins_scenarios``', both plain references of this benchmark.

The semantics checked (``guarantees`` in the configuration's file):
- Pods are tried once each, in arrival order (stable), in waves of
  ``waveWidth`` slots; a pod group (gang) is never split over waves, so a
  wave may close with empty slots. Each slot sees the binds of every slot
  before it, those of its own wave included. Nothing is released.
- Filter. NodeResourcesFit over ALL the configuration's resources: used +
  request <= allocatable for cpu, memory, pods and the extended resource
  ``google.com/tpu``; a node without the device plugin has 0 of it, so a pod
  that asks for any never fits there (upstream: extended resources are
  integers, never overcommitted). TaintToleration: a ``NoSchedule`` taint has
  to be tolerated; the cluster has none of its own (the source's accelerator
  nodes carry none), a scenario's injected taint is tolerated by no pod. A
  down node has allocatable 0 in every resource.
- Score, the default plugin set's as it comes out for this workload, integers:
  NodeResourcesFit LeastAllocated over cpu and memory only (upstream's default
  ``resources``; the extended resource is filtered on, never scored):
  ``floor((floor(100 * free_cpu / alloc_cpu) + floor(100 * free_mem /
  alloc_mem)) / 2)``, free after the pod, weight 1. TaintToleration counts
  untolerated ``PreferNoSchedule`` taints: there are none, so it is the same
  constant on every node; NodeAffinity, InterPodAffinity and
  PodTopologySpread have no term in any pod and give 0 everywhere. A constant
  moves no pick, so the total compared is the fit score. The feasible node of
  the highest score wins, the lowest index among equals; with no feasible
  node the pod is unschedulable and is not tried again.
- Pod groups: a gang with an unplaced member is rolled back whole at the end
  of its wave: every member is handed back unplaced and its binds are gone
  from the next wave on; the slots after it IN its wave saw them.

Departures from upstream, all the repo's own: the gang rule is the program's
wave-local all-or-none, not the coscheduling plugin's Permit wait with a
timeout and a retry (a rolled-back gang is not tried again); scores are
float32 values cut by ``floor`` where upstream divides int64s; one profile,
no preemption (PostFilter off, as the what-if engine runs).

Requests and capacities are multiples of 0.25 cpu and 0.5 GiB and whole
devices, so a node's sums are exact in float32 and the fit test has no edge.
The fit score has: ``100 * free / alloc`` is a float32 quotient and product,
and within ``EDGE`` of a whole number the program's may fall on the other side
of the ``floor`` (PERF.md §2, PR 24's rule): such a node's score is an
interval, and a choice is sound if some scores within the intervals make it
the pick.

``schedule`` runs the rule over a whole trace on one cluster (tests hold the
program to it pod for pod). ``check`` is teacher-forced, as a served model's
is: for (scenario, pod) pairs drawn from the seed, the scenario's state at the
start of the pod's wave is rebuilt from the program's own answers, the wave's
earlier slots are bound as answered, every node of the scenario's table is
scored, and the program's node has to be the pick. The members of a gang that
was rolled back are in no answer: its tentative binds, which the later slots
of its wave saw, are rebuilt by the reference's own picks, and a member's
"unplaced" is sound where the reference too finds a member of that gang with
no feasible node. Only where such a rebuilt pick lies on a score edge (two
nodes could be it) is the sample left out, counted beside a limit. Every
scenario gives its last pod and ``PER_STRATUM`` pods from each stratum (pods
asking for the extended resource, gang members, all pods), so that a fault
that strikes only accelerator pods or only gangs is not diluted.

Rows over EVERY placement of every scenario, limit 0: no pod on a down or
injected-taint node; no node's use of a resource over its allocatable at the
end of the batch (nothing is released, so the end state is the sum of the
binds), the three core resources and the extended one apart; no pod group
partly bound; ``placed`` equals the placements handed back.

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place), ``unperturbed`` (every scenario judged on
the base table), ``no-extended`` (the reference's fit without the
``google.com/tpu`` row) and ``no-gang`` (the reference without the rollback: a
member stands or falls alone): the last two show that the check sees each
mechanism this deployment adds.
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from references import default_plugins_scenarios as plugins
from references.greedy_waves import pack_waves  # a gang is never split over waves

PAD = -1
# What the default-plugin-set reference already holds and this one shares:
# a fit score within its EDGE of a whole number as an interval, the nodes that
# take a pod at all, the bfloat16 cast, and the judgement of one choice.
_edges, _bf16, judge, takes_pods = (
    plugins._edges, plugins._bf16, plugins.judge, plugins.takes_pods)
PER_STRATUM = 1  # pairs a scenario from each of: accelerator pods, gang members
PER_SCENARIO_ALL = 2  # and from all pods, beside the scenario's last pod
CORE = ("cpu", "mem", "pods")
RESOURCES = CORE + ("tpu",)
SCORED = ("cpu", "mem")
CONTROLS = (None, "bf16", "unperturbed", "no-extended", "no-gang")
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


def order_tried(pods: dict, width: int) -> dict:
    """What is static in a batch: the waves, each pod's wave and its place in
    the order tried."""
    idx = pack_waves(pods["arrival"], pods["gang"], width)
    flat = idx.reshape(-1)
    seq = flat[flat >= 0]
    slot = np.full(len(pods["arrival"]), -1, np.int64)
    slot[seq] = np.nonzero(flat >= 0)[0]
    return {"idx": idx, "seq": seq, "wave": slot // width}


def request(pods: dict, k: int) -> dict:
    return {"cpu": F(pods["cpu"][k]), "mem": F(pods["mem"][k]), "pods": F(1),
            "tpu": F(pods["tpu"][k])}


def used_by(nodes: dict, pods: dict, bound, at) -> dict:
    """Usage per node of the pods ``bound`` on the nodes ``at``."""
    N = len(nodes["cpu"])
    return {
        "cpu": np.bincount(at, pods["cpu"][bound], N).astype(F),
        "mem": np.bincount(at, pods["mem"][bound], N).astype(F),
        "pods": np.bincount(at, minlength=N).astype(F),
        "tpu": np.bincount(at, pods["tpu"][bound], N).astype(F),
    }


def bind(used: dict, pods: dict, k: int, n: int) -> None:
    for r, q in request(pods, k).items():
        used[r][n] += q


def pick(nodes, pods, used, k: int, weights: dict, extended=True):
    """(score, lo, hi, ok): each node's score as the float32 chain gives it
    and as an interval, and the feasible nodes. ``extended`` False is the
    control that leaves the extended resource out of the fit."""
    req = request(pods, k)
    ok = ~nodes["injected"]
    for r in (RESOURCES if extended else CORE):
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
             extended=True, gang=True) -> np.ndarray:
    """[P] every pod's node (PAD = unschedulable or rolled back) on one
    cluster: the rule run over the whole trace, the float32 chain's scores as
    they come. ``extended`` and ``gang`` False leave a mechanism out."""
    N = len(nodes["cpu"])
    used = {r: np.zeros(N, F) for r in RESOURCES}
    assign = np.full(len(pods["arrival"]), PAD, np.int64)
    for wave in order_tried(pods, width)["idx"]:
        wave = wave[wave >= 0]
        for k in wave.tolist():
            score, _, _, ok = pick(nodes, pods, used, k, weights, extended)
            if ok.any():
                assign[k] = int(np.argmax(np.where(ok, score, -np.inf)))
                bind(used, pods, k, int(assign[k]))
        if not gang:
            continue
        g = pods["gang"][wave]
        failed = np.unique(g[(g != PAD) & (assign[wave] < 0)])
        for k in wave[np.isin(g, failed) & (assign[wave] >= 0)].tolist():
            for r, q in request(pods, k).items():
                used[r][assign[k]] -= q
            assign[k] = PAD
    return assign


def over_allocatable(nodes: dict, pods: dict, assign) -> tuple:
    """(core, extended): nodes whose use of cpu, memory or pods, and of the
    extended resource, is over their allocatable once every placement of the
    batch is bound (nothing is released)."""
    bound = np.nonzero(assign >= 0)[0]
    used = used_by(nodes, pods, bound, assign[bound])
    over = {r: used[r] > nodes[r] for r in RESOURCES}
    return (int(np.any([over[r] for r in CORE], axis=0).sum()),
            int(over["tpu"].sum()))


def gangs_partly_bound(pods: dict, assign) -> int:
    """Pod groups with a member bound and a member not."""
    g = pods["gang"]
    in_gang = g != PAD
    members = np.bincount(g[in_gang])
    bound = np.bincount(g[in_gang & (assign >= 0)], minlength=len(members))
    return int(((bound > 0) & (bound < members)).sum())


def draw(rng, scenarios: int, seq, samples: int, pods: dict) -> np.ndarray:
    """[n, 2] (scenario, pod) pairs, sorted, without repeats: every scenario's
    strata first, then pairs over all scenarios alike up to ``samples``."""
    P = len(seq)
    strata = [np.nonzero(pods["tpu"] > 0)[0], np.nonzero(pods["gang"] != PAD)[0]]
    pairs = []
    for s in range(scenarios):
        ks = [rng.choice(pool, size=min(PER_STRATUM, len(pool)), replace=False)
              for pool in strata]
        ks.append(rng.choice(P, size=min(PER_SCENARIO_ALL, P), replace=False))
        pairs += [(s, int(k)) for k in np.append(np.concatenate(ks), seq[-1])]
    rest = max(0, samples - len(pairs))
    flat = rng.choice(scenarios * P, size=min(rest, scenarios * P), replace=False)
    pairs += [(int(f // P), int(f % P)) for f in flat]
    return np.unique(np.asarray(pairs, np.int64), axis=0)


def judge_in_wave(nodes, pods, order, assign, k: int, weights: dict,
                  control=None):
    """By how many points the answer for pod ``k`` falls short of the
    reference's pick on the state the answers give (0.0 = sound), or None
    where a rolled-back gang's rebuilt pick before it lies on a score edge.

    The state at the start of ``k``'s wave is every bind of the waves before
    it. Through the wave, a slot whose answer is a node binds there; the
    members of a gang that was rolled back (all handed back unplaced) bind
    where the reference picks. ``k`` itself is judged at its slot: its node
    against the pick; if its gang was rolled back, its "unplaced" is sound
    where the reference finds a member of the gang without a feasible node
    (``no-gang``: where ``k`` itself has none)."""
    extended = control != "no-extended"
    w = int(order["wave"][k])
    wave = order["idx"][w]
    wave = wave[wave >= 0]
    earlier = order["seq"][order["wave"][order["seq"]] < w]
    earlier = earlier[assign[earlier] >= 0]
    used = used_by(nodes, pods, earlier, assign[earlier])
    g = pods["gang"][wave]
    rolled = np.unique(g[(g != PAD) & (assign[wave] < 0)])
    mine = int(pods["gang"][k])
    mine_rolled = mine != PAD and mine in rolled
    # a rolled-back member is judged once its whole gang has been tried
    upto = wave[pods["gang"][wave] == mine][-1] if mine_rolled else k
    for j in wave.tolist():
        rebuilt = int(pods["gang"][j]) in rolled
        if rebuilt or j == k:
            score, lo, hi, ok = pick(nodes, pods, used, j, weights, extended)
        if j == k and not mine_rolled:
            choice = int(assign[k])
            if control == "bf16":
                choice = pick_bf16(nodes, pods, used, k, weights)
            return judge(choice, lo, hi, ok)
        if rebuilt:
            n, certain = the_pick(score, lo, hi, ok)
            if j == k and control == "no-gang":
                return 100.0 if n != PAD else 0.0
            if n == PAD and int(pods["gang"][j]) == mine:
                return 0.0  # the gang has a member that fits nowhere
            if not certain:
                return None
            if n != PAD:
                bind(used, pods, j, n)
        elif assign[j] >= 0:
            bind(used, pods, j, int(assign[j]))
        if j == upto:
            break
    # every member of k's gang had a feasible node: nothing to roll back
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
    pairs = draw(np.random.default_rng(seed), S, order["seq"], samples, pods)
    short = [[] for _ in range(S)]
    left_out = on_blocked = placed_off = over_core = over_ext = partly = 0
    for s in range(S):
        own = node_table(base, scen[s])
        assign = assigns[s]
        on_blocked += int((~takes_pods(own))[assign[assign >= 0]].sum())
        placed_off = max(placed_off, abs(int((assign >= 0).sum())
                                         - int(answers["placed"][s])))
        core, ext = over_allocatable(own, pods, assign)
        over_core, over_ext = over_core + core, over_ext + ext
        partly += gangs_partly_bound(pods, assign)
        nodes = node_table(base, scen[0]) if control == "unperturbed" else own
        for k in pairs[pairs[:, 0] == s, 1].tolist():
            got = judge_in_wave(nodes, pods, order, assign, k, weights, control)
            if got is None:
                left_out += 1
            else:
                short[s].append(got)
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
        ("ref.samples_left_out_share", left_out / max(len(pairs), 1),
         limits["samples_left_out_share"]),
        ("ref.placements_on_down_or_injected_taint_nodes", float(on_blocked), 0),
        ("ref.nodes_over_allocatable_cpu_memory_pods", float(over_core), 0),
        ("ref.nodes_over_allocatable_extended_resource", float(over_ext), 0),
        ("ref.pod_groups_partly_bound", float(partly), 0),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_behind_a_rebuilt_gang_on_an_edge", float(left_out), None),
    ]
