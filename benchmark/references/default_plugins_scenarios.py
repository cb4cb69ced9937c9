"""The plain reference for the default plugin set, held against every scenario
of a what-if batch on that scenario's OWN cluster: numpy only, nothing of the
program imported, nothing the program made taken but its answers.

The semantics checked are upstream kube-scheduler's for one scheduling cycle,
as the repo's CPU plugins have them (``plugins/builtin.py``): pods are tried
once each, in arrival order (stable); each sees the binds of every pod before
it (the program's waves of ``waveWidth`` slots do not show: a slot sees the
slots before it); nothing is released (no durations). A pod goes to the
feasible node of the highest weighted score, the lowest index among equals;
with no feasible node it is unschedulable and stays so.

Filter:
- NodeResourcesFit: used + request <= allocatable for cpu, memory and pods.
- TaintToleration: a ``NoSchedule`` taint has to be tolerated (the cluster's
  ``dedicated=batch`` by the pods that tolerate it, a scenario's injected
  taint by none).
- InterPodAffinity: a required affinity term (own app, by zone) needs a
  matching pod in the node's zone, or, upstream's first-pod exception, none
  anywhere while the pod matches its own term; a required anti-affinity term
  (own app's leaders, by hostname) needs no matching pod on the node; and
  the symmetric check: a pod that matches the anti-affinity term of a pod
  already bound to the node is refused there.
- PodTopologySpread ``DoNotSchedule``: zone count + 1 (the pod matches its
  own selector) - the least count over the zones <= ``maxSkew``.

Score, each plugin normalised to 0-100 over the feasible nodes, times its
weight, integers throughout:
- NodeResourcesFit LeastAllocated: ``floor((floor(100 * free_cpu / alloc_cpu)
  + floor(100 * free_mem / alloc_mem)) / 2)``, free after the pod.
- TaintToleration: 100 less the normalised count of untolerated
  ``PreferNoSchedule`` taints; the deployment has none, so 100 everywhere.
- NodeAffinity: the preferred term's weight on ``tier=hot`` nodes,
  ``floor(100 * raw / max)``.
- InterPodAffinity: preferred terms of the pod and, symmetrically, of bound
  pods, min-max normalised; the deployment has none, so 0 everywhere.
- PodTopologySpread ``ScheduleAnyway``: ``floor(count_in_zone * log(zones +
  2) + maxSkew - 1 + 0.5)`` turned round by ``100 * (max + min - raw) // max``.

Departures from upstream, all the repo's own (``ops/cpu.py``): the spread
weight ``log(zones + 2)`` takes the cluster's zone count, not the count among
the filtered nodes; the ``DoNotSchedule`` minimum runs over every zone of the
cluster, not only zones with a node the pod's node affinity and tolerations
admit; scores are float32 values cut by ``floor`` where upstream divides
int64s; one profile, one namespace, no ``minDomains``, no ``matchLabelKeys``,
no preemption (PostFilter off, as the what-if engine runs).

Requests and capacities are multiples of 0.25 cpu and 0.5 GiB, so a node's
sums are exact in float32 and the fit test has no edge. The fit score has:
``100 * free / alloc`` is a float32 quotient and product, and within ``EDGE``
of a whole number the program's may fall on the other side of the ``floor``
(PERF.md §2, PR 24's rule): such a node's score is an interval, and a choice
is sound if some scores within the intervals make it the pick. A spread score
on such an edge leaves the sample out, counted.

``schedule`` runs the rule over a whole trace on one cluster (tests hold it to
the program's CPU event engine pod for pod). ``check`` is teacher-forced, as a
served model's is: for ``samples`` (scenario, pod) pairs drawn from the seed,
the scenario's state just before the pod is rebuilt from the program's own
answers (usage, and the match counts of the pod's selectors by zone and by
node), every node of the scenario's table is scored, and the program's node
has to be the pick; a pod the program calls unschedulable must have no
feasible node. Every scenario gives ``PER_SCENARIO`` pairs and its last pod:
``PER_STRATUM`` from each stratum of pods whose cycle runs a term (required
zone affinity, hostname anti-affinity, leaders, which the symmetric check
guards, and ``DoNotSchedule`` spread) and the rest from all pods, so that a
fault that strikes only pods under a term is not diluted by the three
quarters that have none.

Three rows run over EVERY placement of every scenario, limit 0 (``terms_broken``):
no pod with the hostname term shares its node with another leader of its app;
a pod with the zone affinity term found a pod of its app in its zone when it
was bound, or none anywhere; a ``DoNotSchedule`` pod's zone count, itself
included, was within ``maxSkew`` of the least zone's when it was bound.

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place), ``unperturbed`` (every scenario judged on
the base table), ``no-interpod`` (InterPodAffinity left out of the
reference's filter) and ``no-spread`` (the ``DoNotSchedule`` filter left out
of it): the last two show that the check sees each new mechanism.
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios

PAD = -1
EDGE = 1e-3  # in score points
PER_SCENARIO = 16
PER_STRATUM = 2
NONE, AFFINITY, ANTI, NODE_PREF = 0, 1, 2, 3  # a pod's ``kind``
RESOURCES = ("cpu", "mem", "pods")
CONTROLS = (None, "bf16", "unperturbed", "no-interpod", "no-spread")
F = np.float32


def node_table(base: dict, sc: dict) -> dict:
    """The scenario's cluster: ``base`` (the trace's node table) under the
    perturbations ``sc``: a down node has allocatable 0 in every resource, a
    scaled node its cpu capacity times the factor (float32, as the cluster
    is), a node with the injected taint is ``injected``."""
    nodes = {k: (v.copy() if isinstance(v, np.ndarray) else v)
             for k, v in base.items()}
    nodes["cpu"][sc["scaled"]] *= F(sc["factor"])
    for r in RESOURCES:
        nodes[r][sc["down"]] = 0.0
    nodes["injected"] = np.zeros(len(nodes["cpu"]), bool)
    nodes["injected"][sc["tainted"]] = True
    return nodes


def takes_pods(nodes: dict) -> np.ndarray:
    """[N] bool: nodes that can take a pod at all in this scenario."""
    return (nodes["pods"] > 0) & ~nodes["injected"]


class State:
    """What a pod's cycle reads of the cluster: usage per node, and for the
    pod's own app the pods of it by zone, its leaders by node and its pods
    holding the anti-affinity term by node."""

    def __init__(self, nodes: dict, apps: int):
        N, Z = len(nodes["cpu"]), nodes["zones"]
        self.zone = nodes["zone"]
        self.used = {r: np.zeros(N, F) for r in RESOURCES}
        self.in_zone = np.zeros((apps, Z), np.int64)
        self.leaders_on = np.zeros((apps, N), np.int64)
        self.anti_on = np.zeros((apps, N), np.int64)

    @classmethod
    def before(cls, nodes: dict, pods: dict, rank, assign, k: int) -> "State":
        """The state just before pod ``k``, from the answers ``assign`` for
        the pods tried before it; only ``k``'s own app is counted."""
        st = cls(nodes, 1)
        N = len(nodes["cpu"])
        b = np.nonzero((rank < rank[k]) & (assign >= 0))[0]
        at = assign[b]
        st.used = {
            "cpu": np.bincount(at, pods["cpu"][b], N).astype(F),
            "mem": np.bincount(at, pods["mem"][b], N).astype(F),
            "pods": np.bincount(at, minlength=N).astype(F),
        }
        own = pods["app"][b] == pods["app"][k]
        st.in_zone[0] = np.bincount(st.zone[at[own]], minlength=nodes["zones"])
        st.leaders_on[0] = np.bincount(at[own & pods["leader"][b]], minlength=N)
        st.anti_on[0] = np.bincount(at[own & (pods["kind"][b] == ANTI)],
                                    minlength=N)
        return st

    def bind(self, pods: dict, k: int, n: int) -> None:
        a = int(pods["app"][k])
        self.used["cpu"][n] += pods["cpu"][k]
        self.used["mem"][n] += pods["mem"][k]
        self.used["pods"][n] += F(1)
        self.in_zone[a, self.zone[n]] += 1
        self.leaders_on[a, n] += bool(pods["leader"][k])
        self.anti_on[a, n] += pods["kind"][k] == ANTI


def request(pods: dict, k: int) -> dict:
    return {"cpu": F(pods["cpu"][k]), "mem": F(pods["mem"][k]), "pods": F(1)}


def feasible_nodes(nodes, pods, st, a: int, k: int, interpod=True,
                   spread=True) -> np.ndarray:
    """[N] bool: the Filter chain. ``a`` is the row of the pod's app in the
    state's count tables. ``interpod`` and ``spread`` False are the controls
    that leave a filter out."""
    req = request(pods, k)
    ok = ~nodes["injected"] & (~nodes["tainted"] | bool(pods["tolerates"][k]))
    for r in RESOURCES:
        ok &= st.used[r] + req[r] <= nodes[r]
    in_zone = st.in_zone[a]
    if interpod:
        if pods["kind"][k] == AFFINITY and in_zone.sum() > 0:
            ok &= in_zone[st.zone] >= 1
        if pods["kind"][k] == ANTI:
            ok &= st.leaders_on[a] == 0
        if pods["leader"][k]:
            ok &= st.anti_on[a] == 0
    if spread and pods["spread_skew"][k] and pods["spread_dns"][k]:
        ok &= in_zone[st.zone] + 1 - in_zone.min() <= pods["spread_skew"][k]
    return ok


def _edges(x, f):
    """floor(x) = f as an interval: x within EDGE of a whole number may have
    been cut to either side of it."""
    lo = np.where(x - f < EDGE, f - 1, f)
    hi = np.where(f + 1 - x < EDGE, f + 1, f)
    return np.maximum(lo, 0), np.minimum(hi, 100)


def pick(nodes, pods, st, a: int, k: int, weights: dict, interpod=True,
         spread=True):
    """(score, lo, hi, ok): each node's total score as the float32 chain
    gives it and as an interval, and the feasible nodes. ``lo`` is None where
    the spread score itself is on an edge."""
    ok = feasible_nodes(nodes, pods, st, a, k, interpod, spread)
    req = request(pods, k)
    fit = lo = hi = 0.0
    for r in ("cpu", "mem"):
        alloc = nodes[r]
        frac = np.where(alloc > 0, (alloc - st.used[r] - req[r])
                        / np.where(alloc > 0, alloc, F(1)), F(0))
        x = np.clip(frac, F(0), F(1)) * F(100)
        f = np.floor(x)
        l, h = _edges(x, f)
        fit, lo, hi = fit + f, lo + l, hi + h
    score = np.floor(fit / 2) * weights["fit"]
    lo, hi = np.floor(lo / 2) * weights["fit"], np.floor(hi / 2) * weights["fit"]
    # TaintToleration: no PreferNoSchedule taint anywhere, raw 0, reversed
    plus = np.full(len(ok), 100.0 * weights["taint"])
    # InterPodAffinity: no preferred term anywhere, raw 0, min-max gives 0
    plus += 0.0 * weights["interPodAffinity"]
    if pods["kind"][k] == NODE_PREF:
        raw = np.where(nodes["hot"], F(pods["na_weight"][k]), F(0))
        top = raw[ok].max(initial=0.0)
        if top > 0:
            plus += weights["nodeAffinity"] * np.floor(raw * F(100) / top)
    if pods["spread_skew"][k] and not pods["spread_dns"][k]:
        x = (st.in_zone[a].astype(F) * F(np.log(nodes["zones"] + 2.0))
             + F(pods["spread_skew"][k] - 1) + F(0.5))
        raw = np.floor(x).astype(np.int64)
        if np.any((x - raw < EDGE) | (raw + 1 - x < EDGE)):
            lo = None
        zones = np.unique(st.zone[ok])
        if zones.size:
            top, low = raw[zones].max(), raw[zones].min()
            by_zone = (100 * (top + low - raw)) // top if top > 0 else raw * 0 + 100
            plus += weights["spread"] * by_zone[st.zone]
    if lo is None:
        return score + plus, None, None, ok
    return score + plus, lo + plus, hi + plus, ok


def _bf16(a):
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16)


def pick_bf16(nodes, pods, st, a: int, k: int, weights: dict) -> int:
    """The node the same rule picks with every value and every operation in
    bfloat16 (numpy rounds each result to the array's type)."""
    b = _bf16
    req = {r: b(v) for r, v in request(pods, k).items()}
    alloc = {r: b(nodes[r]) for r in RESOURCES}
    used = {r: b(st.used[r]) for r in RESOURCES}
    in_zone = b(st.in_zone[a])
    ok = ~nodes["injected"] & (~nodes["tainted"] | bool(pods["tolerates"][k]))
    for r in RESOURCES:
        ok &= (used[r] + req[r]) <= alloc[r]
    if pods["kind"][k] == AFFINITY and in_zone.sum() > 0:
        ok &= in_zone[st.zone] >= 1
    if pods["kind"][k] == ANTI:
        ok &= st.leaders_on[a] == 0
    if pods["leader"][k]:
        ok &= st.anti_on[a] == 0
    if pods["spread_skew"][k] and pods["spread_dns"][k]:
        ok &= (in_zone[st.zone] + b(1) - in_zone.min()
               <= b(pods["spread_skew"][k]))
    if not ok.any():
        return PAD
    total = b(np.zeros(len(ok)))
    for r in ("cpu", "mem"):
        safe = np.where(alloc[r] > 0, alloc[r], b(1))
        frac = np.where(alloc[r] > 0, (alloc[r] - used[r] - req[r]) / safe, b(0))
        total = total + np.floor(np.clip(frac, b(0), b(1)) * b(100))
    total = np.floor(total / b(2)) * b(weights["fit"])
    if pods["kind"][k] == NODE_PREF:
        raw = np.where(nodes["hot"], b(pods["na_weight"][k]), b(0))
        top = raw[ok].max()
        if top > 0:
            total = total + b(weights["nodeAffinity"]) * np.floor(raw * b(100) / top)
    if pods["spread_skew"][k] and not pods["spread_dns"][k]:
        raw = np.floor(in_zone * b(np.log(nodes["zones"] + 2.0))
                       + b(pods["spread_skew"][k] - 1) + b(0.5))
        zones = np.unique(st.zone[ok])
        top, low = raw[zones].max(), raw[zones].min()
        by_zone = (np.floor(b(100) * (top + low - raw) / top) if top > 0
                   else raw * b(0) + b(100))
        total = total + b(weights["spread"]) * by_zone[st.zone]
    return int(np.argmax(np.where(ok, total.astype(F), -np.inf)))


def judge(choice: int, lo, hi, ok) -> float:
    """0.0 where ``choice`` can be the pick, else by how many score points
    it falls short (100.0 for an infeasible node, or for none where one is
    feasible)."""
    if choice == PAD:
        return 100.0 if ok.any() else 0.0
    if choice < 0 or not ok[choice]:
        return 100.0
    # Every other node at its lowest, the choice at its highest: it has to
    # beat the nodes before it and at least equal those after.
    rival = np.where(ok, lo, -np.inf)
    rival[choice] = -np.inf
    short = max(float(rival[:choice].max(initial=-np.inf)) + 1.0,
                float(rival[choice:].max(initial=-np.inf))) - float(hi[choice])
    return max(short, 0.0)


def order_tried(pods: dict):
    """(seq, rank): the pods in the order they are tried, and each pod's
    place in it."""
    seq = np.argsort(pods["arrival"], kind="stable")
    rank = np.empty(len(seq), np.int64)
    rank[seq] = np.arange(len(seq))
    return seq, rank


def schedule(nodes: dict, pods: dict, weights: dict, interpod=True,
             spread=True) -> np.ndarray:
    """[P] every pod's node (PAD = unschedulable) on one cluster: the rule
    run over the whole trace, the float32 chain's scores as they come."""
    apps = int(pods["app"].max()) + 1
    st = State(nodes, apps)
    assign = np.full(len(pods["arrival"]), PAD, np.int64)
    for k in order_tried(pods)[0].tolist():
        score, _, _, ok = pick(nodes, pods, st, int(pods["app"][k]), k,
                               weights, interpod, spread)
        if ok.any():
            assign[k] = int(np.argmax(np.where(ok, score, -np.inf)))
            st.bind(pods, k, int(assign[k]))
    return assign


def terms_broken(nodes: dict, pods: dict, seq, assign) -> tuple:
    """(hostname anti-affinity, zone affinity, DoNotSchedule skew): the pods
    of one scenario placed against their own required term, over every
    placement. Nothing is released, so the first is read off the final
    placements: a pod with the hostname term on a node with another leader
    of its app, whichever came first. The other two hold when the pod is
    bound: they take the count of its app's pods by zone among the pods
    tried before it."""
    N, Z = len(nodes["cpu"]), nodes["zones"]
    apps = int(pods["app"].max()) + 1
    on = assign >= 0
    cell = pods["app"] * N + np.clip(assign, 0, None)
    leaders = np.bincount(cell[on & pods["leader"]], minlength=apps * N)
    anti = on & (pods["kind"] == ANTI)
    hostname = int((leaders[cell[anti]] - pods["leader"][anti] > 0).sum())

    k = seq[on[seq]]  # the placed pods, in the order tried
    z = nodes["zone"][assign[k]]
    bound = np.zeros((len(k), Z), np.int64)
    bound[np.arange(len(k)), z] = 1
    before = np.zeros_like(bound)
    for a in range(apps):
        i = np.nonzero(pods["app"][k] == a)[0]
        before[i] = np.cumsum(bound[i], axis=0) - bound[i]
    here = before[np.arange(len(k)), z]
    lonely = (pods["kind"][k] == AFFINITY) & (here == 0) & (before.sum(1) > 0)
    skew = pods["spread_skew"][k]
    uneven = ((skew > 0) & pods["spread_dns"][k]
              & (here + 1 - before.min(1) > skew))
    return hostname, int(lonely.sum()), int(uneven.sum())


def draw(rng, scenarios: int, seq, samples: int, pods: dict) -> np.ndarray:
    """[n, 2] (scenario, pod) pairs, sorted, without repeats."""
    P = len(seq)
    per = min(PER_SCENARIO, P)
    dns = (pods["spread_skew"] > 0) & pods["spread_dns"]
    strata = [np.nonzero(m)[0] for m in (
        pods["kind"] == AFFINITY, pods["kind"] == ANTI, pods["leader"], dns)]
    pairs = []
    for s in range(scenarios):
        ks = [rng.choice(pool, size=min(PER_STRATUM, len(pool)), replace=False)
              for pool in strata]
        ks.append(rng.choice(P, size=max(per - sum(map(len, ks)), 0),
                             replace=False))
        pairs += [(s, int(k)) for k in np.append(np.concatenate(ks), seq[-1])]
    rest = max(0, samples - len(pairs))
    flat = rng.choice(scenarios * P, size=min(rest, scenarios * P), replace=False)
    pairs += [(int(f // P), int(f % P)) for f in flat]
    return np.unique(np.asarray(pairs, np.int64), axis=0)


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
    seq, rank = order_tried(pods)
    pairs = draw(np.random.default_rng(seed), S, seq, samples, pods)
    short = [[] for _ in range(S)]
    edge = on_blocked = placed_off = 0
    broken = np.zeros(3, np.int64)
    for s in range(S):
        own = node_table(base, scen[s])
        assign = assigns[s]
        on_blocked += int((~takes_pods(own))[assign[assign >= 0]].sum())
        placed_off = max(placed_off, abs(int((assign >= 0).sum())
                                         - int(answers["placed"][s])))
        broken += terms_broken(own, pods, seq, assign)
        nodes = node_table(base, scen[0]) if control == "unperturbed" else own
        for k in pairs[pairs[:, 0] == s, 1].tolist():
            st = State.before(nodes, pods, rank, assign, k)
            _, lo, hi, ok = pick(nodes, pods, st, 0, k, weights,
                                 interpod=control != "no-interpod",
                                 spread=control != "no-spread")
            if lo is None:
                edge += 1
                continue
            choice = int(assign[k])
            if control == "bf16":
                choice = pick_bf16(nodes, pods, st, 0, k, weights)
            short[s].append(judge(choice, lo, hi, ok))
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
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.anti_affinity_terms_broken", float(broken[0]), 0),
        ("ref.zone_affinity_terms_broken", float(broken[1]), 0),
        ("ref.spread_skew_terms_broken", float(broken[2]), 0),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_on_a_spread_score_edge", float(edge), None),
    ]
