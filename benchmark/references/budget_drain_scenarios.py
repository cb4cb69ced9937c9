"""The plain reference for a full Borg cell under a maintenance drain that
respects disruption budgets: ``references/drain_scenarios.py``'s cell (a
resident set, a standing queue re-tried at every chunk boundary, nodes that
leave and come back at boundaries) in which a node is CORDONED first, its
tasks leave only as fast as each application's budget allows, what is left is
forced out at the drain's deadline, and machine failures spend the same
budgets. Numpy only, nothing of the program imported, nothing the program made
taken but its four answers, per plan: every task's node at the end
(``assignments``), the boundary of its LAST bind (``bind_boundary``, the drain
reference's codes), the ``eviction_log`` (boundary, task, the node it held, the
boundary that had bound it or -1, the KIND: 0 voluntary, 1 forced at a
deadline, 2 forced by a failure) and ``node_out_at`` (the boundary a cordoned
node went out, -1 never). Over ``drain_scenarios`` (``History``, ``in_state``,
``State``, ``passed_over``, ``order_last``, ``over_allocatable``),
``backlog_scenarios`` (``schedule``), ``greedy_waves`` and
``greedy_waves_scenarios``; the plans are ``budget_plans``'.

THE RULE, per plan. Applications are the trace's ``app_id``; ``expected_a``
its residents and arriving tasks in the trace (static); ``maxUnavailable_a =
max(1, floor(share x expected_a))``; ``down_a`` the tasks of ``a`` evicted so
far, voluntarily or forced, and not re-bound since (a dropped or stranded
evicted task stays counted: its replacement is Pending). At boundary ``b``,
BEFORE its static releases and its retry pass, in this order:

1. back: a node whose outage ends at ``b`` (a ``node_up``, or ``outFor``
   boundaries after its drain took it out) is in service again, empty, and no
   longer cordoned;
2. forced: a node that fails at ``b`` (the plan's storm; timeline order), then
   a cordoned node at its deadline (the first boundary after its cordon that
   is ``grace`` or more past it; walk order), loses every live bind whatever
   the budgets say; each eviction raises ``down_a``. The failed node waits for
   its ``node_up``, the other is out for ``outFor``;
3. cordon: the plan's next ``step`` nodes take no bind from this boundary's
   pass on; what runs there keeps running and is released when it is due;
4. voluntary: every live bind on a node that is cordoned and not out, in the
   order walk place then task id, is evicted iff ``down_a < maxUnavailable_a``
   at its turn, else asked again at ``b + 1``;
5. a cordoned node that holds no live bind after step 4 goes out at ``b`` and
   is back at ``b + outFor``;
6. then the releases and the retry pass as they are; a RE-BIND of an evicted
   task lowers ``down_a``, which boundary ``b + 1`` sees.

Corner cases. A failure of a cordoned node: it goes out forced, its
``node_out_at`` is that boundary and its maintenance counts as done (the
``node_up`` brings it back uncordoned). A ``node_cordon`` of a node that is
out (failed and not yet up): nothing; its maintenance counts as done too. A
node that fails at the boundary of its cordon: it fails; the cordon is
dropped. A ``node_up`` of a node whose drain has not reached it is plain: the
walk cordons it later like any other. A failure of a node that is out for its
maintenance: it waits for that failure's ``node_up`` and no longer for
``outFor``. A candidate whose static release is due at ``b`` is live: the
events come first, as in the drain cell.

The check is teacher-forced on a history rebuilt from the answers, as the
drain reference's: the samples are its kinds (arrivals on the table of the
nodes that take a bind in their chunk, re-tried binds at their turn, tasks
with no node), a quarter of the re-tried samples on tasks that a budget let
go. Over EVERY stay, row and node of every plan, limit 0 each, beside the
drain reference's rows: ``voluntary_evictions_over_budget``;
``candidates_passed_over_with_allowance_left``;
``voluntary_evictions_out_of_walk_order``;
``binds_on_a_cordoned_or_out_node``; ``tasks_left_on_a_node_that_went_out``;
``nodes_out_while_holding_a_task_before_their_deadline``;
``nodes_not_out_though_empty``; ``forced_flag_disagrees_with_the_plan``;
``returns_not_outFor_after_going_out``. Plan 0 against the run without plans.

Controls, each a change to THIS reference that has to come out not correct:
``bf16``; ``no-budget`` (the drain cell's rule: everything leaves at its
cordon, so the node is out there: the program's waiting tasks were left on a
node that went out); ``budget-never-restored`` (a re-bind gives nothing back:
the program's later evictions are over budget); ``failures-free`` (forced
evictions do not count: the program passed candidates over with allowance
left); ``static-out`` (a node goes out at its deadline only: the program's
binds onto a node back early are returns not ``outFor`` after going out).
"""

from __future__ import annotations

import numpy as np

import budget_plans
from references import backlog_scenarios as B
from references import drain_scenarios as D
from references import greedy_waves as G
from references import greedy_waves_scenarios as GS

PER_SCENARIO = D.PER_SCENARIO
CONTROLS = (None, "bf16", "no-budget", "budget-never-restored",
            "failures-free", "static-out")
NONE = D.NONE
NEVER = 1 << 30
VOLUNTARY, DEADLINE, FAILURE = 0, 1, 2


class Nodes:
    """One plan's nodes through the boundaries, by the rule, from the plan and
    ``node_out_at`` (when a cordoned node was EMPTY is the history's to say;
    the answer is taken and held against the stays by ``exact_rows``).
    ``closed [C, N]``: takes no bind during chunk ``b``. ``mwin [C, N]``: out
    for its maintenance. ``leaves [C, N]``: goes out at ``b`` (every live bind
    must be gone). ``fails`` / ``dead`` / ``asks [C, N]``: what an eviction
    from the node at ``b`` is. ``place [N]``: the walk place of a cordoned
    node; ``cordon``, ``rule_out`` ``[N]``: the boundary of its effective
    cordon and the one it left the cordoned state at by the rule, -1 none."""

    def __init__(self, plan, C, N, out_at, control=None):
        self.closed, self.mwin, self.leaves, self.fails, self.dead, self.asks = (
            np.zeros((C, N), bool) for _ in range(6))
        self.fail_place = np.zeros((C, N), np.int64)
        self.place = np.zeros(N, np.int64)
        self.cordon = np.full(N, -1, np.int64)
        self.rule_out = np.full(N, -1, np.int64)
        cord = np.full(N, -1, np.int64)
        until = np.full(N, -1, np.int64)
        maint = np.zeros(N, bool)
        walked = 0
        grace, out_for = plan["grace"], plan["outFor"]
        for b, (ups, downs, cordons) in enumerate(budget_plans.moves(plan, C)):
            back = until == b
            back[ups] = True
            until[back], cord[back], maint[back] = -1, -1, False
            for i, n in enumerate(downs.tolist()):
                self.fails[b, n] = self.leaves[b, n] = True
                self.fail_place[b, n] = i
                if cord[n] >= 0:
                    self.rule_out[n] = b
                cord[n], until[n], maint[n] = -1, NEVER, False
            at = (cord >= 0) & (cord < b) & (cord + grace <= b)
            self.dead[b] = at
            for n in downs.tolist():
                self.dead[b, n] = False
            for n in cordons.tolist():
                if n not in downs and until[n] < 0 and cord[n] < 0:
                    cord[n] = self.cordon[n] = b
                    self.place[n] = walked
                    walked += 1
            self.asks[b] = cord >= 0
            self.asks[b] &= ~at
            if control == "static-out":
                empty = np.zeros(N, bool)
            elif control == "no-budget":
                empty = cord == b
            else:
                empty = self.asks[b] & (out_at == b)
            goes = at | empty
            self.leaves[b] |= goes
            self.rule_out[goes] = b
            cord[goes], until[goes], maint[goes] = -1, b + out_for, True
            self.closed[b] = (cord >= 0) | (until >= 0)
            self.mwin[b] = maint & (until >= 0)


def budget_rows(tasks, sched, h: D.History, kinds, plan, nodes: Nodes, out_at,
                control=None) -> dict:
    """The counts over every stay, log row and node of one plan that the
    budget rule adds (see the head)."""
    C, N = sched["chunks"], nodes.closed.shape[1]
    A = int(tasks["app_id"].max()) + 1
    max_u = budget_plans.max_unavailable(
        plan["share"], np.bincount(tasks["app_id"], minlength=A))
    app = tasks["app_id"][h.task]
    log = h.log
    lb, lt, ln = (log[:, 0], log[:, 1], log[:, 2]) if len(log) else (NONE,) * 3
    la = tasks["app_id"][lt]
    nlog = len(lt)
    # stays on a node the plan ever touches
    touched = nodes.leaves.any(0) | nodes.asks.any(0) | nodes.closed.any(0)
    on = np.nonzero(touched[h.node])[0]
    s_node, s_since, s_until = h.node[on], h.since[on], h.until[on]
    s_ev, s_app, s_task = h.evicted[on], app[on], h.task[on]
    # the kind each row has to have, and the rows' order
    flag = off = 0
    if nlog:
        want = np.where(nodes.fails[lb, ln], FAILURE, np.where(
            nodes.dead[lb, ln], DEADLINE, np.where(
                nodes.asks[lb, ln], VOLUNTARY, -1)))
        off = int((want < 0).sum())
        flag = int(((want >= 0) & (want != kinds)).sum())
    disorder = walk_off = 0
    if nlog > 1:
        cls = np.where(kinds == FAILURE, 0, np.where(kinds == DEADLINE, 1, 2))
        pos = np.where(kinds == FAILURE, nodes.fail_place[lb, ln], nodes.place[ln])
        key = ((lb * 3 + cls) * (N + 1) + pos) * (len(tasks["app_id"]) + 1) + lt
        bad = np.diff(key) <= 0
        vol = (kinds[1:] == VOLUNTARY) & (kinds[:-1] == VOLUNTARY) & (
            lb[1:] == lb[:-1])
        walk_off = int((bad & vol).sum())
        disorder = int((bad & ~vol).sum())
    # the budgets, boundary by boundary
    down = np.zeros(A, np.int64)
    over = passed = 0
    rebound = np.nonzero((h.bind >= 0) & h.entered_by_eviction)[0]
    counted = kinds != VOLUNTARY
    if control == "failures-free":
        counted = np.zeros(nlog, bool)
    for b in range(C):
        rows = np.nonzero(lb == b)[0]
        forced = rows[counted[rows]]
        down += np.bincount(la[forced], minlength=A)
        vol = rows[kinds[rows] == VOLUNTARY]
        if len(vol):
            a = la[vol]
            order = np.argsort(a, kind="stable")
            first = np.searchsorted(a[order], a[order], "left")
            rank = np.empty(len(vol), np.int64)
            rank[order] = np.arange(len(vol)) - first
            over += int((down[a] + rank >= max_u[a]).sum())
            down += np.bincount(a, minlength=A)
        # who asked and stayed: live on an asking node, not evicted here
        cand = nodes.asks[b, s_node] & (s_since < b) & (s_until >= b)
        stayed = cand & ~(s_ev & (s_until == b))
        passed += int((down[s_app[stayed]] < max_u[s_app[stayed]]).sum())
        if len(vol) and stayed.any():
            # a refused candidate ahead of an admitted one of its application
            key = nodes.place[ln[vol]] * (len(tasks["app_id"]) + 1) + lt[vol]
            last = np.full(A, -1, np.int64)
            np.maximum.at(last, la[vol], key)
            mine = nodes.place[s_node[stayed]] * (len(tasks["app_id"]) + 1) \
                + s_task[stayed]
            walk_off += int((mine < last[s_app[stayed]]).sum())
        if control != "budget-never-restored":
            back = rebound[h.bind[rebound] == b]
            down -= np.bincount(app[back], minlength=A)
    # the stays against the nodes
    made = s_since >= 0
    at = np.clip(s_since, 0, C - 1)
    on_closed = int((made & nodes.closed[at, s_node]).sum())
    early_back = int((made & nodes.mwin[at, s_node]).sum())
    left = 0
    remain = np.zeros((C, N), np.int64)
    for b in range(C):
        live = (s_since < b) & (s_until >= b) & ~(s_ev & (s_until == b))
        left += int((live & nodes.leaves[b, s_node]).sum())
        remain[b] = np.bincount(s_node[live], minlength=N)
    # the fourth answer against the rule and the stays
    holding = not_out = 0
    for n in np.nonzero(nodes.cordon >= 0)[0].tolist():
        cb, ro = int(nodes.cordon[n]), int(nodes.rule_out[n])
        asked = np.nonzero(nodes.asks[:, n])[0]
        asked = asked[asked >= cb]
        if int(out_at[n]) != ro:
            # by the rule it left the cordoned state at ``ro``
            if ro < 0 or (0 <= out_at[n] < ro):
                holding += 1
            else:
                not_out += 1
            continue
        if ro >= 0 and nodes.asks[ro, n] and remain[ro, n] > 0:
            holding += 1
        not_out += int(any(remain[b, n] == 0 for b in asked.tolist()
                           if ro < 0 or b < ro))
    return {"over": over, "passed_over": passed, "walk_off": walk_off,
            "on_closed": on_closed, "left": left, "holding": holding,
            "not_out": not_out, "flag": flag, "early_back": early_back,
            "off": off, "disorder": disorder}


def drain_rows(tasks, sched, h: D.History, assign, bind, buffer) -> dict:
    """``drain_scenarios.exact_rows``' counts that do not read a drain plan:
    the rows' chain, codes against nodes, the queue the answers imply, the
    order of the pass."""
    C = sched["chunks"]
    gang, resident = tasks["group_id"] != G.PAD, tasks["bound_node"] >= 0
    log = h.log
    chain = 0
    if len(log):
        lb, lt, ln, lf = log.T
        chain += int(((lf >= lb) | ((lf >= 0) & (sched["chunk"][lt] >= lf))
                      | ((lf < 0) & (sched["chunk"][lt] >= lb))).sum())
        chain += int((resident[lt] & (lf < 0)
                      & (tasks["bound_node"][lt] != ln)).sum())
        order = np.lexsort((lb, lt))
        st, sb, sf = lt[order], lb[order], lf[order]
        nxt = st[1:] == st[:-1]
        chain += int((nxt & ((sf[1:] < sb[:-1]) | (sf[1:] < 0))).sum())
        last_of = np.ones(len(st), bool)
        last_of[:-1] = ~nxt
        tl, bl = st[last_of], sb[last_of]
        chain += int(((assign[tl] >= 0) & (bind[tl] < bl)).sum())
        chain += int((gang[lt] & (lf >= 0)).sum())
    evicted_once = np.zeros(len(assign), bool)
    evicted_once[log[:, 1]] = True
    none = assign < 0
    codes = int((none != (bind < -1)).sum())
    codes += int((resident & ~evicted_once
                  & ((bind != -1) | (assign != tasks["bound_node"]))).sum())
    codes += int((gang & none & (bind != np.where(evicted_once, -5, -4))).sum())
    codes += int((~gang & none & ~np.isin(bind, (-2, -3))).sum())
    codes += int((gang & (bind >= 0)).sum())
    retried = h.bind >= 0
    first_bind = np.full(len(assign), -9, np.int64)
    order = np.argsort(h.since, kind="stable")[::-1]
    first_bind[h.task[order]] = h.bind[order]
    first_bind[none & ~evicted_once] = bind[none & ~evicted_once]
    failed = ~resident & ~gang & (first_bind != -1)
    fail_drop = failed & (first_bind == -3)
    fails_c = np.bincount(sched["chunk"][failed], minlength=C)
    fdrops_c = np.bincount(sched["chunk"][fail_drop], minlength=C)
    bound_b = np.bincount(h.bind[retried], minlength=C)
    ev_ng = ~gang[log[:, 1]] if len(log) else np.zeros(0, bool)
    ev_drop = np.zeros(len(log), bool)
    if len(log):
        lastrow = np.zeros(len(log), bool)
        lastrow[D.order_last(log)] = True
        ev_drop = lastrow & ev_ng & (bind[log[:, 1]] == -3)
    over = off_rule = depth = 0
    early = int((retried & ~h.entered_by_eviction
                 & ((sched["chunk"][h.task] >= h.bind) | resident[h.task])).sum())
    for b in range(C):
        rows = np.nonzero((log[:, 0] == b) & ev_ng)[0] if len(log) else NONE
        room = buffer - depth
        want = np.arange(len(rows)) >= room
        off_rule += int((ev_drop[rows] != want).sum())
        depth += int((~ev_drop[rows]).sum())
        over += int(depth > buffer)
        depth -= int(bound_b[b])
        room = buffer - depth
        off_rule += int(fdrops_c[b] != max(int(fails_c[b]) - room, 0))
        depth += int(fails_c[b] - fdrops_c[b])
    passed = D.passed_over(tasks, sched, h, failed, fail_drop, ev_drop, ev_ng, C)
    return {"chain": chain, "codes": codes, "over": over, "off_rule": off_rule,
            "passed": passed, "early": early}


def draw(rng, samples, sched, h: D.History, assign, bind, usable, gang, closed,
         voluntary):
    """[(task, boundary or None)] of one plan: the drain reference's kinds; a
    quarter of the re-tried samples on tasks a budget let go (``voluntary``:
    [P] bool)."""
    arriving = sched["seq"]
    pick = lambda pool, n: (rng.choice(pool, size=min(n, len(pool)), replace=False)
                            if len(pool) else NONE)
    first = np.append(pick(arriving, max(PER_SCENARIO, samples // 2)), arriving[-1])
    got = [(int(k), None) for k in np.unique(first[usable[first]])]
    behind = int((~usable[first]).sum())
    held = np.nonzero(assign >= 0)[0]
    retried = held[bind[held] >= 0]
    n = max(1, samples // 3)
    b = bind[retried]
    onto_back = retried[closed[np.clip(b - 1, 0, None), assign[retried]]
                        & ~closed[b, assign[retried]] & (b > 0)]
    pools = (retried[voluntary[retried]], onto_back, retried)
    for pool, share in zip(pools, (n // 4, n // 4, n - 2 * (n // 4))):
        got += [(int(k), int(bind[k])) for k in pick(pool, max(1, share))]
    none = np.nonzero((assign < 0) & ~gang & usable & (sched["rank"] >= 0))[0]
    for k in pick(none, max(1, samples // 8)):
        got.append((int(k), None))
    return got, behind, len(onto_back)


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit); ``limit`` None is printed for the record."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    base, tasks, eng = trace["nodes"], trace["tasks"], config["engine"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    assigns = np.asarray(answers["assignments"], np.int64)
    binds = np.asarray(answers["bind_boundary"], np.int64)
    logs = np.asarray(answers["eviction_log"], np.int64)
    outs = np.asarray(answers["node_out_at"], np.int64)
    buffer = int(answers["retry_buffer"])
    S, N = len(assigns), len(base["cpu"])
    sched = B.schedule(tasks, eng["waveWidth"], eng["chunkWaves"])
    C = sched["chunks"]
    plans = budget_plans.sample(config, base["zone"], S, C)
    gang, resident = tasks["group_id"], tasks["bound_node"] >= 0
    rng = np.random.default_rng(seed)
    short = [[] for _ in range(S)]
    total = dict.fromkeys((
        "over", "passed_over", "walk_off", "on_closed", "left", "holding",
        "not_out", "flag", "early_back", "off", "disorder", "chain", "codes",
        "q_over", "off_rule", "passed", "early"), 0)
    edge = behind = placed_off = overfull = count_off = 0
    n_vol = n_back = n_none = n_retried = n_evicted = 0
    forced_free = late = 0
    late_share = []
    quiet = {"down": NONE, "scaled": NONE, "factor": 1.0, "tainted": NONE}
    for s in range(S):
        log = logs[s][logs[s][:, 1] >= 0]
        kinds = log[:, 4]
        h = D.History(tasks, sched, assigns[s], binds[s], log[:, :4])
        nodes = Nodes(plans[s], C, N, outs[s], control)
        made = ~resident & (assigns[s] >= 0)
        placed_off = max(placed_off, abs(int(made.sum()) - int(answers["placed"][s])))
        count_off += abs(len(log) - int(answers["evictions"][s]))
        rows = budget_rows(tasks, sched, h, kinds, plans[s], nodes, outs[s], control)
        for k, v in rows.items():
            total[k] += v
        rows = drain_rows(tasks, sched, h, assigns[s], binds[s], buffer)
        rows["q_over"] = rows.pop("over")
        for k, v in rows.items():
            total[k] += v
        overfull += D.over_allocatable(base, tasks, sched, h)
        # for the record: the plan-set targets (configs: scenarios.measured)
        if len(log):
            vol = kinds == VOLUNTARY
            forced_free += int(not (kinds == DEADLINE).any())
            waited = log[vol, 0] > nodes.cordon[log[vol, 2]]
            late_share.append(float(waited.mean()) if vol.any() else 0.0)
            late += int(waited.sum())
        never = (assigns[s] < 0) & (binds[s] == -4)
        broken = np.unique(gang[never & (gang != G.PAD)])
        in_broken = np.isin(gang, broken) & (gang != G.PAD)
        first = np.full(int(sched["wave"].max()) + 1, np.iinfo(np.int64).max)
        np.minimum.at(first, sched["wave"][in_broken], sched["slot"][in_broken])
        usable = ~in_broken & ~resident & (sched["slot"] < first[sched["wave"]])
        voluntary = np.zeros(len(assigns[s]), bool)
        voluntary[log[kinds == VOLUNTARY, 1]] = True
        drawn, lost, pool_back = draw(
            rng, samples // S, sched, h, assigns[s], binds[s], usable,
            gang != G.PAD, nodes.closed, voluntary)
        behind += lost
        tables = {}
        for k, at in drawn:
            c = int(sched["chunk"][k]) if at is None else at
            if c not in tables:
                keep = ~nodes.closed[c]
                table = GS.node_table(base, quiet)
                tables[c] = (keep, {key: (v[keep] if isinstance(v, np.ndarray)
                                          else v) for key, v in table.items()})
            keep, table = tables[c]
            new = np.where(keep, np.cumsum(keep) - 1, -2)
            turn_key = None
            if at is not None:
                i = h.last[k]
                turn_key = (h.walk_prio[i], h.enter[i])
            held = D.in_state(h, sched, k, at, turn_key) & (h.task != k)
            st = D.State(base, tasks, h, held, k, keep)
            lo, hi, sure, maybe = G.pick(table, tasks, trace, st, k, weights)
            if lo is None:
                edge += 1
                continue
            if at is None:
                mine = np.nonzero((h.task == k) & (h.bind < 0))[0]
                choice = int(new[h.node[mine[0]]]) if len(mine) else G.PAD
            else:
                choice = int(new[assigns[s][k]])
                n_retried += 1
                n_evicted += int(h.entered_by_eviction[h.last[k]])
                n_vol += int(voluntary[k])
            if control == "bf16":
                choice = G.pick_bf16(table, tasks, trace, st, k, weights)
            n_none += int(choice == G.PAD)
            short[s].append(100.0 if choice == -2 else
                            G.judge(choice, lo, hi, sure, maybe))
        n_back += pool_back
    per = [np.asarray(x) for x in short]
    pooled = np.concatenate(per) if sum(map(len, per)) else np.asarray([100.0])
    worst = max((float((x > 0).mean()) if len(x) else 1.0) for x in per)
    base_off = 0
    plain = answers.get("without_plans")
    if plain is not None:
        plain = plain() if callable(plain) else plain
        base_off = int((np.asarray(plain["assignments"]) != assigns[0]).sum()
                       + (np.asarray(plain["bind_boundary"]) != binds[0]).sum())
    kinds_all = logs[:, :, 4][logs[:, :, 1] >= 0]
    exact = lambda name, key: (f"ref.{name}", float(total[key]), limits[name])
    return [
        ("ref.choices_not_the_references_share",
         float((pooled > 0).mean()), limits["choices_not_the_references_share"]),
        ("ref.choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min"] - len(pooled))), 0),
        ("ref.scenario_choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min_per_scenario"]
                   - min(map(len, per)))), 0),
        exact("voluntary_evictions_over_budget", "over"),
        exact("candidates_passed_over_with_allowance_left", "passed_over"),
        exact("voluntary_evictions_out_of_walk_order", "walk_off"),
        exact("binds_on_a_cordoned_or_out_node", "on_closed"),
        exact("tasks_left_on_a_node_that_went_out", "left"),
        exact("nodes_out_while_holding_a_task_before_their_deadline", "holding"),
        exact("nodes_not_out_though_empty", "not_out"),
        exact("forced_flag_disagrees_with_the_plan", "flag"),
        exact("returns_not_outFor_after_going_out", "early_back"),
        exact("evictions_not_from_a_leaving_node", "off"),
        exact("log_entries_out_of_order_or_doubled", "disorder"),
        exact("retried_binds_out_of_queue_order", "passed"),
        ("ref.releases_not_at_their_boundary", float(overfull),
         limits["releases_not_at_their_boundary"]),
        exact("drops_while_the_queue_had_room", "off_rule"),
        ("ref.log_rows_that_do_not_chain", float(total["chain"]), 0),
        ("ref.codes_that_disagree_with_the_nodes", float(total["codes"]), 0),
        ("ref.retried_binds_not_failed_in_an_earlier_chunk", float(total["early"]), 0),
        ("ref.boundaries_with_the_queue_over_the_buffer", float(total["q_over"]), 0),
        ("ref.scenario0_differs_from_the_run_without_plans", float(base_off),
         limits["scenario0_differs_from_the_run_without_plans"]),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.log_rows_differ_from_evictions", float(count_off), 0),
        ("ref.evictions_handed_back", float(len(kinds_all)), None),
        ("ref.evictions_voluntary", float((kinds_all == VOLUNTARY).sum()), None),
        ("ref.evictions_forced_at_a_deadline", float((kinds_all == DEADLINE).sum()), None),
        ("ref.evictions_forced_by_a_failure", float((kinds_all == FAILURE).sum()), None),
        ("ref.voluntary_evictions_a_boundary_or_more_after_the_cordon",
         float(late), None),
        ("ref.median_plan_share_of_voluntary_evictions_after_the_cordon",
         float(np.median(late_share)) if late_share else 0.0, None),
        ("ref.plans_with_no_deadline_forced_eviction", float(forced_free), None),
        ("ref.plans_with_some_deadline_forced_eviction",
         float(len(late_share) - forced_free), None),
        ("ref.nodes_that_went_out", float((outs >= 0).sum()), None),
        ("ref.retried_binds_compared_share", n_retried / max(len(pooled), 1), None),
        ("ref.evicted_rebinds_compared", float(n_evicted), None),
        ("ref.voluntarily_evicted_rebinds_compared", float(n_vol), None),
        ("ref.binds_onto_a_node_just_back_in_the_answers", float(n_back), None),
        ("ref.tasks_dropped_at_a_full_buffer", float((binds == -3).sum()), None),
        ("ref.no_node_samples_compared", float(n_none), None),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_on_a_zone_score_edge", float(edge), None),
        ("ref.samples_behind_a_rolled_back_gang", float(behind), None),
    ]
