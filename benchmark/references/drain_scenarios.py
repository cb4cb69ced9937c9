"""The plain reference for a full Borg cell under a rolling maintenance drain:
``references/backlog_scenarios.py``'s cell (a resident set, a standing queue
re-tried in priority order at every chunk boundary, every bind released when
it is due) whose nodes LEAVE and COME BACK at chunk boundaries, by the plans
of ``drain_plans``. Numpy only, nothing of the program imported, nothing the
program made taken but its three answers, per plan: every task's node at the
end (``assignments``), the boundary of its LAST bind (``bind_boundary``: -1
its arrival wave or resident, b >= 0 the retry pass of boundary b, -2 queued
at the end, -3 dropped at a full buffer, -4 a gang member refused at arrival,
-5 a gang member evicted and stranded) and the ``eviction_log`` (boundary,
task, the node it held, the boundary that had bound it or -1). Over
``greedy_waves`` (``pick``, ``pick_bf16``, ``judge``), ``greedy_waves_scenarios``
(``node_table``, ``judged_on``) and ``backlog_scenarios`` (``schedule``,
``retried_release``).

The semantics checked (``guarantees`` in the configuration's file): those of
``backlog_scenarios`` and, at boundary ``b``, BEFORE its releases and its
retry pass:

1. the plan's nodes that are back have their own allocatable again, empty;
   the nodes that leave read allocatable 0 from here until they are back;
2. every task bound on a leaving node (resident, arrival bind or re-tried
   bind; one whose release is due at ``b`` too: the events come first) is
   evicted: its usage and its zone count are gone from ``b`` on, the release
   it was owed never fires, and the log takes one row;
3. the evicted that are no gang members join the queue behind what is queued,
   in the log's order (the leaving nodes in the plan's walk order, a node's
   tasks by id), as far as the buffer has room (the rest dropped); an evicted
   gang member is stranded. The pass of ``b`` then walks the queue in
   QueueSort order, priority descending, then the order of entering the
   queue: an evicted task stands behind what was queued at its priority, and
   a task re-bound runs its whole duration again from ``b``.

Departures from the program's anchor (``BoundaryOps.evict_node``): none in
the order; a stay's float residue on an emptied node is not modelled (the
state is rebuilt from the stays that hold a node: an empty node reads 0).

The check is teacher-forced on a history rebuilt from the three answers: a
task's STAYS are its log rows (node, bound by, until the eviction) and, where
it has a node at the end, its last bind (until the boundary the release rule
names). Samples, per plan: arriving tasks at their arrival and the last one
(the nodes that are out in their chunk are not in the table they are judged
on); re-tried binds at their turn in their pass, among them evicted tasks at
the turn that re-bound them and binds onto a node in the pass of the boundary
it came back; tasks with no node. The pooled share against the
configuration's limit. Over EVERY stay of every plan, limit 0 each: a bind on
a node while it was out; a stay that outlasts the boundary its node went out;
an eviction from a node that did not leave there, or that does not chain with
the task's other rows and its last bind; log rows out of order or doubled;
``retried_binds_out_of_queue_order`` with the evicted in the walk; the queue
over the buffer, a drop while the queue the answers imply had room (arrival
failures and evicted alike); codes that disagree with nodes. Plan 0 has no
event: its two arrays have to be those of a batch of the same trace without
plans at the same buffer (``answers["without_plans"]``, made by the adapter).

Controls, each a change to THIS reference that has to come out not correct:
``bf16``; ``no-evict`` (the nodes go out and their tasks stay until their
release rule: the parent's t = 0 semantics moved in time); ``no-return`` (a
node that is back stays out); ``evicted-last`` (the evicted join behind the
whole queue, not by priority).
"""

from __future__ import annotations

import numpy as np

import drain_plans
from references import backlog_scenarios as B
from references import greedy_waves as G
from references import greedy_waves_scenarios as GS

PER_SCENARIO = 16
CONTROLS = (None, "bf16", "no-evict", "no-return", "evicted-last")
NONE = np.zeros(0, np.int64)
LOWEST = np.iinfo(np.int64).min // 4


class History:
    """One plan's stays, from its answers. Arrays over stays: ``task``,
    ``node``, ``bind`` (-1 arrival wave or resident, else the pass), ``since``
    (the chunk it is in the state from), ``until`` (the boundary it is gone
    from), ``evicted`` (it ended in the log), ``enter`` (for a re-tried bind:
    the key of the episode in the queue that led to it). ``last[k]`` is the
    place of task k's last stay among them, -1 where it holds no node."""

    def __init__(self, tasks, sched, assign, bind, log, control=None):
        P = len(assign)
        log = log[log[:, 1] >= 0]
        self.log = log
        lb, lt, ln, lf = log.T if len(log) else (NONE,) * 4
        held = np.nonzero(assign >= 0)[0]
        self.task = np.concatenate([lt, held])
        self.node = np.concatenate([ln, assign[held]])
        self.bind = np.concatenate([lf, bind[held]])
        self.evicted = np.arange(len(self.task)) < len(lt)
        retried = self.bind >= 0
        self.since = np.where(retried, self.bind, sched["chunk"][self.task])
        rule = np.where(
            retried,
            B.retried_release(sched, tasks["duration"][self.task],
                              np.clip(self.bind, 0, None)),
            sched["release"][self.task])
        self.until = np.where(self.evicted, np.concatenate([lb, held * 0]), rule)
        if control == "no-evict":
            self.until = rule
        self.last = np.full(P, -1, np.int64)
        self.last[held] = len(lt) + np.arange(len(held))
        # the queue's episodes: a task enters at the end of the chunk it
        # failed in (kind 0, by arrival) or at the boundary that evicted it
        # (kind 1, in the log's order), and the order of entering is (the
        # boundary it can first be tried at, kind, place)
        prio = tasks["priority"]
        big = max(P, len(lt)) + 1
        fail_key = (sched["chunk"] + 1) * 2 * big + np.clip(sched["rank"], 0, None)
        self.fail_key = fail_key
        self.ev_key = (lb * 2 + 1) * big + np.arange(len(lt)) if len(lt) else NONE
        # the episode behind a re-tried bind: the task's latest eviction at or
        # before the bind, else its failure at arrival
        self.enter = fail_key[self.task].copy()
        self.entered_by_eviction = np.zeros(len(self.task), bool)
        if len(lt):
            wide = int(sched["chunks"]) + 2
            order = np.lexsort((lb, lt))
            st, rows = lt[order], (lt * wide + lb)[order]
            j = np.searchsorted(rows, self.task * wide + self.bind, "right") - 1
            hit = retried & (j >= 0) & (st[np.clip(j, 0, None)] == self.task)
            self.enter = np.where(hit, self.ev_key[order][np.clip(j, 0, None)],
                                  self.enter)
            self.entered_by_eviction = hit
        # behind the whole queue under the control: priority counts for nothing
        self.evicted_last = control == "evicted-last"
        self.walk_prio = np.where(self.evicted_last & self.entered_by_eviction,
                                  LOWEST, prio[self.task])


def in_state(h: History, sched, k: int, at, turn_key=None):
    """[stays] bool: the stays that hold a node just before task ``k`` is
    tried: at its arrival (``at`` None) or at its turn in the pass of ``at``
    (``turn_key``: (priority, entering key) of its episode)."""
    arrival = h.bind < 0
    if at is None:
        c = sched["chunk"][k]
        return (h.until > c) & (
            (arrival & (sched["rank"][h.task] < sched["rank"][k]))
            | (~arrival & (h.bind <= c)))
    prio, key = turn_key
    ahead = (h.walk_prio > prio) | ((h.walk_prio == prio) & (h.enter < key))
    return (h.until > at) & (
        (arrival & (h.since < at)) | (~arrival & (h.bind < at))
        | (~arrival & (h.bind == at) & ahead))


class State:
    """``greedy_waves.pick``'s state from the stays ``held`` (a mask), on the
    table of the nodes that take a task now (``keep``)."""

    def __init__(self, base, tasks, h: History, held, k, keep):
        at = np.nonzero(held)[0]
        same = at[tasks["app_id"][h.task[at]] == tasks["app_id"][k]]
        self.in_zone = np.bincount(base["zone"][h.node[same]],
                                   minlength=base["zones"]).astype(np.float64)
        new = np.where(keep, np.cumsum(keep) - 1, -1)
        at = at[new[h.node[at]] >= 0]
        where, who = new[h.node[at]], h.task[at]
        N = int(keep.sum())
        self.used = {
            "cpu": np.bincount(where, tasks["cpu"][who].astype(np.float64), N),
            "mem": np.bincount(where, tasks["mem"][who].astype(np.float64), N),
            "pods": np.bincount(where, minlength=N).astype(np.float64),
        }


def exact_rows(base, tasks, sched, h: History, assign, bind, plan, out, buffer):
    """The counts over every stay of one plan (see the head)."""
    C, N = sched["chunks"], len(base["cpu"])
    gang, resident = tasks["group_id"] != G.PAD, tasks["bound_node"] >= 0
    log = h.log
    moves = drain_plans.moves(plan, C)
    leave_b = np.full(N, -1, np.int64)
    place = np.zeros(N, np.int64)
    for b, (leave, _) in enumerate(moves):
        leave_b[leave] = b
        place[leave] = np.arange(len(leave))
    # 1. a bind on a node while it was out (a resident was there before)
    made = h.since >= 0
    on_out = int(out[np.clip(h.since[made], 0, C - 1), h.node[made]].sum())
    # 2. a stay that outlasts the boundary its node went out
    L = leave_b[h.node]
    left = int(((L >= 0) & (h.since < L)
                & ((h.until > L) | ((h.until == L) & ~h.evicted))).sum())
    # 3. evictions: from a node that leaves there, and the task's rows chain
    off = 0
    if len(log):
        lb, lt, ln, lf = log.T
        off += int((leave_b[ln] != lb).sum())
        off += int(((lf >= lb) | ((lf >= 0) & (sched["chunk"][lt] >= lf))
                    | ((lf < 0) & (sched["chunk"][lt] >= lb))).sum())
        off += int((resident[lt] & (lf < 0) & (tasks["bound_node"][lt] != ln)).sum())
        order = np.lexsort((lb, lt))
        st, sb, sf = lt[order], lb[order], lf[order]
        nxt = st[1:] == st[:-1]
        # a later row's bind lies at or after the row before it
        off += int((nxt & ((sf[1:] < sb[:-1]) | (sf[1:] < 0))).sum())
        last_of = np.ones(len(st), bool)
        last_of[:-1] = ~nxt
        tl, bl = st[last_of], sb[last_of]
        # the last bind of an evicted task with a node lies at or after its
        # last eviction; a resident never evicted is where it was put
        off += int(((assign[tl] >= 0) & (bind[tl] < bl)).sum())
        off += int((gang[lt] & (lf >= 0)).sum())
    # 4. rows in order: boundaries up, inside one the walk's nodes, a node's
    # tasks by id; none doubled
    disorder = 0
    if len(log) > 1:
        key = (lb * N + place[ln]) * (len(assign) + 1) + lt
        disorder = int((np.diff(key) <= 0).sum())
    # 5. codes against nodes
    evicted_once = np.zeros(len(assign), bool)
    evicted_once[log[:, 1]] = True
    none = assign < 0
    codes = int((none != (bind < -1)).sum())
    codes += int((resident & ~evicted_once
                  & ((bind != -1) | (assign != tasks["bound_node"]))).sum())
    codes += int((gang & none & (bind != np.where(evicted_once, -5, -4))).sum())
    codes += int((~gang & none & ~np.isin(bind, (-2, -3))).sum())
    codes += int((gang & (bind >= 0)).sum())
    # 6. the queue the answers imply: per boundary the evicted join (log
    # order, while there is room), the pass binds, the chunk's failures join
    retried = h.bind >= 0
    # a task failed at its arrival iff its first stay is no arrival bind
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
    # an evicted task was dropped iff that row is its last and it reads -3
    ev_drop = np.zeros(len(log), bool)
    if len(log):
        lastrow = np.zeros(len(log), bool)
        lastrow[order_last(log)] = True
        ev_drop = lastrow & ev_ng & (bind[log[:, 1]] == -3)
    over = off_rule = 0
    depth = 0
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
    # 7. passed over, with the evicted in the walk: an episode that stays
    # queued through pass b while one BEHIND it in the walk, asking at least
    # as much and tolerating no more, is bound in b
    passed = passed_over(tasks, sched, h, failed, fail_drop, ev_drop, ev_ng, C)
    return {"on_out": on_out, "left": left, "off": off, "disorder": disorder,
            "codes": codes, "over": over, "off_rule": off_rule,
            "passed": passed, "early": early}


def order_last(log):
    """Places of each task's last row."""
    order = np.lexsort((log[:, 0], log[:, 1]))
    st = log[order, 1]
    last = np.ones(len(st), bool)
    last[:-1] = st[1:] != st[:-1]
    return order[last]


def passed_over(tasks, sched, h: History, failed, fail_drop, ev_drop, ev_ng,
                C) -> int:
    # episodes: (task, priority as walked, entering key, first pass it can be
    # tried in, the pass that bound it or C)
    log = h.log
    ep_task = [np.nonzero(failed & ~fail_drop)[0]]
    ep_key = [h.fail_key[ep_task[0]]]
    ep_from = [sched["chunk"][ep_task[0]] + 1]
    ep_evicted = [np.zeros(len(ep_task[0]), bool)]
    if len(log):
        rows = np.nonzero(ev_ng & ~ev_drop)[0]
        ep_task.append(log[rows, 1])
        ep_key.append(h.ev_key[rows])
        ep_from.append(log[rows, 0])
        ep_evicted.append(np.ones(len(rows), bool))
    task, key, frm, evd = (np.concatenate(x) for x in
                           (ep_task, ep_key, ep_from, ep_evicted))
    # the bind that ended an episode: the stay of that task with the
    # smallest bind >= the episode's first pass
    ends = np.full(len(task), C, np.int64)
    re = np.nonzero(h.bind >= 0)[0]
    if len(re):
        wide = C + 2
        order = np.lexsort((h.bind[re], h.task[re]))
        st, sb = h.task[re][order], h.bind[re][order]
        j = np.searchsorted(st * wide + sb, task * wide + frm, "left")
        hit = (j < len(st)) & (st[np.clip(j, 0, len(st) - 1)] == task)
        ends = np.where(hit, sb[np.clip(j, 0, len(st) - 1)], C)
    prio = np.where(evd & h.evicted_last, LOWEST, tasks["priority"][task])
    cls = (np.searchsorted(np.unique(tasks["cpu"]), tasks["cpu"]) * 64
           + np.searchsorted(np.unique(tasks["mem"]), tasks["mem"]) * 2
           + tasks["tolerates"])[task]
    kinds = np.unique(cls)
    c_cpu, c_mem, c_tol = kinds // 64, (kinds // 2) % 32, kinds % 2
    dominates = ((c_cpu[None, :] >= c_cpu[:, None]) & (c_mem[None, :] >= c_mem[:, None])
                 & (c_tol[None, :] <= c_tol[:, None]))
    kind_of = np.searchsorted(kinds, cls)
    # one number that sorts like the walk: priority down, then the key
    turn = np.empty(len(task), np.int64)
    turn[np.lexsort((key, -prio))] = np.arange(len(task))
    passed = 0
    for b in np.unique(ends[ends < C]).tolist():
        here = np.nonzero(ends == b)[0]
        last = np.full(len(kinds), -1, np.int64)
        np.maximum.at(last, kind_of[here], turn[here])
        behind = np.where(dominates, last[None, :], -1).max(axis=1)
        stay = np.nonzero((frm <= b) & (ends > b))[0]
        passed += int((behind[kind_of[stay]] > turn[stay]).sum())
    return passed


def draw(rng, samples, sched, h: History, assign, bind, usable, gang, out, plan):
    """[(task, boundary or None)] of one plan (see the head)."""
    arriving = sched["seq"]
    pick = lambda pool, n: (rng.choice(pool, size=min(n, len(pool)), replace=False)
                            if len(pool) else NONE)
    first = np.append(pick(arriving, max(PER_SCENARIO, samples // 2)), arriving[-1])
    got = [(int(k), None) for k in np.unique(first[usable[first]])]
    behind = int((~usable[first]).sum())
    held = np.nonzero(assign >= 0)[0]
    retried = held[bind[held] >= 0]
    evicted_once = np.zeros(len(assign), bool)
    evicted_once[h.log[:, 1]] = True
    n = max(1, samples // 3)
    moves = drain_plans.moves(plan, sched["chunks"])
    back_at = np.full(out.shape[1], -1, np.int64)
    for b, (_, back) in enumerate(moves):
        back_at[back] = b
    onto_back = retried[back_at[assign[retried]] == bind[retried]]
    pools = (retried[evicted_once[retried]], onto_back, retried)
    for pool, share in zip(pools, (n // 2, n // 4, n - n // 2 - n // 4)):
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
    buffer = int(answers["retry_buffer"])
    S, N = len(assigns), len(base["cpu"])
    plans = drain_plans.sample(config, base["zone"], S)
    sched = B.schedule(tasks, eng["waveWidth"], eng["chunkWaves"])
    C = sched["chunks"]
    gang, resident = tasks["group_id"], tasks["bound_node"] >= 0
    rng = np.random.default_rng(seed)
    short = [[] for _ in range(S)]
    total = dict.fromkeys(("on_out", "left", "off", "disorder", "codes", "over",
                           "off_rule", "passed", "early"), 0)
    edge = behind = placed_off = overfull = count_off = 0
    n_evicted = n_back = n_none = n_retried = 0
    quiet = {"down": NONE, "scaled": NONE, "factor": 1.0, "tainted": NONE}
    for s in range(S):
        log = logs[s]
        h = History(tasks, sched, assigns[s], binds[s], log, control)
        out = drain_plans.out_at(plans[s], C, N)
        if control == "no-return":
            out = np.logical_or.accumulate(out, axis=0)
        made = ~resident & (assigns[s] >= 0)
        placed_off = max(placed_off, abs(int(made.sum()) - int(answers["placed"][s])))
        count_off += abs(int((log[:, 1] >= 0).sum()) - int(answers["evictions"][s]))
        rows = exact_rows(base, tasks, sched, h, assigns[s], binds[s], plans[s],
                          out, buffer)
        for k in total:
            total[k] += rows[k]
        overfull += over_allocatable(base, tasks, sched, h)
        # a rolled-back gang's binds were seen by the slots after it in its
        # wave and are in no answer: those slots cannot be rebuilt
        never = (assigns[s] < 0) & (binds[s] == -4)
        broken = np.unique(gang[never & (gang != G.PAD)])
        in_broken = np.isin(gang, broken) & (gang != G.PAD)
        first = np.full(int(sched["wave"].max()) + 1, np.iinfo(np.int64).max)
        np.minimum.at(first, sched["wave"][in_broken], sched["slot"][in_broken])
        usable = ~in_broken & ~resident & (sched["slot"] < first[sched["wave"]])
        drawn, lost, pool_back = draw(
            rng, samples // S, sched, h, assigns[s], binds[s], usable,
            gang != G.PAD, out, plans[s])
        behind += lost
        tables = {}
        for k, at in drawn:
            c = int(sched["chunk"][k]) if at is None else at
            if c not in tables:
                keep = ~out[c]
                nodes = GS.node_table(base, quiet)
                tables[c] = (keep, {key: (v[keep] if isinstance(v, np.ndarray)
                                          else v) for key, v in nodes.items()})
            keep, nodes = tables[c]
            new = np.where(keep, np.cumsum(keep) - 1, -2)
            turn_key = None
            if at is not None:
                i = h.last[k]
                turn_key = (h.walk_prio[i], h.enter[i])
            # at its arrival the state holds the task's own stays too where
            # it is a resident; an arriving task has none before its arrival
            held = in_state(h, sched, k, at, turn_key) & (h.task != k)
            st = State(base, tasks, h, held, k, keep)
            lo, hi, sure, maybe = G.pick(nodes, tasks, trace, st, k, weights)
            if lo is None:
                edge += 1
                continue
            if at is None:
                # the node it took in its arrival wave: its first stay, if
                # that is an arrival bind
                mine = np.nonzero((h.task == k) & (h.bind < 0))[0]
                choice = int(new[h.node[mine[0]]]) if len(mine) else G.PAD
            else:
                choice = int(new[assigns[s][k]])
                n_retried += 1
                n_evicted += int(h.entered_by_eviction[h.last[k]])
            if control == "bf16":
                choice = G.pick_bf16(nodes, tasks, trace, st, k, weights)
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
    return [
        ("ref.choices_not_the_references_share",
         float((pooled > 0).mean()), limits["choices_not_the_references_share"]),
        ("ref.choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min"] - len(pooled))), 0),
        ("ref.scenario_choices_compared_short_of_min",
         float(max(0, limits["choices_compared_min_per_scenario"]
                   - min(map(len, per)))), 0),
        ("ref.binds_on_a_node_while_it_is_out", float(total["on_out"]),
         limits["binds_on_a_node_while_it_is_out"]),
        ("ref.tasks_left_on_a_node_that_went_out", float(total["left"]),
         limits["tasks_left_on_a_node_that_went_out"]),
        ("ref.evictions_not_from_a_leaving_node", float(total["off"]),
         limits["evictions_not_from_a_leaving_node"]),
        ("ref.log_entries_out_of_order_or_doubled", float(total["disorder"]),
         limits["log_entries_out_of_order_or_doubled"]),
        ("ref.retried_binds_out_of_queue_order", float(total["passed"]),
         limits["retried_binds_out_of_queue_order"]),
        ("ref.releases_not_at_their_boundary", float(overfull),
         limits["releases_not_at_their_boundary"]),
        ("ref.drops_while_the_queue_had_room", float(total["off_rule"]),
         limits["drops_while_the_queue_had_room"]),
        ("ref.codes_that_disagree_with_the_nodes", float(total["codes"]), 0),
        ("ref.retried_binds_not_failed_in_an_earlier_chunk", float(total["early"]), 0),
        ("ref.boundaries_with_the_queue_over_the_buffer", float(total["over"]), 0),
        ("ref.scenario0_differs_from_the_run_without_plans", float(base_off),
         limits["scenario0_differs_from_the_run_without_plans"]),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.log_rows_differ_from_evictions", float(count_off), 0),
        ("ref.evictions_handed_back", float((logs[:, :, 1] >= 0).sum()), None),
        ("ref.retried_binds_compared_share", n_retried / max(len(pooled), 1), None),
        ("ref.evicted_rebinds_compared", float(n_evicted), None),
        ("ref.binds_onto_a_node_just_back_in_the_answers", float(n_back), None),
        ("ref.tasks_dropped_at_a_full_buffer", float((binds == -3).sum()), None),
        ("ref.no_node_samples_compared", float(n_none), None),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_on_a_zone_score_edge", float(edge), None),
        ("ref.samples_behind_a_rolled_back_gang", float(behind), None),
    ]


def over_allocatable(nodes, tasks, sched, h: History) -> int:
    """Stays made in the window (arrival or re-tried binds) onto a node that
    stands over its allocatable at the end of the chunk they begin in, every
    stay held from its chunk until exactly the boundary it is gone from."""
    N, C = len(nodes["cpu"]), sched["chunks"]
    row = h.node * (C + 2)
    since = row + np.clip(h.since, -1, None) + 1  # residents: column 0
    until = row + np.clip(np.minimum(h.until, C), 0, None) + 1
    full = np.zeros((N, C + 2), bool)
    for r, req in (("cpu", tasks["cpu"][h.task].astype(np.float64)),
                   ("mem", tasks["mem"][h.task].astype(np.float64)),
                   ("pods", np.ones(len(h.task)))):
        delta = (np.bincount(since, req, N * (C + 2))
                 - np.bincount(until, req, N * (C + 2))).reshape(N, C + 2)
        use = np.cumsum(delta, axis=1)  # column c + 1: during chunk c
        full |= use > nodes[r][:, None] * (1 + G.FIT_EDGE) + 1e-9
    made = h.since >= 0
    return int(full[h.node[made], h.since[made] + 1].sum())
