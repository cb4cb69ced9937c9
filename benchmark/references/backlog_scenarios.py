"""The plain reference for a Borg cell that is full: a resident set, a
standing pending queue re-tried in priority order at every chunk boundary,
and every bind released when it is due. Numpy only, nothing of the program
imported, nothing the program made taken but its answers: per scenario every
task's node (``assignments``) and the boundary that bound it
(``bind_boundary``: -1 its arrival wave or resident, b >= 0 the retry pass of
boundary b, -2 still queued at the end, -3 dropped at a full buffer, -4
refused at arrival and never queued). Over ``greedy_waves`` (``pack_waves``,
``pick``, ``pick_bf16``, ``judge``) and ``greedy_waves_scenarios``
(``node_table``, ``takes_tasks``, ``judged_on``).

The semantics checked (``guarantees`` in the configuration's file). The
residents (``bound_node >= 0``) hold their nodes from t = 0. The arriving
tasks are tried in arrival order in waves of ``waveWidth`` slots, a gang never
split, each slot seeing the binds before it, a gang with an unplaced member
rolled back at the end of its wave. Before chunk ``b`` of ``chunkWaves`` waves
(boundary ``b``, at the arrival time ``t_b`` of the chunk's first task), in
this order:

1. every task bound in its arrival wave (or resident) whose ``arrival +
   duration`` is at or before ``t_b``, and that was bound two chunks back or
   earlier, gives its resources back (``greedy_waves``' rule; a resident
   counts as bound at chunk -2);
2. every task bound by an earlier retry pass, of boundary ``b'``, gives them
   back at the first boundary whose start reaches ``t_b' + duration``
   (float32), at least ``b' + 1``;
3. the retry pass: the queue (every non-gang task that fitted nowhere at its
   arrival in an earlier chunk, was not dropped and is not bound yet) is
   walked in kube's QueueSort order, priority descending, then arrival; a
   task goes to the node the scoring rule picks on the state that the
   releases above and the binds before it in the walk give, or stays queued.

A task that fails at its arrival joins the queue if it is no gang member and
the queue holds fewer than ``retryBuffer`` tasks at that moment (the tasks
that stayed after the last pass and the failures of this chunk so far);
otherwise it is dropped for good.

The check is teacher-forced on the program's own answers. Samples:
``PER_SCENARIO`` tasks of every scenario and its last, and in every scenario
tasks bound by a retry pass (a quarter of all samples at least, where the
answers hold that many) and tasks with no node. For a task bound in its
arrival wave the state just before it is rebuilt (the residents, the arrival
binds before it, the re-tried binds of boundaries up to its chunk, less every
release due by then) and its node has to be the pick; for one bound at
boundary ``b``, the state after the releases due at ``b`` and the re-tried
binds that stand before it in the walk of ``b``; one with no node has to be
infeasible at its arrival and, if it was queued through the last pass, at its
turn in it. Over EVERY task of every scenario, limit 0 each: the codes
agree with the nodes; a re-tried bind failed in a chunk before its boundary
and is no gang member; the queue the answers imply never passes the buffer
and a task is dropped exactly where it was full (the newest);
``retried_binds_out_of_queue_order``: a task that stays queued through a pass
while a task BEHIND it in the walk, asking at least as much of every
resource and tolerating no more, is bound in that pass (the node had the
room at the earlier task's turn, so the walk was not in order);
``releases_not_at_their_boundary``: binds onto a node that, with every bind
held exactly until the boundary the rules above name, stands over its
allocatable at the end of that chunk (an early release; a late or lost one
shows in the samples: a task refused where the rule's state has room).

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place), ``unperturbed`` (every scenario judged
on the base table) and ``arrival_state`` (a re-tried bind judged on the
state at its arrival instead of at its boundary: the check sees
``bind_boundary``).
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from references import greedy_waves as G
from references import greedy_waves_scenarios as GS

PER_SCENARIO = 16
NEVER = 1 << 30
CONTROLS = (None, "bf16", "unperturbed", "arrival_state")


def schedule(tasks: dict, width: int, chunk_waves: int) -> dict:
    """What is static: the arriving tasks' order, wave, slot and chunk, the
    boundaries' start times, and the boundary at which a task bound in its
    arrival wave (or resident) gives its resources back."""
    P = len(tasks["arrival"])
    arriving = np.nonzero(tasks["bound_node"] < 0)[0]
    idx = G.pack_waves(tasks["arrival"][arriving], tasks["group_id"][arriving], width)
    flat = idx.reshape(-1)
    seq = arriving[flat[flat >= 0]]
    slot = np.full(P, -1, np.int64)
    slot[seq] = np.nonzero(flat >= 0)[0]
    chunk = np.where(slot >= 0, slot // (width * chunk_waves), -2)
    starts = tasks["arrival"][arriving[idx[0::chunk_waves, 0]]]  # [chunks] f64
    end = tasks["arrival"] + tasks["duration"].astype(np.float64)
    release = np.maximum(np.searchsorted(starts, end, side="left"), chunk + 2)
    rank = np.full(P, -1, np.int64)
    rank[seq] = np.arange(len(seq))
    return {"seq": seq, "slot": slot, "wave": slot // width, "chunk": chunk,
            "release": np.where(release < len(starts), release, NEVER),
            "rank": rank, "starts": starts, "chunks": len(starts)}


def retried_release(sched: dict, duration: np.ndarray, bound_at: np.ndarray):
    """The boundary at which a task bound by the retry pass of boundary
    ``bound_at`` gives its resources back: float32, at least one later."""
    tb = sched["starts"].astype(np.float32)
    b = np.clip(bound_at, 0, len(tb) - 1)
    at = np.searchsorted(tb, tb[b] + duration.astype(np.float32), side="left")
    at = np.maximum(at, bound_at + 1)
    return np.where(at < len(tb), at, NEVER)


class Held:
    """Per scenario, from its answers: where every bound task sits
    (``assign``: its node in the scenario's whole table), from which chunk
    on and until which boundary. ``node`` is its node in the table choices
    are judged on (``judged_on``: below 0 on a node that takes no task,
    where only a resident can sit: it uses nothing of any node that can be
    chosen, and still counts among its app's tasks in its zone)."""

    def __init__(self, tasks, sched, assign, bind, node=None, zone=None):
        self.assign, self.bind = assign, bind
        self.node = assign if node is None else node
        self.zone = zone
        self.bound = assign >= 0
        retried = bind >= 0
        # the chunk a bind is in the state from: -2 a resident, its own chunk
        # for an arrival bind, the boundary's chunk for a re-tried one
        self.since = np.where(retried, bind, sched["chunk"])
        self.until = np.where(
            retried, retried_release(sched, tasks["duration"], bind),
            sched["release"])
        self.until = np.where(self.bound, self.until, -1)
        # the walk's order among the tasks of one pass
        walk = np.lexsort((sched["rank"], -tasks["priority"]))
        self.turn = np.empty(len(assign), np.int64)
        self.turn[walk] = np.arange(len(assign))


class State:
    """The cluster just before one task is tried, rebuilt from answers: at
    its arrival (``at`` None) or at its turn in the pass of boundary ``at``."""

    def __init__(self, nodes, tasks, sched, held: Held, k, at=None):
        h = held
        arrival_bind = h.bound & (h.bind == -1)
        if at is None:
            c = sched["chunk"][k]
            # a resident's rank is -1: before every arriving task
            before = (h.until > c) & (
                (arrival_bind & (sched["rank"] < sched["rank"][k]))
                | ((h.bind >= 0) & (h.bind <= c)))
        else:
            before = (h.until > at) & (
                (arrival_bind & (sched["chunk"] < at))
                | ((h.bind >= 0) & (h.bind < at))
                | ((h.bind == at) & (h.turn < h.turn[k])))
        same = np.nonzero(before & (tasks["app_id"] == tasks["app_id"][k]))[0]
        self.in_zone = np.bincount(h.zone[same],
                                   minlength=nodes["zones"]).astype(np.float64)
        before = np.nonzero(before & (h.node >= 0))[0]
        where = h.node[before]
        N = len(nodes["cpu"])
        self.used = {
            "cpu": np.bincount(where, tasks["cpu"][before].astype(np.float64), N),
            "mem": np.bincount(where, tasks["mem"][before].astype(np.float64), N),
            "pods": np.bincount(where, minlength=N).astype(np.float64),
        }


def draw(rng, samples: int, sched, held: Held, usable, gang):
    """([(task, boundary or None)], drawn but behind a rolled-back gang) of
    one scenario: ``samples`` / 2 (``PER_SCENARIO`` at least) of all arriving
    tasks and the last one, at their arrival; ``samples`` / 3 of the tasks a
    retry pass bound, at their turn in that pass; ``samples`` / 8 of the
    non-gang tasks with no node, at their arrival and, where one was queued
    through the last pass, at its turn in it."""
    arriving = sched["seq"]
    pick = lambda pool, n: (rng.choice(pool, size=min(n, len(pool)), replace=False)
                            if len(pool) else np.zeros(0, np.int64))
    first = np.append(pick(arriving, max(PER_SCENARIO, samples // 2)), arriving[-1])
    out = [(int(k), None) for k in np.unique(first[usable[first]])]
    behind = int((~usable[first]).sum())
    out += [(int(k), int(held.bind[k]))
            for k in pick(np.nonzero(held.bind >= 0)[0], max(1, samples // 3))]
    none = np.nonzero(~held.bound & (gang == G.PAD) & usable & (sched["rank"] >= 0))[0]
    for k in pick(none, max(1, samples // 8)):
        out.append((int(k), None))
        # queued to the end and in the queue before the last pass (a task
        # that failed in the last chunk joined after it: no pass tried it)
        if held.bind[k] == -2 and sched["chunk"][k] < sched["chunks"] - 1:
            out.append((int(k), sched["chunks"] - 1))
    return out, behind


def queue_rows(tasks, sched, held: Held, gang, buffer: int):
    """Over every task of one scenario: (codes that disagree with the nodes,
    re-tried binds that did not fail in an earlier chunk or are gang members,
    boundaries at which the implied queue passes the buffer, drops that are
    not the newest failures at a full buffer, tasks passed over out of queue
    order)."""
    bind, chunk, C = held.bind, sched["chunk"], sched["chunks"]
    resident = tasks["bound_node"] >= 0
    codes = int((held.bound != (bind >= -1)).sum())
    codes += int((resident & ((bind != -1) | (held.assign != tasks["bound_node"]))).sum())
    codes += int(((gang != G.PAD) & ~held.bound & (bind != -4)).sum())
    codes += int(((gang == G.PAD) & ~held.bound & ~resident & (bind == -4)).sum())
    retried = bind >= 0
    early = int((retried & ((chunk >= bind) | (gang != G.PAD) | resident)).sum())
    # the queue the answers imply: failures of chunk c join after c's waves
    failed = ~resident & (gang == G.PAD) & (bind != -1)
    dropped = failed & (bind == -3)
    fails_c = np.bincount(chunk[failed], minlength=C)
    drops_c = np.bincount(chunk[dropped], minlength=C)
    bound_b = np.bincount(bind[retried], minlength=C)
    over = off_rule = 0
    depth = 0
    room_c = np.zeros(C, np.int64)
    for c in range(C):
        over += int(depth > buffer)
        depth -= int(bound_b[c])
        room_c[c] = buffer - depth
        off_rule += int(drops_c[c] != max(int(fails_c[c]) - int(room_c[c]), 0))
        depth += int(fails_c[c] - drops_c[c])
    # the dropped are the newest: a chunk's failures in arrival order, the
    # first ``room`` of them kept
    order = np.nonzero(failed)[0]
    order = order[np.lexsort((sched["rank"][order], chunk[order]))]
    first = np.concatenate(([0], np.cumsum(fails_c)[:-1]))
    nth = np.arange(len(order)) - first[chunk[order]]
    off_rule += int((dropped[order] != (nth >= room_c[chunk[order]])).sum())
    # passed over: queued through pass b (failed before b, bound later or
    # never, not dropped) while a task behind it in the walk that asks at
    # least as much and tolerates no more was bound in b
    cls = (np.searchsorted(np.unique(tasks["cpu"]), tasks["cpu"]) * 64
           + np.searchsorted(np.unique(tasks["mem"]), tasks["mem"]) * 2
           + tasks["tolerates"])
    kinds = np.unique(cls[failed]) if failed.any() else np.zeros(0, np.int64)
    c_cpu, c_mem, c_tol = kinds // 64, (kinds // 2) % 32, kinds % 2
    # dominates[a, b]: a task of kind b asks at least as much as one of kind a
    dominates = ((c_cpu[None, :] >= c_cpu[:, None]) & (c_mem[None, :] >= c_mem[:, None])
                 & (c_tol[None, :] <= c_tol[:, None]))
    kind_of = np.searchsorted(kinds, cls)
    passed = 0
    waits_until = np.where(retried, bind, C)  # queued through passes < this
    queued = failed & ~dropped
    for b in np.unique(bind[retried]).tolist():
        here = np.nonzero(bind == b)[0]
        last = np.full(len(kinds), -1, np.int64)
        np.maximum.at(last, kind_of[here], held.turn[here])
        behind = np.where(dominates, last[None, :], -1).max(axis=1)
        stay = np.nonzero(queued & (chunk < b) & (waits_until > b))[0]
        passed += int((behind[kind_of[stay]] > held.turn[stay]).sum())
    return codes, early, over, off_rule, passed


def over_allocatable(nodes, tasks, sched, held: Held) -> int:
    """Binds (arrival or re-tried) onto a node that stands over its
    allocatable at the end of the chunk they fall in, every bind held from
    its chunk until exactly the boundary the rules name."""
    N, C = len(nodes["cpu"]), sched["chunks"]
    b = np.nonzero(held.bound)[0]
    row = held.assign[b] * (C + 2)
    since = row + np.clip(held.since[b], -1, None) + 1  # residents: column 0
    until = row + np.minimum(held.until[b], C) + 1
    full = np.zeros((N, C + 2), bool)
    for r, req in (("cpu", tasks["cpu"][b].astype(np.float64)),
                   ("mem", tasks["mem"][b].astype(np.float64)),
                   ("pods", np.ones(len(b)))):
        delta = (np.bincount(since, req, N * (C + 2))
                 - np.bincount(until, req, N * (C + 2))).reshape(N, C + 2)
        use = np.cumsum(delta, axis=1)  # column c + 1: during chunk c
        full |= use > nodes[r][:, None] * (1 + G.FIT_EDGE) + 1e-9
    made = b[tasks["bound_node"][b] < 0]
    return int(full[held.assign[made], held.since[made] + 1].sum())


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit); ``limit`` None is printed for the record."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    base, tasks, eng = trace["nodes"], trace["tasks"], config["engine"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    assigns = np.asarray(answers["assignments"], np.int64)
    binds = np.asarray(answers["bind_boundary"], np.int64)
    buffer = int(answers["retry_buffer"])
    S, P = assigns.shape
    scen = whatif_scenarios.sample(config, len(base["cpu"]), S)
    sched = schedule(tasks, eng["waveWidth"], eng["chunkWaves"])
    gang, resident = tasks["group_id"], tasks["bound_node"] >= 0
    rng = np.random.default_rng(seed)
    short = [[] for _ in range(S)]
    edge = behind = on_blocked = placed_off = 0
    codes = early = over = off_rule = passed = overfull = 0
    n_retried = n_none = 0
    base_table = GS.node_table(base, scen[0])
    for s in range(S):
        own = GS.node_table(base, scen[s])
        made = ~resident & (assigns[s] >= 0)
        on_blocked += int((~GS.takes_tasks(own))[assigns[s][made]].sum())
        placed_off = max(placed_off, abs(int(made.sum()) - int(answers["placed"][s])))
        held_full = Held(tasks, sched, assigns[s], binds[s])
        rows = queue_rows(tasks, sched, held_full, gang, buffer)
        codes, early, over, off_rule, passed = (
            a + b for a, b in zip((codes, early, over, off_rule, passed), rows))
        overfull += over_allocatable(own, tasks, sched, held_full)
        # judged on the table of the nodes that can take a task; a resident
        # of a node that is down or cordoned stays there and counts as used
        # on no node of that table
        nodes, assign = GS.judged_on(
            base_table if control == "unperturbed" else own, assigns[s])
        held = Held(tasks, sched, assigns[s], binds[s], node=assign,
                    zone=base["zone"][np.clip(assigns[s], 0, None)])
        # a rolled-back gang's binds were seen by the slots after it in its
        # wave and are in no answer: those slots cannot be rebuilt
        broken = np.unique(gang[~held_full.bound & (gang != G.PAD)])
        in_broken = np.isin(gang, broken) & (gang != G.PAD)
        first = np.full(int(sched["wave"].max()) + 1, np.iinfo(np.int64).max)
        np.minimum.at(first, sched["wave"][in_broken], sched["slot"][in_broken])
        usable = ~in_broken & ~resident & (sched["slot"] < first[sched["wave"]])
        drawn, lost = draw(rng, samples // S, sched, held_full, usable, gang)
        behind += lost
        for k, at in drawn:
            retried = held_full.bind[k] >= 0
            if control == "arrival_state" and retried:
                at = None
            st = State(nodes, tasks, sched, held, k, at)
            lo, hi, sure, maybe = G.pick(nodes, tasks, trace, st, k, weights)
            if lo is None:
                edge += 1
                continue
            # at its arrival a task bound by a later pass found no node
            choice = int(assign[k]) if (at is not None or not retried
                                        or control == "arrival_state") else G.PAD
            if control == "bf16":
                choice = G.pick_bf16(nodes, tasks, trace, st, k, weights)
            n_retried += int(retried and at is not None)
            n_none += int(choice == G.PAD)
            short[s].append(100.0 if choice == -2 else
                            G.judge(choice, lo, hi, sure, maybe))
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
        ("ref.retried_binds_out_of_queue_order", float(passed),
         limits["retried_binds_out_of_queue_order"]),
        ("ref.releases_not_at_their_boundary", float(overfull),
         limits["releases_not_at_their_boundary"]),
        ("ref.codes_that_disagree_with_the_nodes", float(codes), 0),
        ("ref.retried_binds_not_failed_in_an_earlier_chunk", float(early), 0),
        ("ref.boundaries_with_the_queue_over_the_buffer", float(over), 0),
        ("ref.drops_not_the_newest_at_a_full_buffer", float(off_rule), 0),
        ("ref.placements_on_down_or_injected_taint_nodes", float(on_blocked), 0),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.retried_binds_compared_share",
         n_retried / max(len(pooled), 1), None),
        ("ref.retried_binds_handed_back", float((binds >= 0).sum()), None),
        ("ref.tasks_dropped_at_a_full_buffer", float((binds == -3).sum()), None),
        ("ref.no_node_samples_compared", float(n_none), None),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.samples_on_a_zone_score_edge", float(edge), None),
        ("ref.samples_behind_a_rolled_back_gang", float(behind), None),
    ]
