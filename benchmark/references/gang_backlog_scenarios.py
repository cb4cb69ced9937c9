"""The plain reference for a shared GPU training cluster with a standing job
queue: jobs of 1 to 64 workers that bind whole or not at all, end and free
their GPUs, and WAIT in a queue that is tried again, whole job by whole job,
at every chunk boundary. Numpy only, nothing of the program imported, nothing
the program made taken but its answers: per scenario every pod's node
(``assignments``), the boundary that bound it (``bind_boundary``: -1 its
arrival wave, b >= 0 the retry pass of boundary b, -2 still queued at the end,
-3 dropped at a full buffer) and the small per-scenario counters
(``groups``). The filter, the score, the judgement of one choice on score
intervals and the arrival packer are ``gang_jobs_scenarios``' (a plain
reference of this benchmark).

The rule (``guarantees`` in the configuration's file). A JOB is a pod group
(a pod in none is a job of one); its members carry one arrival time, one
priority and one duration.

1. At arrival: ``gang_jobs_scenarios``' rule. Waves of ``waveWidth`` slots in
   arrival order; a group of at most a wave never split; a wider one from a
   wave's first slot over consecutive waves as ONE transaction: members after
   a failed one still bind tentatively, the verdict falls at the end of the
   wave that holds the last member, and what a rolled-back job took is free
   again for the first pod of the next wave.
2. Into the queue: at the first boundary after its closing wave a rolled-back
   job joins with ALL its members, in QueueSort order (priority descending,
   then the job's arrival, then the member's place in the job). Jobs join in
   arrival order while the buffer (``retryBuffer`` pods) has room for all
   their members; one that finds less is dropped whole, and jobs behind it
   that fit still join.
3. At boundary ``b`` (before chunk ``b`` of ``chunkWaves`` waves; its time
   ``t_b`` is the arrival of the chunk's first pod), in this order: a job
   bound at its arrival is released whole at the first boundary whose time
   reaches ``arrival + duration``, and no earlier than two boundaries after
   the chunk that holds its last member; a job bound by boundary ``b0``'s
   pass at the first boundary whose time reaches ``t_b0 + duration``
   (float32), at least ``b0 + 1``; then the pass.
4. The pass walks the queue in QueueSort order, every job tried exactly as at
   its arrival (members in order, each on the state the binds before it give,
   its own job's tentative binds included; members after a failed one still
   tried). Bound: committed, ``bind_boundary = b``, it leaves the queue.
   Rolled back: what it took is given back before the next job's first
   member, and all its members keep their place. A job behind a blocked one
   is still tried: small jobs backfill past a waiting 64-GPU job.
5. A transaction open across a boundary (an arriving wide job whose waves
   straddle a chunk edge) holds its tentative binds through the boundary's
   releases and pass, and is no candidate for release.

``schedule`` runs the rule over a whole trace on one cluster (tests hold the
program to it pod for pod; ``deployment_counts`` reads the configuration's
targets (a) to (f) off it). ``check`` is teacher-forced on the program's own
answers: for (scenario, pod) pairs drawn from the seed the cluster state at
the pod's turn is rebuilt (every bind held from its chunk or boundary until
exactly the boundary rule 3 names) and
* a pod bound at its arrival has to sit on the pick, a pod with no node at
  its arrival has to belong to a job one of whose members finds no node
  (``gang_jobs_scenarios.judge_at``'s rebuild, on the live state);
* a pod bound by the pass of boundary ``b`` has to sit on the pick at its
  turn in that pass: behind the releases due at ``b``, the jobs that pass
  bound before it in QueueSort order, and its own job's members before it;
* a pod whose job stayed queued THROUGH a pass (a stratum of its own: jobs
  bound by a later pass, so rolled back at least once) has to belong to a job
  one of whose members finds no node at the job's turn in that pass, the
  members before it bound where the reference picks.
Over EVERY pod of every scenario, limit 0 each: the codes agree with the
nodes; no job is split between placed, queued and dropped; a re-tried job
closed in a chunk before its boundary; the queue the answers imply never
passes the buffer and the jobs dropped are the ones rule 2 drops; no node
stands over its allocatable in any resource (``nvidia.com/gpu`` among them)
at the end of any chunk, every bind held exactly as rule 3 says; no pod on a
down or injected-taint node; placed + still queued + dropped = offered; the
per-scenario counters are what the two arrays imply.

Controls, each of which has to come out not correct: ``bf16`` (the reference
in bfloat16 in the program's place), ``never-retried`` (a reference that
never re-tries a group: a group member bound by a pass is not its answer),
``members-singly`` (a reference that re-tries members one by one: a member
that finds a node stays bound whatever its job's other members do) and
``wave-local-pass`` (a reference that judges a wide job wave by wave in the
pass: only the members of a wave in which one failed are rolled back).
"""

from __future__ import annotations

import numpy as np

import whatif_scenarios
from references import gang_jobs_scenarios as GJ

PAD, F = GJ.PAD, GJ.F
RESOURCES = GJ.RESOURCES
NEVER = 1 << 30
CONTROLS = (None, "bf16", "never-retried", "members-singly", "wave-local-pass")
PER_SCENARIO = 6       # arrival samples a scenario, beside its strata
CLASSES = ("1", "2-8", "16", "32", "64")
COUNTERS = ("jobs_bound_arrival", "jobs_bound_pass", "pass_attempts",
            "pass_rollbacks", "dropped_jobs") + tuple(
    f"{k}_{c}" for k in ("bound_pass", "wait_sum", "wait_max") for c in CLASSES)


def size_class(size):
    size = np.asarray(size)
    return ((size > 1).astype(np.int64) + (size > 8) + (size > 16) + (size > 32))


def layout(pods: dict, width: int, chunk_waves: int) -> dict:
    """What is static in a batch: ``gang_jobs_scenarios.order_tried`` and, per
    pod, its slot, chunk, job (the job's first member), place in the job and
    the job's size and closing chunk; the boundaries' times; the boundary at
    which a pod bound at its arrival is released."""
    order = GJ.order_tried(pods, width)
    idx, seq = order["idx"], order["seq"]
    P = len(pods["arrival"])
    flat = idx.reshape(-1)
    slot = np.full(P, -1, np.int64)
    slot[seq] = np.nonzero(flat >= 0)[0]
    chunk = slot // (width * chunk_waves)
    g = pods["gang"]
    head = np.arange(P)
    first = np.full(len(order["size"]), flat.size, np.int64)
    np.minimum.at(first, g[g != PAD], slot[g != PAD])
    job_slot = np.where(g != PAD, first[np.clip(g, 0, None)], slot)
    by_slot = np.full(flat.size, -1, np.int64)
    by_slot[slot] = head
    job = by_slot[job_slot]
    size = np.where(g != PAD, order["size"][np.clip(g, 0, None)], 1)
    closing = np.zeros(P, np.int64)
    np.maximum.at(closing, job, chunk)
    closing = closing[job]
    starts = pods["arrival"][idx[0::chunk_waves, 0]].astype(np.float64)
    end = pods["arrival"] + pods["duration"].astype(np.float64)
    release = np.maximum(np.searchsorted(starts, end, side="left"), closing + 2)
    rank = np.empty(P, np.int64)
    rank[seq] = np.arange(P)
    return {**order, "slot": slot, "chunk": chunk, "job": job,
            "pos": slot - job_slot, "jsize": size, "closing": closing,
            "starts": starts, "chunks": len(starts), "rank": rank,
            "release": np.where(release < len(starts), release, NEVER),
            "chunk_waves": chunk_waves}


def retried_release(lay: dict, duration, bound_at):
    """The boundary at which a pod bound by the pass of boundary ``bound_at``
    is released: float32, at least one later."""
    tb = lay["starts"].astype(np.float32)
    b = np.clip(bound_at, 0, len(tb) - 1)
    at = np.searchsorted(tb, tb[b] + np.asarray(duration, np.float32), side="left")
    at = np.maximum(at, np.asarray(bound_at) + 1)
    return np.where(at < len(tb), at, NEVER)


def walk_key(pods: dict, lay: dict):
    """[P] each pod's place in QueueSort order: priority descending, then the
    job's arrival (its first member's slot), then the member's place."""
    order = np.lexsort((lay["slot"], -pods["priority"].astype(np.int64)))
    key = np.empty(len(order), np.int64)
    key[order] = np.arange(len(order))
    return key


def the_node(nodes, pods, used, k, weights):
    score, _, _, ok = GJ.pick(nodes, pods, used, k, weights)
    return int(np.argmax(np.where(ok, score, -np.inf))) if ok.any() else PAD


# -- the rule, run whole -----------------------------------------------------


def schedule(nodes: dict, pods: dict, width: int, chunk_waves: int,
             buffer: int, weights: dict, stats=None):
    """``(assign [P], bind [P])``: the rule over the whole trace on one
    cluster, the float32 chain's scores as they come. ``stats``, a dict,
    takes the counters (``COUNTERS`` and ``pass_rollbacks_after_bind``,
    ``dropped``, ``depth_max``), the queue's depth and the GPUs in use at
    every boundary, and what the configuration's targets need."""
    N, P = len(nodes["cpu"]), len(pods["arrival"])
    lay = layout(pods, width, chunk_waves)
    key = walk_key(pods, lay)
    used = {r: np.zeros(N, F) for r in RESOURCES}
    assign = np.full(P, PAD, np.int64)
    bind = np.full(P, -3, np.int64)
    until = np.full(P, NEVER, np.int64)
    g_all, wide = pods["gang"], lay["wide"]
    queue: list = []
    n = dict.fromkeys(COUNTERS + ("pass_rollbacks_after_bind", "dropped",
                                  "depth_max", "pass_waves"), 0)
    depth, gpus, fails_of = [], [], np.zeros(P, np.int64)
    txn, txn_all, txn_failed = [], [], False

    def undo(members):
        for k in members:
            GJ.bind(used, pods, k, int(assign[k]), -1)
            assign[k] = PAD

    for c in range(lay["chunks"]):
        # rule 3: releases, then the pass
        due = np.nonzero((until == c) & (assign >= 0))[0]
        for k in due.tolist():
            GJ.bind(used, pods, k, int(assign[k]), -1)
        until[due] = -1
        queue.sort(key=lambda k: key[k])
        n["depth_max"] = max(n["depth_max"], len(queue))
        depth.append(len(queue))
        still, i = [], 0
        while i < len(queue):
            size = int(lay["jsize"][queue[i]])
            members, i = queue[i:i + size], i + size
            n["pass_attempts"] += 1
            n["pass_waves"] += -(-size // width)
            got = []
            for k in members:
                node = the_node(nodes, pods, used, k, weights)
                if node != PAD:
                    assign[k] = node
                    GJ.bind(used, pods, k, node)
                    got.append(k)
            if len(got) < size:
                undo(got)
                n["pass_rollbacks"] += 1
                n["pass_rollbacks_after_bind"] += bool(got and size > width)
                fails_of[members[0]] += 1
                still += members
                continue
            cls = CLASSES[int(size_class(size))]
            wait = c - int(lay["closing"][members[0]])
            n["jobs_bound_pass"] += 1
            n[f"bound_pass_{cls}"] += 1
            n[f"wait_sum_{cls}"] += wait
            n[f"wait_max_{cls}"] = max(n[f"wait_max_{cls}"], wait)
            bind[members] = c
            until[members] = retried_release(lay, pods["duration"][members], c)
        queue = still
        gpus.append(float(used["gpu"].sum()))
        # rule 1: the chunk's waves
        failed = []  # (first slot, members) of the jobs rolled back here
        for w in range(c * chunk_waves, min((c + 1) * chunk_waves,
                                            lay["idx"].shape[0])):
            wave = lay["idx"][w]
            wave = wave[wave >= 0]
            for k in wave.tolist():
                node = the_node(nodes, pods, used, k, weights)
                if node != PAD:
                    assign[k] = node
                    GJ.bind(used, pods, k, node)
            g = g_all[wave]
            carried = (g != PAD) & wide[np.clip(g, 0, None)]
            local = ~carried
            lost = np.unique(lay["job"][wave[local & (assign[wave] < 0)]])
            for j in lost.tolist():
                mem = wave[lay["job"][wave] == j]
                undo(mem[assign[mem] >= 0].tolist())
                failed.append((int(lay["slot"][j]), mem.tolist()))
            ok = wave[local & ~np.isin(lay["job"][wave], lost)]
            bind[ok], until[ok] = -1, lay["release"][ok]
            n["jobs_bound_arrival"] += int((lay["pos"][ok] == 0).sum())
            if carried.any():
                mine = wave[carried]
                txn += mine[assign[mine] >= 0].tolist()
                txn_all += mine.tolist()
                txn_failed |= bool((assign[mine] < 0).any())
                if lay["last"][g_all[mine[0]]] == w:
                    if txn_failed:
                        undo(txn)
                        failed.append((int(lay["slot"][txn_all[0]]), txn_all))
                    else:
                        bind[txn_all], until[txn_all] = -1, lay["release"][txn_all]
                        n["jobs_bound_arrival"] += 1
                    txn, txn_all, txn_failed = [], [], False
        # rule 2: the chunk's rolled-back jobs join, whole, in arrival order
        for _, members in sorted(failed):
            if len(queue) + len(members) <= buffer:
                queue += members
            else:
                n["dropped_jobs"] += 1
                n["dropped"] += len(members)
    bind[queue] = -2
    if stats is not None:
        stats.update(n, depth=depth, gpus_in_use=gpus, fails_of=fails_of,
                     layout=lay)
    return assign, bind


def deployment_counts(nodes: dict, pods: dict, width: int, chunk_waves: int,
                      buffer: int, weights: dict) -> dict:
    """The counts the configuration's file records for scenario 0 (its
    targets (a) to (f)), from ``schedule`` on the unperturbed table."""
    stats: dict = {}
    assign, bind = schedule(nodes, pods, width, chunk_waves, buffer, weights,
                            stats)
    lay = stats["layout"]
    P, C = len(assign), lay["chunks"]
    g = pods["gang"]
    in_wide = (g != PAD) & lay["wide"][np.clip(g, 0, None)]
    head = lay["pos"] == 0
    depth = np.asarray(stats["depth"])
    later = depth[C // 4:]
    by_pass = head & (bind >= 0) & in_wide
    held = np.where(bind >= 0, retried_release(lay, pods["duration"],
                                               np.clip(bind, 0, None)),
                    lay["release"])
    placed = assign >= 0
    released = placed & (held < C)
    gpu = pods["gpu"] > 0
    in_use = np.asarray(stats["gpus_in_use"]) / float(nodes["gpu"].sum())
    peak = np.argsort(depth)[-max(C // 8, 1):]  # the boundaries of the deepest queue
    # GPU-seconds as held (from the bind's boundary to the release boundary,
    # or the batch's end) against as asked (the duration, cut at the end)
    tb = lay["starts"]
    since = np.where(bind >= 0, tb[np.clip(bind, 0, C - 1)], pods["arrival"])
    until = np.where(held < C, tb[np.clip(held, 0, C - 1)], tb[-1])
    until = np.maximum(until, since)
    asked = np.maximum(np.minimum(since + pods["duration"], tb[-1]) - since, 0.0)
    sel = placed & gpu
    out = {k: int(v) for k, v in stats.items() if k in COUNTERS
           or k in ("pass_rollbacks_after_bind", "dropped", "depth_max",
                    "pass_waves")}
    out.update({
        "pods": P, "jobs": int(head.sum()), "waves": int(lay["idx"].shape[0]),
        "boundaries": C, "wide_groups": int(lay["wide"].sum()),
        "pods_in_wide_groups": int(in_wide.sum()),
        "a_pods_in_wide_groups_share": float(in_wide.mean()),
        "b_boundaries_queue_non_empty_share_after_first_quarter":
            float((later > 0).mean()),
        "c_wide_jobs_bound_by_a_pass": int(by_pass.sum()),
        "c_of_them_after_two_failed_passes_share":
            float((stats["fails_of"][by_pass] >= 2).mean()) if by_pass.any() else 0.0,
        "e_placed_pods_released_inside_the_batch_share":
            float(released.sum() / max(placed.sum(), 1)),
        "f_gpu_pods_placed_share": float((placed & gpu).sum() / max(gpu.sum(), 1)),
        "f_gpus_in_use_at_peak_boundaries_share": float(in_use[peak].mean()),
        "gpus_in_use_share_max": float(in_use.max()),
        "gpus_held": float(nodes["gpu"].sum()),
        "gpus_asked": float(pods["gpu"].sum()),
        "placed": int(placed.sum()), "queued_at_end": int((bind == -2).sum()),
        "gpu_hours_held_over_asked": float(
            (pods["gpu"][sel] * (until - since)[sel]).sum()
            / max((pods["gpu"][sel] * asked[sel]).sum(), 1e-9)),
        "chunk_span_s": {"median": float(np.median(np.diff(tb))),
                         "max": float(np.diff(tb).max())},
        "duration_s": {"median": float(np.median(pods["duration"][head])),
                       "mean": float(pods["duration"][head].mean())},
    })
    return out


# -- the check, teacher-forced on the answers ----------------------------------


class Held:
    """One scenario's answers as holdings: every bound pod's node, the chunk
    (arrival bind) or boundary (re-tried bind) it holds from, and the
    boundary that releases it."""

    def __init__(self, pods, lay, assign, bind):
        self.assign, self.bind = assign, bind
        self.bound = assign >= 0
        retried = bind >= 0
        self.since = np.where(retried, bind, lay["chunk"])
        self.until = np.where(
            retried, retried_release(lay, pods["duration"], np.clip(bind, 0, None)),
            lay["release"])
        self.until = np.where(self.bound, self.until, -1)


def used_when(nodes, pods, lay, held: Held, live):
    ks = np.nonzero(live & held.bound)[0]
    return GJ.used_by(nodes, pods, ks, held.assign[ks])


def live_at_wave(lay, held: Held, wave: int):
    """The binds that stand when wave ``wave`` starts: arrival binds of the
    waves before it and re-tried binds of the boundaries up to its chunk,
    less what a boundary up to its chunk released."""
    c = wave // lay["chunk_waves"]
    return (held.until > c) & (
        ((held.bind == -1) & (lay["wave"] < wave))
        | ((held.bind >= 0) & (held.bind <= c)))


def live_at_pass(lay, held: Held, key, b: int, turn: int):
    """... at the turn ``turn`` (a place in QueueSort order) of boundary
    ``b``'s pass: behind the releases due at ``b``."""
    return (held.until > b) & (
        ((held.bind == -1) & (lay["chunk"] < b))
        | ((held.bind >= 0) & (held.bind < b))
        | ((held.bind == b) & (key < turn)))


def replay_waves(nodes, pods, lay, held: Held, key, rolled_g, start: int,
                 stop: int, weights, k=None, control=None, edges=None):
    """Waves ``start`` to ``stop`` replayed on the state the answers give at
    ``start``: a slot whose answer is an arrival bind binds there, a member of
    a job that was rolled back at its arrival binds where the reference
    picks; at a chunk edge the state is rebuilt from the answers (releases
    and the pass of that boundary) with the open job's tentative binds held.
    With ``k``: the judgement of pod ``k`` at its slot (``judge_at``'s);
    without: the tentative binds that stand after wave ``stop``."""
    g_all, wide = pods["gang"], lay["wide"]
    used = used_when(nodes, pods, lay, held, live_at_wave(lay, held, start))
    mine = int(lay["job"][k]) if k is not None else -1
    mine_rolled = k is not None and held.bind[k] != -1
    w_k = int(lay["wave"][k]) if k is not None else -1
    held_open: list = []
    for v in range(start, stop + 1):
        if v > start and v % lay["chunk_waves"] == 0:
            used = used_when(nodes, pods, lay, held, live_at_wave(lay, held, v))
            for j, n in held_open:
                GJ.bind(used, pods, j, n)
        row = lay["idx"][v]
        row = row[row >= 0]
        local_here = []
        for j in row.tolist():
            rebuilt = held.bind[j] != -1  # its job was rolled back at arrival
            if rebuilt or j == k:
                score, lo, hi, ok = GJ.pick(nodes, pods, used, j, weights)
            if j == k and not mine_rolled:
                choice = int(held.assign[k])
                if control == "bf16":
                    choice = GJ.pick_bf16(nodes, pods, used, k, weights)
                return GJ.judge(choice, lo, hi, ok)
            if rebuilt:
                n, certain = GJ.the_pick(score, lo, hi, ok)
                if edges is not None and not certain:
                    edges[0] += 1
                if n == PAD and lay["job"][j] == mine:
                    return 0.0  # the job has a member that fits nowhere
                if n != PAD:
                    GJ.bind(used, pods, j, n)
                    gj = int(g_all[j])
                    (held_open if gj != PAD and wide[gj] else local_here).append((j, n))
            elif held.bind[j] == -1 and held.assign[j] >= 0:
                GJ.bind(used, pods, j, int(held.assign[j]))
        for j, n in local_here:  # a wave-local job rebuilt here is gone again
            GJ.bind(used, pods, j, n, -1)
        # a wide job that closes here and was rolled back gives its binds back
        closing = [(j, n) for j, n in held_open
                   if lay["last"][g_all[j]] == v]
        for j, n in closing:
            GJ.bind(used, pods, j, n, -1)
        held_open = [x for x in held_open if x not in closing]
        if k is not None and mine_rolled and v >= max(
                w_k, int(lay["last"][g_all[k]]) if g_all[k] != PAD else w_k):
            return 100.0  # every member of k's job had a node: nothing to roll back
    return held_open


def span_of(pods, lay, held: Held, wave: int):
    """(first, last) wave to replay for a judgement in wave ``wave``: from the
    first wave of a wide job rolled back at its arrival whose span holds
    ``wave``, else ``wave`` alone."""
    g_all, wide = pods["gang"], lay["wide"]
    for j in lay["idx"][wave]:
        gj = int(g_all[j]) if j >= 0 else PAD
        if gj != PAD and wide[gj] and held.bind[j] != -1:
            return int(lay["first"][gj]), int(lay["last"][gj])
    return wave, wave


def judge_arrival(nodes, pods, lay, held, key, k, weights, control, edges):
    start, stop = span_of(pods, lay, held, int(lay["wave"][k]))
    if held.bind[k] == -1 or pods["gang"][k] == PAD or not lay["wide"][pods["gang"][k]]:
        stop = int(lay["wave"][k])
    return replay_waves(nodes, pods, lay, held, key, None, start, stop, weights,
                        k=k, control=control, edges=edges)


def open_at(nodes, pods, lay, held, key, b: int, weights, cache: dict):
    """The tentative binds that an arriving wide job, rolled back at its
    arrival, holds across boundary ``b`` (rule 5), by the reference's picks."""
    if b not in cache:
        cache[b] = []
        edge = b * lay["chunk_waves"]
        if 0 < edge < lay["idx"].shape[0]:
            first = lay["idx"][edge, 0]
            gj = int(pods["gang"][first]) if first >= 0 else PAD
            if (gj != PAD and lay["wide"][gj] and lay["first"][gj] < edge
                    and held.bind[first] != -1):
                cache[b] = replay_waves(nodes, pods, lay, held, key, None,
                                        int(lay["first"][gj]), edge - 1, weights)
    return cache[b]


def judge_pass(nodes, pods, lay, held: Held, key, k, b, weights, control,
               width, cache, edges):
    """Pod ``k`` at its turn in boundary ``b``'s pass, by how many points its
    answer falls short (0.0 = sound): its job's first member's turn gives the
    state, the members before ``k`` bind as answered (a job that pass bound)
    or where the reference picks (a job that stayed queued through it)."""
    members = np.nonzero(lay["job"] == lay["job"][k])[0]
    members = members[np.argsort(lay["pos"][members])]
    used = used_when(nodes, pods, lay, held,
                     live_at_pass(lay, held, key, b, int(key[members[0]])))
    for j, n in open_at(nodes, pods, lay, held, key, b, weights, cache):
        GJ.bind(used, pods, j, n)
    bound_here = held.bind[k] == b
    if bound_here and control == "never-retried" and len(members) > 1:
        return 100.0  # that reference never tries a group again
    my_wave = int(lay["pos"][k]) // width
    for j in members.tolist():
        score, lo, hi, ok = GJ.pick(nodes, pods, used, j, weights)
        if bound_here:
            if j == k:
                choice = int(held.assign[k])
                if control == "bf16":
                    choice = GJ.pick_bf16(nodes, pods, used, k, weights)
                return GJ.judge(choice, lo, hi, ok)
            GJ.bind(used, pods, j, int(held.assign[j]))
            continue
        n, certain = GJ.the_pick(score, lo, hi, ok)
        if edges is not None and not certain:
            edges[0] += 1
        if control == "members-singly":
            if j == k:
                return 100.0 if n != PAD else 0.0
        elif control == "wave-local-pass" and len(members) > width:
            if n == PAD and int(lay["pos"][j]) // width == my_wave:
                return 0.0
        elif n == PAD:
            return 0.0  # the job has a member that fits nowhere: rolled back
        if n != PAD:
            GJ.bind(used, pods, j, n)
    return 100.0  # every member found a node and the job stayed queued


def implied_queue(pods, lay, key, bind, buffer: int):
    """From one scenario's two arrays: (boundaries at which the implied queue
    passes the buffer, pods whose drop or join is not what rule 2 gives, the
    counters the arrays imply)."""
    C = lay["chunks"]
    head = lay["pos"] == 0
    failed = np.nonzero(head & (bind != -1))[0]
    failed = failed[np.argsort(lay["slot"][failed])]
    by_chunk = [[] for _ in range(C)]
    for j in failed.tolist():
        by_chunk[int(lay["closing"][j])].append(j)
    left_at = np.bincount(bind[head & (bind >= 0)], minlength=C + 1)
    size_left = np.bincount(bind[bind >= 0], minlength=C + 1)
    n = dict.fromkeys(COUNTERS, 0)
    n["jobs_bound_arrival"] = int((head & (bind == -1)).sum())
    depth = jobs_in = over = off_rule = depth_max = 0
    for c in range(C):
        depth_max = max(depth_max, depth)
        over += int(depth > buffer)
        n["pass_attempts"] += jobs_in
        depth -= int(size_left[c])
        jobs_in -= int(left_at[c])
        for j in by_chunk[c]:
            size = int(lay["jsize"][j])
            fits = depth + size <= buffer
            off_rule += int(fits == (bind[j] == -3))
            if fits:
                depth, jobs_in = depth + size, jobs_in + 1
            else:
                n["dropped_jobs"] += 1
    got = np.nonzero(head & (bind >= 0))[0]
    n["jobs_bound_pass"] = len(got)
    n["pass_rollbacks"] = n["pass_attempts"] - len(got)
    wait = bind[got] - lay["closing"][got]
    cls = size_class(lay["jsize"][got])
    for i, name in enumerate(CLASSES):
        n[f"bound_pass_{name}"] = int((cls == i).sum())
        n[f"wait_sum_{name}"] = int(wait[cls == i].sum())
        n[f"wait_max_{name}"] = int(wait[cls == i].max(initial=0))
    return over, off_rule, depth_max, n


def over_allocatable(nodes, pods, lay, held: Held) -> int:
    """Binds onto a node that stands over its allocatable, in any resource,
    at the end of the chunk they fall in, every bind held from its chunk or
    boundary until exactly the boundary rule 3 names."""
    N, C = len(nodes["cpu"]), lay["chunks"]
    b = np.nonzero(held.bound)[0]
    row = held.assign[b] * (C + 1)
    since = row + held.since[b]
    until = row + np.minimum(held.until[b], C)
    full = np.zeros((N, C + 1), bool)
    for r, req in (("cpu", pods["cpu"][b]), ("mem", pods["mem"][b]),
                   ("pods", np.ones(len(b))), ("gpu", pods["gpu"][b])):
        req = req.astype(np.float64)
        delta = (np.bincount(since, req, N * (C + 1))
                 - np.bincount(until, req, N * (C + 1))).reshape(N, C + 1)
        full |= np.cumsum(delta, axis=1) > nodes[r][:, None] + 1e-9
    return int(full[held.assign[b], held.since[b]].sum())


def draw(rng, lay, held: Held, samples: int, width: int):
    """[(pod, None for its arrival or the boundary of a pass)] of one
    scenario: ``PER_SCENARIO`` of all pods and the last one, at their
    arrival; a third of ``samples`` of the members of jobs a pass bound, at
    their turn in it; a third of the members of jobs a LATER pass bound, at
    the pass before it (the job was rolled back there: the stratum of the
    carried transaction in the pass), three in four of them members of jobs
    wider than the wave; the rest pods with no node, at their arrival."""
    P = len(held.assign)
    pick = lambda pool, n: (rng.choice(pool, size=min(n, len(pool)), replace=False)
                            if len(pool) and n > 0 else np.zeros(0, np.int64))
    out = [(int(k), None) for k in np.append(pick(np.arange(P), PER_SCENARIO),
                                             lay["seq"][-1])]
    share = max(1, samples // 3)
    retried = np.nonzero(held.bind >= 0)[0]
    out += [(int(k), int(held.bind[k])) for k in pick(retried, share)]
    waited = retried[held.bind[retried] > lay["closing"][retried] + 1]
    wide = waited[lay["jsize"][waited] > width]
    for pool, n in ((wide, share - share // 4), (waited, share // 4)):
        out += [(int(k), int(held.bind[k]) - 1) for k in pick(pool, n)]
    none = np.nonzero(~held.bound)[0]
    out += [(int(k), None) for k in pick(none, max(1, samples - 3 * share))]
    # a scenario whose strata are short (few jobs waited) is filled up at
    # the pods' arrivals
    out += [(int(k), None) for k in pick(np.arange(P), samples - len(out))]
    return out


def check(trace: dict, config: dict, answers: dict, seed: int,
          samples: int, control=None) -> list:
    """Rows (name, value, limit); ``limit`` None is printed for the record."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    base, pods, eng = trace["nodes"], trace["tasks"], config["engine"]
    weights, limits = config["scheduler"]["weights"], config["limits"]
    width = eng["waveWidth"]
    assigns = np.asarray(answers["assignments"], np.int64)
    binds = np.asarray(answers["bind_boundary"], np.int64)
    buffer = int(answers["retry_buffer"])
    S, P = assigns.shape
    scen = whatif_scenarios.sample(config, len(base["cpu"]), S)
    lay = layout(pods, width, eng["chunkWaves"])
    key = walk_key(pods, lay)
    rng = np.random.default_rng(seed)
    short = [[] for _ in range(S)]
    edges = [0]
    codes = split = early = over = off_rule = overfull = on_blocked = 0
    placed_off = unaccounted = counters_off = 0
    n_pass = n_stay = n_none = 0
    for s in range(S):
        nodes = GJ.node_table(base, scen[s])
        assign, bind = assigns[s], binds[s]
        held = Held(pods, lay, assign, bind)
        codes += int((held.bound != (bind >= -1)).sum() + (bind < -3).sum())
        job = lay["job"]
        split += int((bind != bind[job]).sum() + (held.bound != held.bound[job]).sum())
        early += int(((bind >= 0) & (bind <= lay["closing"])).sum())
        on_blocked += int((~GJ.takes_pods(nodes))[assign[held.bound]].sum())
        placed_off = max(placed_off, abs(int(held.bound.sum())
                                         - int(answers["placed"][s])))
        unaccounted = max(unaccounted, abs(
            int(held.bound.sum() + (bind == -2).sum() + (bind == -3).sum()) - P))
        q_over, q_off, depth_max, implied = implied_queue(pods, lay, key, bind, buffer)
        over, off_rule = over + q_over, off_rule + q_off
        told = answers["groups"]
        counters_off += sum(int(told[k][s]) != v for k, v in implied.items())
        counters_off += int(int(told["depth_max"][s]) != depth_max)
        counters_off += int(int(told["dropped"][s]) != int((bind == -3).sum()))
        overfull += over_allocatable(nodes, pods, lay, held)
        cache: dict = {}
        for k, at in draw(rng, lay, held, samples // S, width):
            if at is None:
                got = judge_arrival(nodes, pods, lay, held, key, k, weights,
                                    control, edges)
                n_none += int(not held.bound[k])
            else:
                got = judge_pass(nodes, pods, lay, held, key, k, at, weights,
                                 control, width, cache, edges)
                n_pass += int(held.bind[k] == at)
                n_stay += int(held.bind[k] != at)
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
        ("ref.codes_that_disagree_with_the_nodes", float(codes), 0),
        ("ref.pods_split_from_their_job", float(split), 0),
        ("ref.retried_jobs_not_closed_in_an_earlier_chunk", float(early), 0),
        ("ref.boundaries_with_the_queue_over_the_buffer", float(over), 0),
        ("ref.jobs_dropped_or_joined_against_the_rule", float(off_rule), 0),
        ("ref.binds_on_a_node_over_its_allocatable", float(overfull), 0),
        ("ref.placements_on_down_or_injected_taint_nodes", float(on_blocked), 0),
        ("ref.placed_differs_from_answers_max", float(placed_off), 0),
        ("ref.pods_unaccounted_for_max", float(unaccounted), 0),
        ("ref.counters_the_arrays_do_not_imply", float(counters_off), 0),
        ("ref.retried_binds_compared", float(n_pass), None),
        ("ref.rolled_back_in_a_pass_compared", float(n_stay), None),
        ("ref.no_node_samples_compared", float(n_none), None),
        ("ref.retried_binds_handed_back", float((binds >= 0).sum()), None),
        ("ref.pods_still_queued", float((binds == -2).sum()), None),
        ("ref.pods_dropped_at_a_full_buffer", float((binds == -3).sum()), None),
        ("ref.worst_scenario_choices_not_the_references_share", worst, None),
        ("ref.choice_short_by_points_max", float(pooled.max()), None),
        ("ref.rebuilt_picks_on_a_score_edge", float(edges[0]), None),
    ]
