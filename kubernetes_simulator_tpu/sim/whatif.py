"""What-if scenario engine (SURVEY.md §3.2): S perturbed cluster states
evaluated as ONE SPMD program.

The reference evaluates scenarios with its per-pod loop, one scenario at a
time ([BASELINE]); here the scenario axis is a ``vmap`` dimension sharded
over the TPU mesh, so ``whatIf(1024 scenarios)`` is a single jitted scan
whose every step evaluates ``[S_local, N]`` masks/scores per pod.

Perturbation DSL (cluster-state perturbations, per [BASELINE]):
- ``scale_capacity(nodes, resource, factor)``
- ``node_down(nodes)`` (allocatable → 0)
- ``add_taint(nodes, key, value, effect)`` (spare taint slots are added)
- ``set_label(nodes, key, value)`` (topology domains are re-derived)

Pod-side tensors are shared across scenarios (the trace is common); only
node-side tensors are stacked ``[S, ...]``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field, replace as dc_replace
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.framework import FrameworkConfig
from ..models.core import Effect
from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..models.state import init_state
from ..ops import tpu as T
from ..ops.release_planes import bf16_parts, release_planes
from ..parallel import dcn
from ..parallel.mesh import (
    SCENARIO_AXIS,
    collective_lines,
    make_mesh,
    replicate_tree,
    replicated,
    scenario_sharding,
    shard_scenario_tree,
    spans_processes,
    tree_bytes,
)
from ..utils.profiling import make_span as _make_span
from ..utils.profiling import register_call as _register_call
from ..utils.profiling import shape_structs as _shape_structs
from ..utils.profiling import stage
from .boundary import BUDGET_COUNTERS, EVICT_KINDS
from .jax_runtime import StepSpec
from .waves import (
    GROUP_COUNTERS, job_table, job_waits, pack_waves, refuse_split_jobs,
    refuse_wide_gangs, widest_gang,
)

# The release program's vmap axis: named so that the rank rounds of a block
# run to ONE trip count, the largest among the scenarios (ops.release_planes).
_RELEASE_VMAP = "release_scenarios"
_RETRY_VMAP = "retry_scenarios"
_EVICT_VMAP = "evict_scenarios"

@dataclass
class Perturbation:
    """One mutation of the base cluster. ``nodes`` is a boolean mask or
    index array over nodes."""

    op: str  # "scale_capacity" | "node_down" | "add_taint" | "set_label"
    nodes: np.ndarray
    resource: Optional[str] = None
    factor: float = 1.0
    key: Optional[str] = None
    value: Optional[str] = None
    effect: str = "NoSchedule"


@dataclass
class Scenario:
    perturbations: List[Perturbation] = field(default_factory=list)
    # Timed failure/recovery timeline (chaos campaigns, round 7): a list
    # of sim.runtime.NodeEvent applied to THIS scenario at the first chunk
    # boundary at or after each event's time: node_down zeroes the node's
    # allocatable and evicts its bound pods (NoExecute) into the retry
    # buffer, node_up gives the node its own allocatable back, empty.
    # Where it runs: with ``retry_buffer > 0`` on the device-release path
    # the eviction is a device program (``WhatIfEngine._evict_fn``: no host
    # mirror, nothing of [S, P] size leaves the device between chunks; a
    # mesh, several processes and capacity_scale are refused there); with
    # ``preemption="kube"`` through the per-scenario host mirrors
    # (``sim.boundary``), capacity_scale too. Static t=0 perturbations
    # above evict nothing and work everywhere. A fourth kind, node_cordon
    # (device path only), closes a node to new binds and starts its drain
    # under ``budget``.
    events: List = field(default_factory=list)
    # A ``sim.runtime.DisruptionBudget``: the scenario's timeline is a
    # maintenance drain. A cordoned node's tasks leave only as fast as each
    # application's ``max_unavailable`` allows, the node goes out when the
    # device finds it empty (back ``out_for`` boundaries later) or, with
    # what it still holds, ``grace`` boundaries after its cordon; a
    # node_down evicts past the budgets and counts against them; a re-bind
    # of an evicted task gives its application's allowance back. Data of
    # the scenario; a batch's budgets share ``app_of`` (the trace's). A
    # scenario without one in a batch that has some is never refused
    # anything (every limit infinite, grace 0).
    budget: Optional[object] = None


class ScenarioSet:
    """Stacked [S, ...] node-side tensors for a batch of scenarios."""

    def __init__(self, ec: EncodedCluster, scenarios: Sequence[Scenario],
                 spare_taint_slots: int = 2, keep_host_stacks: bool = False):
        self.ec = ec
        self.num_scenarios = len(scenarios)
        S = self.num_scenarios
        vocab = ec.vocab

        # Spare taint slots so add_taint has room (shared shape across S).
        TT = ec.taint_key.shape[1] + spare_taint_slots
        base_tk = np.full((ec.num_nodes, TT), PAD, np.int32)
        base_tv = np.full((ec.num_nodes, TT), PAD, np.int32)
        base_te = np.zeros((ec.num_nodes, TT), np.int32)
        base_tk[:, : ec.taint_key.shape[1]] = ec.taint_key
        base_tv[:, : ec.taint_key.shape[1]] = ec.taint_kv
        base_te[:, : ec.taint_key.shape[1]] = ec.taint_effect

        alloc = np.repeat(ec.allocatable[None], S, axis=0).copy()
        tk = np.repeat(base_tk[None], S, axis=0).copy()
        tv = np.repeat(base_tv[None], S, axis=0).copy()
        te = np.repeat(base_te[None], S, axis=0).copy()
        lk = np.repeat(ec.node_label_key[None], S, axis=0).copy()
        lv = np.repeat(ec.node_label_kv[None], S, axis=0).copy()
        ln = np.repeat(ec.node_label_num[None], S, axis=0).copy()
        labels_dirty = np.zeros(S, dtype=bool)
        ov_sets: Dict[int, set] = {}  # scenario → perturbed-label node ids

        for si, sc in enumerate(scenarios):
            for pt in sc.perturbations:
                mask = np.zeros(ec.num_nodes, dtype=bool)
                mask[pt.nodes] = True
                if pt.op == "scale_capacity":
                    ri = vocab._r.get(pt.resource)
                    if ri is None:
                        continue
                    alloc[si, mask, ri] = alloc[si, mask, ri] * pt.factor
                elif pt.op == "node_down":
                    alloc[si, mask, :] = 0.0
                elif pt.op == "add_taint":
                    kid = vocab.key(pt.key)
                    kvid = vocab.kv(pt.key, pt.value or "")
                    eff = int(Effect.parse(pt.effect))
                    for n in np.nonzero(mask)[0]:
                        free = np.nonzero(tk[si, n] == PAD)[0]
                        if free.size == 0:
                            raise ValueError("no spare taint slot; raise spare_taint_slots")
                        tk[si, n, free[0]] = kid
                        tv[si, n, free[0]] = kvid
                        te[si, n, free[0]] = eff
                elif pt.op == "set_label":
                    kid = vocab.key(pt.key)
                    kvid = vocab.kv(pt.key, pt.value or "")
                    try:
                        num = float(pt.value)
                    except (TypeError, ValueError):
                        num = np.nan
                    for n in np.nonzero(mask)[0]:
                        slots = np.nonzero(lk[si, n] == kid)[0]
                        slot = slots[0] if slots.size else np.nonzero(lk[si, n] == PAD)[0][0]
                        lk[si, n, slot] = kid
                        lv[si, n, slot] = kvid
                        ln[si, n, slot] = num
                        ov_sets.setdefault(si, set()).add(int(n))
                    labels_dirty[si] = True
                else:
                    raise ValueError(f"unknown perturbation op {pt.op!r}")

        # Re-derive topology domains where labels changed (domain ids are
        # ranks of kv ids among values present — matches the encoder's
        # sorted-unique ordering because kv ids were interned in vocab order;
        # we rank by label VALUE string to stay consistent).
        nd = np.repeat(ec.node_domain[None], S, axis=0).copy()
        ndom = np.repeat(ec.num_domains[None], S, axis=0).copy()
        dirty = np.nonzero(labels_dirty)[0]
        kv_by_topo: Dict[int, np.ndarray] = {}  # ti → [Sd, N] kv ids
        if dirty.size:
            # Vectorized over nodes (the old per-node Python scan was
            # O(S·T·N·slots) and dominated label-perturbation setup).
            n_kv = len(vocab.kvs)
            lk_d = lk[dirty]  # [Sd, N, L]
            lv_d = lv[dirty]
            for ti, tkey in enumerate(vocab.topo_keys):
                kid = vocab._k.get(tkey)
                if kid is None:
                    continue
                # Global string-order position per kv id of this key: the
                # per-scenario dense rank of present values then matches the
                # encoder's sorted-unique ordering.
                kv_of_key = [
                    i for i in range(n_kv) if vocab.kvs[i][0] == tkey
                ]
                kv_of_key.sort(key=lambda i: vocab.kvs[i][1])
                gpos = np.full(n_kv + 1, -1, np.int64)
                for pos, i in enumerate(kv_of_key):
                    gpos[i] = pos
                is_k = lk_d == kid  # [Sd, N, L]
                has = is_k.any(axis=2)
                slot = is_k.argmax(axis=2)
                vals = np.where(
                    has,
                    np.take_along_axis(lv_d, slot[..., None], 2)[..., 0],
                    -1,
                )  # [Sd, N] kv ids
                g = np.where(vals >= 0, gpos[np.clip(vals, 0, n_kv)], -1)
                kv_by_topo[ti] = vals
                for s_i, si in enumerate(dirty):
                    row = g[s_i]
                    present = row >= 0
                    uniq = np.unique(row[present])
                    out = np.full(ec.num_nodes, PAD, np.int32)
                    out[present] = np.searchsorted(uniq, row[present]).astype(
                        np.int32
                    )
                    nd[si, ti] = out
                    ndom[si, ti] = len(uniq)
        self.max_domains = max(int(ndom.max()) if ndom.size else 1, ec.max_domains, 1)
        self.labels_dirty = bool(labels_dirty.any())
        # Per-scenario DynTables (round 3): keep the base (shared)
        # expansion tables and thread tiny per-scenario corrections through
        # the wave step. Domain ids are APPEND-style — existing label values
        # keep their base ids, new values get ids past the base count.
        # Internal ids are semantics-free (all consumers use per-domain
        # counts / existence / sizes), so this differs from a re-encode's
        # rank-style re-derivation without changing any observable result.
        self.dyn = None
        if self.labels_dirty:
            self.dyn = self._build_dyn(
                ec, S, dirty, ov_sets, kv_by_topo
            )
        # Injected PreferNoSchedule taints re-enable the taint score row
        # (StepSpec.taint_score is derived from the base cluster only).
        self.injected_prefer_taint = any(
            pt.op == "add_taint"
            and int(Effect.parse(pt.effect)) == int(Effect.PREFER_NO_SCHEDULE)
            for sc in scenarios
            for pt in sc.perturbations
        )

        # Host copies for the kube boundary passes (labels are excluded
        # by the engine gate, so only alloc/taints vary per scenario).
        self.host_stacks = (
            {"alloc": alloc, "tk": tk, "tv": tv, "te": te}
            if keep_host_stacks
            else None
        )
        self.dc = self._build_dc(ec, S, alloc, lk, lv, ln, tk, tv, te, nd, ndom)

    def host_clusters(self, ec: EncodedCluster) -> List[EncodedCluster]:
        """Per-scenario EncodedCluster twins (requires keep_host_stacks)
        for the kube boundary passes: the CPU plugin path then sees each
        scenario's perturbed allocatable/taints exactly."""
        from dataclasses import replace as dc_replace

        hs = self.host_stacks
        return [
            dc_replace(
                ec,
                allocatable=hs["alloc"][s],
                taint_key=hs["tk"][s],
                taint_kv=hs["tv"][s],
                taint_effect=hs["te"][s],
            )
            for s in range(self.num_scenarios)
        ]

    def _build_dc(self, ec, S, alloc, lk, lv, ln, tk, tv, te, nd, ndom):
        return T.DevCluster(
            allocatable=jnp.asarray(alloc),
            node_label_key=jnp.asarray(lk),
            node_label_kv=jnp.asarray(lv),
            node_label_num=jnp.asarray(ln),
            taint_key=jnp.asarray(tk),
            taint_kv=jnp.asarray(tv),
            taint_effect=jnp.asarray(te),
            node_domain=jnp.asarray(nd),
            num_domains=jnp.asarray(ndom),
            expr_key=jnp.asarray(np.repeat(ec.expr_key[None], S, 0)),
            expr_op=jnp.asarray(np.repeat(ec.expr_op[None], S, 0)),
            expr_vals=jnp.asarray(np.repeat(ec.expr_vals[None], S, 0)),
            expr_num=jnp.asarray(np.repeat(ec.expr_num[None], S, 0)),
            group_topo=jnp.asarray(np.repeat(ec.group_topo[None], S, 0)),
        )

    def _build_dyn(self, ec, S, dirty, ov_sets, kv_by_topo):
        """Append-style per-scenario domain tables (ScenarioDyn docstring).
        All host-side numpy; every array is tiny ([S, G, K] / [S, G, D])."""
        vocab = ec.vocab
        Tn = ec.node_domain.shape[0]
        K = max((len(v) for v in ov_sets.values()), default=0)
        if K == 0:
            return None
        from ..ops.tpu3 import DMAX_COARSE

        dirty_pos = {int(si): i for i, si in enumerate(dirty)}
        # Base value→domain maps per topology (from the base label arrays;
        # vectorized — a per-node Python loop here would re-dominate
        # labels_dirty setup at Borg scale, the round-2 finding).
        base_kv2dom = []
        for ti, tkey in enumerate(vocab.topo_keys):
            m = {}
            kid = vocab._k.get(tkey)
            if kid is not None:
                is_k = ec.node_label_key == kid  # [N, L]
                has = is_k.any(axis=1)
                slot = is_k.argmax(axis=1)
                kvv = np.where(
                    has,
                    np.take_along_axis(ec.node_label_kv, slot[:, None], 1)[:, 0],
                    -1,
                )
                bm = ec.node_domain[ti]
                sel = has & (bm >= 0)
                kv_u, first = np.unique(kvv[sel], return_index=True)
                dom_u = bm[sel][first]
                m = dict(zip(kv_u.tolist(), dom_u.tolist()))
            base_kv2dom.append(m)
        base_nd = [int(ec.num_domains[t]) for t in range(Tn)]
        coarse_t = [base_nd[t] <= DMAX_COARSE for t in range(Tn)]
        # Appended ids for values absent from the base (sorted by kv id —
        # the choice is semantics-free; only counts/existence/size matter).
        app_ids = {}
        Dext = max([nd for t, nd in enumerate(base_nd) if coarse_t[t]] + [1])
        for si, nodes in ov_sets.items():
            s_i = dirty_pos[si]
            for ti in range(Tn):
                kvv = kv_by_topo.get(ti)
                if kvv is None:
                    continue
                newkvs = {
                    int(kvv[s_i, n])
                    for n in nodes
                    if int(kvv[s_i, n]) >= 0
                    and int(kvv[s_i, n]) not in base_kv2dom[ti]
                }
                ids = {
                    kv: base_nd[ti] + r for r, kv in enumerate(sorted(newkvs))
                }
                app_ids[(si, ti)] = ids
                if coarse_t[ti]:
                    Dext = max(Dext, base_nd[ti] + len(ids))
        # Per-domain node counts → existence. Coarse topologies only:
        # host-scale ones (hostname at Borg scale) would make this an
        # O(S·T·N) allocation, and they never change here (host_changed
        # refuses the batch otherwise) — their nd_exist is the base count.
        cnt = np.zeros((S, Tn, Dext), np.int64)
        for t in range(Tn):
            if not coarse_t[t]:
                continue
            bm = ec.node_domain[t]
            labeled = bm[bm >= 0]
            if labeled.size:
                bc = np.bincount(labeled, minlength=Dext)[:Dext]
                cnt[:, t, :] = bc[None, :]
        ov_nodes = np.full((S, K), PAD, np.int32)
        new_tn = np.full((S, Tn, K), float(PAD), np.float32)
        old_tn = np.full((S, Tn, K), float(PAD), np.float32)
        for si, nodes in ov_sets.items():
            s_i = dirty_pos[si]
            nlist = sorted(nodes)
            ov_nodes[si, : len(nlist)] = nlist
            for ti in range(Tn):
                kvv = kv_by_topo.get(ti)
                bm = ec.node_domain[ti]
                for j, n in enumerate(nlist):
                    old = int(bm[n])
                    if kvv is None:
                        newd = old  # topology untouched by any set_label
                    else:
                        kv = int(kvv[s_i, n])
                        if kv < 0:
                            newd = PAD
                        else:
                            newd = base_kv2dom[ti].get(kv)
                            if newd is None:
                                newd = app_ids[(si, ti)][kv]
                    new_tn[si, ti, j] = newd
                    old_tn[si, ti, j] = old
                    if coarse_t[ti] and newd != old:
                        if old >= 0:
                            cnt[si, ti, old] -= 1
                        if newd >= 0:
                            cnt[si, ti, newd] += 1
        ex = cnt > 0
        nd_exist = ex.sum(axis=2)  # [S, Tn]
        for t in range(Tn):
            if not coarse_t[t]:
                nd_exist[:, t] = base_nd[t]  # unchanged (host_changed gate)
        # A perturbation that moves a node's domain under a HOST-scale
        # topology cannot be corrected (host planes are node-space) — the
        # engine refuses the whole batch.
        # PAD-padded slots have new == old == PAD, so the inequality
        # alone suffices.
        host_changed = any(
            not coarse_t[t] and (new_tn[:, t, :] != old_tn[:, t, :]).any()
            for t in range(Tn)
        )
        G = max(ec.num_groups, 1)
        gt = (
            ec.group_topo[:G]
            if ec.group_topo.shape[0] >= G
            else np.full(G, PAD, np.int32)
        )
        ov_gdom = np.full((S, G, K), float(PAD), np.float32)
        ov_old = np.full((S, G, K), float(PAD), np.float32)
        dexist = np.zeros((S, G, Dext), np.float32)  # coarse width only
        sp_w = np.full(
            (S, G), np.float32(np.log(np.float64(2.0))), np.float32
        )  # nd=0 groups: log(0+2), matching _spread_w_table
        for g in range(G):
            t = int(gt[g])
            if t < 0:
                continue
            ov_gdom[:, g, :] = new_tn[:, t, :]
            ov_old[:, g, :] = old_tn[:, t, :]
            if coarse_t[t]:
                dexist[:, g, :] = ex[:, t, :]
            sp_w[:, g] = np.log(
                nd_exist[:, t].astype(np.float64) + 2.0
            ).astype(np.float32)
        dyn = ScenarioDyn(ov_nodes, ov_gdom, ov_old, dexist, sp_w, Dext)
        dyn.host_changed = host_changed
        # Key-presence changes (a node gaining/losing a topology key) are
        # rare; when absent the wave step statically drops the validity-
        # flip half of its correction matmul.
        dyn.has_presence_change = bool(
            ((new_tn >= 0) != (old_tn >= 0)).any()
        )
        return dyn


class ScenarioDyn:
    """Per-scenario domain tables for v3 labels_dirty batches (append-style
    ids; see ScenarioSet). All arrays lead with the scenario axis and are
    tiny relative to the [S, N] planes:

    - ``ov_nodes`` [S, K] i32 — label-perturbed node ids (PAD-padded)
    - ``ov_gdom`` [S, G, K] f32 — the node's NEW domain under each group's
      topology (== base where that topology is unchanged; PAD where the
      group has no topology or the node lacks the key)
    - ``ov_old`` [S, G, K] f32 — the node's BASE domain (PAD likewise)
    - ``dexist`` [S, G, Dcap] f32 — 1.0 where the domain has ≥1 node
    - ``sp_w_g`` [S, G] f32 — upstream log(size+2) with size = number of
      EXISTING domains per scenario (f64 log on host, matching the CPU
      path value-for-value)
    """

    def __init__(self, ov_nodes, ov_gdom, ov_old, dexist, sp_w_g, Dcap):
        self.ov_nodes = ov_nodes
        self.ov_gdom = ov_gdom
        self.ov_old = ov_old
        self.dexist = dexist
        self.sp_w_g = sp_w_g
        self.Dcap = int(Dcap)  # required Dcap (base + appended values)

    @property
    def K(self) -> int:
        return self.ov_nodes.shape[1]


class RetryQueue(NamedTuple):
    """What the device retry path carries from one chunk call to the next,
    per scenario (a leading ``[S]`` on every leaf): the pending queue and
    the record of every bind its passes made.

    ``ids`` / ``prio`` / ``dur`` ``[RB]``: the queued tasks in kube's
    QueueSort order (priority descending, then arrival; -1 an empty slot)
    with what a pass needs of each, sorted along so that nothing is
    gathered by task id. ``t_*`` ``[boundaries, ..., RB]``: row ``b`` holds
    what boundary ``b``'s pass bound, by queue position: the task, its node
    (-1: this slot bound nothing), the boundary it releases at (``1 << 30``
    never) and the rows its release rewinds (requests, matched groups, and
    the anti / preferred terms where the trace carries them, else None).
    The record IS the pending-release table: a boundary holds every earlier
    row against its own index, so no bind can lose its release to a full
    list, and the hand-back program writes each re-tried task's node and
    boundary from it into the task-order arrays, on the device
    (``_handback_retry``; the record never comes to the host). ``owed``
    less ``released`` at the end of a run is ``release_leaked`` (0 by
    construction). ``pass_waves`` counts the wave steps the batch's retry
    passes executed: every pass ends with the last queued wave of the
    fullest scenario it is vmapped with, so the count is one number for all
    the scenarios of a device, and ``buffer / W`` a pass is its ceiling.

    Under ``retry_groups`` the record is ONE LOG a scenario and no row a
    boundary: ``t_*`` ``[..., log]`` with ``log = (ceil(tasks / RB) + 1) *
    RB``, a pass APPENDS what it bound (brought to the front in queue order)
    at ``fill``, and ``t_b`` says which boundary's pass that was. A task is
    bound by a pass at most once there (no timeline evicts), so the log
    never runs out; the due releases read the blocks of ``RB`` entries up to
    the fullest scenario's ``fill``: what they cost follows the binds the
    passes made, not the boundaries behind (PERF.md §6, PR 54)."""

    ids: jax.Array
    prio: jax.Array
    dur: jax.Array
    count: jax.Array
    dropped: jax.Array
    depth_max: jax.Array
    owed: jax.Array
    released: jax.Array
    pass_waves: jax.Array
    t_id: jax.Array
    t_node: jax.Array
    t_relb: jax.Array
    t_req: jax.Array
    t_mg: jax.Array
    t_an: Optional[jax.Array] = None
    t_pf: Optional[jax.Array] = None
    t_pw: Optional[jax.Array] = None
    # Only where the scenarios carry timelines: ``[RB]`` beside ``ids``,
    # the boundary that evicted the queued task (-1: it failed at its
    # arrival), sorted along with it.
    ev_at: Optional[jax.Array] = None
    # Only under ``retry_groups`` (a queue entry belongs to a JOB): the
    # counters ``sim.waves.GROUP_COUNTERS`` names, ``[len]`` i32; the
    # boundary whose pass bound each entry of the log; the log's entries.
    gn: Optional[jax.Array] = None
    t_b: Optional[jax.Array] = None
    fill: Optional[jax.Array] = None


_GN = {k: i for i, k in enumerate(GROUP_COUNTERS)}

# The counters of ``EvictState.n``, by place.
_EV_COUNTERS = (
    "logged",         # rows written to the log (the cursor)
    "lost",           # evictions past the victims' slots or the log's room (the run is redone)
    "evictions",      # every task taken off a leaving node
    "evict_gang",     # ... a gang member: not queued, stranded
    "evict_dropped",  # ... that found the buffer full
    "evict_arriving", # ... no resident: it leaves ``placed``
    "evict_retried",  # ... bound by an earlier retry pass (its record row goes)
    "rebound",        # evicted tasks a retry pass bound again
    "rebound_same",   # ... in the pass of the boundary that evicted them
    "rebound_resident",  # ... residents (never counted in ``placed``)
    "wait_sum",       # boundaries from eviction to the re-bind, summed
    "wait_max",       # ... the greatest
)
_EV = {k: i for i, k in enumerate(_EV_COUNTERS)}
_BN = {k: i for i, k in enumerate(BUDGET_COUNTERS)}
# What an entry of a budgeted boundary's node list is (``_stage_budget_events``).
_LK = {"pad": 0, "failure": 1, "new": 2, "cordoned": 3, "deadline": 4}
# ``evict_search`` compares a slot with every block's offset while the blocks
# are no more than this (both Borg cells: 4,077 and 4,096), and in two steps
# past it: one step costs what its blocks cost, the second step's gather does
# not, and they met near 4,800 blocks on the chip (PERF.md §6, PR 50).
_SEARCH_BLOCKS = 4096


class EvictState(NamedTuple):
    """What the device eviction path carries from one boundary to the next,
    per scenario (a leading ``[S]`` on every leaf): ``down [N]`` the nodes
    that TAKE NO BIND now, out or (under budgets) cordoned: their
    allocatable reads 0 in the two chunk programs, which read this one
    mask. ``log [4, cap]`` every eviction in the order made (boundary,
    task, the node it held, the boundary whose pass had bound it or -1),
    ``n`` the counters ``_EV_COUNTERS`` names, ``wait_s`` the virtual
    seconds from eviction to re-bind, summed.

    Only where the scenarios carry budgets (None otherwise, so a batch
    without them keeps its programs): the log has a fifth row, the
    eviction's kind (``sim.boundary.EVICT_KINDS``); ``until [N]`` which of
    the ``down`` are OUT and the boundary they are back at (-1: in service
    or cordoned; ``1 << 30``: failed, back by a ``node_up``); ``out_at
    [N]`` the boundary a cordoned node went out, -1 never
    (``WhatIfResult.node_out_at``); ``unavail [A]`` the tasks of each
    application evicted and not re-bound since (the retry pass gives back
    what it re-binds); ``bn`` the counters ``BUDGET_COUNTERS`` names. The
    planes stay with the eviction program: the retry program is handed the
    state without them."""

    down: jax.Array
    log: jax.Array
    n: jax.Array
    wait_s: jax.Array
    until: Optional[jax.Array] = None
    out_at: Optional[jax.Array] = None
    unavail: Optional[jax.Array] = None
    bn: Optional[jax.Array] = None


def evict_search(vassign, live_v, t_node, live_r, task_v, t_id, nodes, on, E):
    """The candidate search of both eviction programs, one scenario's, under
    ``ksim.evict/Search``: every LIVE bind on a listed node, in the two
    places the device holds a bind, ``vassign [V]`` (whose tasks are
    ``task_v``) then the record's ``t_node`` (whose tasks are ``t_id``),
    brought to the front in the places' order. ``nodes [L]`` is the
    boundary's list, a node at most once, ``on [L]`` the entries that count
    here; ``live_v`` / ``live_r`` say which places still hold their bind.
    ``-> (hv, hr, hits, ok, at, task, walk)``: the hit bit of every place,
    their count, and for each of ``E`` slots whether it holds a candidate,
    its place (``vassign``'s first), its task and its entry of the list; a
    slot past ``hits`` reads place 0, task 0 and entry ``L``.

    * ONE compare pass over the places gives the hit bit, a compare and an
      or a pair (an entry that is off names a node no place holds): a node
      mask read by the place's node is a gather a scenario, 786 ms at the
      Borg cell's shape (PERF.md §6, PR 45). Which entry a place is on is
      worked out for the ``E`` candidates alone, from the candidate's node:
      the list holds a node once.
    * the candidates come to the front BY RANK, with no sort over the places
      and no scatter (one sort of key and source over all places 131.5 ms
      there, ``jnp.nonzero`` 593.8): the places are cut into blocks of 128
      and counted, a slot finds its block by comparing itself with the
      blocks' running offsets and takes the lane of the block's hit row
      whose count within the row is its rank. The block's own offset is the
      largest the slot has reached, a lane of what it holds. Past
      ``_SEARCH_BLOCKS`` blocks the offsets are compared in two steps, rows
      of 128 blocks and then the row's own, read by a gather of its own.
    * a slot GATHERS twice: its block's hit row, and its place's task and
      node from one stacked table (a read by place costs three times a read
      by block: the table is every place). ``scripts/
      chip_forms_evict_search.py`` ranks the forms on the chip at both
      cells' shapes (PERF.md §6, PR 50)."""
    with stage("ksim.evict/Search"):
        L, V = nodes.shape[0], vassign.shape[0]
        slot = jnp.arange(E, dtype=jnp.int32)
        entry = jnp.where(on, nodes, jnp.iinfo(jnp.int32).min)
        listed = lambda x: (
            entry.reshape((L,) + (1,) * x.ndim) == x[None]).any(0)
        hv = listed(vassign) & live_v
        hr = listed(t_node) & live_r
        places = V + t_node.size
        two = -(-places // 128) > _SEARCH_BLOCKS
        pad = -places % (128 * 128 if two else 128)  # whole blocks, whole rows
        cat = lambda v, r, fill: jnp.concatenate(
            [v, r.reshape(-1), jnp.full((pad,), fill, v.dtype)])
        hit = cat(hv, hr, False).reshape(-1, 128)
        count = hit.sum(1, dtype=jnp.int32)
        start = jnp.cumsum(count) - count
        hits = count.sum()
        if two:
            rows = count.reshape(-1, 128).sum(1, dtype=jnp.int32)
            row_start = jnp.cumsum(rows) - rows
            sup = (row_start[None, :] <= slot[:, None]).sum(
                1, dtype=jnp.int32) - 1
            offsets, base = start.reshape(-1, 128)[sup], sup * 128
        else:
            offsets, base = start[None, :], 0
        reached = offsets <= slot[:, None]
        block = base + reached.sum(1, dtype=jnp.int32) - 1
        rank = slot - jnp.where(reached, offsets, 0).max(1)
        row = hit[block]
        upto = jnp.cumsum(row.astype(jnp.int32), axis=1)
        lane = jnp.argmax((upto == rank[:, None] + 1) & row, axis=1).astype(
            jnp.int32)
        ok = slot < hits
        at = jnp.where(ok, block * 128 + lane, 0)
        task, node = jnp.stack(
            [cat(task_v, t_id, 0), cat(vassign, t_node, -1)])[:, at]
        walk = ((node[:, None] == entry)
                * jnp.arange(L, dtype=jnp.int32)).sum(-1, dtype=jnp.int32)
        return (hv, hr, hits, ok, at, jnp.where(ok, task, 0),
                jnp.where(ok, walk, L))


def queued_rows(tasks: T.PackedRows, q, wave_width: int):
    """Everything the retry pass reads of its queue's tasks BY THEIR ID, from
    ONE gather of one packed row a slot: ``tasks`` packs ``(SlotSource,
    ExtraSource, the record's rows)`` (``_stage_dev_rel``'s ``rows``), ``q``
    ``[RB]`` is the queue (``PAD`` an empty slot). ``(slots, extra, rec)``:
    the first two what ``gather_slots_device`` / ``gather_extra_device`` give
    at ``q`` laid out in waves (``[RB / W, W, ...]``), leaf for leaf and bit
    for bit; ``rec`` the record's rows ``[RB, ...]``, each ``table[clip(q,
    0)]``. A gather costs by the index on the chip, not by the byte: a column
    at a time this read was 86 ms of a boundary at ``RB`` 8,192 (PERF.md §5)."""
    from ..ops import tpu3 as V3

    src_r, xsrc_r, rec = tasks.take(jnp.clip(q, 0))
    waves = lambda a: a.reshape(
        (q.shape[0] // wave_width, wave_width) + a.shape[1:]
    )
    slots, extra = jax.tree.map(
        waves, (T.slots_of_rows(src_r, q), V3.extra_of_rows(xsrc_r, q))
    )
    return slots, extra, rec


@dataclass
class WhatIfResult:
    placed: np.ndarray  # [S] i32
    unschedulable: np.ndarray  # [S] i32
    total_placed: int
    wall_clock_s: float
    placements_per_sec: float  # aggregate over all scenarios
    # [S, P] when collected. On the device paths the array is the fetched
    # copy itself, read-only and this run's own: copy it to write into it.
    assignments: Optional[np.ndarray] = None
    # Beside ``assignments`` under the device ``retry_buffer``: [S, P] i32,
    # -1 bound in its arrival wave (or resident), b >= 0 bound by the retry
    # pass of boundary b; for a task with no node -2 still queued at the
    # end, -3 dropped at a full buffer, -4 refused at arrival and never
    # queued (a gang member), -5 a gang member evicted with its node and
    # stranded. Of a task's LAST bind where timelines evict. Final as it
    # leaves the device, and read-only as ``assignments`` is.
    bind_boundary: Optional[np.ndarray] = None
    # Where the scenarios carry timelines on the device path: [S, E, 4]
    # i32, every eviction of a scenario in the order made, padded with -1
    # to the longest: (boundary, task, the node it held, the boundary whose
    # retry pass had bound it; -1 its arrival wave or a resident).
    # ``evictions[s]`` rows are filled. Under disruption budgets a row has
    # a fifth number, the eviction's kind (``sim.boundary.EVICT_KINDS``: 0
    # voluntary, 1 forced at a drain's deadline, 2 forced by a node_down).
    eviction_log: Optional[np.ndarray] = None
    # Under disruption budgets: [S, N] i32, the boundary a cordoned node
    # went out (empty, at its deadline, or by a failure), -1 never.
    node_out_at: Optional[np.ndarray] = None
    # Under ``retry_groups``: per counter of ``sim.waves.GROUP_COUNTERS``
    # its [S] i32 (jobs bound at arrival and by a pass, pass attempts and
    # rollbacks, jobs dropped whole), and ``dropped`` (pods) and
    # ``depth_max`` (the queue's greatest depth); and, where the placements
    # are handed back, ``sim.waves.job_waits`` of ``bind_boundary``: per job
    # size of the trace the jobs a pass bound and the boundaries they waited.
    group_counts: Optional[Dict[str, np.ndarray]] = None
    job_waits: Optional[Dict[str, np.ndarray]] = None
    utilization_cpu: Optional[np.ndarray] = None  # [S]
    # Which semantics this batch actually ran under (round 4: two batches
    # evaluated under different semantics must be programmatically
    # distinguishable — advisor round 3).
    completions_on: bool = False
    engine: str = "v3"
    # Per-scenario eviction counts (kube batches, round 5) and
    # retry-buffer drops — nonzero drops mean placements were lost to
    # buffer CAPACITY, not infeasibility (VERDICT r4 weak #2). Round 6:
    # ``retry_dropped`` is reported by EVERY engine that can drop pods —
    # the kube host mirrors AND the non-kube device retry path (its
    # queue counts overflow exactly like the host analogue).
    preemptions: Optional[np.ndarray] = None  # [S] i32
    retry_dropped: Optional[np.ndarray] = None  # [S] i32
    # Per-scenario chaos disruption (timelines: kube batches through the
    # host mirrors, round 7; device retry batches through the eviction
    # program): node_down NoExecute evictions, DISTINCT from
    # scheduler-initiated `preemptions`. `evict_latency_mean` is the mean
    # virtual eviction→re-bind time (boundary-granular).
    evictions: Optional[np.ndarray] = None  # [S] i32
    evict_rescheduled: Optional[np.ndarray] = None  # [S] i32
    evict_stranded: Optional[np.ndarray] = None  # [S] i32
    evict_latency_mean: Optional[np.ndarray] = None  # [S] f64
    # Per-scenario first-bind scheduling-latency quantiles (telemetry
    # layer, kube batches only — the host mirrors are the only per-
    # scenario bind-time carrier; plain/batch paths report None, their
    # placements are all wave placements with latency 0 by construction).
    # NaN where a scenario bound nothing.
    latency_p50: Optional[np.ndarray] = None  # [S] f64
    latency_p90: Optional[np.ndarray] = None  # [S] f64
    latency_p99: Optional[np.ndarray] = None  # [S] f64
    # Per-scenario fragmentation economics (round 13, kube batches only —
    # like the latency quantiles, the host mirrors are the only carrier
    # of per-scenario committed state + pending sets; plain/batch paths
    # report None). Bit-matches the single kube replay's
    # ReplayResult.fragmentation on the same scenario.
    stranded_cpu: Optional[np.ndarray] = None  # [S] f64
    frag_index_cpu: Optional[np.ndarray] = None  # [S] f64
    packing_efficiency: Optional[np.ndarray] = None  # [S] f64
    # Per-scenario ReplayTelemetry (kube batches at series+; else None).
    scenario_telemetry: Optional[list] = None
    # Fleet-merged ReplayTelemetry (round 12): every process's partial
    # telemetry merged via ReplayTelemetry.merge — it rides the ONE
    # end-of-replay gather, never adds a collective. Phase timers are
    # kept distinct per process ("p<pid>/<phase>"); latency/rejection
    # aggregates are exact merges, so the 2-process fleet view bit-matches
    # the single-process oracle (tests/test_dcn.py). None at telemetry
    # granularity "off".
    fleet_telemetry: Optional["ReplayTelemetry"] = None
    # Mesh provenance (round 10): which parallel configuration produced
    # the numbers — bench rounds and tuner runs stamp these so results
    # from different device counts are never silently compared.
    n_devices: int = 1
    mesh_shape: Optional[dict] = None  # {axis_name: size} or None
    # DCN provenance (round 11): how many processes contributed scenario
    # blocks. >1 means run() gathered per-process results exactly once at
    # assembly; n_devices/mesh_shape then describe the GLOBAL device
    # footprint (process_count × local devices).
    process_count: int = 1


class WhatIfEngine:
    """Batched scenario evaluation: ``vmap`` over local scenarios, optional
    mesh sharding over devices (config #3 / #5 shapes)."""

    def __init__(
        self,
        ec: EncodedCluster,
        pods: EncodedPods,
        scenarios: Sequence[Scenario],
        config: Optional[FrameworkConfig] = None,
        wave_width: int = 8,
        chunk_waves: int = 1024,
        mesh=None,
        collect_assignments: bool = False,
        fork_checkpoint: Optional[str] = None,
        preemption: bool = False,
        completions: Optional[bool] = None,
        retry_buffer: int = 0,
        granularity_guard: bool = True,
        telemetry=None,
        policies=None,
        _dcn_recovery: Optional[dict] = None,
        retry_groups: bool = False,
    ):
        """``collect_assignments``: hand back every task's node in
        ``WhatIfResult.assignments`` ([S, P], -1 = none). It picks no
        path: on the device-release path the placements are copied from
        the on-device wave-order buffer once, when the last chunk is done
        (the ``handback`` phase); on the other paths they come from the
        per-chunk choices those paths fetch anyway. With the device
        ``retry_buffer`` a second array comes back beside it,
        ``WhatIfResult.bind_boundary``: which boundary's retry pass bound
        each task (-1: its arrival wave; -2 / -3 / -4: no node, and why);
        the same program merges the re-tried binds into both, so the host
        fetches two final arrays and no record. What the device paths hand
        back are the fetched copies, read-only, every ``run()`` its own.

        ``fork_checkpoint``: path to a JaxReplayEngine checkpoint — the
        what-if FORK POINT (SURVEY.md §5 checkpoint/resume): every scenario
        starts from that replay's mid-trace state and continues with its own
        perturbed cluster over the remaining waves.

        ``completions``: chunk-granular pod completions per scenario (the
        JaxReplayEngine mechanism, applied to each scenario's own
        placements). Default ON since round 3 (``None`` = on): release
        folding runs one chunk behind the device pipeline (boundary b
        sees chunks ≤ b−2 — the one-chunk slack, shared with the greedy
        anchor), so the host-side deltas overlap the in-flight chunk
        instead of stalling it. Requires the v3 engine;
        when a batch with finite durations cannot honor them the engine
        WARNS and reverts to arrivals-only semantics — pass an explicit
        ``completions=True`` to get a ``ValueError`` instead, or read
        ``WhatIfResult.completions_on``. A trace with no finite durations
        runs arrivals-only silently (the semantics are identical).
        Round 5 (VERDICT r4 #4): tier preemption × completions is a
        SUPPORTED batch configuration on the no-mesh path — folds run
        EAGERLY per chunk (evictions must precede the next boundary's
        release decisions; the slack becomes an explicit bind-chunk
        gate), released non-gang pods also drop the per-scenario tier
        planes via compact device-side scatters, and evicted pods never
        release. Under a mesh the batch stays arrivals-only (loudly):
        the eager per-chunk fetch would serialize the scenario axis.
        Anchored by ``greedy_replay(preemption='tier',
        completions_chunk_waves=...)`` per scenario.

        ``retry_buffer`` (round 4): device-path unschedulable RETRY — the
        [K8S] activeQ flush-on-event analogue. Non-gang pods that miss
        placement enter a per-scenario buffer (capacity rounded up to a
        wave multiple; overflow drops the newest); at every chunk
        boundary, after releases apply, one bounded retry pass re-runs
        the normal wave step over the buffer in kube's QueueSort order
        (priority descending, then arrival). Pods placed on retry start
        AT THE BOUNDARY: they release at the first boundary whose start
        time reaches ``t_b + duration`` (f32), at least ``b+1``, however
        many are outstanding: every pass's binds are recorded
        (``RetryQueue``) and each boundary holds the whole record against
        its index (the releases ride the same commit-block core as the
        static lists, so the full default plugin set is covered).
        ``summary()["retry"]`` counts the passes' binds, the drops, the
        queue's depth, ``release_leaked`` and the wave steps the passes
        executed (``pass_waves``: a pass ends with the fullest scenario's
        last queued wave, not with the buffer's). Semantics anchored by
        ``greedy_replay(retry_buffer=...)``. ``retry_groups`` (the scheduler
        profile's, semantics like ``retry_buffer``): a queue entry belongs
        to a JOB (a pod group; a pod in none is a job of one). A job that is
        rolled back joins the queue WHOLE at the boundary after its closing
        wave, or is dropped whole where the buffer lacks room for all of it;
        every pass lays each queued job out from a fresh wave, a job wider
        than the wave over consecutive waves as ONE transaction of the
        pass's own, records its binds only where the verdict is "bound" and
        leaves a rolled-back job where it stood; a job's members are
        released together. With it a group wider than the wave runs with
        completions and the buffer
        (``sim.waves.WIDE_GANG_WITH_RETRY_GROUPS``), and
        ``summary()["retry"]["groups"]`` holds ``sim.waves.GROUP_COUNTERS``.
        Requires the device-release
        completions path without DynTables; 0 = off (the r01–r03
        semantics).

        ``policies`` (round 9, sim.tuner): a [S, len(ops.tpu.POLICY_COLS)]
        f32 array of PER-SCENARIO policy vectors — score-plugin weights
        plus the NodeResourcesFit strategy selector — threaded into the
        score fold as a traced input on the scenario axis. The whole
        population compiles ONCE (only vector VALUES differ per
        scenario); swap values between runs with :meth:`set_policies`.
        Supported on the plain, device-release and host pending-fold
        paths (vmap and mesh); kube/tier preemption, retry_buffer and
        fork checkpoints keep static weights."""
        from .greedy import normalize_preemption
        from .telemetry import TelemetryConfig

        self.telemetry_cfg = TelemetryConfig.resolve(telemetry)
        pmode = normalize_preemption(preemption)
        # "kube" (round 5): the EXACT minimal-victims PostFilter runs in
        # per-scenario HOST boundary passes (sim.boundary) against the
        # plain batched chunk program — each scenario carries its own
        # host mirror of the perturbed cluster, so the decision
        # arithmetic is the CPU engine's verbatim. Sized for small/
        # moderate S (the passes are S× host work per boundary).
        self.kube = pmode == "kube"
        if self.kube:
            if mesh is not None:
                raise ValueError(
                    "kube preemption requires a no-mesh batch (the eager "
                    "per-chunk folds would serialize the scenario axis)"
                )
            if fork_checkpoint is not None:
                raise ValueError(
                    "kube preemption does not support fork checkpoints"
                )
            if not retry_buffer:
                raise ValueError(
                    "preemption='kube' requires retry_buffer > 0 (failed "
                    "pods reach the PostFilter through the boundary retry "
                    "pass)"
                )
            if completions is False:
                raise ValueError(
                    "completions=False is not supported with kube "
                    "preemption (the boundary pass owns releases) — "
                    "same rule as the single-replay engine"
                )
        preemption = pmode == "tier"
        # ---- Multi-host DCN replay (round 11, parallel.dcn) ----
        # Each process takes the contiguous ``jax.process_index()`` block
        # of the scenario axis and runs the ENTIRE chunk loop on it
        # process-locally (the mesh is localized below, the boundary host
        # mirrors exist only for local scenarios, _fetch/_fold touch only
        # addressable shards); the processes meet exactly once per replay,
        # in run()'s end-of-replay gather. Engine-level gates the
        # single-process oracle derives from the FULL scenario list
        # (taint-score enable, bf16 host-plane exactness) are computed
        # here from the full list BEFORE slicing, so the compiled chunk
        # programs — and therefore the results — stay bit-identical
        # across process counts.
        scenarios = list(scenarios)
        self.S_global = len(scenarios)
        self._dcn_sliced = False
        self._dcn_spare = False
        # Round 18 work-stealing queue: run() routes through _run_workqueue
        # instead of the static chunk loop; _dcn_wq_info marks a BLOCK
        # engine built by _wq_exec_block (rides in via _dcn_recovery).
        self._dcn_wq = False
        self._wq_exec_chunks = 0
        self._dcn_recovery = dict(_dcn_recovery) if _dcn_recovery else None
        self._dcn_wq_info = (
            dict(self._dcn_recovery.get("wq") or {})
            if self._dcn_recovery is not None and self._dcn_recovery.get("wq")
            else None
        )
        # Everything a survivor needs to rebuild a DEAD sibling's engine
        # bit-identically (round 15): the FULL scenario list plus the raw
        # ctor knobs. Captured only on the sliced path — recovery re-runs
        # through a second WhatIfEngine with ``_dcn_recovery`` set.
        self._dcn_rebuild: Optional[dict] = None
        self._proc_lo = 0
        self._dcn_prefer_taint = False
        self._dcn_scales_pods = False
        # Full-tensor replications performed by _fetch this run — the
        # round-11 contract pins this at ZERO inside the chunk loop
        # (tests/test_dcn.py): replication may happen at most once per
        # replay, at result assembly, never per chunk.
        self._replicate_count = 0
        nproc = jax.process_count()
        if self._dcn_recovery is not None:
            # Round 15 survivor rebalance: this engine re-executes a dead
            # sibling's contiguous block. Slicing and the engine gates are
            # dictated by the claimant (they were derived from the full
            # list once, in the original ctor) — never re-derived, so the
            # compiled chunk programs match the dead process's exactly.
            lo, hi = (int(x) for x in self._dcn_recovery["block"])
            self._dcn_prefer_taint = bool(
                self._dcn_recovery.get("prefer_taint", False)
            )
            self._dcn_scales_pods = bool(
                self._dcn_recovery.get("scales_pods", False)
            )
            scenarios = scenarios[lo:hi]
            self._proc_lo = lo
            if policies is not None:
                pol_g = np.asarray(policies)
                if pol_g.ndim == 2 and pol_g.shape[0] == self.S_global:
                    policies = pol_g[lo:hi]
        elif nproc > 1 and self.S_global:
            if any(
                pt.op == "set_label"
                for sc in scenarios
                for pt in sc.perturbations
            ):
                raise ValueError(
                    "set_label perturbations are not supported in "
                    "multi-process (DCN) runs: labels_dirty batches "
                    "derive per-scenario domain tables and the engine "
                    "choice from the WHOLE batch, which would diverge "
                    "across process-local slices. Run label sweeps "
                    "single-process, or split them into their own batch."
                )
            workers = dcn.worker_count()
            if self.S_global % workers == 0:
                self._dcn_prefer_taint = any(
                    pt.op == "add_taint"
                    and int(Effect.parse(pt.effect))
                    == int(Effect.PREFER_NO_SCHEDULE)
                    for sc in scenarios
                    for pt in sc.perturbations
                )
                self._dcn_scales_pods = any(
                    pt.op == "scale_capacity"
                    and pt.resource == "pods"
                    and pt.factor > 1
                    for sc in scenarios
                    for pt in sc.perturbations
                )
                sl = dcn.local_slice(self.S_global)
                self._dcn_rebuild = dict(
                    scenarios=list(scenarios),
                    config=config,
                    wave_width=wave_width,
                    chunk_waves=chunk_waves,
                    collect_assignments=collect_assignments,
                    fork_checkpoint=fork_checkpoint,
                    preemption=pmode,
                    completions=completions,
                    retry_buffer=retry_buffer,
                    granularity_guard=granularity_guard,
                    telemetry=telemetry,
                    policies=(
                        None if policies is None else np.asarray(policies)
                    ),
                )
                scenarios = scenarios[sl]
                self._proc_lo = sl.start
                self._dcn_sliced = True
                # Spare processes (KSIM_DCN_SPARES tail pids, round 15)
                # own no block: construction proceeds on the mirrored
                # slice for shapes only; run() skips the chunk loop and
                # sits in the gather as claim-eligible elastic capacity.
                self._dcn_spare = dcn.is_spare()
                # Work-stealing queue (round 18): the slice above is kept
                # only for shapes/compile warm-up parity — run() leases
                # scenario BLOCKS from the KV queue instead of executing
                # the static slice, and every process (workers, spares,
                # joiners) drains the same queue.
                self._dcn_wq = dcn.wq_enabled()
                if policies is not None:
                    pol_g = np.asarray(policies)
                    if pol_g.ndim == 2 and pol_g.shape[0] == self.S_global:
                        policies = pol_g[sl]
            else:
                from ..utils.metrics import log

                log.warning(
                    "DCN: %d scenarios do not divide over %d worker "
                    "processes — running fully replicated (every process "
                    "computes all scenarios; no gather). Pad the batch to "
                    "a multiple of the worker count to scale.",
                    self.S_global, workers,
                )
        mesh = dcn.localize_mesh(mesh)
        # Per-scenario timed failure/recovery timelines (chaos campaigns,
        # round 7), applied at chunk boundaries. Kept as given: validation
        # (``_check_timelines``, once the batch's path is known) enforces
        # time-sortedness, and an unsorted timeline must ERROR, not be
        # silently fixed.
        self._timelines = [
            list(getattr(sc, "events", None) or []) for sc in scenarios
        ]
        self._budgets = [getattr(sc, "budget", None) for sc in scenarios]
        # the applications the programs are compiled for: a batch swapped in
        # without budgets (nothing is refused there) keeps them
        self._budget_proto = next(
            (b for b in self._budgets if b is not None), None)
        self.ec = ec
        self.pods = pods
        self._config = config
        self.spec = StepSpec.from_config(ec, config, pods)
        # "auto": measured optimum is W=8 across shapes (see JaxReplayEngine).
        self.wave_width = wave_width = 8 if wave_width == "auto" else wave_width
        self.chunk_waves = chunk_waves
        self.mesh = mesh
        # Always False after localize_mesh above; result paths branch on
        # this instead of process_count (a local mesh in a DCN run needs
        # no global-array plumbing).
        self._mesh_spans_procs = spans_processes(mesh)
        self.collect_assignments = collect_assignments
        self.fork_checkpoint = fork_checkpoint
        self.sset = ScenarioSet(ec, scenarios, keep_host_stacks=self.kube)
        self.S = self.sset.num_scenarios
        if (
            self.sset.injected_prefer_taint or self._dcn_prefer_taint
        ) and not self.spec.taint_score:
            self.spec = dc_replace(self.spec, taint_score=True)
        if mesh is not None:
            ndev = mesh.devices.size
            if self.S % ndev != 0:
                raise ValueError(f"num scenarios {self.S} must divide over {ndev} devices")
        self.D = max(self.sset.max_domains, 1)
        # The one device engine: what WhatIfResult.engine and the JSONL
        # rows stamp.
        self.engine = "v3"
        # A labels_dirty batch runs on per-scenario domain tables
        # (DynTables; round 3) or not at all: the envelope is the list of
        # reasons below, each one a predicate of the gate.
        self._dyn = None
        if self.sset.labels_dirty:
            # DynTables batches honor completions on the DEVICE-release
            # path since round 4 (per-scenario release domain
            # corrections); off that path the gate below WARNS/raises.
            dyn = self.sset.dyn
            reasons = []
            if dyn is None:
                reasons.append("no DynTables")
            else:
                if dyn.host_changed:
                    reasons.append("host-scale topology change")
                if dyn.K > 32:
                    reasons.append(
                        f">{32} perturbed nodes/scenario (K={dyn.K})"
                    )
            if preemption:
                reasons.append("preemption")
            if fork_checkpoint is not None:
                reasons.append("fork checkpoint")
            if bool((pods.bound_node >= 0).any()):
                reasons.append("pre-bound pods")
            if reasons:
                raise ValueError(
                    "what-if: set_label batch outside the DynTables "
                    f"envelope ({', '.join(reasons)}). Run one single replay "
                    "per scenario instead: apply the scenario's "
                    "perturbations to a copy of the Cluster, encode() it and "
                    "replay it with JaxReplayEngine"
                )
            self._dyn = dyn
        self.preemption = preemption
        if self.kube and self.sset.labels_dirty:
            raise ValueError(
                "kube preemption requires a batch with no label "
                "perturbations (the per-scenario host mirrors share the "
                "base topology-domain tables)"
            )
        if preemption and fork_checkpoint:
            raise ValueError(
                "what-if preemption requires no fork checkpoint"
            )
        if preemption and bool((pods.bound_node >= 0).any()):
            # The aggregate tally cannot distinguish pre-bound victims from
            # replay placements; use JaxReplayEngine for that combination.
            raise ValueError(
                "what-if preemption does not support pre-bound pods"
            )
        from ..ops import tpu3 as V3
        from .jax_runtime import rep_slots_for

        # Perturbations that scale the "pods" capacity can exceed the
        # bf16 host-plane exactness bound.
        scales_pods = self._dcn_scales_pods or any(
            pt.op == "scale_capacity" and pt.resource == "pods" and pt.factor > 1
            for sc in scenarios
            for pt in sc.perturbations
        )
        # Remembered so set_scenarios can refuse a swapped-in batch
        # that needs the f32 host plane this engine was built without.
        self._scales_pods = scales_pods
        self.static3 = V3.V3Static.build(
            ec, pods, self.spec, preemption=preemption,
            allow_bf16_host=not scales_pods,
            dcap_min=(self._dyn.Dcap if self._dyn is not None else 0),
            wave_width=self.wave_width,
        )
        self.shared3 = V3.Shared3.build(ec, self.static3)
        self.rep_slots = rep_slots_for(self.static3, pods)
        if self._dyn is not None:
            d = self._dyn
            self._dyn_dev = V3.DynTables(
                ov_nodes=jnp.asarray(d.ov_nodes),
                ov_gdom=jnp.asarray(d.ov_gdom),
                ov_old=jnp.asarray(d.ov_old),
                dexist=jnp.asarray(d.dexist),
                sp_w_g=jnp.asarray(d.sp_w_g),
            )
        else:
            self._dyn_dev = None
        self._replicate_fn = None
        self._sub_jit = None
        if self._dyn is not None and self.spec.sp_norm_f32:
            # Per-scenario spread weights (appended domains) can exceed the
            # bound under which the f32 normalize division is exactly the
            # integer division — re-validate with the per-scenario maxima
            # and drop the fast form if they might.
            from .jax_runtime import _spread_norm_f32_ok

            sp_w_max = tuple(
                float(x) for x in self._dyn.sp_w_g.max(axis=0)
            )
            if not _spread_norm_f32_ok(sp_w_max, pods):
                self.spec = dc_replace(self.spec, sp_norm_f32=False)
        self.waves = pack_waves(pods, self.wave_width)
        rel = pods.arrival + np.where(
            np.isfinite(pods.duration), pods.duration, np.inf
        )
        self._rel_time = rel
        # Loud, not silent (round 4): a batch that cannot honor the
        # default-on completions WARNS (or raises, when the caller passed
        # an explicit True); the outcome is exposed on the result. A trace
        # with no finite durations is exempt — arrivals-only and
        # completions-on semantics coincide there.
        want = completions is not False  # None (the default) = on
        have_durations = bool(np.isfinite(rel).any())
        # Structural eligibility of the DEVICE-release path (used both
        # for the gate below and to decide whether a DynTables batch can
        # honor completions at all — the host fold path cannot apply
        # per-scenario domain corrections, the device commit blocks can).
        s3 = self.static3
        # Round 10: the device-release path runs UNDER A MESH too —
        # the bucketed release fns and the vassign fold are
        # per-scenario programs, so shard_map wraps them like the
        # chunk program (replicated release tables, sharded
        # state/vassign). Only label-perturbation DynTables batches
        # stay off it there: their per-scenario domain-override
        # corrections would need the override tables threaded through
        # every bucketed release call's shard specs.
        dev_ok = bool(
            not preemption
            and not self.kube  # BoundaryOps owns releases in kube mode
            and fork_checkpoint is None
            and (self.mesh is None or self._dyn is None)
            and s3.single_g[s3.mc_h_ids].all()
            and s3.single_g[s3.anti_h_ids].all()
            and s3.single_g[s3.pref_h_ids].all()
        )
        blockers = []
        # Tier preemption × completions is SUPPORTED since round 5 on the
        # no-mesh batch path (eager eviction-aware host folds, the
        # single-replay round-4 mechanism S-stacked; VERDICT r4 next #4).
        # Under a mesh the eager per-chunk fetch + scatter-applied tier
        # releases would serialize the scenario axis — still arrivals-only
        # there, loudly.
        if preemption and mesh is not None:
            blockers.append("device tier preemption under a mesh")
        if self._dyn is not None and not dev_ok:
            # _dyn is only set with fork_checkpoint None and engine v3,
            # so the failing dev_ok condition is one of these two.
            why = []
            if self.mesh is not None:
                why.append("mesh")
            if not why:
                why.append("non-singleton host-scale count planes")
            blockers.append(
                "labels_dirty DynTables batches off the device-release "
                f"path ({'/'.join(why)} — per-scenario release domain "
                "corrections need the device path)"
            )
        self.completions_on = bool(want and have_durations and not blockers)
        if want and have_durations and blockers:
            msg = (
                "what-if completions cannot be honored with "
                + "; ".join(blockers)
                + " — this batch runs ARRIVALS-ONLY (placed pods never "
                "release resources)"
            )
            if completions is True:
                raise ValueError(msg)
            import warnings

            warnings.warn(msg, stacklevel=2)
        # DEVICE-side releases (round 3, generalized round 4): on the
        # perf path the release bookkeeping lives on device — static
        # per-boundary release lists applied as one-hot commit blocks,
        # placements folded into a wave-order vassign buffer — because
        # ANY per-chunk choice fetch stalls the pipeline. Round 4 widened
        # the envelope to anti/pref planes, multi-topology traces and
        # host-scale rows; the one remaining structural gate is NON-SINGLETON
        # host-scale topologies (their [H, N] planes broadcast a domain
        # aggregate across member nodes — the release delta would need
        # an [N, N]-class regroup; hostname, the host-scale case that
        # exists in practice, is singleton). Everything else keeps the
        # host pending-fold path.
        self._completions_dev = bool(self.completions_on and dev_ok)
        # A pod group wider than the wave runs on the v3 engine's
        # arrivals-only paths (sim.waves.WIDE_GANG_UNSUPPORTED).
        # (but under ``retry_groups``: a standing queue of whole jobs)
        self.retry_groups = bool(retry_groups)
        refuse_wide_gangs(
            self.wave_width, widest_gang(self.pods),
            retry_groups=self.retry_groups,
            completions=self.completions_on,
            retry_buffer=bool(retry_buffer), kube_preemption=self.kube,
            fork_checkpoint=fork_checkpoint is not None,
        )
        if self.completions_on and not self._completions_dev:
            # The device-release fast path is gated — say WHY (VERDICT r4
            # missing #6: the non-singleton host-scale regroup gate was
            # silent; the host pending-fold path honors the same
            # semantics at a measured cost — see COVERAGE.md).
            s3 = self.static3
            why = []
            if self.mesh is not None and self._dyn is not None:
                why.append("mesh with label-perturbation DynTables")
            if preemption:
                why.append("preemption (eager eviction-aware folds)")
            if self.kube:
                why.append(
                    "kube preemption (per-scenario boundary passes own "
                    "the releases)"
                )
            if fork_checkpoint is not None:
                why.append("fork checkpoint")
            if not (
                s3.single_g[s3.mc_h_ids].all()
                and s3.single_g[s3.anti_h_ids].all()
                and s3.single_g[s3.pref_h_ids].all()
            ):
                why.append(
                    "non-singleton host-scale count planes (the release "
                    "delta would need an [N, N]-class regroup)"
                )
            from ..utils.metrics import log

            log.info(
                "what-if completions run on the HOST pending-fold path "
                "(%s) — semantics identical, per-chunk choice fetches "
                "instead of device-side releases",
                "; ".join(why) or "unhandled gate condition",
            )

        if self.completions_on:
            # Granularity-envelope guard (round 5, VERDICT r4 #2): a trace
            # whose durations are ≪ the chunk arrival span silently loses
            # most placements under chunk-granular releases — warn and
            # shrink the chunks toward the duration scale (see
            # sim.granularity). Opt out with granularity_guard=False.
            from .granularity import guard as _gran_guard

            self.chunk_waves, retry_buffer = _gran_guard(
                pods, self.waves.idx, self.chunk_waves, retry_buffer,
                enabled=granularity_guard, engine_name="what-if engine",
            )

        self.retry_buffer = int(retry_buffer)
        if self.retry_buffer:
            # Round up to a wave multiple (the retry pass reuses the
            # normal W-wide wave step).
            self.retry_buffer = (
                -(-self.retry_buffer // wave_width) * wave_width
            )
            if not self.kube and not (self._completions_dev and self._dyn is None):
                # kube mode: the buffer lives in the host BoundaryOps,
                # not the device retry pass — no device-release gate.
                raise ValueError(
                    "retry_buffer requires the device-release completions "
                    "path (v3 engine, finite durations, no "
                    "preemption/fork, singleton host-scale topologies) "
                    "without label-perturbation DynTables (meshes are "
                    "supported since round 10)"
                )
        if self.retry_groups:
            refuse_split_jobs(pods)
            blockers_g = [w for w, on in (
                ("retry_buffer > 0 on the device-release path",
                 not (self.retry_buffer and self._completions_dev)),
                ("no kube preemption", self.kube),
                ("no node timelines", any(self._timelines)),
                ("no mesh", self.mesh is not None),
                ("a chunk that holds the widest job",
                 self.chunk_waves * wave_width < widest_gang(self.pods)),
            ) if on]
            if blockers_g:
                raise ValueError(
                    "retry_groups requires " + ", ".join(blockers_g)
                )
        self._check_timelines(self._timelines, self._budgets)
        # Timelines on the device path: the eviction program, its carry and
        # the queue's ``ev_at`` exist only in such a batch (fixed here: an
        # engine built without them cannot be handed them later).
        self._events_dev = bool(any(self._timelines) and not self.kube)
        # ... under disruption budgets: the program's admission, its node
        # planes and counters (fixed here as well).
        self._budget_on = self._events_dev and any(
            b is not None for b in self._budgets)
        self._evict_stage: Optional[dict] = None
        self._evict_sizes: Optional[dict] = None
        self._evict_scale = 1
        # Host-side completions need per-scenario choices even when the
        # caller only wants counts; the device-release path never fetches
        # them per chunk: asked for placements, it hands its on-device
        # placement buffer back once, at the end of run(). kube mode folds
        # every chunk into the host mirrors.
        self._need_choices = self.kube or (
            not self._completions_dev
            and (collect_assignments or self.completions_on)
        )
        # Per-scenario policy vectors (round 9 tuner). Validated AFTER the
        # retry/granularity resolution above: the gates below read the
        # final self.retry_buffer, not the requested one.
        self._policies = None
        if policies is not None:
            pol = np.asarray(policies, dtype=np.float32)
            K = len(T.POLICY_COLS)
            if pol.ndim != 2 or pol.shape[1] != K:
                raise ValueError(
                    f"policies must be [num_scenarios, {K}] (columns "
                    f"{T.POLICY_COLS}), got shape {pol.shape}"
                )
            if pol.shape[0] != self.S:
                raise ValueError(
                    f"policies rows ({pol.shape[0]}) must match "
                    f"num_scenarios ({self.S})"
                )
            blockers_p = []
            if self.kube:
                blockers_p.append("kube preemption")
            if self.preemption:
                blockers_p.append("tier preemption")
            if self.retry_buffer:
                blockers_p.append("retry_buffer")
            if fork_checkpoint is not None:
                blockers_p.append("fork checkpoints")
            if blockers_p:
                raise ValueError(
                    "per-scenario policies run on the plain/completions "
                    "what-if paths — not supported with "
                    + ", ".join(blockers_p)
                )
            self._policies = pol
        self._rel_fn_cache: Dict[tuple, Callable] = {}
        # run()'s small programs (state and vassign broadcasts, the count
        # reductions, utilization): built at first use, kept here, so a
        # second run() traces and compiles nothing.
        self._run_jits: Dict[str, Callable] = {}
        self._run_calls = 0  # ordinal of the next run()'s root span
        self._rel_core: Optional[Callable] = None
        self._dev_rel_stage: Optional[dict] = None
        # Under a mesh: the scenario tables as put on the devices (static
        # per scenario batch: sharded once, kept); without a fork checkpoint
        # the one initial state and the chunks' wave indices too (static
        # per engine: replicated once, kept); the transfer counters of the
        # batch in flight; and the collectives counted in the compiled
        # chunk, hand-back and gather programs (read once per engine).
        self._dc_mesh = None
        self._state_one = None
        self._state_one_mesh = None
        self._idx_chunks_mesh: Optional[list] = None
        self._mesh_batch: Optional[Dict[str, float]] = None
        self._mesh_collectives: Optional[Dict[str, int]] = None
        self._mesh_programs: Optional[Dict[str, tuple]] = None
        # With a ``retry_buffer`` on the device-release path a boundary is
        # TWO programs: the retry pass (``_retry_fn``, else None), then the
        # chunk.
        self._record_fn = None  # ``retry_groups``: the record's append
        self._retry_fn, self._chunk_fn = self._build_chunk_fns()
        # Device-resident slot sources (one upload per engine): the chunk
        # loop then gathers rows on device — see ops.tpu.SlotSource.
        # Scenario-shared, so under a mesh they replicate ONCE and every
        # device gathers its chunk rows locally (round 10: the mesh path
        # stopped host-gathering slots per chunk).
        srcs = (
            T.SlotSource.build(pods),
            V3.ExtraSource.build(self.static3, pods.num_pods),
        )
        if self.mesh is not None:
            srcs = replicate_tree(self.mesh, srcs)
        self._slot_srcs = srcs

    def _check_timelines(self, timelines, budgets=()) -> None:
        """Validate a batch's per-scenario timelines (``Scenario.events``)
        and budgets (``Scenario.budget``), or say why this engine cannot
        run them. They run where a task can be evicted and offered again:
        through the per-scenario host mirrors (``preemption="kube"``:
        ``node_down`` / ``node_up`` / ``capacity_scale``), or on the device
        retry path (``retry_buffer > 0``: ``node_down`` / ``node_up`` /
        ``node_cordon`` and budgets, one process, no mesh)."""
        budgeted = any(b is not None for b in budgets)
        cordons = any(ev.kind == "node_cordon" for tl in timelines for ev in tl)
        if not any(timelines) and not budgeted:
            return
        reasons = []
        if not self.kube:
            if not self.retry_buffer:
                reasons.append(
                    "retry_buffer > 0 (an evicted task re-enters the "
                    "pending queue and is re-bound by a boundary's retry "
                    "pass; static t=0 Perturbations evict nothing and need "
                    "none)"
                )
            if self.mesh is not None:
                reasons.append("no mesh (the eviction program is unmeshed)")
            if jax.process_count() > 1:
                reasons.append("a single process (no DCN slicing)")
            if any(ev.kind == "capacity_scale" for tl in timelines for ev in tl):
                reasons.append(
                    "no capacity_scale event without preemption='kube' "
                    "(the device path moves nodes out and back, whole)"
                )
        elif cordons or budgeted:
            reasons.append(
                "no preemption='kube' with a node_cordon event or a "
                "disruption budget (the budgeted drain is the device "
                "path's; JaxReplayEngine.replay(budget=...) is its host "
                "twin, one replay at a time)"
            )
        if reasons:
            raise ValueError(
                "per-scenario timed event timelines (Scenario.events: "
                "node_down, node_up, node_cordon, capacity_scale) and "
                "disruption budgets (Scenario.budget) "
                "require " + "; ".join(reasons)
            )
        from .runtime import validate_node_events

        for si, tl in enumerate(timelines):
            try:
                validate_node_events(tl, self.ec.num_nodes)
            except ValueError as e:
                raise ValueError(f"scenario {si}: {e}") from None
        if cordons and not budgeted:
            raise ValueError(
                "a node_cordon event starts a drain under Scenario.budget "
                "(sim.runtime.DisruptionBudget): the batch carries none"
            )
        given = [b for b in budgets if b is not None]
        first = self._budget_proto or (given[0] if given else None)
        for b in given:
            if len(b.app_of) != self.pods.num_pods or len(
                b.max_unavailable
            ) != len(first.max_unavailable) or not np.array_equal(
                b.app_of, first.app_of
            ):
                raise ValueError(
                    "the budgets of a batch share app_of [P] (the trace's "
                    "applications) and the number of applications"
                )

    @property
    def _wide_gangs(self) -> bool:
        """The trace has a pod group wider than the wave: the state carries
        its transaction (``ops.tpu3.GangTxn``)."""
        return self.static3.has_wide_gangs

    def _gangs_summary(self, txn) -> dict:
        """``summary()["gangs"]`` of the batch that just ran: the static
        layout, and the verdicts counted on the device and fetched here
        (summed over the scenarios)."""
        from ..ops import tpu3 as V3

        rolled, undone = self._fetch(
            self._jit_once("gangs", lambda: jax.jit(
                lambda t: jnp.stack([
                    t.log.sum(dtype=jnp.int32), t.undone.sum(dtype=jnp.int32)
                ])
            ))(txn)
        )
        return V3.gangs_summary(self.static3, rolled, undone)

    @property
    def release_path(self) -> Optional[str]:
        """Where this batch's completions release: ``"device"`` (the
        bucketed release program over the on-device placement buffer),
        ``"host"`` (per-chunk choice fetches and host deltas, kube
        boundary passes among them), or None for an arrivals-only batch."""
        if not self.completions_on:
            return None
        return "device" if self._completions_dev else "host"

    def set_policies(self, policies) -> None:
        """Swap the per-scenario policy VECTORS without rebuilding the
        engine: the compiled chunk program takes the vectors as a traced
        [S, K] input, so same-shape updates reuse the executable — the
        round 9 tuner runs its whole search against one compile (pinned
        by tests/test_tuner.py via ``_chunk_fn._cache_size()``)."""
        if self._policies is None:
            raise ValueError(
                "engine was built without policies — pass policies=[S, K] "
                "at construction to enable the policy axis"
            )
        pol = np.asarray(policies, dtype=np.float32)
        # DCN: callers hand the GLOBAL [S_global, K] population; every
        # process slices its own contiguous block (same rows the engine
        # took at construction).
        if (
            self._dcn_sliced
            and pol.ndim == 2
            and pol.shape[0] == self.S_global
            and self.S_global != self.S
        ):
            pol = pol[self._proc_lo : self._proc_lo + self.S]
        if pol.shape != self._policies.shape:
            raise ValueError(
                f"policies shape {pol.shape} must match the engine's "
                f"{self._policies.shape} (the compiled program is "
                "shape-specialized)"
            )
        self._policies = pol

    def set_scenarios(self, scenarios) -> None:
        """Swap the scenario BATCH without rebuilding the engine.

        The compiled chunk program takes the scenario cluster stacks as
        traced ``[S, ...]`` inputs, so a same-shape batch reuses the
        executable exactly like ``set_policies`` reuses it for policy
        vectors — this is what lets a resident ``SimulatorService``
        answer warm queries with zero recompilation. Everything
        per-batch that ``run()`` reads is rebuilt here (``ScenarioSet``
        stacks + chaos timelines); everything baked into the compile
        (shapes, dtypes, engine mode, domain capacity) is checked and
        REFUSED on mismatch rather than silently recompiled.
        """
        if self._dcn_sliced or self._dcn_recovery is not None:
            raise ValueError(
                "set_scenarios is single-process only: a DCN-sliced "
                "engine owns a contiguous block of a global batch and "
                "cannot swap scenarios underneath the slice bookkeeping"
            )
        if self.sset.labels_dirty:
            raise ValueError(
                "set_scenarios does not support engines built with "
                "label perturbations (DynTables are baked per batch) — "
                "rebuild the engine instead"
            )
        scenarios = list(scenarios)
        if len(scenarios) != self.S:
            raise ValueError(
                f"scenario count ({len(scenarios)}) must match the "
                f"engine's ({self.S}) — the compiled program is "
                "shape-specialized"
            )
        timelines = [
            list(getattr(sc, "events", None) or []) for sc in scenarios
        ]
        budgets = [getattr(sc, "budget", None) for sc in scenarios]
        self._check_timelines(timelines, budgets)
        if any(timelines) and not self.kube and not self._events_dev:
            raise ValueError(
                "scenario batch carries timed event timelines but the "
                "engine was built without any (the eviction program and "
                "its carry are compiled in) — rebuild the engine"
            )
        if any(b is not None for b in budgets) and not self._budget_on:
            raise ValueError(
                "scenario batch carries disruption budgets but the engine "
                "was built without any (the admission and its node planes "
                "are compiled in) — rebuild the engine"
            )
        sset = ScenarioSet(self.ec, scenarios, keep_host_stacks=self.kube)
        if sset.labels_dirty:
            raise ValueError(
                "set_scenarios does not support label perturbations "
                "(the swapped batch would need fresh DynTables) — "
                "rebuild the engine instead"
            )
        if max(sset.max_domains, 1) != self.D:
            raise ValueError(
                f"scenario batch needs domain capacity "
                f"{max(sset.max_domains, 1)} but the engine compiled "
                f"with {self.D}"
            )
        if sset.injected_prefer_taint and not self.spec.taint_score:
            raise ValueError(
                "scenario batch injects prefer-taints but the engine "
                "compiled without taint scoring — rebuild the engine"
            )
        if not self._scales_pods and any(
            pt.op == "scale_capacity"
            and pt.resource == "pods"
            and pt.factor > 1
            for sc in scenarios
            for pt in sc.perturbations
        ):
            raise ValueError(
                "scenario batch scales the 'pods' capacity up but the "
                "engine compiled on the bf16 host plane — rebuild the "
                "engine with such a scenario present"
            )

        def _sig(dc):
            return [
                (tuple(x.shape), str(x.dtype))
                for x in jax.tree_util.tree_leaves(dc)
            ]

        if _sig(sset.dc) != _sig(self.sset.dc):
            raise ValueError(
                "scenario batch changes the compiled array shapes/"
                "dtypes — the executable is shape-specialized; rebuild "
                "the engine for this batch"
            )
        self.sset = sset
        self._dc_mesh = None
        self._timelines = timelines
        self._budgets = budgets
        self._evict_stage = None

    def _build_chunk_fns(self):
        """(the retry pass program or None, the chunk program)."""
        collect = self._need_choices
        spec, wave_width = self.spec, self.wave_width
        pol_on = self._policies is not None

        def finalize(fn, axes, donate):
            """jit the vmapped per-scenario program; under a mesh, wrap
            it in shard_map first. shard_map, NOT jit-with-shardings: the
            scenario axis is embarrassingly parallel, and shard_map makes
            that a compile-time guarantee — each device runs the
            per-scenario program on its local slice and the partitioner
            never sees the whole computation. Under GSPMD (jit +
            in_shardings) sharding propagation is free to "help" by
            splitting REPLICATED slot-derived intermediates across
            devices (wave-width-8 axes match the 8-device mesh) and
            gathering them back — real all-gathers inside the chunk scan,
            pinned absent by tests/test_mesh_hlo.py. The shard specs
            derive from the vmap axes one-for-one: mapped (0) arguments
            shard over the scenario axis, broadcast (None) arguments
            replicate."""
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            from jax.sharding import PartitionSpec as P

            sh, rp = P(SCENARIO_AXIS), P()
            return jax.jit(
                jax.shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=tuple(sh if a == 0 else rp for a in axes),
                    out_specs=sh,
                    check_vma=False,
                ),
                donate_argnums=donate,
            )

        from ..ops import tpu3 as V3

        st3, sh3, reps = self.static3, self.shared3, self.rep_slots

        pre_on = self.preemption
        dyn_on = self._dyn_dev is not None
        narrow = self.ec.num_nodes < 2**15 - 1
        dev_rel = self._completions_dev
        dyn_flip = bool(
            self._dyn is not None
            and getattr(self._dyn, "has_presence_change", True)
        )

        def per_scenario(dc, state, slots, extra, dyn=None, wvec=None):
            with stage("ksim.derive"):
                d = T.Derived.build(dc)
                cmasks = V3.class_masks(dc, d, st3, spec, reps)
            wave_step = V3.make_wave_step3(
                dc, d, sh3, st3, wave_width, spec, cmasks, dyn=dyn,
                dyn_flip=dyn_flip, wvec=wvec, scenario_axis=True,
            )

            def step(st, batch):
                st, out = wave_step(st, batch)
                if pre_on:
                    choices, ev_node, ev_tier, ev_prior, ev_total = out
                    placed_w = (
                        jnp.sum((choices >= 0) & batch[0].valid) - ev_prior
                    ).astype(jnp.int32)
                    out = (
                        (choices, ev_node, ev_tier)
                        if collect
                        else placed_w
                    )
                    return st, out
                choices = out
                placed_w = jnp.sum((choices >= 0) & batch[0].valid).astype(jnp.int32)
                if dev_rel:
                    # Device-release path: choices stay ON DEVICE for
                    # the assignment fold; counts ride along.
                    return st, (choices, placed_w)
                if collect and narrow:
                    # Completions fetch choices back every chunk; with
                    # N < 2^15 an int16 stream halves the D2H volume.
                    choices = choices.astype(jnp.int16)
                return st, (choices if collect else placed_w)

            state, outs = jax.lax.scan(step, state, (slots, extra))
            return state, outs

        # Device-side slot gathers INSIDE the jitted program: one
        # dispatch per chunk, only indices as per-chunk input
        # (scenario-shared → gathered once, not per scenario).
        def per_scenario_src(dc, state, src, xsrc, idx, dyn=None, wvec=None):
            with stage("ksim.gather"):
                slots = T.gather_slots_device(src, idx)
                extra = V3.gather_extra_device(xsrc, idx)
            return per_scenario(dc, state, slots, extra, dyn, wvec)

        if self._completions_dev:
            def per_scenario_rel(
                dc, state, src, xsrc, idx, b, vassign, dyn=None,
                wvec=None,
            ):
                # Static releases run in the separate bucketed
                # _release_fn BEFORE this call (ordering by data
                # dependency on state/vassign). Here: the normal
                # chunk scan + the WAVE-ORDER assignment fold —
                # a dynamic_update_slice (pure DMA), not a
                # [C·W]-index scatter: choices land at their flat
                # wave positions, which is exactly how the static
                # release lists address them (rel_pos).
                state, out = per_scenario_src(
                    dc, state, src, xsrc, idx, dyn, wvec
                )
                choices, counts = out
                with stage("ksim.release"):
                    vassign = jax.lax.dynamic_update_slice(
                        vassign,
                        choices.reshape(-1),
                        (b * idx.size,),
                    )
                return state, vassign, counts

            if self.retry_buffer:
                RB = self.retry_buffer
                RBW = RB // wave_width
                BIG = 1 << 30

                rel_core = self._release_core()
                want_an, want_pf = rel_core.want_an, rel_core.want_pf
                ev_on = self._events_dev
                bud_on = self._budget_on

                def derive(dc, down):
                    """What both retry programs build of a scenario's
                    tables: where the scenarios carry timelines a node
                    that is out reads allocatable 0."""
                    if ev_on:
                        dc = dc._replace(allocatable=jnp.where(
                            down[:, None], 0.0, dc.allocatable
                        ))
                    d = T.Derived.build(dc)
                    return dc, d, V3.class_masks(dc, d, st3, spec, reps)

                grp_on = self.retry_groups
                wide_on = st3.has_wide_gangs

                def counted(add):
                    """``RetryQueue.gn``'s vector of the counters named."""
                    return jnp.stack([
                        add.get(k, jnp.int32(0)) for k in GROUP_COUNTERS
                    ])

                def pass_of_jobs(retry_step, state, tasks, q):
                    """The pass under ``retry_groups``: every queued JOB
                    from a fresh wave (member ``m`` in slot ``m % W`` of its
                    wave ``m // W``, the rest of its last wave empty), a job
                    wider than the wave over consecutive waves as ONE
                    transaction of the pass's own (``ops.tpu3.GangTxn``:
                    the arrival scan's, which may stand open across this
                    boundary with its tentative binds in ``used``, is set
                    aside and handed on untouched). ``-> (state, the node
                    each queue entry's job bound it to (PAD: its job was
                    rolled back or stays), the record's rows, the wave
                    steps made, the wide jobs rolled back after a member
                    had bound)``.

                    A job's members stand together in the queue in their
                    order (the upkeep's join and sort keep them so), so a
                    member's place is a running count and no scan: the loop
                    carries the entry its next wave starts at, reads that
                    wave's ``W`` rows as ONE tile of the rows gathered once
                    by task id, and blanks the lanes past the job's end.
                    The trip count is the fullest scenario's waves (PR 46's
                    rule, in waves of this layout)."""
                    W = wave_width
                    with stage("ksim.retry/Gather"):
                        qtab = tasks.table[jnp.clip(q, 0)]
                        _, _, rec_r = tasks.unpack(qtab)
                    with stage("ksim.retry/Layout"):
                        on = q >= 0
                        heads = on & (rec_r["jp"] == 0)
                        trips = jax.lax.pmax(jnp.where(
                            heads, -(-rec_r["js"] // W), 0
                        ).sum(dtype=jnp.int32), _RETRY_VMAP)
                        tiles = jnp.concatenate(
                            [jnp.where(on, q, PAD)[:, None], qtab], axis=1
                        )
                        tiles = jnp.concatenate([tiles, jnp.full(
                            (W, tiles.shape[1]), PAD, jnp.int32
                        )])
                        lane = jnp.arange(W, dtype=jnp.int32)
                        entry = jnp.arange(RB + W, dtype=jnp.int32)
                    arrival_txn = state.txn
                    if wide_on:
                        state = state._replace(txn=V3.GangTxn(
                            plane=jnp.zeros_like(arrival_txn.plane),
                            bound=jnp.int32(0), failed=jnp.bool_(False),
                            log=jnp.zeros((1,), bool), undone=jnp.int32(0),
                        ))

                    def pass_wave(i, carry):
                        st, out, at, first, after = carry
                        with stage("ksim.retry/Close"):
                            tile = jax.lax.dynamic_slice(
                                tiles, (at, 0), (W, tiles.shape[1])
                            )
                            src_t, xsrc_t, rec_t = tasks.unpack(tile[:, 1:])
                            js0, jp0 = rec_t["js"][0], rec_t["jp"][0]
                            n = jnp.where(
                                tile[0, 0] >= 0, jnp.minimum(js0 - jp0, W), W
                            )
                            ids = jnp.where(lane < n, tile[:, 0], PAD)
                            extra = V3.extra_of_rows(xsrc_t, ids)
                            if wide_on:
                                # the pass's log has ONE place: no verdict
                                # of a pass is read from it
                                extra = extra._replace(txn=extra.txn.at[
                                    :, 2].set(0))
                                undone = st.txn.undone
                            first = jnp.where(jp0 == 0, at, first)
                        st, picks = retry_step(
                            st, (T.slots_of_rows(src_t, ids), extra)
                        )
                        with stage("ksim.retry/Close"):
                            # the picks go to the wave's entries by a
                            # compare over the buffer: a write at the
                            # scenario's own cursor is one small write a
                            # scenario, 0.14 ms a wave on the chip
                            # (PERF.md §6, PR 54)
                            hit = entry[None, :] == (at + lane)[:, None]
                            out = jnp.where(hit.any(0), jnp.where(
                                hit, picks.astype(out.dtype)[:, None], 0
                            ).sum(0), out)
                            if wide_on:
                                # a wide job that closed here rolled back
                                # after binding: its members read no node
                                rolled = st.txn.undone > undone
                                out = jnp.where(
                                    rolled & (entry >= first)
                                    & (entry < at + n), PAD, out,
                                )
                                after = after + rolled.astype(jnp.int32)
                        return st, out, at + n, first, after

                    state, out, _, _, after = jax.lax.fori_loop(
                        0, trips, pass_wave, (
                            state, jnp.full((RB + W,), PAD, jnp.int32),
                            jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        ),
                    )
                    state = state._replace(txn=arrival_txn)
                    return state, out[:RB], rec_r, trips, after

                def pass_tally(gn, placed_r, on, rec_r, after_bind):
                    """``RetryQueue.gn`` after a pass: a job's first member
                    speaks for it."""
                    heads = on & (rec_r["jp"] == 0)
                    tally = lambda m: m.sum(dtype=jnp.int32)
                    return gn + counted({
                        "jobs_bound_pass": tally(heads & placed_r),
                        "pass_attempts": tally(heads),
                        "pass_rollbacks": tally(heads & ~placed_r),
                        "pass_rollbacks_after_bind": after_bind,
                    })

                def per_scenario_retry(
                    dc, state, tasks, tbt, t_b, b, rq, ev=None,
                ):
                    """The FIRST of the two programs a boundary of a batch
                    with a ``retry_buffer`` dispatches (semantics:
                    sim.greedy.greedy_replay(retry_buffer=...)); the
                    second is ``per_scenario_arrivals``. Each holds ONE
                    loop that carries ``state``: in one program the pass
                    loop followed by the arrival scan leave the step's
                    node planes in HBM (ROADMAP S2.5). Static releases ran
                    in the separate bucketed _release_fn before this call.
                    Order here: the releases of re-tried binds that are
                    due -> the retry pass over the queue (in QueueSort
                    order since the last boundary's upkeep) and its record
                    (under ``retry_groups`` the pass's row is handed on and
                    ``whatif_record``, a program of its own between the
                    two, appends it to the record's log).
                    ``tasks`` is everything the program reads of a queued
                    task BY ITS ID (``_stage_dev_rel``'s ``rows``: both
                    slot sources and the record's rows as one
                    ``ops.tpu.PackedRows`` table), read ONCE, one row a
                    slot of the queue.
                    ``rq`` is the scenario's ``RetryQueue``; it goes back
                    with the placed marked -1 in ``ids`` and taken off
                    ``count``, ``prio`` / ``dur`` / ``ev_at`` untouched and
                    still aligned, for the upkeep of the second program.
                    EVERY pass ends early: its
                    trip count is read from the queue (the deepest among
                    the scenarios vmapped together, one ``pmax``), never
                    from the buffer's size, and ``rq.pass_waves`` sums the
                    trips. Where the scenarios carry
                    timelines (``ev``, the scenario's ``EvictState``; the
                    eviction program ran before the static releases): a
                    node that is out reads allocatable 0 here, and the
                    pass counts the evicted tasks it binds again. Under
                    disruption budgets (the rows' ``app``, each task's
                    application) such a re-bind gives its application's
                    allowance back: ``ev.unavail`` loses one there, and the
                    next boundary's eviction program reads it."""
                    dc, d, cmasks = derive(dc, ev.down if ev_on else None)
                    # The pass walks the scenario's own queue: its
                    # slots differ by scenario, the arrival scan's do
                    # not (V3.class_row_reads).
                    retry_step = V3.make_wave_step3(
                        dc, d, sh3, st3, wave_width, spec, cmasks,
                        scenario_axis=True, slots_by_scenario=True,
                    )
                    if grp_on:
                        # the record is one log: block ``r`` of ``RB`` entries
                        row = lambda t, r: jax.lax.dynamic_slice_in_dim(
                            t, r * RB, RB, axis=t.ndim - 1
                        )
                        rows_held = jax.lax.pmax(
                            -(-rq.fill // RB), _RETRY_VMAP
                        )
                    else:
                        row = lambda t, r: jax.lax.dynamic_index_in_dim(
                            t, r, keepdims=False
                        )
                        rows_held = b
                        put = lambda t, v: jax.lax.dynamic_update_index_in_dim(
                            t, v.astype(t.dtype), b, 0
                        )
                    none_i = jnp.full((RB, 1), PAD, jnp.int32)
                    none_f = jnp.zeros((RB, 1), jnp.float32)

                    # 1. a re-tried bind is released at the boundary
                    # its record names (t_relb, the f32 comparison made
                    # when it bound): every earlier pass's row is held
                    # against b, however many binds are outstanding (under
                    # ``retry_groups`` every block of the log that holds a
                    # bind, in the fullest scenario).
                    def rel_row(r, carry):
                        st, n = carry
                        due = row(rq.t_relb, r) == b
                        st, _, _ = rel_core(
                            st,
                            jnp.where(due, row(rq.t_node, r), -1),
                            row(rq.t_req, r).T,
                            row(rq.t_mg, r).T,
                            row(rq.t_an, r).T if want_an else none_i,
                            row(rq.t_pf, r).T if want_pf else none_i,
                            row(rq.t_pw, r).T if want_pf else none_f,
                            axis_name=_RETRY_VMAP,
                        )
                        return st, n + due.sum(dtype=jnp.int32)

                    with stage("ksim.release"):
                        state, released = jax.lax.fori_loop(
                            0, rows_held, rel_row, (state, rq.released)
                        )
                    # 2. the retry pass: the NORMAL wave step over the
                    # queue (empty slots are invalid no-ops), then the
                    # record of its binds in row b: task, node, release
                    # boundary (placed pods start NOW: f32 boundary
                    # search, at least b+1) and the rows the release
                    # rewinds.
                    with stage("ksim.retry"):
                        q = rq.ids
                        if grp_on:
                            state, choices_r, rec_r, trips, after_bind = (
                                pass_of_jobs(retry_step, state, tasks, q)
                            )
                        else:
                            with stage("ksim.retry/Gather"):
                                slots_r, extra_r, rec_r = queued_rows(
                                    tasks, q, wave_width
                                )
                            # The queue stands at the front of its buffer
                            # (the sorts put the holes last), so the pass
                            # ends with the fullest scenario's last queued
                            # wave: a buffer sized for the deepest backlog
                            # or an eviction burst costs its steps only
                            # where one is queued, and a wave not walked
                            # reads as no bind.
                            trips = jax.lax.pmax(
                                -(-rq.count // wave_width), _RETRY_VMAP
                            )

                            def pass_wave(i, carry):
                                st, out = carry
                                st, picks = retry_step(st, jax.tree.map(
                                    lambda a: jax.lax.dynamic_index_in_dim(
                                        a, i, keepdims=False
                                    ), (slots_r, extra_r),
                                ))
                                return st, jax.lax.dynamic_update_index_in_dim(
                                    out, picks.astype(out.dtype), i, 0
                                )

                            state, choices_r = jax.lax.fori_loop(
                                0, trips, pass_wave, (state, jnp.full(
                                    (RBW, wave_width), PAD, jnp.int32
                                )),
                            )
                        with stage("ksim.retry/Record"):
                            flat_cr = choices_r.reshape(RB)
                            placed_r = (flat_cr >= 0) & (q >= 0)
                            retry_placed = placed_r.sum(dtype=jnp.int32)
                            rbn = jnp.searchsorted(
                                tbt, t_b + rq.dur, side="left",
                                # one compare a boundary: the default's
                                # bisection took 57 ms over 102 boundaries
                                # on the chip (PERF.md §6, PR 54); the
                                # accepted cells keep their text
                                **({"method": "compare_all"} if grp_on else {}),
                            )
                            relb = jnp.where(
                                placed_r & (rbn < tbt.shape[0]),
                                jnp.maximum(rbn, b + 1),
                                BIG,
                            ).astype(jnp.int32)
                            if grp_on:
                                # The pass's row goes into the record's log
                                # in a program of its own (``whatif_record``,
                                # which reads the rows a release rewinds by
                                # task id itself): written here, or handed
                                # on whole, it takes the on-chip memory the
                                # wave step's loop holds ``allocatable`` in
                                # (PERF.md §6, PR 54).
                                row_r = {
                                    "t_id": jnp.where(placed_r, q, -1),
                                    "t_node": jnp.where(placed_r, flat_cr, -1),
                                    "t_relb": relb,
                                }
                                rq = rq._replace(
                                    ids=jnp.where(placed_r, -1, q),
                                    count=rq.count - retry_placed,
                                    owed=rq.owed
                                    + (relb < BIG).sum(dtype=jnp.int32),
                                    released=released,
                                    depth_max=jnp.maximum(
                                        rq.depth_max, rq.count),
                                    pass_waves=rq.pass_waves + trips,
                                    gn=pass_tally(
                                        rq.gn, placed_r, q >= 0, rec_r,
                                        after_bind),
                                )
                                return state, rq, retry_placed, row_r
                            rq = rq._replace(
                                ids=jnp.where(placed_r, -1, q),
                                count=rq.count - retry_placed,
                                t_id=put(rq.t_id, jnp.where(placed_r, q, -1)),
                                t_node=put(
                                    rq.t_node, jnp.where(placed_r, flat_cr, -1)
                                ),
                                t_relb=put(rq.t_relb, relb),
                                t_req=put(
                                    rq.t_req, slots_r.req.reshape(RB, -1).T
                                ),
                                t_mg=put(rq.t_mg, rec_r["mg"].T),
                                owed=rq.owed
                                + (relb < BIG).sum(dtype=jnp.int32),
                                released=released,
                                depth_max=jnp.maximum(rq.depth_max, rq.count),
                                pass_waves=rq.pass_waves + trips,
                            )
                            if want_an:
                                rq = rq._replace(
                                    t_an=put(rq.t_an, rec_r["an"].T)
                                )
                            if want_pf:
                                rq = rq._replace(
                                    t_pf=put(rq.t_pf, rec_r["pf"].T),
                                    t_pw=put(rq.t_pw, rec_r["pw"].T),
                                )
                            if ev_on:
                                back = placed_r & (rq.ev_at >= 0)
                                wait = jnp.where(back, b - rq.ev_at, 0)
                                tally = lambda m: m.sum(dtype=jnp.int32)
                                delta = {
                                    "rebound": tally(back),
                                    "rebound_same": tally(back & (rq.ev_at == b)),
                                    "rebound_resident": tally(back & rec_r["resd"]),
                                    "wait_sum": wait.sum(dtype=jnp.int32),
                                }
                                n = ev.n + jnp.stack([
                                    delta.get(k, jnp.int32(0)) for k in _EV_COUNTERS
                                ])
                                ev = ev._replace(
                                    n=n.at[_EV["wait_max"]].max(wait.max()),
                                    wait_s=ev.wait_s + jnp.where(
                                        back, t_b - tbt[jnp.clip(rq.ev_at, 0)], 0.0
                                    ).sum(),
                                )
                                if bud_on:
                                    app_r = jnp.where(back, rec_r["app"], -1)
                                    ev = ev._replace(unavail=ev.unavail - (
                                        app_r[:, None] == jnp.arange(
                                            ev.unavail.shape[0], dtype=jnp.int32)
                                    ).sum(0, dtype=jnp.int32))
                    if ev_on:
                        return state, rq, ev, retry_placed
                    return state, rq, retry_placed

                def join_jobs(state, xsrc, jobt, cin, idx, b, choices,
                              vassign, rq):
                    """The queue's upkeep under ``retry_groups``, before
                    its sort: which of the chunk's JOBS join, whole. ``->
                    (the chunk's choices with every verdict applied,
                    vassign, the joining task ids in arrival order (-1:
                    none), their clipped ids, pods failed, rq with its
                    counters)``. The candidates are the chunk's slots
                    behind ``cin``, the members that a job WIDER than the
                    wave left in the chunk before (static per chunk: such a
                    job joins, and is judged, at the boundary after the
                    chunk that closes it). A job failed where its members
                    read no node: a wave-local group's all do, a wide one's
                    verdict is the arrival transaction's log at its
                    ordinal. The verdict is applied to the placement buffer
                    HERE, before any release reads it: a rolled-back wide
                    job's tentative binds go out of the chunk's choices and
                    out of the last places of the chunk before. Jobs join
                    in arrival order while the buffer has room for ALL
                    their members; one that finds less is dropped whole
                    (counted) and those behind it that fit still join: each
                    trip of the loop drops the first job that does not fit
                    behind the ones still standing."""
                    with stage("ksim.retry/Join"):
                        M = cin.shape[0]
                        ext = jnp.concatenate([cin, idx.reshape(-1)])
                        esafe = jnp.clip(ext, 0)
                        jt = jobt[esafe]
                        jsz, jps, jcl = jt[:, 0], jt[:, 1], jt[:, 2]
                        there = ext >= 0
                        ch = choices.reshape(-1)
                        failed = there & jnp.concatenate(
                            [jnp.zeros((M,), bool), ch < 0]
                        )
                        if wide_on:
                            tx = xsrc.txn[esafe]
                            wide_m = there & (tx[:, 0] >= 0)
                            gone = wide_m & (jcl == b) & jnp.take(
                                state.txn.log, tx[:, 2]
                            )
                            failed = jnp.where(wide_m, gone, failed)
                            choices = jnp.where(
                                gone[M:], PAD, ch
                            ).reshape(choices.shape)
                            lo = b * idx.size - M
                            seg = jax.lax.dynamic_slice(vassign, (lo,), (M,))
                            vassign = jax.lax.dynamic_update_slice(
                                vassign, jnp.where(gone[:M], PAD, seg), (lo,)
                            )
                        heads = jps == 0
                        tails = failed & (jps == jsz - 1)
                        room = RB - rq.count
                        place = jnp.arange(ext.shape[0], dtype=jnp.int32)
                        misfit = lambda alive: alive & tails & (
                            jnp.cumsum(alive.astype(jnp.int32)) > room
                        )

                        def drop_first(alive):
                            j = jnp.argmax(misfit(alive)).astype(jnp.int32)
                            return alive & ~(
                                (place > j - jsz[j]) & (place <= j)
                            )

                        take = jax.lax.while_loop(
                            lambda alive: misfit(alive).any(), drop_first,
                            failed,
                        )
                        tally = lambda m: m.sum(dtype=jnp.int32)
                        add = {
                            "jobs_bound_arrival": tally(
                                there & heads & (jcl == b) & ~failed),
                            "dropped_jobs": tally(failed & heads)
                            - tally(take & heads),
                        }
                        rq = rq._replace(gn=rq.gn + counted(add))
                        return (choices, vassign, jnp.where(take, ext, -1),
                                esafe, tally(failed), tally(take), rq)

                def per_scenario_arrivals(
                    dc, state, src, xsrc, durt, priot, idx, b,
                    vassign, rq, down=None, jobt=None, cin=None,
                ):
                    """The SECOND program of a boundary with a
                    ``retry_buffer``, dispatched on the arrays
                    ``per_scenario_retry`` hands back (nothing reaches the
                    host between them): the main chunk scan -> the queue's
                    upkeep (the chunk's failures join behind what the pass
                    left, one stable sort by priority; ``ev_at`` rides
                    it) -> the assignment fold. ``down`` is the
                    ``EvictState``'s where the scenarios carry timelines:
                    a node that is out reads allocatable 0 here too."""
                    dc, d, cmasks = derive(dc, down)
                    wave_step = V3.make_wave_step3(
                        dc, d, sh3, st3, wave_width, spec, cmasks,
                        scenario_axis=True,
                    )
                    # 3. the main chunk scan, as without a queue.
                    slots = T.gather_slots_device(src, idx)
                    extra = V3.gather_extra_device(xsrc, idx)

                    def step(st, batch):
                        st, choices = wave_step(st, batch)
                        placed_w = jnp.sum(
                            (choices >= 0) & batch[0].valid
                        ).astype(jnp.int32)
                        return st, (choices, placed_w)

                    state, (choices, counts) = jax.lax.scan(
                        step, state, (slots, extra)
                    )
                    # 4. the queue's upkeep: the chunk's failed
                    # non-gang tasks join behind the tasks that stay,
                    # in arrival order, as far as there is room
                    # (overflow drops the newest, COUNTED); ONE stable
                    # sort by priority then leaves the queue in kube's
                    # QueueSort order (priority descending, then
                    # arrival) with the holes of the placed at its end.
                    with stage("ksim.retry"):
                        if grp_on:
                            (choices, vassign, joining, rsafe, nfail, njoin,
                             rq) = join_jobs(
                                state, xsrc, jobt, cin, idx, b, choices,
                                vassign, rq,
                            )
                            room = njoin  # what joins is what fits
                        else:
                            rows = idx.reshape(-1)
                            fail = (
                                (choices < 0) & slots.valid & (slots.group < 0)
                            ).reshape(-1)
                            room = RB - rq.count
                            nfail = fail.sum(dtype=jnp.int32)
                            take = fail & (
                                jnp.cumsum(fail.astype(jnp.int32)) <= room
                            )
                            rsafe = jnp.clip(rows, 0)
                            joining = jnp.where(take, rows, -1)
                        cat_ids = jnp.concatenate([rq.ids, joining])
                        cat_prio = jnp.concatenate([rq.prio, priot[rsafe]])
                        cat_dur = jnp.concatenate([rq.dur, durt[rsafe]])
                        key = jnp.where(
                            cat_ids >= 0, -cat_prio, jnp.iinfo(jnp.int32).max
                        )
                        rides = (cat_ids, cat_prio, cat_dur)
                        if ev_on:
                            rides += (jnp.concatenate(
                                [rq.ev_at, jnp.full_like(rows, -1)]
                            ),)
                        _, cat_ids, cat_prio, cat_dur, *cat_ev = jax.lax.sort(
                            (key,) + rides, num_keys=1, is_stable=True,
                        )
                        rq = rq._replace(
                            ids=cat_ids[:RB], prio=cat_prio[:RB],
                            dur=cat_dur[:RB],
                            count=rq.count + jnp.minimum(nfail, room),
                            dropped=rq.dropped
                            + jnp.maximum(nfail - room, 0),
                        )
                        if ev_on:
                            rq = rq._replace(ev_at=cat_ev[0][:RB])
                    # 5. fold arrival-chunk placements at their flat
                    # wave positions (re-tried placements stay in the
                    # queue's record: their arrival slot keeps PAD so
                    # the static release entry never fires).
                    with stage("ksim.release"):
                        vassign = jax.lax.dynamic_update_slice(
                            vassign,
                            choices.reshape(-1),
                            (b * idx.size,),
                        )
                    return state, vassign, rq, counts

                def whatif_record(rq, row, tasks, b):
                    """The THIRD program of a boundary under
                    ``retry_groups``, between the two loop programs: what
                    boundary ``b``'s pass bound (``row``: task, node and
                    release boundary by the pass's queue positions, -1
                    where a slot bound nothing) is brought to the front in
                    queue order and appended to the record's log at
                    ``rq.fill``, with the rows a release rewinds read by
                    task id: one window of ``RB`` entries a scenario at the
                    scenario's own place; the places behind what was bound
                    read "nothing" and are written again by the next
                    pass."""
                    with stage("ksim.retry"), stage("ksim.retry/Record"):
                        # ONE stable sort brings what was bound to the
                        # front with its node and boundary riding (a read
                        # by the sorted place is a gather a column, 10.7
                        # ms each on the chip); the rows a release rewinds
                        # are read ONCE, one packed row a task
                        placed = row["t_node"] >= 0
                        _, t_id, t_node, t_relb = jax.lax.sort(
                            ((~placed).astype(jnp.int32), row["t_id"],
                             row["t_node"], row["t_relb"]),
                            num_keys=1, is_stable=True,
                        )
                        src_e, _, rec_r = tasks.unpack(
                            tasks.table[jnp.clip(t_id, 0)])
                        row = dict(t_id=t_id, t_node=t_node, t_relb=t_relb,
                                   t_req=src_e.requests.T, t_mg=rec_r["mg"].T)
                        if want_an:
                            row["t_an"] = rec_r["an"].T
                        if want_pf:
                            row["t_pf"] = rec_r["pf"].T
                            row["t_pw"] = rec_r["pw"].T
                        put = lambda t, v: jax.lax.dynamic_update_slice_in_dim(
                            t, v.astype(t.dtype), rq.fill, axis=t.ndim - 1,
                        )
                        wrote = {k: put(getattr(rq, k), v)
                                 for k, v in row.items()}
                        return rq._replace(
                            t_b=put(rq.t_b, jnp.full_like(row["t_id"], b)),
                            fill=rq.fill + placed.sum(dtype=jnp.int32),
                            **wrote,
                        )

                if grp_on:
                    self._record_fn = finalize(
                        jax.vmap(whatif_record, in_axes=(0, 0, None, None)),
                        (0, 0, None, None), (0,),
                    )
                axes_retry = (0, 0) + (None,) * 4 + (0,) + (
                    (0,) if ev_on else ()
                )
                axes_arr = (0, 0) + (None,) * 6 + (0, 0) + (
                    (0,) if ev_on else ()
                ) + ((None,) * 3 if grp_on else ())  # no ``down``, jobt, cin
                return finalize(
                    jax.vmap(
                        per_scenario_retry, in_axes=axes_retry,
                        axis_name=_RETRY_VMAP,
                    ),
                    axes_retry, (1, 6) + ((7,) if ev_on else ()),
                ), finalize(
                    jax.vmap(per_scenario_arrivals, in_axes=axes_arr),
                    axes_arr, (1, 8, 9),
                )

            # vmap matches in_axes against the args actually
            # passed; with policies on, a literal None rides the
            # dyn slot (no leaves — its axis spec is inert) and
            # the [S, K] policy matrix maps on axis 0.
            axes_rel = [0, 0, None, None, None, None, 0]
            if dyn_on:
                axes_rel.append(0)
            elif pol_on:
                axes_rel.append(None)
            if pol_on:
                axes_rel.append(0)
            vmapped_rel = jax.vmap(
                per_scenario_rel, in_axes=tuple(axes_rel)
            )
            return None, finalize(vmapped_rel, tuple(axes_rel), (1, 6))
        # vmap matches in_axes against the args actually passed,
        # so the defaulted dyn arg needs no wrapper.
        axes_src = [0, 0, None, None, None]
        if dyn_on:
            axes_src.append(0)
        elif pol_on:
            axes_src.append(None)
        if pol_on:
            axes_src.append(0)
        vmapped_src = jax.vmap(
            per_scenario_src, in_axes=tuple(axes_src)
        )
        return None, finalize(vmapped_src, tuple(axes_src), (1,))

    def _release_core(self):
        """Shared device release-update core (cached): subtract a K-list
        of released placements from every carried plane — used by the
        bucketed static-release fns AND the retry path's pending
        releases. Two node-space accumulators come from
        ``ops.release_planes`` (node-factored one-hot contractions on the
        MXU, no scatter): ``rel [R, N]``, the released requests summed per
        node IN LIST ORDER — the host reference's own arithmetic
        (``models.state.release_delta``: ``np.add.at``, then ONE
        subtraction), because resources are not associative-exact (a Borg
        0.1-core request is no dyadic rational) — and ``rc``, the
        released matched-group / anti / pref-weight counts, integers and
        exact in any order. From them: used, coarse domain planes
        (per-topology static matmuls), singleton host-scale rows,
        anti/pref when the trace carries the terms, match_total. Returns
        ``core(state, nd, req, mg, an, pf, pw, axis_name=None) ->
        (state, rc_raw, rounds)``; ``nd == -1`` ("not placed") matches no
        node; ``rc_raw`` is the UNMASKED node-space count stack (the
        DynTables correction input); ``rounds`` is the largest number of
        rank rounds a block of the list needed; ``axis_name`` names the
        enclosing ``vmap`` axis so the rounds share one trip count."""
        if self._rel_core is not None:
            return self._rel_core
        from ..ops import tpu3 as V3

        st3 = self.static3
        ec = self.ec
        Dcap = st3.Dcap
        N = ec.num_nodes
        G = st3.G
        gdom = V3._gdom_table(ec, G)  # [G, N] np
        gate_np = np.asarray(
            (ec.group_topo[:G] >= 0) & (st3.nd_g > 0), np.float32
        )
        vdom = jnp.asarray(
            (gdom >= 0).astype(np.float32) * gate_np[:, None]
        )  # [G, N]
        gt = ec.group_topo[:G]
        coarse = (~st3.is_host) & (gt >= 0)
        # Per coarse topology: its groups and the static node→domain
        # one-hot. ``row_of[g]`` is group g's row in the stacked products
        # (the last, zero row for a group with no coarse topology), so the
        # [G, Dcap] delta is one static take and the program has no scatter.
        topo_tables, n_rows = [], 0
        row_of = np.full(G, int(coarse.sum()), np.int32)
        for t in sorted(set(gt[coarse].tolist())):
            ids = np.nonzero(coarse & (gt == t))[0]
            row_of[ids] = n_rows + np.arange(len(ids))
            n_rows += len(ids)
            oh_t = (
                ec.node_domain[t][:, None]
                == np.arange(Dcap, dtype=np.int64)[None, :]
            ) & (ec.node_domain[t][:, None] >= 0)
            topo_tables.append(
                (jnp.asarray(ids), jnp.asarray(oh_t.astype(np.float32)))
            )
        row_of = jnp.asarray(row_of)
        h_sel = [
            jnp.asarray(np.asarray(ids, np.int32))
            for ids in (st3.mc_h_ids, st3.anti_h_ids, st3.pref_h_ids)
        ]
        ar_G = jnp.arange(G, dtype=jnp.int32)[None, None, :]
        want_an = bool(st3.maintain_anti)
        want_pf = bool(st3.maintain_pref)
        nparts = 1 + want_an + want_pf

        def coarse_delta(rc):
            rows = [
                jnp.matmul(rc[ids], oh_t, precision=T._HI)
                for ids, oh_t in topo_tables
            ]
            rows.append(jnp.zeros((1, Dcap), jnp.float32))
            return jnp.concatenate(rows)[row_of]

        def core(state, nd, req_rows, mg_rows, an_rows, pf_rows, pw_rows,
                 axis_name=None):
            # Count channels have to be exact in bfloat16: a 0/1 match sum
            # over at most 256 term slots is, a summed preference weight
            # is cut into three parts that are.
            if max(mg_rows.shape[1], an_rows.shape[1]) > 256:
                raise ValueError("release core: over 256 term slots a pod")
            parts = [(mg_rows[:, :, None] == ar_G).sum(1)]
            if want_an:
                parts.append((an_rows[:, :, None] == ar_G).sum(1))
            if want_pf:
                parts.extend(bf16_parts(
                    ((pf_rows[:, :, None] == ar_G) * pw_rows[:, :, None])
                    .sum(1)
                ))
            mm = jnp.concatenate(parts, axis=1).astype(jnp.float32)
            rel, rc, rounds = release_planes(
                nd, req_rows, mm, N, axis_name=axis_name
            )
            if want_pf:  # integer parts: exact in any order
                lo = (nparts - 1) * G
                rc = jnp.concatenate([
                    rc[:lo],
                    rc[lo : lo + G] + rc[lo + G : lo + 2 * G] + rc[lo + 2 * G :],
                ])
            used = state.used - rel
            rc_raw = rc
            rc = rc * jnp.tile(vdom, (nparts, 1))
            chunks = jnp.split(rc, nparts, axis=0)
            rc_mc = chunks[0]
            rc_an = chunks[1] if want_an else None
            rc_pf = chunks[1 + want_an] if want_pf else None
            new = {
                "used": used,
                "mc_dom": state.mc_dom - coarse_delta(rc_mc),
                "match_total": state.match_total - rc_mc.sum(-1),
            }
            if want_an:
                new["anti_dom"] = state.anti_dom - coarse_delta(rc_an)
            if want_pf:
                new["pref_dom"] = state.pref_dom - coarse_delta(rc_pf)
            for pkey, ids, rcx in (
                ("mc_host", h_sel[0], rc_mc),
                ("anti_host", h_sel[1], rc_an),
                ("pref_host", h_sel[2], rc_pf),
            ):
                if ids.shape[0] and rcx is not None:
                    plane = getattr(state, pkey)
                    new[pkey] = plane - rcx[ids].astype(plane.dtype)
            out = state._replace(**new)
            return out, rc_raw, rounds

        core.nparts = nparts
        core.want_an = want_an
        core.want_pf = want_pf
        self._rel_core = core
        return core

    def _release_fn(self, K: int):
        """Jitted static-release application for a pow2 bucket size K
        (device-release path). Separate from the chunk program so each
        boundary pays only its own (bucketed) release-list width instead
        of the global maximum — the Borg duration distribution makes the
        max ~2.4× the mean.

        Both node-space accumulators are node-factored one-hot
        contractions (``ops.release_planes``; the module's docstring has
        the arithmetic): a node is ``128 * hi + lo``, so placing a value
        at its node is a ``[C * NH, K] x [K, 128]`` product on the MXU
        with bfloat16 operands and float32 accumulation, and no
        ``[K, N]`` one-hot and no scatter is left. The counts take ONE
        product over the whole list; the count planes then drop to domain
        space through ONE static node→domain one-hot matmul, match_total
        is its row sum. ``used`` is summed in list order: per block of
        128 rows each row's collision rank is counted, and round r
        contracts the rows of rank r only, so no product ever adds two
        values. A single-term product returns the float32 itself because
        its three bfloat16 parts lie on disjoint bits of it. History, on
        the v5e: at default precision a 0.1-core request came back as
        0.10009766; at ``HIGHEST`` with colliding rows in one product the
        sum took the MXU's order, placements left the host reference
        (PR 21), and no CPU test could see either (that backend is exact
        and sequential); the 256-blocked scatter-add that repaired it
        executed one update after another, 62% of the program (PR 27).
        ``chip_smoke.py``'s parity phase holds the chip to the host
        reference on a case where every block collides."""
        dyn_mode = self._dyn is not None
        key = (K, dyn_mode)
        fn = self._rel_fn_cache.get(key)
        if fn is not None:
            return fn
        core = self._release_core()
        Dcap = self.static3.Dcap
        nparts = core.nparts
        want_an, want_pf = core.want_an, core.want_pf

        def rel_one(state, vassign, rounds, rel_pos, rel_req, rel_mg,
                    rel_anti, rel_pref, rel_prefw,
                    ov_nodes=None, ov_gdom=None, ov_old=None):
            with stage("ksim.release"):
                node_k = vassign[rel_pos]  # sentinel pos → the PAD tail slot
                nd = jnp.where(node_k >= 0, node_k, -1)  # -1 matches no node
                state, rc_raw, top = core(
                    state, nd, rel_req, rel_mg, rel_anti, rel_pref,
                    rel_prefw, axis_name=_RELEASE_VMAP,
                )
                rounds = jnp.maximum(rounds, top)
                if not dyn_mode:
                    return state, rounds
                # DynTables correction layered on the base update: a node the
                # scenario relabeled releases into its OVERRIDDEN domain (and
                # base validity doesn't apply — a node that gained the key
                # releases into the appended domain the bind counted). Uses
                # the UNMASKED accumulator; old/new one-hots encode validity.
                raw_chunks = jnp.split(rc_raw, nparts, axis=0)
                safe_ov = jnp.where(ov_nodes >= 0, ov_nodes, 0)
                ok_ov = (ov_nodes >= 0).astype(jnp.float32)  # [K32]
                ar_D = jnp.arange(Dcap, dtype=jnp.float32)
                mk_oh = lambda a: (
                    (a[..., None] == ar_D) & (a[..., None] >= 0)
                ).astype(jnp.float32)  # [G, K, Dcap]
                doh = mk_oh(ov_gdom) - mk_oh(ov_old)

                def corr_of(raw):
                    rv = raw[:, safe_ov] * ok_ov[None, :]  # [G, K32]
                    return jnp.einsum(
                        "gk,gkd->gd", rv, doh, precision=T._HI
                    )

                corr_mc = corr_of(raw_chunks[0])
                new = {
                    "mc_dom": state.mc_dom - corr_mc,
                    "match_total": state.match_total - corr_mc.sum(-1),
                }
                if want_an:
                    new["anti_dom"] = state.anti_dom - corr_of(raw_chunks[1])
                if want_pf:
                    new["pref_dom"] = state.pref_dom - corr_of(
                        raw_chunks[1 + want_an]
                    )
                return state._replace(**new), rounds

        axes = (0, 0, 0, None, None, None, None, None, None) + (
            (0, 0, 0) if dyn_mode else ()
        )
        fn_v = jax.vmap(rel_one, in_axes=axes, axis_name=_RELEASE_VMAP)
        if self.mesh is not None:
            # Same shard_map discipline as the chunk program (round 10):
            # sharded state/vassign, replicated release tables — each
            # device rewinds its local scenarios, no collectives (the rank
            # loop's trip count is the largest among a device's own).
            from jax.sharding import PartitionSpec as P

            sh, rp = P(SCENARIO_AXIS), P()
            fn_v = jax.shard_map(
                fn_v,
                mesh=self.mesh,
                in_specs=tuple(sh if a == 0 else rp for a in axes),
                out_specs=sh,
                check_vma=False,
            )
        # One XLA module name per bucket, whatever vmap or shard_map would
        # call their wrapper: a trace finds the release program by it.
        def whatif_release(*args):
            return fn_v(*args)

        whatif_release.__name__ = f"whatif_release_k{K}"
        fn = jax.jit(whatif_release, donate_argnums=(0,))
        self._rel_fn_cache[key] = fn
        return fn

    def _state_proto(self):
        from ..ops import tpu3 as V3

        # Real domain width: host_part indexes planes with actual
        # domain ids, so width-1 placeholders would go out of bounds.
        D = max(self.ec.max_domains, 1)
        z = np.zeros((self.static3.G, D), np.float32)
        return V3.DevState3.from_host(
            np.zeros((self.ec.num_nodes, self.ec.num_resources), np.float32),
            z, z, z, self.ec, self.static3,
        )

    def _load_fork_or_init(self):
        """Fork bookkeeping shared by every engine path: (used, match_count)
        host arrays, with ``_fork_waves_done``/``_fork_choices`` set. The
        source replay pads its wave list to a multiple of its chunk size —
        clamp to the REAL wave count so padded tail waves aren't treated
        as already-scheduled."""
        self._fork_waves_done = 0
        self._fork_choices = None
        if self.fork_checkpoint:
            from .checkpoint import ReplayCheckpoint

            ck = ReplayCheckpoint.load(self.fork_checkpoint)
            if ck.boundary is not None:
                raise ValueError(
                    "cannot fork from a boundary-mode (retry/kube) "
                    "checkpoint: its placements live in the host mirror, "
                    "not the saved outs; resume it on a matching "
                    "JaxReplayEngine instead"
                )
            self._fork_ck = ck
            if ck.outs:
                fork = np.concatenate(ck.outs, axis=0)  # [waves(+pad), W]
                self._fork_waves_done = min(
                    fork.shape[0], self.waves.idx.shape[0]
                )
                self._fork_choices = fork[: self._fork_waves_done]
            return ck.used, ck.match_count
        host = init_state(self.ec, self.pods)  # pre-bound pods
        return host.used, host.match_count

    def _jit_once(self, name: str, build: Callable) -> Callable:
        """``build()``'s jitted function, made once per engine: jit caches
        by function identity, so a ``jax.jit(lambda ...)`` written inline
        in run() is a new program, and a compile, in every call."""
        fn = self._run_jits.get(name)
        if fn is None:
            fn = self._run_jits[name] = build()
        return fn

    def _mesh_put(self, span, tree, replicate: bool = False):
        """``tree`` put on the mesh's devices, its leading axis sharded over
        the scenario axis or (``replicate``) whole on each: the ``mesh_put``
        span when ``span`` (the run's) is armed, and its bytes (as they land
        on the devices) and host seconds in the counters of the batch in flight
        (``summary()["mesh"]``). The seconds are those of the put calls,
        which return before a copy is done."""
        n = tree_bytes(tree)
        if replicate:
            n *= int(self.mesh.devices.size)
        with span.mark("mesh_put", bytes=n):
            t = time.perf_counter()
            out = (replicate_tree if replicate else shard_scenario_tree)(
                self.mesh, tree
            )
            if self._mesh_batch is not None:
                self._mesh_batch["put_bytes"] += n
                self._mesh_batch["put_s"] += time.perf_counter() - t
        return out

    def _init_states(self, span=None):
        """The batch's initial [S, ...] state stack. ``span`` is the run's
        span primitive (for ``mesh_put``); a caller outside ``run()`` gets
        one of its own."""
        if span is None:
            span = _make_span()
        if self._state_one_mesh is not None:
            # Meshed, no fork: the one initial state is static and already
            # lies whole on every device; a batch only broadcasts it.
            self._fork_waves_done, self._fork_choices = 0, None
            return self._run_jits["states"](self._state_one_mesh)
        self._load_fork_or_init()  # sets fork bookkeeping
        if self._state_one is not None:
            return self._run_jits["states"](self._state_one)
        if self.fork_checkpoint:
            ck = self._fork_ck
            host = init_state(self.ec, self.pods, apply_prebound=False)
            host.used = ck.used
            host.match_count = ck.match_count
            host.anti_active = ck.anti_active
            host.pref_wsum = ck.pref_wsum
        else:
            host = init_state(self.ec, self.pods)  # pre-bound pods
        from ..ops import tpu3 as V3

        one = V3.DevState3.from_host(
            host.used, host.match_count, host.anti_active, host.pref_wsum,
            self.ec, self.static3, ep=self.pods,
        )
        # ONE jitted broadcast dispatch instead of a jnp.repeat
        # round-trip per leaf. Under a mesh the one state is replicated
        # and every device broadcasts its own scenarios' share: the
        # [S, ...] stack is born sharded and never lies whole on one
        # device to be dealt out again.
        S = self.S
        _bc = lambda s: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (S,) + a.shape), s
        )
        if self.mesh is not None:
            one = self._mesh_put(span, one, replicate=True)
        states_fn = self._jit_once("states", lambda: (
            jax.jit(_bc, out_shardings=scenario_sharding(self.mesh))
            if self.mesh is not None
            else jax.jit(_bc)
        ))
        if self.mesh is not None and not self.fork_checkpoint:
            self._state_one_mesh = one
        elif not self.fork_checkpoint:
            # static per engine, as under a mesh: the host fold of the
            # pre-bound pods and the upload run once
            self._state_one = one
        return states_fn(one)

    def _subtract_stacked_planes(self, states, used_d, mc_d, aa_d, pw_d):
        """Scenario-stacked host-layout delta planes ([S, N, R] /
        [S, G, D]) → v3 device layout, subtracted from the carried
        states (shared by the release path and the kube boundary
        passes; the transform is linear)."""
        from ..ops import tpu3 as V3

        ec, st3 = self.ec, self.static3
        S, N = self.S, ec.num_nodes
        D = mc_d.shape[2]
        Dcap = st3.Dcap
        w = min(D, Dcap)

        def dom_part(arr):
            out = np.zeros((S, st3.G, Dcap), np.float32)
            out[:, : arr.shape[1], :w] = np.where(
                st3.is_host[None, : arr.shape[1], None], 0.0, arr[:, :, :w]
            )
            return out

        gdom = V3._gdom_table(ec, st3.G)

        def host_part(arr, ids, dtype):
            H = len(ids)
            out = np.zeros((S, H, N), np.float32)
            for li, g in enumerate(ids):
                if g < arr.shape[1]:
                    dg = gdom[g]
                    valid = dg >= 0
                    out[:, li, valid] = arr[:, g, np.clip(dg, 0, None)][:, valid]
            return out.astype(dtype)

        delta = V3.DevState3(
            used=jnp.asarray(
                np.ascontiguousarray(np.transpose(used_d, (0, 2, 1)))
            ),
            mc_dom=jnp.asarray(dom_part(mc_d)),
            anti_dom=jnp.asarray(dom_part(aa_d)),
            pref_dom=jnp.asarray(dom_part(pw_d)),
            # .dtype on the jax array directly — np.asarray here forced a
            # full device→host copy of the [S, H, N] plane per release
            # chunk just to read its dtype (advisor round-2).
            mc_host=jnp.asarray(
                host_part(mc_d, st3.mc_h_ids, states.mc_host.dtype)
            ),
            anti_host=jnp.asarray(
                host_part(aa_d, st3.anti_h_ids, states.anti_host.dtype)
            ),
            pref_host=jnp.asarray(
                host_part(pw_d, st3.pref_h_ids, np.float32)
            ),
            match_total=jnp.asarray(
                np.pad(
                    mc_d.sum(axis=2), ((0, 0), (0, st3.G - mc_d.shape[1]))
                ).astype(np.float32)
                if mc_d.shape[1] < st3.G
                else mc_d.sum(axis=2).astype(np.float32)
            ),
            used_tier=jnp.zeros_like(states.used_tier),
            npods_tier=jnp.zeros_like(states.npods_tier),
        )
        if self.mesh is not None:
            delta = shard_scenario_tree(self.mesh, delta)
        return self._donated_subtract(states, delta)

    def _donated_subtract(self, states, delta):
        """Subtract a delta tree from the carried chunk-loop states with
        the STATES buffers donated (round 11 donation audit): the eager
        ``jax.tree.map(jnp.subtract, ...)`` here allocated a second full
        state copy per release/boundary chunk. Cached on the engine — jit
        caches by function identity."""
        if self._sub_jit is None:
            def release_subtract(s, d):
                with stage("ksim.release"):
                    return jax.tree.map(jnp.subtract, s, d)

            self._sub_jit = jax.jit(release_subtract, donate_argnums=(0,))
        return self._sub_jit(states, delta)

    def _apply_stacked_boundary_delta(self, states, subs, adds):
        """Per-scenario (pods, nodes) array pairs from the kube boundary
        passes (sub = releases + evictions, add = retried/preempting
        binds) → one stacked device delta. The domain tables are the
        BASE cluster's for every scenario (label perturbations are
        rejected in kube mode), so release_delta against the base ec is
        exact per scenario."""
        from ..models.state import release_delta

        ec = self.ec
        S, N, R = self.S, ec.num_nodes, ec.num_resources
        G = max(ec.num_groups, 1)
        D = max(ec.max_domains, 1)
        used_d = np.zeros((S, N, R), np.float32)
        mc_d = np.zeros((S, G, D), np.float32)
        aa_d = np.zeros((S, G, D), np.float32)
        pw_d = np.zeros((S, G, D), np.float32)
        any_delta = False
        for s in range(S):
            for (pids, pnds), sign in ((subs[s], 1.0), (adds[s], -1.0)):
                if not pids.size:
                    continue
                any_delta = True
                du, dmc, daa, dpw = release_delta(
                    ec, self.pods, pids, pnds
                )
                used_d[s] += sign * du
                mc_d[s] += sign * dmc
                aa_d[s] += sign * daa
                pw_d[s] += sign * dpw
        if not any_delta:
            return states
        return self._subtract_stacked_planes(
            states, used_d, mc_d, aa_d, pw_d
        )

    def _apply_releases(self, states, host_assign, released, cand):
        """Subtract completed pods' contributions per scenario (the
        JaxReplayEngine chunk-boundary mechanism, scenario-stacked; one
        batched scatter pass across all scenarios — at Borg scale every
        pod releases once, so per-scenario Python would dominate).
        Mutates ``released`` in place. ``cand``: [K] pod ids — this
        boundary's static candidate bucket (staged once per run: the
        earliest boundary where ``rel_time <= tb[b]`` AND the one-chunk
        slack has elapsed is known up front, so the per-boundary work is
        [S, K] instead of the old [S, P] mask — K is the handful of pods
        completing at this boundary, which is what fixes the S-scaling)."""
        from ..ops import tpu3 as V3

        ec, ep, st3 = self.ec, self.pods, self.static3
        due = (host_assign[:, cand] != PAD) & ~released[:, cand]
        if not due.any():
            return states
        s_idx, k_idx = np.nonzero(due)
        p_idx = cand[k_idx]
        released[s_idx, p_idx] = True
        nodes = host_assign[s_idx, p_idx]
        S, N, R = self.S, ec.num_nodes, ec.num_resources
        G = max(ec.num_groups, 1)
        D = max(ec.max_domains, 1)
        used_d = np.zeros((S, N, R), np.float32)
        np.add.at(used_d, (s_idx, nodes), ep.requests[p_idx])
        gt = ec.group_topo[:G]
        dom = np.where(
            (gt >= 0)[:, None], ec.node_domain[np.clip(gt, 0, None)][:, nodes], PAD
        )  # [G, K]
        mc_d = np.zeros((S, G, D), np.float32)
        aa_d = np.zeros((S, G, D), np.float32)
        pw_d = np.zeros((S, G, D), np.float32)
        sel = (dom >= 0) & ep.pod_matches_group[p_idx].T[:G]
        gg, kk = np.nonzero(sel)
        np.add.at(mc_d, (s_idx[kk], gg, dom[gg, kk]), 1.0)
        for col in range(ep.anti_req.shape[1]):
            g = ep.anti_req[p_idx, col]
            ok = (g >= 0) & (dom[np.clip(g, 0, None), np.arange(len(p_idx))] >= 0)
            if ok.any():
                np.add.at(
                    aa_d,
                    (s_idx[ok], g[ok], dom[g[ok], np.nonzero(ok)[0]]),
                    1.0,
                )
        for col in range(ep.pref_aff.shape[1]):
            g = ep.pref_aff[p_idx, col]
            w = ep.pref_aff_w[p_idx, col]
            ok = (g >= 0) & (dom[np.clip(g, 0, None), np.arange(len(p_idx))] >= 0)
            if ok.any():
                np.add.at(
                    pw_d,
                    (s_idx[ok], g[ok], dom[g[ok], np.nonzero(ok)[0]]),
                    w[ok].astype(np.float32),
                )

        states = self._subtract_stacked_planes(
            states, used_d, mc_d, aa_d, pw_d
        )
        if self.preemption and states.used_tier.shape[1]:  # [S, Tt, R, N]
            # Tier planes drop completed NON-GANG pods too (pod tiers are
            # static, so releases are attributable; gangs never enter the
            # tier planes — the single-replay round-4 rule, S-stacked).
            # Compact (s, tier, node, req) scatter on device: the dense
            # [S, Tt, R, N] host delta would be 8x the base-plane traffic.
            ng = ep.group_id[p_idx] == PAD
            if ng.any():
                si = s_idx[ng].astype(np.int32)
                ti = st3.pod_tier[p_idx[ng]].astype(np.int32)
                nd = nodes[ng].astype(np.int32)
                rq = ep.requests[p_idx[ng]].astype(np.float32)
                K = len(si)
                pad = 1 << max(K - 1, 0).bit_length()  # pow2 bucket
                if pad > K:
                    z = np.zeros(pad - K, np.int32)
                    si, ti, nd = (
                        np.concatenate([si, z]),
                        np.concatenate([ti, z]),
                        np.concatenate([nd, z]),
                    )
                    rq = np.concatenate(
                        [rq, np.zeros((pad - K, rq.shape[1]), np.float32)]
                    )
                states = states._replace(
                    used_tier=self._tier_rel_fn()(
                        states.used_tier, si, ti, nd, rq
                    ),
                    npods_tier=self._npods_rel_fn()(
                        states.npods_tier, si, ti, nd,
                        (np.arange(pad) < K).astype(np.float32),
                    ),
                )
        return states

    def _tier_rel_fn(self):
        """Cached jit: used_tier[S, Tt, R, N] -= scatter of [K] release
        rows (zero-padded rows subtract 0 — index 0 is safe)."""
        if getattr(self, "_tier_rel_jit", None) is None:
            def f(ut, si, ti, nd, rq):
                R = ut.shape[2]
                Kp = si.shape[0]
                s = jnp.repeat(si, R)
                t = jnp.repeat(ti, R)
                r = jnp.tile(jnp.arange(R, dtype=jnp.int32), Kp)
                n = jnp.repeat(nd, R)
                return ut.at[s, t, r, n].add(-rq.reshape(-1))

            self._tier_rel_jit = jax.jit(f, donate_argnums=(0,))
        return self._tier_rel_jit

    def _npods_rel_fn(self):
        if getattr(self, "_npods_rel_jit", None) is None:
            def f(nt, si, ti, nd, w):
                return nt.at[si, ti, nd].add(-w)

            self._npods_rel_jit = jax.jit(f, donate_argnums=(0,))
        return self._npods_rel_jit

    def _fold(self, host_assign, rows, choices) -> None:
        """Apply a chunk's choices to the per-scenario assignment table.
        ``choices``: device [S, C, W] from the scan, or host [C, W] shared
        pre-fork placements."""
        ch = np.asarray(choices) if isinstance(choices, np.ndarray) else (
            self._fetch(choices)
        )
        v = rows >= 0
        if ch.ndim == 2:
            host_assign[:, rows[v]] = ch[v][None, :]
        else:
            host_assign[:, rows[v]] = ch.reshape((self.S,) + rows.shape)[:, v]

    def _fetch(self, x) -> np.ndarray:
        """Device→host for a result tensor. Round 11: under DCN the
        engine's mesh is process-LOCAL (localize_mesh in __init__), every
        shard is addressable, and this is a plain local copy — the
        per-chunk cross-process replication that used to live here (the
        round-10 ``process_count() > 1`` branch) is gone; processes meet
        once per replay in run()'s gather instead. The replication branch
        survives only for a caller handing in a genuinely cross-process
        mesh, and counts itself so tests can pin it at zero."""
        if self._mesh_spans_procs:
            self._replicate_count += 1
            if self._replicate_fn is None:
                self._replicate_fn = jax.jit(
                    lambda a: a, out_shardings=replicated(self.mesh)
                )
            x = self._replicate_fn(x)
        return np.asarray(x)

    def _handback(
        self, span, wave_order, pos, count: bool = False, txn=None
    ) -> Tuple[np.ndarray, int, Optional[np.ndarray]]:
        """(assignments [S, P], bytes copied, placed [S] or None), under the
        run's ``span`` (a mesh's gather and fetch is ``mesh_fetch``): every
        task's node, task by task, and with ``count`` how many of each
        scenario's tasks have one, counted on the device (on the host the
        compare writes a [S, P] temporary: 21 ms at 1,024 x 10,000, PERF.md
        §6, PR 33). ``wave_order`` holds the placements in wave order on the
        device: the device-release path's ``vassign`` buffer (the pre-bound
        tasks in its tail), or, where nothing is released on the device (an
        arrivals-only batch, the host fold path), the list of every chunk's
        choices ``[S, C, W]``, strung together here with a PAD column at
        the end for a task in no wave. ``pos()`` gives the static map from
        task to place in that order; one gather on the device puts the
        placements into task order, and the result comes to the host in one
        copy. On a v5e at 128 x 131,072: 0.03 s, against 0.29 s for the
        copy first and ``np.take`` on the host (PERF.md §6, PR 27); at
        256 x 50,000 from ten chunks 0.027 s against 0.11 s for a fetch a
        chunk and a host scatter (PR 31).

        Under a mesh the result is sharded on the scenario axis. Its shards
        come to the host in 2.4 ms, but stringing them together there costs
        72 ms at 1,024 x 10,000 on a v5e host (the fresh 41 MB array is
        page-faulted in; four threads: 29 ms), against 4.5 ms for the same
        bytes fetched whole from one device, which the host takes as they
        land (my chip run, PR 33). So one more program, ``jit_whatif_gather``,
        replicates the placements over the mesh (one all-gather over ICI,
        the one collective of a batch, after the chunk and hand-back
        programs, which hold none) and the host fetches one device's copy.

        ``txn`` (the final state's, where the trace has a pod group wider
        than the wave): a member of such a group wrote its node when its
        wave ran, before the group closed; the group's verdict per scenario
        (``txn.log``), taken through the static map from task to group
        ordinal inside the same program, hands a rolled-back group back
        unplaced, member for member."""
        def build():
            pos_d = jnp.asarray(pos())
            if txn is not None:
                tab = self.static3.txn_tab
                # a task in no wide group reads the False column at the end
                ord_d = jnp.asarray(
                    np.where(tab[:, 0] >= 0, tab[:, 2], self.static3.wide_groups)
                )

            def whatif_handback(buf, log=None):
                if isinstance(buf, (list, tuple)):
                    flat = [c.reshape(c.shape[0], -1) for c in buf]
                    none = jnp.full((flat[0].shape[0], 1), PAD, flat[0].dtype)
                    buf = jnp.concatenate(flat + [none], axis=1)
                node = jnp.take(buf, pos_d, axis=1).astype(jnp.int32)
                if log is None:
                    return node
                stood = jnp.zeros((log.shape[0], 1), bool)
                rolled = jnp.take(
                    jnp.concatenate([log, stood], axis=1), ord_d, axis=1
                )
                return jnp.where(rolled, PAD, node)

            return jax.jit(whatif_handback)

        fn = self._jit_once("handback", build)
        args = (wave_order,) if txn is None else (wave_order, txn.log)
        if self._mesh_programs is not None:
            self._mesh_programs["handback"] = (fn, _shape_structs(args))
        placed = fn(*args)
        counts = None
        if count:
            counts = self._jit_once("handback_placed", lambda: jax.jit(
                lambda a: (a >= 0).sum(axis=1, dtype=jnp.int32)
            ))(placed)
        if self.mesh is None:
            out = self._fetch(placed)
        else:
            def build_gather():
                def whatif_gather(a):
                    return a

                return jax.jit(
                    whatif_gather, out_shardings=replicated(self.mesh)
                )

            with span.mark("mesh_fetch", bytes=int(placed.nbytes)):
                t = time.perf_counter()
                gather = self._jit_once("gather", build_gather)
                if self._mesh_programs is not None:
                    self._mesh_programs["gather"] = (
                        gather, (_shape_structs(placed),)
                    )
                out = self._fetch(gather(placed))
                self._mesh_batch["fetch_bytes"] += int(out.nbytes)
                self._mesh_batch["fetch_s"] += time.perf_counter() - t
        if counts is not None:
            counts = self._fetch(counts).astype(np.int32)
        return out, int(out.nbytes), counts

    def _fetch_answer(self, span, answer: str, x) -> np.ndarray:
        """``_fetch`` of one answer of a retry batch's hand-back, under a
        ``handback_fetch`` span that carries which answer it is and the
        bytes the copy brings to the host."""
        with span.mark("handback_fetch", answer=answer, bytes=int(x.nbytes)):
            return self._fetch(x)

    def _handback_retry(
        self, span, vassign_d, rq: RetryQueue, retry_placed: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """(assignments [S, P], bind_boundary [S, P], merged [S], bytes
        copied) of a batch on the device retry path: ONE program, one fetch
        an array, and the host rewrites nothing. Under the run's ``span``
        the program's time is ``handback_wait`` (its dispatch to its outputs
        being ready) and each answer's copy a ``handback_fetch``. The program puts the
        arrival binds into task order (``vassign`` through the static
        ``pos`` map, the residents in its tail) and writes beside each task
        what it reads if nothing else is known of it: -1 where it has a
        node, -4 for an unplaced gang member (never queued), -3 for any
        other (dropped at a full buffer). Then it
        merges the queue's record and the queue into both arrays: every
        record row ``(b, j)`` with a task writes that task's node and ``b``,
        and every task still queued reads -2 (a task that failed in the
        last chunk is queued untried). The indices are unique: a task
        leaves the queue when a pass binds it, so it is in at most one
        record row, and a queued task is in none; so the queue rides as one
        more row and the order of the writes is free.

        The form, by measurement at 128 x 337,430 tasks, 22 rows of 4,096
        (PERF.md §6, PR 43): a scatter costs 7 ns a row, empty or not, and
        seven rows in eight are empty. So ONE sort a scenario by task id
        (an empty slot last) brings the filled slots to the front, and a
        loop scatters one block of ``RB`` columns of the whole batch a
        trip, the scenario a column of the index into ``[S, P]`` (no
        ``vmap``, no flat ``s * P + task`` that would have to fit int32),
        as many trips as the fullest scenario needs: 7 or 8 of 23 there,
        78 ms with the sort where all 23 rows at once take 163-172. The
        arrays the loop carries are the program's own temporaries, updated
        in place: it holds no third ``[S, P]`` array (and no input has an
        output's shape, so there is nothing to donate). The scatters do not
        claim ``indices_are_sorted``: with dropped rows among them the
        chip's sorted path wrote wrong entries.

        ``merged`` is ``(bind_boundary >= 0).sum(axis=1)``, the re-tried
        binds written, counted on the device; it has to be ``retry_placed``
        (what the passes bound, less the re-tried binds a timeline evicted
        again) scenario for scenario, and a batch where it is not raises: a
        lost bind is a wrong answer. A gang member that a timeline evicted
        (-2 in ``vassign``) reads no node and -5. Both copies to the host
        are started before
        the first is waited for, and the arrays come back as fetched,
        read-only, each run's own."""
        def build():
            S, RB = self.S, self.retry_buffer
            pos_d = jnp.asarray(self._dev_rel_stage["pos"])
            gang_d = jnp.asarray(self.pods.group_id >= 0)
            none = jnp.iinfo(jnp.int32).max  # past every task: dropped
            # an unplaced gang member was never queued, but under
            # ``retry_groups``: there its job was dropped whole
            never = -3 if self.retry_groups else -4

            def whatif_handback_retry(buf, rq):
                node = jnp.take(buf, pos_d, axis=1).astype(jnp.int32)
                code = jnp.where(
                    node >= 0, -1, jnp.where(gang_d[None, :], never, -3)
                ).astype(jnp.int32)
                if self._events_dev:
                    # the eviction program left -2 where a gang member stood
                    code = jnp.where(node == -2, -5, code)
                    node = jnp.maximum(node, PAD)
                # the queue rides as one more row: no node, code -2
                if self.retry_groups:
                    # the record is one log, which says what boundary's
                    # pass wrote an entry; else that is the entry's row
                    rows = lambda record, queue: jnp.concatenate(
                        [record, queue], axis=1)
                    wrote = lambda: rq.t_b
                else:
                    rows = lambda record, queue: jnp.concatenate(
                        [record, queue[:, None]], axis=1
                    ).reshape(S, -1)
                    boundary = jnp.arange(rq.t_id.shape[1], dtype=jnp.int32)
                    wrote = lambda: jnp.broadcast_to(
                        boundary[None, :, None], rq.t_id.shape)
                task = rows(rq.t_id, rq.ids)
                task, wrote, on = jax.lax.sort((
                    jnp.where(task >= 0, task, none),
                    rows(wrote(), jnp.full_like(rq.ids, -2)),
                    rows(rq.t_node, jnp.full_like(rq.ids, PAD)),
                ), dimension=1, num_keys=1, is_stable=False)
                filled = (task < none).sum(axis=1).max()
                scen = jax.lax.broadcasted_iota(jnp.int32, (S, RB), 0)

                def block(i, arrays):
                    node, code = arrays
                    cols = lambda a: jax.lax.dynamic_slice(
                        a, (0, i * RB), (S, RB)
                    )
                    t, b = cols(task), cols(wrote)
                    put = dict(mode="drop", unique_indices=True)
                    return (
                        node.at[scen, jnp.where(b >= 0, t, none)].set(
                            cols(on), **put),
                        code.at[scen, t].set(b, **put),
                    )

                node, code = jax.lax.fori_loop(
                    0, -(-filled // RB), block, (node, code)
                )
                return node, code, (code >= 0).sum(axis=1, dtype=jnp.int32)

            return jax.jit(whatif_handback_retry)

        fn = self._jit_once("handback_retry", build)
        with span.mark("handback_wait"):
            got = fn(vassign_d, rq)
            if not self._mesh_spans_procs:
                for a in got:  # the second array's copy overlaps the first's
                    a.copy_to_host_async()
            # the copies are under way: the fetches below wait for them alone
            jax.block_until_ready(got)
        assignments = self._fetch_answer(span, "assignments", got[0])
        bind_boundary = self._fetch_answer(span, "bind_boundary", got[1])
        merged = self._fetch(got[2])
        if not np.array_equal(merged, retry_placed):
            s = int(np.argmax(merged != retry_placed))
            raise RuntimeError(
                "the retry hand-back merged another number of re-tried binds "
                f"than the passes made: scenario {s} has {int(merged[s])} in "
                f"bind_boundary and {int(retry_placed[s])} in retry_placed"
            )
        return (assignments, bind_boundary, merged,
                int(assignments.nbytes + bind_boundary.nbytes))

    def _retry_counts(self, rq: RetryQueue, outs, dropped) -> dict:
        """What ``summary()["retry"]`` holds per scenario (``[S]`` each) of
        the batch that just ran, in one copy from the device: the tasks the
        passes bound, the queue's depth at the boundaries (its largest, and
        what is still queued at the end), ``release_leaked`` and
        ``pass_waves`` (the wave steps the passes executed: a pass ends with
        the last queued wave of the fullest scenario on its device, so the
        number is the same in every scenario of a device and differs by
        device under a mesh), with the drops the run has already fetched."""
        keys = ("retry_placed", "depth_max", "depth_at_end", "release_leaked",
                "pass_waves")
        got = self._fetch(self._jit_once(
            "retry_counts", lambda: jax.jit(lambda o, q: jnp.stack([
                jnp.stack([r for _, r in o], axis=1).sum(axis=1, dtype=jnp.int32),
                q.depth_max, q.count, q.owed - q.released, q.pass_waves,
            ]))
        )(outs, rq))  # [5, S]
        return dict(zip(keys, got), retry_dropped=dropped)

    def _evict_counts(self, ev_n: np.ndarray) -> dict:
        """What ``summary()["retry"]`` holds per scenario (``[S]`` each) of
        a batch whose timelines evict, from ``EvictState.n`` as fetched:
        the evictions; of them the tasks a retry pass bound again, in the
        pass of the boundary that evicted them or in a later one; the
        stranded (evicted and with no node at the end: still queued,
        dropped at a full buffer, or a gang member), the last two kinds
        on their own; the re-tried binds among the victims; how long the
        re-bound waited, in boundaries (mean, and the longest).
        (``pass_waves`` is no eviction counter: every retry batch has it,
        from the ``RetryQueue``, through ``_retry_counts``.)"""
        col = lambda k: ev_n[:, _EV[k]]
        back = col("rebound")
        return {
            "evictions": col("evictions"),
            "evict_rebound_same_boundary": col("rebound_same"),
            "evict_rebound_later": back - col("rebound_same"),
            "evict_stranded": col("evictions") - back,
            "evict_dropped": col("evict_dropped"),
            "evict_gang_stranded": col("evict_gang"),
            "evict_retried": col("evict_retried"),
            "evict_wait_boundaries_mean": col("wait_sum") / np.maximum(back, 1),
            "evict_wait_boundaries_max": col("wait_max"),
        }

    def _handback_log(self, span, ev: EvictState, evictions) -> np.ndarray:
        """``WhatIfResult.eviction_log`` ``[S, E, 4]`` (``5`` under
        disruption budgets: the kind last): the log's filled
        columns (to the longest scenario's, rounded up to 1,024 so that a
        batch made again compiles nothing), turned on the device and
        fetched once, cut on the host to the longest; -1 where a scenario
        has fewer."""
        longest = int(np.max(evictions, initial=0))
        cap = int(ev.log.shape[2])
        width = min(cap, -(-max(longest, 1) // 1024) * 1024)
        turn = self._jit_once(f"evict_log:{width}", lambda: jax.jit(
            lambda log: jnp.swapaxes(log[:, :, :width], 1, 2)
        ))
        return self._fetch_answer(
            span, "eviction_log", turn(ev.log))[:, :longest]

    def _retry_summary(self, per: dict, passes: int) -> dict:
        """``summary()["retry"]`` from ``_retry_counts``: the buffer, the
        passes made (one a boundary), and per scenario (mean and max over
        the batch, scenario 0's own under ``scenario0``) the tasks the
        passes bound, the tasks dropped at a full buffer, the queue's depth
        at the boundaries (its largest, and what is still queued at the
        end), ``release_leaked``: re-tried binds with a release boundary
        inside the trace that no boundary released (0 by construction: the
        counter that says no release was lost), ``pass_waves``: the wave
        steps the passes executed, to hold against ``passes * buffer / W``,
        what passes that walked the whole buffer would take, and, where the
        placements were handed back, ``handback_merged``: the re-tried binds the
        hand-back program wrote into ``bind_boundary`` (``retry_placed`` by
        construction: the counter that says the merge ran on the device
        and lost nothing). Where timelines evict, ``_evict_counts``'
        besides, and ``retry_dropped`` counts the evicted that found the
        buffer full too."""
        out: dict = {"buffer": int(self.retry_buffer), "passes": passes}
        plain = lambda x: float(x) if isinstance(x, np.floating) else int(x)
        for k, v in per.items():
            v = np.asarray(v)
            out[k] = {"mean": float(v.mean()), "max": plain(v.max())}
        out["scenario0"] = {k: plain(np.asarray(v)[0]) for k, v in per.items()}
        return out

    def _mesh_summary(self) -> dict:
        """``summary()["mesh"]`` of the batch that just ran: devices,
        scenarios a device, bytes put on the devices and fetched from them
        (and the host seconds of those calls, nested in ``stage`` and
        ``handback``), and the cross-device instructions in the compiled
        programs: 0 in ``chunk`` (and in ``retry``, the pass program of a
        batch with a ``retry_buffer``) and ``handback``, the scenario axis is
        embarrassingly parallel; ``gather``, which brings the placements
        to one device for the fetch (``_handback``), is the batch's one
        all-gather. The programs are read once per engine, at
        the end of its first meshed run: each is lowered again on the
        shapes it was called with (the process already holds its
        executable) and its optimized HLO is searched."""
        if self._mesh_collectives is None:
            self._mesh_collectives = {
                name: len(collective_lines(
                    fn.lower(*structs).compile().as_text()
                ))
                for name, (fn, structs) in (self._mesh_programs or {}).items()
            }
            self._mesh_programs = None
        ndev = int(self.mesh.devices.size)
        b = self._mesh_batch
        return {
            "devices": ndev,
            "scenarios_per_device": int(self.S) // ndev,
            "put_bytes": int(b["put_bytes"]),
            "fetch_bytes": int(b["fetch_bytes"]),
            "put_s": round(b["put_s"], 6),
            "fetch_s": round(b["fetch_s"], 6),
            "collectives": dict(self._mesh_collectives),
        }

    def _retry_queue(self, boundaries: int) -> RetryQueue:
        """A batch's empty ``RetryQueue``, ``[S]`` in front of every leaf:
        nothing queued, no bind recorded in any of the ``boundaries`` rows."""
        S, RB = self.S, self.retry_buffer
        core = self._release_core()
        stg = self._dev_rel_stage
        full = lambda shape, fill, dt: jnp.full((S,) + shape, fill, dt)
        if self.retry_groups:  # one log a scenario (``RetryQueue``)
            log = (-(-self.pods.num_pods // RB) + 1) * RB
            rec = lambda fill, dt: full((log,), fill, dt)
            tab = lambda width, fill, dt: full((width, log), fill, dt)
        else:
            rec = lambda fill, dt: full((boundaries, RB), fill, dt)
            tab = lambda width, fill, dt: full(
                (boundaries, width, RB), fill, dt)
        return self._jit_once("retry_queue", lambda: jax.jit(lambda: RetryQueue(
            ids=full((RB,), PAD, jnp.int32),
            prio=full((RB,), 0, jnp.int32),
            dur=full((RB,), 0.0, jnp.float32),
            count=full((), 0, jnp.int32),
            dropped=full((), 0, jnp.int32),
            depth_max=full((), 0, jnp.int32),
            owed=full((), 0, jnp.int32),
            released=full((), 0, jnp.int32),
            pass_waves=full((), 0, jnp.int32),
            t_id=rec(PAD, jnp.int32),
            t_node=rec(PAD, jnp.int32),
            t_relb=rec(1 << 30, jnp.int32),
            t_req=tab(self.ec.num_resources, 0.0, jnp.float32),
            t_mg=tab(stg["mgt"].shape[1], PAD, jnp.int32),
            t_an=tab(stg["antit"].shape[1], PAD, jnp.int32)
            if core.want_an else None,
            t_pf=tab(stg["preft"].shape[1], PAD, jnp.int32)
            if core.want_pf else None,
            t_pw=tab(stg["prefwt"].shape[1], 0.0, jnp.float32)
            if core.want_pf else None,
            ev_at=full((RB,), -1, jnp.int32) if self._events_dev else None,
            gn=full((len(GROUP_COUNTERS),), 0, jnp.int32)
            if self.retry_groups else None,
            t_b=rec(PAD, jnp.int32) if self.retry_groups else None,
            fill=full((), 0, jnp.int32) if self.retry_groups else None,
        )))()

    def _stage_events(self) -> dict:
        """The batch's timelines as what the device path takes: per
        boundary (the first whose start reaches an event's time, as every
        event of this engine) and scenario, the nodes that LEAVE there
        (every ``node_down`` due, in timeline order: the order their tasks
        join the queue in) and the nodes that are BACK (the last event due
        is a ``node_up``), ``[S, L]`` each, -1 padded, on the device; None
        for a boundary at which no scenario has an event. With them the
        sizes the eviction program is compiled for: ``L``, the victims a
        boundary can take (``E``) and the log's room (both reckoned from
        the timelines at the trace's mean tasks a node; a run that finds
        either too small doubles ``_evict_scale`` and is made again).
        Static per scenario batch: staged once and kept."""
        if self._evict_stage is not None:
            return self._evict_stage
        if self._budget_on:
            return self._stage_budget_events()
        tb = self._dev_rel_stage["tb_host"]
        S, nb = self.S, len(tb)
        leave = [[[] for _ in range(nb)] for _ in range(S)]
        back = [[[] for _ in range(nb)] for _ in range(S)]
        for s, tl in enumerate(self._timelines):
            at = np.searchsorted(tb, [float(e.time) for e in tl], side="left")
            last: Dict[tuple, str] = {}
            for e, bb in zip(tl, at.tolist()):
                if bb >= nb:
                    break  # past the last boundary: never applied
                node = int(e.node)
                if e.kind == "node_down" and node not in leave[s][bb]:
                    leave[s][bb].append(node)
                last[(bb, node)] = e.kind
            for (bb, node), kind in last.items():
                if kind == "node_up":
                    back[s][bb].append(node)
        widest = max(len(x) for per in (leave, back) for row in per for x in row)
        L = 1 << max(3, (max(widest, 1) - 1).bit_length())
        # a batch swapped in (``set_scenarios``) that needs no more room
        # keeps the sizes the programs were compiled for
        kept = self._evict_sizes or {"L": 0, "E": 0, "cap": 0}
        L = max(L, kept["L"])
        P, N = self.pods.num_pods, self.ec.num_nodes
        per_node = -(-P // N)
        most = max(len(x) for row in leave for x in row)
        scale = self._evict_scale
        E = max(kept["E"], 128, 1 << (
            max(most * per_node * scale, 1) - 1).bit_length())
        total = max(sum(len(x) for x in row) for row in leave)
        cap = total * per_node * scale + 2 * E
        cap = max(kept["cap"], -(-cap // 128) * 128)
        self._evict_sizes = {"L": L, "E": E, "cap": cap}
        pad = lambda rows: np.asarray(
            [r + [-1] * (L - len(r)) for r in rows], np.int32
        )
        calls = []
        for bb in range(nb):
            if not any(leave[s][bb] or back[s][bb] for s in range(S)):
                calls.append(None)
                continue
            calls.append((
                jnp.asarray(pad([leave[s][bb] for s in range(S)])),
                jnp.asarray(pad([back[s][bb] for s in range(S)])),
            ))
        self._evict_stage = {
            "calls": calls, "L": L, "E": E, "cap": cap,
        }
        return self._evict_stage

    def _stage_budget_events(self) -> dict:
        """``_stage_events`` for a batch under disruption budgets. Per
        boundary and scenario ONE list of nodes ``[S, L]`` with each
        entry's ``kind`` beside it (``_LK``): the ``node_down`` s due
        (timeline order), then the nodes that may be DRAINING there, those
        cordoned at ``cb`` with ``cb <= b <= cb + max(grace, 1)``, in walk
        order (the order of their ``node_cordon`` events; one that fails at
        ``b`` stands among the failures only): cordoned here, cordoned
        earlier, or at their deadline. Whether such a node is still
        cordoned, when it went out and when it is back is the device's to
        know (``EvictState.until``), so there is no list of returns but the
        ``node_up`` s' (``back`` ``[S, Lb]``); a boundary is dispatched
        while any scenario may still have a node cordoned or out by its
        drain. A boundary's call carries the budgets as arrays too (``max_u
        [S, A]``, ``out_for [S]``); with them ``app_t [P]`` and the sizes: ``E`` candidates a
        boundary can bring to the front, ``Ea`` evictions it can make (the
        release core's list: forced ones and what the budgets admit), the
        log's room; a run that finds one too small doubles
        ``_evict_scale`` and is made again."""
        tb = self._dev_rel_stage["tb_host"]
        S, nb = self.S, len(tb)
        P, N = self.pods.num_pods, self.ec.num_nodes
        proto = self._budget_proto
        A = len(proto.max_unavailable)
        FREE = 1 << 30
        max_u = np.full((S, A), FREE, np.int32)
        grace = np.zeros(S, np.int64)
        out_for = np.ones(S, np.int32)
        for s, bud in enumerate(self._budgets):
            if bud is not None:
                max_u[s] = np.minimum(bud.max_unavailable, FREE)
                grace[s], out_for[s] = int(bud.grace), int(bud.out_for)
        fails = [[[] for _ in range(nb)] for _ in range(S)]
        back = [[[] for _ in range(nb)] for _ in range(S)]
        drain = [[[] for _ in range(nb)] for _ in range(S)]
        live = np.zeros(nb + 1, bool)  # a boundary some scenario needs
        for s, tl in enumerate(self._timelines):
            at = np.searchsorted(tb, [float(e.time) for e in tl], side="left")
            last: Dict[tuple, str] = {}
            window: Dict[int, int] = {}
            reach = max(int(grace[s]), 1)
            for e, bb in zip(tl, at.tolist()):
                if bb >= nb:
                    break  # past the last boundary: never applied
                node = int(e.node)
                live[bb] = True
                if e.kind == "node_down" and node not in fails[s][bb]:
                    fails[s][bb].append(node)
                if e.kind in ("node_down", "node_up"):
                    last[(bb, node)] = e.kind
                if e.kind == "node_cordon":
                    if window.get(node, -1) >= bb:
                        raise ValueError(
                            f"scenario {s}: node {node} is cordoned again "
                            f"at boundary {bb}, inside the drain its last "
                            f"node_cordon began"
                        )
                    window[node] = bb + reach
                    for b in range(bb, min(bb + reach, nb - 1) + 1):
                        kind = _LK["new"] if b == bb else (
                            _LK["deadline"] if b >= bb + grace[s]
                            else _LK["cordoned"])
                        drain[s][b].append((node, kind))
                    live[bb:min(bb + reach + int(out_for[s]), nb - 1) + 1] = True
            for (bb, node), kind in last.items():
                if kind == "node_up":
                    back[s][bb].append(node)
        lists = [[
            [(n, _LK["failure"]) for n in fails[s][b]]
            + [x for x in drain[s][b] if x[0] not in fails[s][b]]
            for b in range(nb)] for s in range(S)]
        kept = self._evict_sizes or {"L": 0, "Lb": 0, "E": 0, "Ea": 0, "cap": 0}
        widest = max((len(x) for row in lists for x in row), default=0)
        L = max(kept["L"], -(-max(widest, 1) // 128) * 128)
        Lb = max(kept["Lb"], 1 << max(3, (max(
            (len(x) for row in back for x in row), default=1) - 1).bit_length()))
        per_node = -(-P // N)
        scale = self._evict_scale
        pow2 = lambda x: 1 << (max(int(x), 1) - 1).bit_length()
        fresh = lambda row: sum(k in (_LK["failure"], _LK["new"]) for _, k in row)
        # a node cordoned earlier still holds what its budgets refused: half
        # a node's tasks is the reckoning, the redo rule the guard
        most = max((fresh(x) + (len(x) - fresh(x)) / 2
                    for row in lists for x in row), default=0)
        E = max(kept["E"], 128, pow2(most * per_node * scale))
        budget_room = np.minimum(max_u.astype(np.int64).sum(axis=1), FREE)
        most_a = max((
            sum(k == _LK["failure"] for _, k in x) * per_node
            + sum(k == _LK["deadline"] for _, k in x) * per_node / 4
            + min(int(budget_room[s]),
                  sum(k != _LK["failure"] for _, k in x) * per_node)
            for s, row in enumerate(lists) for x in row), default=0)
        Ea = min(E, max(kept["Ea"], 128, pow2(most_a * scale)))
        total = max((len({n for x in row for n, _ in x}) for row in lists),
                    default=0)
        cap = total * per_node * scale + 2 * Ea
        cap = max(kept["cap"], -(-cap // 128) * 128)
        self._evict_sizes = {"L": L, "Lb": Lb, "E": E, "Ea": Ea, "cap": cap}
        pad = lambda rows, width, fill: jnp.asarray(
            [r + [fill] * (width - len(r)) for r in rows], jnp.int32
        )
        budgets = (jnp.asarray(max_u), jnp.asarray(out_for))
        calls = [
            (pad([[n for n, _ in lists[s][b]] for s in range(S)], L, -1),
             pad([[k for _, k in lists[s][b]] for s in range(S)], L, _LK["pad"]),
             pad([back[s][b] for s in range(S)], Lb, -1)) + budgets
            if live[b] else None for b in range(nb)
        ]
        self._evict_stage = {
            "calls": calls, "L": L, "Lb": Lb, "E": E, "Ea": Ea, "cap": cap,
            "A": A, "app_t": jnp.asarray(proto.app_of),
        }
        return self._evict_stage

    def _evict_state(self) -> EvictState:
        """A batch's ``EvictState`` at its start: no node out, an empty log."""
        evs = self._stage_events()
        S, N, cap = self.S, self.ec.num_nodes, evs["cap"]
        bud = self._budget_on

        def empty():
            planes = {
                "until": jnp.full((S, N), -1, jnp.int32),
                "out_at": jnp.full((S, N), -1, jnp.int32),
                "unavail": jnp.zeros((S, evs["A"]), jnp.int32),
                "bn": jnp.zeros((S, len(BUDGET_COUNTERS)), jnp.int32),
            } if bud else {}
            return EvictState(
                down=jnp.zeros((S, N), bool),
                log=jnp.full((S, 5 if bud else 4, cap), -1, jnp.int32),
                n=jnp.zeros((S, len(_EV_COUNTERS)), jnp.int32),
                wait_s=jnp.zeros((S,), jnp.float32),
                **planes,
            )

        return self._jit_once("evict_state", lambda: jax.jit(empty))()

    def _evict_fn(self):
        """The eviction program of a boundary (``jit_whatif_evict``; device
        retry path, scenarios with timelines), called before the boundary's
        static releases: ``(state, vassign, rq, ev, leave, back, b) ->
        (state, vassign, rq, ev)`` with ``leave`` / ``back`` ``[S, L]`` the
        nodes that go out and come back there. Per scenario, under
        ``ksim.evict``:

        * the victims: every LIVE bind on a leaving node, in the two places
          the device holds a bind: ``vassign`` (arrival binds, the residents
          in its tail; live until the boundary its static release is due
          at, this one included: the events come first) and the record's
          ``t_node`` rows (re-tried binds; live while ``t_relb >= b``),
          found and brought to the front by ``evict_search``, the one
          search of both eviction programs (``ksim.evict/Search``): ``E``
          slots, and where a scenario has more victims the run is made
          again with twice the room (``_evict_scale``). The ``E`` victims
          alone are then sorted into the order ``BoundaryOps.evict_node``
          makes them: the leaving nodes in timeline order, a node's tasks
          by id.
        * the binds go where they stand: ``vassign`` reads PAD (-2 for a
          gang member: the hand-back's code -5), so the static release
          finds nothing; the record row reads no task, no node and no
          release, and ``owed`` gives the cancelled release back
          (``release_leaked`` stays 0).
        * their usage and counts are rewound through the release core (a
          node's victims dealt over the blocks of the list, so that its
          rank rounds stay few), the non-gang ones join the queue behind
          what is there, as far as there is room (the rest dropped,
          counted), one stable sort by priority; the victims go into the
          log behind its cursor.
        * a node that left holds nothing: its ``used`` reads 0.0, exactly
          (the rewind's float residue goes with it), and ``down`` takes the
          nodes that left and gives back the ones that returned.

        Under disruption budgets the program is ``_evict_budget_fn``'s."""
        if self._budget_on:
            return self._evict_budget_fn()

        def build():
            stg, evs = self._dev_rel_stage, self._stage_events()
            L, E, cap = evs["L"], evs["E"], evs["cap"]
            RB, N = self.retry_buffer, self.ec.num_nodes
            BIG = 1 << 30
            relb_pos, task_pos, gang_pos, resd = (
                stg["relb_pos"], stg["task_pos"], stg["gang_pos"], stg["resd"]
            )
            V = int(relb_pos.shape[0])
            ar_N = jnp.arange(N, dtype=jnp.int32)
            rewind, join, cut = self._evict_tail(E)

            def evict_one(state, vassign, rq, ev, leave, back, b):
                hv, hr, hits, ok, at, task, walk = evict_search(
                    vassign, relb_pos >= b, rq.t_node, rq.t_relb >= b,
                    task_pos, rq.t_id, leave, leave >= 0, E)
                # the anchor's order: a node's place in the timeline, then
                # the task's id (E victims: a small sort)
                with stage("ksim.evict/Sort"):
                    walk, task, at = jax.lax.sort(
                        (walk, task, at), num_keys=2, is_stable=False
                    )
                    node = jnp.where(ok, leave[jnp.clip(walk, 0, L - 1)], -1)
                    bound_at = jnp.where(ok & (at >= V), (at - V) // RB, -1)
                with stage("ksim.evict/Write"):
                    vassign = jnp.where(
                        hv, jnp.where(gang_pos, -2, PAD), vassign
                    ).astype(vassign.dtype)
                state = rewind(state, node, task)
                gang, nasks, room, queue = join(rq, ok, task, b)
                with stage("ksim.evict/Write"):
                    rows = jnp.stack([
                        jnp.where(ok, b, -1), jnp.where(ok, task, -1), node,
                        bound_at,
                    ])
                    logged = ev.n[_EV["logged"]]
                    lost = jnp.maximum(hits - E, 0) + jnp.where(
                        (logged + E > cap) & (hits > 0), hits, 0)
                    tally = lambda m: m.sum(dtype=jnp.int32)
                    delta = {
                        "logged": tally(ok), "lost": lost,
                        "evictions": tally(ok),
                        "evict_gang": tally(ok & gang),
                        "evict_dropped": jnp.maximum(nasks - room, 0),
                        "evict_arriving": tally(ok & ~resd[task]),
                        "evict_retried": tally(ok & (bound_at >= 0)),
                    }
                    member = lambda nodes: (
                        (ar_N[:, None] == nodes) & (nodes >= 0)
                    ).any(-1)
                    left = member(leave)
                    state = state._replace(
                        used=jnp.where(left[None, :], 0.0, state.used)
                    )
                    rq = rq._replace(
                        t_id=jnp.where(hr, -1, rq.t_id),
                        t_node=jnp.where(hr, -1, rq.t_node),
                        t_relb=jnp.where(hr, BIG, rq.t_relb),
                        owed=rq.owed
                        - (hr & (rq.t_relb < BIG)).sum(dtype=jnp.int32),
                    )
                rq = cut(rq, queue, nasks, room)
                with stage("ksim.evict/Write"):
                    ev = ev._replace(
                        down=(ev.down | left) & ~member(back),
                        log=jax.lax.dynamic_update_slice(
                            ev.log, rows, (0, jnp.minimum(logged, cap - E))),
                        n=ev.n + jnp.stack(
                            [delta.get(c, jnp.int32(0)) for c in _EV_COUNTERS]),
                    )
                return state, vassign, rq, ev

            fn_v = jax.vmap(
                evict_one, in_axes=(0, 0, 0, 0, 0, 0, None),
                axis_name=_EVICT_VMAP,
            )

            def whatif_evict(*args):
                with stage("ksim.evict"):
                    return fn_v(*args)

            return jax.jit(whatif_evict, donate_argnums=(0, 1, 2, 3))

        return self._jit_once("evict", build)

    def _evict_tail(self, width: int):
        """What both eviction programs do with the ``width`` tasks that
        leave: ``rewind(state, node, task)`` takes their usage and counts
        back through the release core (a node's tasks dealt over the blocks
        of the list, so that its rank rounds stay few), and ``join(rq, go,
        task, b)`` queues the non-gang ones behind what is there, as far as
        there is room, one stable sort by priority: ``(gang, nasks, room,
        the sorted ids / prio / dur / ev_at)``, which ``cut(rq, queue,
        nasks, room)`` cuts to the buffer. Each under its own sub-scope of
        ``ksim.evict`` (``/Rewind``, ``/Join``): a traced run splits the
        program by them (``utils.profiling.SUB_STAGES``)."""
        stg = self._dev_rel_stage
        RB = self.retry_buffer
        NONE = jnp.iinfo(jnp.int32).max
        rel_core = self._release_core()
        want_an, want_pf = rel_core.want_an, rel_core.want_pf
        mgt, antit, preft, prefwt = (
            stg["mgt"], stg["antit"], stg["preft"], stg["prefwt"]
        )
        durt, priot = stg["durt"], stg["priot"]
        req_t = self._slot_srcs[0].requests
        gang_t = self._slot_srcs[0].group_id >= 0
        deal = lambda a: a.reshape((128, width // 128) + a.shape[1:]).swapaxes(
            0, 1).reshape(a.shape)

        def rewind(state, node, task):
            with stage("ksim.evict/Rewind"):
                none_i = jnp.full((width, 1), PAD, jnp.int32)
                state, _, _ = rel_core(
                    state, deal(node), deal(req_t[task]), deal(mgt[task]),
                    deal(antit[task]) if want_an else none_i,
                    deal(preft[task]) if want_pf else none_i,
                    deal(prefwt[task]) if want_pf
                    else jnp.zeros((width, 1), jnp.float32),
                    axis_name=_EVICT_VMAP,
                )
            return state

        def join(rq, go, task, b):
            with stage("ksim.evict/Join"):
                gang = gang_t[task]
                asks = go & ~gang
                room = RB - rq.count
                take = asks & (jnp.cumsum(asks.astype(jnp.int32)) <= room)
                nasks = asks.sum(dtype=jnp.int32)
                cat_ids = jnp.concatenate([rq.ids, jnp.where(take, task, -1)])
                cat_prio = jnp.concatenate([rq.prio, priot[task]])
                key = jnp.where(cat_ids >= 0, -cat_prio, NONE)
                _, *queue = jax.lax.sort(
                    (key, cat_ids, cat_prio,
                     jnp.concatenate([rq.dur, durt[task]]),
                     jnp.concatenate(
                         [rq.ev_at, jnp.full((width,), b, jnp.int32)])),
                    num_keys=1, is_stable=True,
                )
            return gang, nasks, room, queue

        def cut(rq, queue, nasks, room):
            ids, prio, dur, ev_at = queue
            with stage("ksim.evict/Join"):
                return rq._replace(
                    ids=ids[:RB], prio=prio[:RB], dur=dur[:RB],
                    ev_at=ev_at[:RB], count=rq.count + jnp.minimum(nasks, room),
                    dropped=rq.dropped + jnp.maximum(nasks - room, 0),
                )

        return rewind, join, cut

    def _evict_budget_fn(self):
        """``_evict_fn`` for a batch under disruption budgets (the same
        program name, ``jit_whatif_evict``): ``(state, vassign, rq, ev,
        nodes, kind, back, max_u, out_for, b) -> (state, vassign, rq, ev)``
        with ``nodes`` / ``kind`` ``[S, L]`` the boundary's list
        (``_stage_budget_events``) and ``back`` ``[S, Lb]`` its
        ``node_up`` s. Per scenario, ``BoundaryOps.budget_events``' rule:

        * what each entry of the list IS now follows from the planes: a
          node is back where ``until == b`` or a ``node_up`` names it; an
          entry is FORCED where it fails or stands cordoned at its
          deadline, and ASKS where it is cordoned here (unless it is out)
          or was cordoned earlier and is not out.
        * the candidates, every live bind on a forced or an asking entry,
          are ``evict_search``'s, as ``_evict_fn``'s victims are, ``E`` of
          them, and sorted: the forced first (list order), then the asking
          (walk order), a node's tasks by id.
        * the ADMISSION, under ``ksim.evict/Budget``: a forced candidate
          leaves; an asking one iff its rank among its application's
          asking candidates is below ``max_u - unavail`` (the forced of
          this boundary counted first): one running count over ``[E, A]``.
          The admitted are brought to the front (a stable sort on one
          bit) and cut to ``Ea``: the rewind, the queue's merge and the log
          take that list, no longer than a boundary can evict.
        * only the admitted binds go: their places in ``vassign`` and in
          the record are written by index (the one scatter: ``Ea`` unique
          places).
        * an asking entry of which every candidate left holds nothing: it
          goes out here, back at ``b + out_for``, as an entry at its
          deadline does; a failed node waits for its ``node_up``. ``down``
          (no bind) keeps the cordoned and the out, ``used`` reads 0.0 on
          a node that went out, ``unavail`` takes what left."""
        def build():
            stg, evs = self._dev_rel_stage, self._stage_events()
            L, E, Ea, cap, A = (evs[k] for k in ("L", "E", "Ea", "cap", "A"))
            app_t = evs["app_t"]
            RB, N = self.retry_buffer, self.ec.num_nodes
            BIG = 1 << 30
            relb_pos, task_pos, gang_pos, resd = (
                stg["relb_pos"], stg["task_pos"], stg["gang_pos"], stg["resd"]
            )
            V = int(relb_pos.shape[0])
            ar_L = jnp.arange(L, dtype=jnp.int32)
            ar_N = jnp.arange(N, dtype=jnp.int32)
            ar_A = jnp.arange(A, dtype=jnp.int32)
            rewind, join, cut = self._evict_tail(Ea)
            tally = lambda m: m.sum(dtype=jnp.int32)
            K = _LK

            def evict_one(state, vassign, rq, ev, nodes, kind, back, max_u,
                          out_for, b):
                # -- what each entry is now
                at_l = jnp.clip(nodes, 0)
                in_ups = ((nodes[:, None] == back[None, :])
                          & (back[None, :] >= 0)).any(-1)
                until_l, down_l = ev.until[at_l], ev.down[at_l]
                back_l = in_ups | (until_l == b)
                until_l = jnp.where(back_l, -1, until_l)
                cord_l = down_l & ~back_l & (until_l < 0)
                fail_l = kind == K["failure"]
                dead_l = (kind == K["deadline"]) & cord_l
                forced_l = fail_l | dead_l
                asks_l = (until_l < 0) & (
                    (kind == K["new"]) | ((kind == K["cordoned"]) & cord_l))
                on_l = forced_l | asks_l

                _, _, hits, ok, at, task, walk = evict_search(
                    vassign, relb_pos >= b, rq.t_node, rq.t_relb >= b,
                    task_pos, rq.t_id, nodes, on_l, E)
                with stage("ksim.evict/Sort"):
                    forced = ((walk[:, None] == ar_L) & forced_l).any(-1)
                    # the anchor's order: the forced before the asking, an
                    # entry's place in the list, then the task's id
                    turn = jnp.where(ok, jnp.where(forced, walk, L + walk), 2 * L)
                    turn, task, at = jax.lax.sort(
                        (turn, task, at), num_keys=2, is_stable=False
                    )
                    ok = turn < 2 * L
                    walk = jnp.where(ok, turn % L, 0)
                    asking = ok & (turn >= L)
                with stage("ksim.evict/Budget"):
                    app = jnp.where(ok, app_t[task], -1)
                    of_app = (app[:, None] == ar_A)
                    spent = ev.unavail + (
                        of_app & (ok & ~asking)[:, None]
                    ).sum(0, dtype=jnp.int32)
                    mine = of_app & asking[:, None]
                    before = jnp.cumsum(mine.astype(jnp.int32), axis=0) - mine
                    room = jnp.where(mine, (max_u - spent)[None, :] - before, 0)
                    admit = ok & (~asking | (app < 0) | (room.sum(1) > 0))
                    n_admit = tally(admit)
                    # who leaves, at the front, in the order made
                    _, turn_a, task_a, at_a = jax.lax.sort(
                        (~admit, turn, task, at), num_keys=1, is_stable=True
                    )
                    turn_a, task_a, at_a = turn_a[:Ea], task_a[:Ea], at_a[:Ea]
                    go = jnp.arange(Ea, dtype=jnp.int32) < n_admit
                    walk_a = jnp.where(go, turn_a % L, 0)
                    # what an entry held, and what it lost
                    of_entry = walk[:, None] == ar_L
                    held_l = (of_entry & ok[:, None]).sum(0, dtype=jnp.int32)
                    lost_l = (of_entry & admit[:, None]).sum(0, dtype=jnp.int32)
                    app_a = jnp.where(go, app_t[task_a], -1)
                    unavail = ev.unavail + (app_a[:, None] == ar_A).sum(
                        0, dtype=jnp.int32)
                with stage("ksim.evict/Write"):
                    task = jnp.where(go, task_a, 0)
                    kind_a = jnp.where(
                        turn_a >= L, EVICT_KINDS["voluntary"], jnp.where(
                            kind[walk_a] == K["failure"], EVICT_KINDS["failure"],
                            EVICT_KINDS["deadline"]))
                    node = jnp.where(go, nodes[walk_a], -1)
                    bound_at = jnp.where(go & (at_a >= V), (at_a - V) // RB, -1)
                    # the binds go where they stand: only the admitted
                    # (a place that is not written gets an index of its own past
                    # the end: dropped, and the indices stay unique)
                    past = jnp.arange(Ea, dtype=jnp.int32)
                    v_at = jnp.where(go & (at_a < V), at_a, V + past)
                    vassign = vassign.at[v_at].set(
                        jnp.where(gang_pos[jnp.clip(v_at, 0, V - 1)], -2, PAD
                                  ).astype(vassign.dtype),
                        mode="drop", unique_indices=True,
                    )
                    r_at = jnp.where(go & (at_a >= V), at_a - V,
                                     rq.t_id.size + past)
                    gone = jnp.zeros((rq.t_id.size,), bool).at[r_at].set(
                        True, mode="drop", unique_indices=True
                    ).reshape(rq.t_id.shape)
                state = rewind(state, node, task)
                gang, nasks, room, queue = join(rq, go, task, b)
                with stage("ksim.evict/Write"):
                    rows = jnp.stack([
                        jnp.where(go, b, -1), jnp.where(go, task, -1), node,
                        bound_at, jnp.where(go, kind_a, -1),
                    ])
                    logged = ev.n[_EV["logged"]]
                    lost = jnp.maximum(hits - E, 0) + jnp.maximum(
                        n_admit - Ea, 0) + jnp.where(
                        (logged + Ea > cap) & (n_admit > 0), n_admit, 0)
                    delta = {
                        "logged": tally(go), "lost": lost,
                        "evictions": tally(go),
                        "evict_gang": tally(go & gang),
                        "evict_dropped": jnp.maximum(nasks - room, 0),
                        "evict_arriving": tally(go & ~resd[task]),
                        "evict_retried": tally(go & (bound_at >= 0)),
                    }
                    # -- the nodes: who goes out, who stays cordoned, who is back
                    empty_l = asks_l & (held_l == lost_l)
                    out_l = dead_l | empty_l
                    gone_l = fail_l & ~in_ups
                    member = lambda flag: (
                        (ar_N[:, None] == nodes) & flag
                    ).any(-1)
                    backm = ((ar_N[:, None] == back) & (back >= 0)).any(-1) | (
                        ev.until == b)
                    m_out, m_gone = member(out_l), member(gone_l)
                    state = state._replace(used=jnp.where(
                        (m_out | member(fail_l))[None, :], 0.0, state.used
                    ))
                    until = jnp.where(backm, -1, ev.until)
                    until = jnp.where(m_out, b + out_for, until)
                    until = jnp.where(m_gone, BIG, until)
                    bdelta = {
                        "evict_voluntary": tally(
                            go & (kind_a == EVICT_KINDS["voluntary"])),
                        "evict_forced_deadline": tally(
                            go & (kind_a == EVICT_KINDS["deadline"])),
                        "evict_forced_failure": tally(
                            go & (kind_a == EVICT_KINDS["failure"])),
                        "evict_deferred": tally(ok & ~admit),
                        "nodes_drained": tally(empty_l) + tally(
                            dead_l & (held_l == 0)),
                        "nodes_forced": tally(dead_l & (held_l > 0)),
                    }
                    bn = ev.bn + jnp.stack(
                        [bdelta.get(c, jnp.int32(0)) for c in BUDGET_COUNTERS])
                    rq = rq._replace(
                        t_id=jnp.where(gone, -1, rq.t_id),
                        t_node=jnp.where(gone, -1, rq.t_node),
                        t_relb=jnp.where(gone, BIG, rq.t_relb),
                        owed=rq.owed
                        - (gone & (rq.t_relb < BIG)).sum(dtype=jnp.int32),
                    )
                rq = cut(rq, queue, nasks, room)
                with stage("ksim.evict/Write"):
                    ev = ev._replace(
                        down=(ev.down & ~backm) | m_out | m_gone | member(
                            asks_l & ~empty_l),
                        log=jax.lax.dynamic_update_slice(
                            ev.log, rows, (0, jnp.minimum(logged, cap - Ea))),
                        n=ev.n + jnp.stack(
                            [delta.get(c, jnp.int32(0)) for c in _EV_COUNTERS]),
                        until=until,
                        out_at=jnp.where(m_out | member(fail_l & cord_l), b,
                                         ev.out_at),
                        unavail=unavail,
                        bn=bn.at[_BN["budget_spent_max"]].max(unavail.sum()),
                    )
                return state, vassign, rq, ev

            fn_v = jax.vmap(
                evict_one, in_axes=(0,) * 9 + (None,),
                axis_name=_EVICT_VMAP,
            )

            def whatif_evict(*args):
                with stage("ksim.evict"):
                    return fn_v(*args)

            return jax.jit(whatif_evict, donate_argnums=(0, 1, 2, 3))

        return self._jit_once("evict", build)

    def _chunks_pos(self, idx: np.ndarray) -> np.ndarray:
        """[P] each task's place in the chunks' wave order; a task in no
        wave reads the PAD column after the last slot."""
        flat_idx = idx.reshape(-1)
        valid = np.nonzero(flat_idx >= 0)[0]
        pos = np.full(self.pods.num_pods, flat_idx.size, np.int32)
        pos[flat_idx[valid]] = valid
        return pos

    def _stage_dev_rel(self, idx: np.ndarray, C: int) -> dict:
        """Host bucketing + device staging for the device-release path —
        all static per engine (wave packing, durations, chunk layout), so
        it runs once; repeated run() calls reuse the device arrays."""
        from ..ops import tpu3 as V3

        P = self.pods.num_pods
        W = idx.shape[1]
        nchunks = idx.shape[0] // C
        flat_all = idx.reshape(-1)
        vmask = flat_all >= 0
        # Flat WAVE position per pod — release entries address the
        # vassign fold by position (static), not by pod id.
        pos_of = np.full(P, -1, np.int64)
        pos_of[flat_all[vmask]] = np.nonzero(vmask)[0]
        chunk_of = np.full(P, 1 << 30, np.int64)
        chunk_of[flat_all[vmask]] = np.nonzero(vmask)[0] // (C * W)
        prebound = np.nonzero(self.pods.bound_node >= 0)[0]
        Wtot = flat_all.shape[0]
        # Pre-bound pods live in a static tail region of vassign; the
        # final slot is a dedicated PAD sentinel (padded release entries
        # point there and read "not placed").
        jobt = None
        if self.retry_groups:
            # A job's members are released together: each counts as bound
            # in the chunk that holds the job's last member.
            jobt = job_table(self.pods, idx, C)
            chunk_of[flat_all[vmask]] = jobt[flat_all[vmask], 2]
        chunk_of[prebound] = -2
        pos_of[prebound] = Wtot + np.arange(prebound.size)
        SENT = Wtot + prebound.size
        matched = V3._matched_idx(
            self.pods.pod_matches_group,
            np.ones(self.pods.pod_matches_group.shape[1], bool),
        )
        if matched.shape[1] == 0:
            matched = np.full((P, 1), PAD, np.int32)
        first = idx[:, 0]
        wave_t = np.where(
            first >= 0, self.pods.arrival[np.clip(first, 0, None)], np.inf
        )
        # First boundary each pod is eligible at, in f64 on host — the
        # non-finite boundary tail (PAD-only waves) never releases.
        tb_all = wave_t[0 :: C][:nchunks]
        nfin = int(np.isfinite(tb_all).sum())
        elig = np.searchsorted(
            tb_all[:nfin], self._rel_time, side="left"
        ).astype(np.int64)
        elig_ok = np.isfinite(self._rel_time) & (elig < nfin)
        # The boundary each pod releases at is STATIC: first boundary ≥
        # its eligibility that also respects the one-chunk slack (chunks
        # ≤ b−2 folded). Bucket pods per boundary on host so the device
        # touches only that boundary's K_b pods (padded to a pow2
        # bucket, NOT the global max — the Borg duration skew makes the
        # max ~2.4× the mean).
        b_rel = np.maximum(elig, chunk_of + 2)
        ok = elig_ok & (b_rel < nchunks) & (pos_of >= 0)
        pods_ok = np.nonzero(ok)[0].astype(np.int64)
        b_ok = b_rel[pods_ok]
        order = np.lexsort((pods_ok, b_ok))
        pods_s = pods_ok[order]
        b_s = b_ok[order]
        counts = np.bincount(b_s, minlength=nchunks)
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        R = self.ec.num_resources
        Mm = matched.shape[1]
        # Per-pod anti/pref term tables (the bind-side contributions the
        # release must rewind; width ≥ 1 so the commit-block reshapes
        # stay non-degenerate).
        def _w1(a, fill, dt):
            if a.shape[1] == 0:
                return np.full((a.shape[0], 1), fill, dt)
            return a.astype(dt)

        anti_t = _w1(self.pods.anti_req, PAD, np.int32)
        pref_t = _w1(self.pods.pref_aff, PAD, np.int32)
        prefw_t = _w1(self.pods.pref_aff_w, 0.0, np.float32)
        Ma, Mp = anti_t.shape[1], pref_t.shape[1]
        rel_calls = []  # per boundary: None | device (pos, req, mg, ...)
        for bb in range(nchunks):
            k = int(counts[bb])
            if k == 0:
                rel_calls.append(None)
                continue
            # pow2 bucket, floor = the commit-block width (small
            # boundaries must not pay a 4096-wide padded scan; each
            # distinct Kp compiles one small release fn, cache-persisted).
            Kp = 1 << max(8, (k - 1).bit_length())
            seg = pods_s[starts[bb] : starts[bb] + k]
            posb = np.full(Kp, SENT, np.int64)
            posb[:k] = pos_of[seg]
            reqb = np.zeros((Kp, R), np.float32)
            reqb[:k] = self.pods.requests[seg]
            mgb = np.full((Kp, Mm), PAD, np.int32)
            mgb[:k] = matched[seg]
            antib = np.full((Kp, Ma), PAD, np.int32)
            antib[:k] = anti_t[seg]
            prefb = np.full((Kp, Mp), PAD, np.int32)
            prefb[:k] = pref_t[seg]
            prefwb = np.zeros((Kp, Mp), np.float32)
            prefwb[:k] = prefw_t[seg]
            rel_calls.append((
                jnp.asarray(posb.astype(np.int32)),
                jnp.asarray(reqb),
                jnp.asarray(mgb),
                jnp.asarray(antib),
                jnp.asarray(prefb),
                jnp.asarray(prefwb),
            ))
        va = np.full(Wtot + prebound.size + 1, PAD, np.int32)
        va[Wtot : Wtot + prebound.size] = self.pods.bound_node[prebound]
        stg = {
            "rel_calls": rel_calls,
            "b_c": [jnp.asarray(np.int32(bb)) for bb in range(nchunks)],
            "va": jnp.asarray(va),
            # Each task's slot in vassign, for the hand-back (a task in no
            # wave and not pre-bound reads the PAD sentinel).
            "pos": np.where(pos_of >= 0, pos_of, SENT).astype(np.int32),
        }
        if self.retry_buffer:
            stg["mgt"] = jnp.asarray(matched.astype(np.int32))
            stg["antit"] = jnp.asarray(anti_t)
            stg["preft"] = jnp.asarray(pref_t)
            stg["prefwt"] = jnp.asarray(prefw_t)
            stg["durt"] = jnp.asarray(self.pods.duration.astype(np.float32))
            stg["priot"] = jnp.asarray(self.pods.priority.astype(np.int32))
            stg["tbt"] = jnp.asarray(tb_all[:nfin].astype(np.float32))
            stg["tb_c"] = [
                jnp.asarray(np.float32(tb_all[b])) for b in range(nchunks)
            ]
        if self._events_dev:
            # What the eviction program reads of a place in vassign: the
            # task there, the boundary its static release is due at (a bind
            # is live until then), whether it is a gang member; and, by
            # task, whether it is a resident. The boundaries' start times
            # on the host's clock place a timeline's events.
            relb_pos = np.full(SENT + 1, 1 << 30, np.int64)
            relb_pos[pos_of[pods_ok]] = b_ok
            task_pos = np.zeros(SENT + 1, np.int64)
            have = np.nonzero(pos_of >= 0)[0]
            task_pos[pos_of[have]] = have
            stg["relb_pos"] = jnp.asarray(relb_pos.astype(np.int32))
            stg["task_pos"] = jnp.asarray(task_pos.astype(np.int32))
            stg["gang_pos"] = jnp.asarray(self.pods.group_id[task_pos] >= 0)
            stg["resd"] = jnp.asarray(self.pods.bound_node >= 0)
            stg["tb_host"] = tb_all[:nchunks]
        if self.retry_buffer:
            # Everything the pass program reads of a queued task BY ITS ID,
            # side by side: the pass reads ONE row a slot of the queue.
            core = self._release_core()
            rec = {"mg": stg["mgt"]}
            if core.want_an:
                rec["an"] = stg["antit"]
            if core.want_pf:
                rec["pf"], rec["pw"] = stg["preft"], stg["prefwt"]
            if self._events_dev:
                rec["resd"] = stg["resd"]
            if self._budget_on:
                rec["app"] = jnp.asarray(self._budget_proto.app_of)
            if self.retry_groups:
                # What the pass and the upkeep read of a task's JOB, and,
                # per chunk, the members that a job wider than the wave
                # left in the chunk before (the last places of that chunk:
                # whole waves of it), PAD where it left none.
                rec["js"], rec["jp"], rec["jc"] = (
                    jnp.asarray(jobt[:, k]) for k in range(3)
                )
                stg["jobt"] = jnp.asarray(jobt)
                stg["job_host"] = jobt  # ``sim.waves.job_waits`` reads it
                M = max(W, -(-widest_gang(self.pods) // W) * W)
                cin = np.full((nchunks, M), PAD, np.int32)
                for bb in range(1, nchunks):
                    tail = flat_all[max(bb * C * W - M, 0) : bb * C * W]
                    open_ = (tail >= 0) & (
                        jobt[np.clip(tail, 0, None), 2] == bb)
                    cin[bb, M - tail.size :] = np.where(open_, tail, PAD)
                stg["cin"] = [jnp.asarray(c) for c in cin]
            stg["rows"] = jax.jit(T.PackedRows.pack)(
                (*self._slot_srcs, rec)
            )
        return stg

    def _dcn_recover_block(self, dead_pid: int, gen: int = 0) -> dict:
        """``recover`` callback for :func:`parallel.dcn.gather` (round
        15): rebuild ``dead_pid``'s contiguous scenario block through a
        fresh engine over THIS process's local mesh, resuming from the
        dead process's newest published checkpoint when one exists. The
        replay is deterministic, so the returned payload is byte-
        identical to what ``dead_pid`` would have published itself.
        ``gen`` (round 17) is the claim generation — nonzero when an
        earlier claimant died mid-recovery and this call is the fenced
        hand-off; it rides into the recovery engine's fleet telemetry."""
        rb = self._dcn_rebuild
        if rb is None:
            raise RuntimeError(
                "DCN recovery callback invoked on an engine that was "
                "never scenario-sliced"
            )
        per = self.S_global // dcn.worker_count()
        lo, hi = int(dead_pid) * per, (int(dead_pid) + 1) * per
        if dcn.heartbeat_every() > 0:
            # Immediate liveness under OUR pid with the claimed block
            # named, BEFORE the (possibly compile-heavy) engine build —
            # a second failure during recovery must be attributed to the
            # claimant, and siblings must not open the next claim
            # generation while we are still warming up.
            dcn.heartbeat(
                -1, block=(lo, hi), state="recover",
                extra={
                    "recovering_for": int(dead_pid),
                    # Round 21: the fenced claim generation, surfaced by
                    # dcn_launch --watch as recovering-p<dead>@g<gen>.
                    "recover_gen": int(gen),
                },
            )
        eng = WhatIfEngine(
            self.ec, self.pods, rb["scenarios"],
            config=rb["config"],
            wave_width=rb["wave_width"],
            chunk_waves=rb["chunk_waves"],
            mesh=self.mesh,
            collect_assignments=rb["collect_assignments"],
            fork_checkpoint=rb["fork_checkpoint"],
            preemption=rb["preemption"],
            completions=rb["completions"],
            retry_buffer=rb["retry_buffer"],
            granularity_guard=rb["granularity_guard"],
            telemetry=rb["telemetry"],
            policies=rb["policies"],
            _dcn_recovery=dict(
                block=(lo, hi),
                for_pid=int(dead_pid),
                gen=int(gen),
                epoch=dcn.gather_seq(),
                prefer_taint=self._dcn_prefer_taint,
                scales_pods=self._dcn_scales_pods,
            ),
        )
        res = eng.run()
        return dict(
            placed=res.placed,
            assignments=res.assignments,
            util=res.utilization_cpu,
            preemptions=res.preemptions,
            dropped=res.retry_dropped,
            evictions=res.evictions,
            resched=res.evict_rescheduled,
            stranded=res.evict_stranded,
            evict_lat=res.evict_latency_mean,
            lat50=res.latency_p50,
            lat90=res.latency_p90,
            lat99=res.latency_p99,
            frag_stranded=res.stranded_cpu,
            frag_index=res.frag_index_cpu,
            frag_pack=res.packing_efficiency,
            telemetry=res.scenario_telemetry,
            fleet=res.fleet_telemetry,
        )

    def _run_spare(self) -> WhatIfResult:
        """Round 15 elastic spare (tail pids under ``KSIM_DCN_SPARES``):
        owns no scenario block — publish liveness, enter the gather
        immediately as claim-eligible capacity (its sentinel payload is
        available at once, so no worker ever waits on a spare), and
        assemble the same gathered result every worker returns. Fork
        checkpoints are not supported on the spare path."""
        from .telemetry import ReplayTelemetry

        t0 = time.perf_counter()
        if dcn.heartbeat_every() > 0:
            dcn.heartbeat(-1, state="spare", wall_s=0.0)
        parts = dcn.gather(
            "whatif",
            {"spare": True},
            recover=(
                self._dcn_recover_block
                if self._dcn_rebuild is not None
                else None
            ),
        )
        parts = [
            p for p in parts
            if not (isinstance(p, dict) and p.get("spare"))
        ]

        def _cat(k):
            if parts[0][k] is None:
                return None
            return np.concatenate([p[k] for p in parts], axis=0)

        placed = _cat("placed")
        fleet_tel = None
        if parts[0].get("fleet") is not None:
            fleet_tel = ReplayTelemetry.merge(
                [p["fleet"] for p in parts],
                process_ids=list(range(len(parts))),
            )
        wall = time.perf_counter() - t0
        to_schedule = int((self.waves.idx >= 0).sum())
        total = int(placed.sum())
        ndev_local = (
            int(self.mesh.devices.size) if self.mesh is not None else 1
        )
        dev_scale = len(parts)
        return WhatIfResult(
            placed=placed,
            unschedulable=(to_schedule - placed).astype(np.int32),
            total_placed=total,
            wall_clock_s=wall,
            placements_per_sec=total / wall if wall > 0 else 0.0,
            assignments=_cat("assignments"),
            utilization_cpu=_cat("util"),
            completions_on=self.completions_on,
            engine=self.engine,
            preemptions=_cat("preemptions"),
            retry_dropped=_cat("dropped"),
            evictions=_cat("evictions"),
            evict_rescheduled=_cat("resched"),
            evict_stranded=_cat("stranded"),
            evict_latency_mean=_cat("evict_lat"),
            latency_p50=_cat("lat50"),
            latency_p90=_cat("lat90"),
            latency_p99=_cat("lat99"),
            stranded_cpu=_cat("frag_stranded"),
            frag_index_cpu=_cat("frag_index"),
            packing_efficiency=_cat("frag_pack"),
            scenario_telemetry=(
                None
                if parts[0]["telemetry"] is None
                else [t for p in parts for t in p["telemetry"]]
            ),
            fleet_telemetry=fleet_tel,
            n_devices=ndev_local * dev_scale,
            mesh_shape=(
                dict(zip(
                    self.mesh.axis_names,
                    (
                        int(d) * dev_scale
                        for d in self.mesh.devices.shape
                    ),
                ))
                if self.mesh is not None
                else None
            ),
            process_count=jax.process_count(),
        )

    def _wq_exec_block(
        self, bid, lo, hi, resume_pid, gen, speculative, queue_depth
    ) -> dict:
        """``execute`` callback for :func:`parallel.dcn.wq_run`: run
        scenario block ``[lo, hi)`` through a fresh engine on THIS
        process's local mesh and return the 17-key gather payload. The
        chunk program is a pure function of the block contents and the
        full-list engine gates (dictated below, never re-derived), so any
        process executing the block — holder, speculator, or thief —
        produces byte-identical results. ``resume_pid >= 0`` resumes from
        that pid's newest published checkpoint for this block's own
        (negative) epoch; speculative/steal provenance rides into the
        block engine's fleet telemetry via the ``wq`` info dict."""
        rb = self._dcn_rebuild
        if rb is None:
            raise RuntimeError(
                "work-queue execute callback invoked on an engine that "
                "was never scenario-sliced"
            )
        if dcn.heartbeat_every() > 0:
            dcn.heartbeat(
                -1, block=(int(lo), int(hi)),
                state="spec" if speculative else "run",
                extra={
                    "wq_block": int(bid),
                    "leased_blocks": 1,
                    "queue_depth": int(queue_depth),
                },
            )
        eng = WhatIfEngine(
            self.ec, self.pods, rb["scenarios"],
            config=rb["config"],
            wave_width=rb["wave_width"],
            chunk_waves=rb["chunk_waves"],
            mesh=self.mesh,
            collect_assignments=rb["collect_assignments"],
            fork_checkpoint=rb["fork_checkpoint"],
            preemption=rb["preemption"],
            completions=rb["completions"],
            retry_buffer=rb["retry_buffer"],
            granularity_guard=rb["granularity_guard"],
            telemetry=rb["telemetry"],
            policies=rb["policies"],
            _dcn_recovery=dict(
                block=(int(lo), int(hi)),
                for_pid=int(resume_pid),
                gen=int(gen),
                epoch=dcn.wq_ckpt_epoch(dcn.gather_seq(), int(bid)),
                prefer_taint=self._dcn_prefer_taint,
                scales_pods=self._dcn_scales_pods,
                wq=dict(
                    block=int(bid),
                    speculative=bool(speculative),
                    queue_depth=int(queue_depth),
                ),
            ),
        )
        res = eng.run()
        dcn.note_block_chunks(eng._wq_exec_chunks)
        return dict(
            placed=res.placed,
            assignments=res.assignments,
            util=res.utilization_cpu,
            preemptions=res.preemptions,
            dropped=res.retry_dropped,
            evictions=res.evictions,
            resched=res.evict_rescheduled,
            stranded=res.evict_stranded,
            evict_lat=res.evict_latency_mean,
            lat50=res.latency_p50,
            lat90=res.latency_p90,
            lat99=res.latency_p99,
            frag_stranded=res.stranded_cpu,
            frag_index=res.frag_index_cpu,
            frag_pack=res.packing_efficiency,
            telemetry=res.scenario_telemetry,
            fleet=res.fleet_telemetry,
        )

    def _run_workqueue(self) -> WhatIfResult:
        """Round 18 work-stealing scenario-block queue: every process
        (worker, spare, mid-replay joiner) drains
        :func:`parallel.dcn.wq_run` and assembles the per-block payloads
        in block order — structurally the :meth:`_run_spare` assembly,
        keyed by block id instead of pid, so the result is byte-identical
        to the static-slicing oracle for ANY lease interleaving."""
        from .telemetry import ReplayTelemetry

        t0 = time.perf_counter()
        if dcn.heartbeat_every() > 0:
            dcn.heartbeat(
                -1, state="run", wall_s=0.0,
                extra={"leased_blocks": 0},
            )
        blocks = dcn.wq_blocks(self.S_global)
        parts = dcn.wq_run("whatif", blocks, self._wq_exec_block)

        def _cat(k):
            if parts[0][k] is None:
                return None
            return np.concatenate([p[k] for p in parts], axis=0)

        placed = _cat("placed")
        fleet_tel = None
        if parts[0].get("fleet") is not None:
            fleet_tel = ReplayTelemetry.merge(
                [p["fleet"] for p in parts],
                process_ids=list(range(len(parts))),
            )
        wall = time.perf_counter() - t0
        # Mirror the single-process path's to_schedule: waves already
        # covered by a fork checkpoint are not demand, so they must not
        # count against placed when deriving unschedulable. The outer
        # wq engine never runs _init_states (only block engines do), so
        # load the fork bookkeeping here.
        self._fork_waves_done = 0
        if self.fork_checkpoint:
            self._load_fork_or_init()
        idx = self.waves.idx
        if self._fork_waves_done:
            idx = idx[self._fork_waves_done:]
        to_schedule = int((idx >= 0).sum())
        total = int(placed.sum())
        ndev_local = (
            int(self.mesh.devices.size) if self.mesh is not None else 1
        )
        dev_scale = dcn.worker_count()
        return WhatIfResult(
            placed=placed,
            unschedulable=(to_schedule - placed).astype(np.int32),
            total_placed=total,
            wall_clock_s=wall,
            placements_per_sec=total / wall if wall > 0 else 0.0,
            assignments=_cat("assignments"),
            utilization_cpu=_cat("util"),
            completions_on=self.completions_on,
            engine=self.engine,
            preemptions=_cat("preemptions"),
            retry_dropped=_cat("dropped"),
            evictions=_cat("evictions"),
            evict_rescheduled=_cat("resched"),
            evict_stranded=_cat("stranded"),
            evict_latency_mean=_cat("evict_lat"),
            latency_p50=_cat("lat50"),
            latency_p90=_cat("lat90"),
            latency_p99=_cat("lat99"),
            stranded_cpu=_cat("frag_stranded"),
            frag_index_cpu=_cat("frag_index"),
            packing_efficiency=_cat("frag_pack"),
            scenario_telemetry=(
                None
                if parts[0]["telemetry"] is None
                else [t for p in parts for t in p["telemetry"]]
            ),
            fleet_telemetry=fleet_tel,
            n_devices=ndev_local * dev_scale,
            mesh_shape=(
                dict(zip(
                    self.mesh.axis_names,
                    (
                        int(d) * dev_scale
                        for d in self.mesh.devices.shape
                    ),
                ))
                if self.mesh is not None
                else None
            ),
            process_count=jax.process_count(),
        )

    def run(self) -> WhatIfResult:
        # Per-run counter for the round-11 contract test: full-tensor
        # cross-process replication in _fetch must be 0 for this replay.
        self._replicate_count = 0
        if self._dcn_wq:
            # Work-queue mode subsumes the spare path: a spare is just a
            # process that loses every generation-0 lease race and waits
            # for stealable/speculation-eligible work.
            return self._run_workqueue()
        if self._dcn_spare:
            return self._run_spare()
        # Engine-level wall-clock phase breakdown (round 12): the what-if
        # chunk loop gets the same PHASE_NAMES timers the single-replay
        # paths carry, feeding heartbeats, the fleet telemetry merge, and
        # the bench `phases` detail, and the same span primitive: with
        # profiling armed every phase is a TraceAnnotation under one root
        # ``whatif_run:<n>``, ``n`` this engine's call ordinal.
        from .telemetry import PhaseTimers, ReplayTelemetry

        # The call's ONE look at the environment; _init_states, _mesh_put
        # and _handback are handed it.
        span = _make_span(PhaseTimers())
        n, self._run_calls = self._run_calls, self._run_calls + 1
        # Every span below is a ``with`` block, so no exception leaves one
        # open. The body stays in this function: one more Python frame
        # between the caller and the jitted calls makes every lowering a
        # quarter slower (PERF.md §6, PR 35).
        with span.mark(f"whatif_run:{n}"):
            run_phases = span.timers
            with span("stage"):
                if self.mesh is not None:
                    self._mesh_batch = {
                        "put_bytes": 0, "put_s": 0.0, "fetch_bytes": 0, "fetch_s": 0.0,
                    }
                    # First meshed run of the engine: remember the chunk and
                    # hand-back programs as called, to count their collectives.
                    self._mesh_programs = {} if self._mesh_collectives is None else None
                states = self._init_states(span)  # sets fork bookkeeping first
                idx = self.waves.idx
                if self._fork_waves_done:
                    idx = idx[self._fork_waves_done :]
                    if idx.shape[0] == 0:
                        idx = np.full((1, self.waves.wave_width), PAD, np.int32)
                C = min(self.chunk_waves, max(idx.shape[0], 1))
                pad_to = ((idx.shape[0] + C - 1) // C) * C
                if pad_to != idx.shape[0]:
                    idx = np.concatenate([idx, np.full((pad_to - idx.shape[0], idx.shape[1]), PAD, np.int32)])
                dc = self.sset.dc
                if self.mesh is not None:
                    # The scenario tables are static per scenario batch: sharded
                    # over the devices at the engine's first run and kept (each
                    # run() used to deal them out again from device 0). The
                    # state stack is born sharded (_init_states).
                    if self._dc_mesh is None:
                        self._dc_mesh = self._mesh_put(span, dc)
                    dc = self._dc_mesh
                comp_on = (
                    self.completions_on
                    and not self._completions_dev
                    and not self.kube  # BoundaryOps owns releases in kube mode
                )
                dev_rel = self._completions_dev
                if dev_rel:
                    # Everything here is static per engine — staged ONCE and
                    # cached (a second run() pays zero host bucketing/upload).
                    if self._dev_rel_stage is None:
                        self._dev_rel_stage = self._stage_dev_rel(idx, C)
                    stg = self._dev_rel_stage
                    rel_calls, b_c = stg["rel_calls"], stg["b_c"]
                    # vassign is donated through the chunk calls — fresh per run.
                    # Under a mesh it materializes SHARDED (each device holds its
                    # scenarios' buffer; the broadcast never builds a global copy).
                    S = self.S
                    _bc = lambda a: jnp.broadcast_to(a[None], (S,) + a.shape)
                    vassign_d = self._jit_once("vassign", lambda: (
                        jax.jit(_bc, out_shardings=scenario_sharding(self.mesh))
                        if self.mesh is not None
                        else jax.jit(_bc)
                    ))(stg["va"])
                    # Largest number of rank rounds a release block needed so far: a
                    # running max per scenario beside the state, fetched at gather.
                    rounds_d = jnp.zeros(S, jnp.int32)
                    if self.mesh is not None:
                        rounds_d = self._mesh_put(span, rounds_d)
                    if self.retry_buffer:
                        RB = self.retry_buffer
                        durt_d = stg["durt"]
                        tbt_d, tb_c = stg["tbt"], stg["tb_c"]
                        sh_s = (
                            (lambda a: jax.device_put(
                                a, scenario_sharding(self.mesh)
                            ))
                            if self.mesh is not None
                            else (lambda a: a)
                        )
                        priot_d = stg["priot"]
                        rq_d = jax.tree.map(
                            sh_s, self._retry_queue(len(stg["b_c"]))
                        )
                        if self._events_dev:
                            evs = self._stage_events()
                            ev_calls = evs["calls"]
                            ev_d = self._evict_state()
                pending_fold = None  # (rows, choices) of the not-yet-folded chunk
                if comp_on:
                    from .jax_runtime import wave_start_times

                    wave_t = wave_start_times(self.pods, idx)
                    host_assign = np.tile(
                        np.where(
                            self.pods.bound_node >= 0, self.pods.bound_node, PAD
                        ).astype(np.int32),
                        (self.S, 1),
                    )
                    if self._fork_choices is not None:
                        # Fold pre-fork placements except the SOURCE's last chunk,
                        # which stays pending — restoring the one-chunk slack the
                        # uninterrupted source run would be carrying here.
                        C_src = (
                            self._fork_ck.outs[0].shape[0]
                            if self._fork_ck.outs
                            else 0
                        )
                        cut = (
                            min((self._fork_ck.chunk_cursor - 1) * C_src,
                                self._fork_waves_done)
                            if C_src
                            else self._fork_waves_done
                        )
                        cut = max(cut, 0)
                        pidx = self.waves.idx[:cut].reshape(-1)
                        pch = self._fork_choices[:cut].reshape(-1)
                        pv = pidx >= 0
                        host_assign[:, pidx[pv]] = pch[pv][None, :]
                        if cut < self._fork_waves_done:
                            pending_fold = (
                                self.waves.idx[cut : self._fork_waves_done],
                                self._fork_choices[cut : self._fork_waves_done],
                            )
                    released = np.zeros((self.S, self.pods.num_pods), bool)
                    if self.fork_checkpoint and self._fork_waves_done:
                        # The forked state already carries the source replay's
                        # pre-fork releases (completions default ON there): seed
                        # from the persisted mask, or reconstruct what the source
                        # applied at its own chunk boundaries — else the first
                        # post-fork boundary re-subtracts every pre-fork release,
                        # driving count planes negative (advisor round-2 medium).
                        ck = self._fork_ck
                        if ck.released is not None:
                            rel0 = ck.released.astype(bool)
                        else:
                            from .jax_runtime import rebuild_fork_state

                            C_src = ck.outs[0].shape[0] if ck.outs else 0
                            full_first = self.waves.idx[:, 0]
                            full_t = np.where(
                                full_first >= 0,
                                self.pods.arrival[np.clip(full_first, 0, None)],
                                np.inf,
                            )
                            if C_src:
                                # The source padded ITS wave list to a multiple of
                                # C_src — mirror that so chunk rows line up.
                                # (slack=0: a maskless checkpoint predates the
                                # slack rule — see JaxReplayEngine.replay.)
                                idx_src = self.waves.idx
                                need = ck.chunk_cursor * C_src
                                if idx_src.shape[0] < need:
                                    idx_src = np.concatenate([
                                        idx_src,
                                        np.full(
                                            (need - idx_src.shape[0], idx_src.shape[1]),
                                            PAD, np.int32,
                                        ),
                                    ])
                                    full_t = np.concatenate([
                                        full_t,
                                        np.full(need - full_t.shape[0], np.inf),
                                    ])
                                _, rel0 = rebuild_fork_state(
                                    self.pods, idx_src, C_src, ck.outs,
                                    full_t, ck.chunk_cursor, slack=0,
                                )
                            else:
                                rel0 = np.zeros(self.pods.num_pods, bool)
                        released |= rel0[None, :]
                dyn_sharded = self._dyn_dev
                if dyn_sharded is not None and self.mesh is not None:
                    # Chunk-invariant: shard once, not per chunk.
                    dyn_sharded = shard_scenario_tree(self.mesh, dyn_sharded)
                pol_d = None
                if self._policies is not None:
                    # Per-scenario policy vectors (round 9): value-only input to
                    # the compiled chunk program — set_policies + run() reuses the
                    # executable. Sharded once (chunk-invariant) under a mesh.
                    pol_d = jnp.asarray(self._policies)
                    if self.mesh is not None:
                        pol_d = shard_scenario_tree(self.mesh, pol_d)
                srcs = self._slot_srcs
                if self._idx_chunks_mesh is not None:
                    idx_chunks = self._idx_chunks_mesh
                else:
                    idx_chunks = [
                        jnp.asarray(idx[c0 : c0 + C])
                        for c0 in range(0, idx.shape[0], C)
                    ]
                    if self.mesh is not None:
                        # Scenario-shared like the sources: whole on every device
                        # before the loop, not dealt out from device 0 by each
                        # dispatch; and, with no fork to cut the wave list, the
                        # same in every batch: kept.
                        idx_chunks = self._mesh_put(span, idx_chunks, replicate=True)
                        if not self.fork_checkpoint:
                            self._idx_chunks_mesh = idx_chunks
                pre_comp = comp_on and self.preemption
                kbops = None
                if self.kube:
                    # Per-scenario host mirrors over the PERTURBED clusters: the
                    # PostFilter pass then runs the CPU engine's arithmetic per
                    # scenario, and deltas land stacked (sim.boundary docstring).
                    from dataclasses import replace as cfg_replace

                    from ..framework.framework import (
                        FrameworkConfig as _FC,
                        SchedulerFramework,
                    )
                    from .boundary import BoundaryOps
                    from .waves import WaveBatch

                    cfgk = cfg_replace(
                        self._config if self._config is not None else _FC(),
                        enable_preemption=True,
                    )
                    from .telemetry import TelemetryCollector

                    wb = WaveBatch(idx=idx, wave_width=self.wave_width)
                    # One collector per scenario: the host mirrors are the only
                    # carrier of per-scenario bind times / rejection reasons.
                    ktel = [
                        TelemetryCollector(self.telemetry_cfg)
                        if self.telemetry_cfg.enabled
                        else None
                        for _ in range(self.S)
                    ]
                    kbops = [
                        BoundaryOps(
                            ec_s, self.pods, SchedulerFramework(ec_s, self.pods, cfgk),
                            wb, self.wave_width, C,
                            retry_buffer=self.retry_buffer, kube=True, lazy=True,
                            telemetry=ktel[si],
                        )
                        for si, ec_s in enumerate(self.sset.host_clusters(self.ec))
                    ]
                    from .jax_runtime import wave_start_times

                    kube_wave_t = wave_start_times(self.pods, idx)
                    # Lazy boundary sync (round 6): per chunk, fetch only a [S]
                    # non-gang failure count; the full choices fetch + mirror
                    # folds run AFTER the next dispatch (overlapped) unless some
                    # scenario's retry pass will actually read its mirror.
                    # Series telemetry disables the deferral entirely: every
                    # boundary SAMPLES the mirror's occupancy planes
                    # (BoundaryOps.boundary's tel.sample), so the fold must land
                    # pre-boundary at every chunk — otherwise WHICH boundaries
                    # see chunk ci-1's binds depends on the batch-mates' failure
                    # clustering, and the per-scenario gauge series would differ
                    # across DCN slicings of the same scenario list (round 15:
                    # survivor-rebuilt blocks must bit-match the dead process).
                    kwant_series = self.telemetry_cfg.want_series
                    kube_ng = jnp.asarray(self.pods.group_id == PAD)
                    if getattr(self, "_kfail_jit", None) is None:
                        self._kfail_jit = jax.jit(
                            lambda ch, ix, ng: (
                                (ix >= 0)[None]
                                & (ch.reshape((ch.shape[0],) + ix.shape) < 0)
                                & ng[jnp.clip(ix, 0)][None]
                            ).sum(axis=(1, 2), dtype=jnp.int32)
                        )
                    kpending = None  # (ci, rows, choices_dev, nfail_dev[S])

                    def _kfold_pending():
                        nonlocal kpending
                        if kpending is not None:
                            ci_p, rows_p, out_p, _nf = kpending
                            with span("host_mirror"):
                                ch = jax.device_get(out_p)
                                for s in range(self.S):
                                    kbops[s].fold_chunk(ci_p, rows_p, ch[s])
                            kpending = None

                    # Per-scenario timed timelines (chaos campaigns, round 7).
                    # The mirrors' EncodedCluster twins hold VIEWS of
                    # host_stacks["alloc"][s], so mutating the stack rows keeps
                    # host and (re-uploaded) device allocatable in lockstep.
                    hs = self.sset.host_stacks
                    ktimelines = self._timelines
                    kev_cursor = [0] * self.S
                    khas_events = any(ktimelines)
                    if khas_events:
                        ksaved_alloc = hs["alloc"].copy()  # [S, N, R] at t=0
                if pre_comp:
                    # Eager eviction-aware folds (the single-replay round-4 rule,
                    # S-stacked): eviction events must land in the host
                    # bookkeeping BEFORE the next boundary's release decisions,
                    # so the one-chunk slack becomes an explicit bind-chunk gate
                    # instead of a fold lag.
                    from .jax_runtime import bind_chunk_of

                    chunk_of = bind_chunk_of(self.pods, idx, C)
                    nongang = self.pods.group_id == PAD
                rel_bkt = None
                if comp_on:
                    # Static release buckets (round 6): each pod's earliest
                    # eligible boundary — rel_time <= tb[b] and the one-chunk
                    # slack elapsed — is known up front, so boundary b scans only
                    # its own candidates ([S, K_b]) instead of an [S, P] mask.
                    # The dynamic residue (actually assigned, not yet released /
                    # evicted) is re-checked in _apply_releases; a pod still PAD
                    # at its bucket boundary stays PAD forever on these paths, so
                    # the single check is exact.
                    from .jax_runtime import bind_chunk_of as _bco

                    chunk_of_rel = _bco(self.pods, idx, C)
                    if self._fork_choices is not None and not pre_comp:
                        # Lagged-fold fork semantics: pre-fork folded pods can
                        # release from boundary 0 (floor -2+2), the source's
                        # pending last chunk from boundary 1 (floor -1+2 = 1).
                        # (Under pre_comp the eager gate keys off THIS run's idx
                        # only — pre-fork pods keep the 'absent' sentinel there,
                        # matching the eager mask exactly.)
                        C_src = (
                            self._fork_ck.outs[0].shape[0]
                            if self._fork_ck.outs
                            else 0
                        )
                        cut = (
                            min((self._fork_ck.chunk_cursor - 1) * C_src,
                                self._fork_waves_done)
                            if C_src
                            else self._fork_waves_done
                        )
                        cut = max(cut, 0)
                        fidx = self.waves.idx[:cut].reshape(-1)
                        chunk_of_rel[fidx[fidx >= 0]] = -2
                        hidx = self.waves.idx[cut : self._fork_waves_done].reshape(-1)
                        chunk_of_rel[hidx[hidx >= 0]] = -1
                    tb_rel = wave_t[0::C]
                    nfin_rel = int(np.isfinite(tb_rel).sum())
                    b_rel = np.maximum(
                        np.searchsorted(
                            tb_rel[:nfin_rel], self._rel_time, side="left"
                        ),
                        chunk_of_rel + 2,
                    )
                    rcand = np.nonzero(b_rel < nfin_rel)[0].astype(np.int64)
                    rcand = rcand[np.argsort(b_rel[rcand], kind="stable")]
                    roff = np.concatenate(
                        ([0], np.cumsum(
                            np.bincount(b_rel[rcand], minlength=max(nfin_rel, 1))
                        ))
                    ).astype(np.int64)
                    rel_bkt = (rcand, roff, nfin_rel)
                ppending = None  # pre_comp deferred chunk: dict, see closures
                if pre_comp:
                    from .jax_runtime import preemption_walk

                    def _pre_walk():
                        """Fetch the [S] eviction summary of the deferred chunk
                        and walk ONLY the evicting scenarios (rare). Idempotent —
                        caches the fetches on the entry."""
                        e = ppending
                        if e is None or e["ev"] is not None:
                            return
                        ev = np.asarray(jax.device_get(e["ev_d"])).astype(bool)
                        e["ev"] = ev
                        if ev.any():
                            ch, evn, evt = jax.device_get(
                                (e["out"][0], e["out"][1], e["out"][2])
                            )
                            e["ch"] = ch
                            rows = e["rows"]
                            for s in np.nonzero(ev)[0]:
                                preemption_walk(
                                    host_assign[s], rows,
                                    ch[s].reshape(rows.shape), evn[s], evt[s],
                                    self.static3.pod_tier, nongang,
                                    released=released[s],
                                )

                    def _pre_finish():
                        """Complete the deferred chunk: eviction walks (if not
                        already done), then ONE vectorized fold for every
                        no-eviction scenario — with zero events the walk is
                        exactly `assignments[rows] = finals`, so the bulk
                        assignment is bit-identical to S per-scenario walks."""
                        nonlocal ppending
                        e = ppending
                        if e is None:
                            return
                        _pre_walk()
                        quiet = np.nonzero(~e["ev"])[0]
                        if quiet.size:
                            ch = e["ch"]
                            if ch is None:
                                ch = np.asarray(jax.device_get(e["out"][0]))
                            rows = e["rows"]
                            flat = rows.reshape(-1)
                            v = np.nonzero(flat >= 0)[0]
                            if v.size:
                                host_assign[np.ix_(quiet, flat[v])] = (
                                    ch.reshape(self.S, -1)[np.ix_(quiet, v)]
                                )
                        ppending = None

                    if getattr(self, "_evany_jit", None) is None:
                        self._evany_jit = jax.jit(
                            lambda evn: (evn >= 0).any(axis=1)
                        )
                outs = []
                # PUBLISH_STATS / RETRY_STATS / CRC_STATS are cumulative module
                # state — snapshot them so the fleet phases below surface only
                # THIS run's publications, KV retries and CRC fallbacks (a prior
                # run in the same process must not leak into the phase map).
                _ps_start = dcn.publish_stats()
                _bg_start = dcn.bg_publish_stats()
                _rs_start = dcn.retry_stats()
                _cs_start = dcn.crc_stats()
                n_chunks = len(range(0, idx.shape[0], C))
                # Liveness heartbeats (round 12): one overwritten KV beacon per
                # process on a chunk cadence — plain puts, never a gather. A
                # recovery engine (round 15) beats too, under the CLAIMANT's own
                # pid with state="recover" and the claimed block named, so a
                # SECOND failure during recovery is attributed to the claimant.
                recovering = self._dcn_recovery is not None
                wq_info = self._dcn_wq_info  # block engine under the round-18 queue
                hb_on = (
                    self._dcn_sliced or recovering
                ) and dcn.heartbeat_every() > 0
                hb_block = (self._proc_lo, self._proc_lo + self.S)
                if wq_info is not None:
                    # Work-queue block engine: beats under our OWN pid with the
                    # lease named (dcn.heartbeat also renews the lease on every
                    # beat). wq_rate — chunks per wall second, the straggler
                    # watermark's input — is refreshed per beat in the loop.
                    hb_kw = dict(
                        state="spec" if wq_info.get("speculative") else "run",
                        extra={
                            "wq_block": int(wq_info.get("block", -1)),
                            "leased_blocks": 1,
                            "queue_depth": int(wq_info.get("queue_depth", 0)),
                            "wq_rate": 0.0,
                        },
                    )
                elif recovering:
                    hb_kw = dict(
                        state="recover",
                        extra={
                            "recovering_for": int(
                                self._dcn_recovery.get("for_pid", -1)
                            ),
                            "recover_gen": int(self._dcn_recovery.get("gen", 0)),
                        },
                    )
                else:
                    hb_kw = {}
                # Recoverable work-queue (round 15, parallel.dcn): on a chunk
                # cadence, publish a compressed host snapshot of the loop
                # carriers so a survivor can resume THIS block mid-replay after
                # a host loss. Supported on the device-carrier paths (plain
                # and device-release ± retry, where the whole block state
                # lives in `states`/`vassign`/retry tensors plus `outs`); the
                # host-fold modes (completions host path, kube mirrors) carry
                # state in per-scenario host structures instead — a claimed
                # block there re-executes from chunk 0, still byte-identical.
                ck_ok = kbops is None and not comp_on
                # Queue block engines checkpoint too (under the block's own
                # negative epoch) — that is what a speculator or thief resumes.
                ck_every = (
                    dcn.ckpt_every()
                    if ck_ok
                    and (
                        (self._dcn_sliced and not self._dcn_spare)
                        or wq_info is not None
                    )
                    else 0
                )

                def _carriers():
                    c = {"states": states}
                    if dev_rel:
                        c["vassign"] = vassign_d
                        if self.retry_buffer:
                            c["retry"] = rq_d
                    return c

                evicting = dev_rel and self._events_dev

                _ck_sig = [
                    self.engine, bool(dev_rel), int(self.retry_buffer),
                    int(self.S), int(C), int(n_chunks),
                ]
                start_ci = 0
                # for_pid < 0 is a generation-0 queue lease: nobody ran this block
                # before us, so there is no checkpoint to resume — execute from
                # chunk 0 (steals/speculation name the holder via for_pid >= 0).
                resume_pid, resume_epoch = -1, None
                if recovering and ck_ok:
                    resume_pid = int(self._dcn_recovery.get("for_pid", -1))
                    resume_epoch = self._dcn_recovery.get("epoch")
                elif (
                    ck_ok
                    and ck_every > 0
                    and wq_info is None
                    and self._dcn_sliced
                    and not self._dcn_spare
                    and dcn.resume_enabled()
                    and dcn.durable_dir()
                ):
                    # Durable ground (round 20): a restarted fleet (dcn_launch
                    # --resume after whole-fleet death) seeds each process's OWN
                    # static block from its newest complete durable checkpoint.
                    # Epoch defaults to checkpoint_epoch(), which matches the
                    # dead fleet's — the gather sequence replays
                    # deterministically — and load_checkpoint merges the journal
                    # mirror into its candidate walk, so the torn-newest-cursor
                    # fallback applies to journal files too.
                    resume_pid = dcn.process_info()[1]
                if resume_pid >= 0:
                    from ..utils.metrics import log as _log
                    from .jax_runtime import restore_carriers

                    dead = resume_pid
                    # Round 17: walk the dead process's checkpoints newest-first.
                    # dcn.load_checkpoint already skips CRC-invalid blobs; this
                    # loop additionally falls back past blobs that validate on
                    # the wire but turn out unusable here (signature or carrier-
                    # shape mismatch), via `before_cursor`, instead of giving up
                    # on the whole resume.
                    before = None
                    while True:
                        ckd = dcn.load_checkpoint(
                            dead,
                            epoch=resume_epoch,
                            before_cursor=before,
                        )
                        if ckd is None:
                            if before is not None:
                                _log.warning(
                                    "dcn: no usable checkpoint left for process "
                                    "%d — re-executing its block from chunk 0",
                                    dead,
                                )
                            break
                        before = int(ckd["cursor"])
                        pay = ckd["payload"]
                        if not (
                            isinstance(pay, dict)
                            and tuple(ckd["block"])
                            == (int(hb_block[0]), int(hb_block[1]))
                            and pay.get("sig") == _ck_sig
                        ):
                            _log.warning(
                                "dcn: ignoring mismatched checkpoint (cursor %d) "
                                "for process %d — trying an older one",
                                before, dead,
                            )
                            continue
                        try:
                            carr = restore_carriers(_carriers(), pay["leaves"])
                        except ValueError as e:
                            _log.warning(
                                "dcn: process %d's checkpoint at cursor %d is "
                                "unusable (%s) — trying an older one",
                                dead, before, e,
                            )
                            continue
                        states = carr["states"]
                        if dev_rel:
                            vassign_d = carr["vassign"]
                            if self.retry_buffer:
                                rq_d = carr["retry"]
                        outs = list(pay["outs"])
                        start_ci = int(pay["cursor"])
                        _log.warning(
                            "dcn: resumed process %d's block [%d, %d) from "
                            "its newest checkpoint at chunk %d/%d",
                            dead, hb_block[0], hb_block[1], start_ci, n_chunks,
                        )
                        break
                # Chunks this engine will actually execute (resumes skip the
                # carried prefix) — the queue driver charges these to
                # spec_wasted_chunks when a speculative duplicate is discarded.
                self._wq_exec_chunks = max(n_chunks - start_ci, 0)
                # With profiling armed, the v3 chunk program and each release
                # bucket's program go to utils.profiling.stage_tables by module
                # name, on the shapes of their first call (taken before the call:
                # it donates its buffers).
                registered: set = set()

                def _reg(fn, args, kind="chunk"):
                    if self._mesh_programs is not None:
                        self._mesh_programs.setdefault(
                            kind, (fn, _shape_structs(args))
                        )
                    if span.armed and fn not in registered:
                        registered.add(fn)
                        _register_call(fn, args)

                rel_buckets: set = set()  # the pow2 release widths this run used
            t0 = time.perf_counter()
            for ci, c0 in enumerate(range(0, idx.shape[0], C)):
                if ci < start_ci:
                    continue  # chunks already carried by the resumed state
                if ck_every and ci and ci % ck_every == 0:
                    from .jax_runtime import checkpoint_payload

                    # Round-19 split: only the device→host snapshot stays on
                    # the loop thread (it must see the state exactly as of
                    # chunk ci); encode + CRC framing + the retried KV sets
                    # — and the round-20 durable-journal mirror — ride the
                    # single-flight publisher thread, newest-wins. Drained
                    # before the final gather below — the one place this
                    # leg needs a durable cursor.
                    with span("checkpoint"):
                        dcn.publish_checkpoint_async(
                            ci,
                            checkpoint_payload(ci, _ck_sig, _carriers(), outs),
                            hb_block,
                            epoch=(self._dcn_recovery or {}).get("epoch"),
                        )
                if hb_on:
                    if wq_info is not None and ci > start_ci:
                        wall_now = time.perf_counter() - t0
                        if wall_now > 0:
                            hb_kw["extra"]["wq_rate"] = round(
                                (ci - start_ci) / wall_now, 4
                            )
                    dcn.maybe_heartbeat(
                        ci - 1,
                        total=n_chunks,
                        block=hb_block,
                        wall_s=time.perf_counter() - t0,
                        phases=run_phases.acc,
                        **hb_kw,
                    )
                if kbops is not None:
                    t_now = kube_wave_t[c0]
                    due_any = khas_events and any(
                        kev_cursor[s] < len(ktimelines[s])
                        and ktimelines[s][kev_cursor[s]].time <= t_now
                        for s in range(self.S)
                    )
                    if kpending is not None and (
                        kwant_series
                        or np.asarray(kpending[3]).any()
                        or any(b.retry_q for b in kbops)
                        or due_any
                    ):
                        # Some scenario's retry pass will read its mirror —
                        # or a due node_down must evict against bookkeeping
                        # current through chunk ci-1: resolve the deferred
                        # fold (all scenarios — failures cluster, and the
                        # boundary pass needs every mirror current anyway).
                        _kfold_pending()
                    chaos = None
                    if due_any:
                        chaos = []  # per-scenario eviction PairArrays (or None)
                        dirty_alloc = False
                        for s in range(self.S):
                            tl, cur = ktimelines[s], kev_cursor[s]
                            cps, cns = [], []
                            while cur < len(tl) and tl[cur].time <= t_now:
                                ev = tl[cur]
                                cur += 1
                                dirty_alloc = True
                                if (
                                    ktel[s] is not None
                                    and ktel[s].cfg.want_timeline
                                    and ev.kind in ("node_down", "node_up")
                                ):
                                    ktel[s].event(
                                        ev.kind, float(ev.time), -1, int(ev.node)
                                    )
                                if ev.kind == "node_down":
                                    hs["alloc"][s, ev.node] = 0.0
                                    cp, cn = kbops[s].evict_node(
                                        ev.node, ci, float(t_now)
                                    )
                                    if cp.size:
                                        cps.append(cp)
                                        cns.append(cn)
                                elif ev.kind == "node_up":
                                    hs["alloc"][s, ev.node] = ksaved_alloc[
                                        s, ev.node
                                    ]
                                elif ev.kind == "capacity_scale":
                                    hs["alloc"][s, ev.node] = (
                                        ksaved_alloc[s, ev.node] * ev.scale
                                    )
                            kev_cursor[s] = cur
                            chaos.append(
                                (np.concatenate(cps), np.concatenate(cns))
                                if cps
                                else None
                            )
                        if dirty_alloc:
                            # One [S, N, R] upload per event-bearing boundary
                            # — events are sparse in virtual time, so this
                            # stays off the steady-state chunk path.
                            dc = dc._replace(
                                allocatable=jnp.asarray(hs["alloc"])
                            )
                    subs = []
                    adds = []
                    any_bdelta = False
                    for s, b in enumerate(kbops):
                        rel, binds, evicts = b.boundary(ci, kube_wave_t[c0])
                        cev = chaos[s] if chaos is not None else None
                        sub = (
                            np.concatenate(
                                [rel[0], evicts[0]]
                                + ([cev[0]] if cev is not None else [])
                            ),
                            np.concatenate(
                                [rel[1], evicts[1]]
                                + ([cev[1]] if cev is not None else [])
                            ),
                        )
                        if sub[0].size or binds[0].size:
                            any_bdelta = True
                        subs.append(sub)
                        adds.append(binds)
                    if any_bdelta:
                        with span("boundary_fold"):
                            states = self._apply_stacked_boundary_delta(
                                states, subs, adds
                            )
                if comp_on and ci < rel_bkt[2]:
                    cand_b = rel_bkt[0][rel_bkt[1][ci] : rel_bkt[1][ci + 1]]
                    if cand_b.size:
                        if pre_comp and ppending is not None:
                            # Evicting scenarios must walk chunk ci-1 BEFORE
                            # the release decision (evicted pods never
                            # release); quiet scenarios' folds stay deferred —
                            # their ci-1 binds are not candidates here.
                            _pre_walk()
                        with span("boundary_fold"):
                            states = self._apply_releases(
                                states, host_assign, released, cand_b
                            )
                if evicting and ev_calls[ci] is not None:
                    # The events due by this boundary come first: the nodes
                    # that leave give up their tasks (into the queue) before
                    # anything is released or re-tried. What the host sends
                    # is the boundary's leaving and returning nodes.
                    with span.mark("host_events"):
                        args = (states, vassign_d, rq_d, ev_d) + ev_calls[ci] + (
                            b_c[ci],
                        )
                        evict_fn = self._evict_fn()
                        _reg(evict_fn, args)
                    with span("boundary_fold"):
                        states, vassign_d, rq_d, ev_d = evict_fn(*args)
                if dev_rel:
                    # Static releases first (the bucketed fn; ordering is by
                    # data dependency on states/vassign), then the chunk.
                    rc = rel_calls[ci]
                    if rc is not None:
                        args = (states, vassign_d, rounds_d) + rc
                        if self._dyn is not None:
                            # Per-scenario domain overrides: releases of
                            # relabeled nodes land in the overridden domain.
                            args = args + (
                                self._dyn_dev.ov_nodes,
                                self._dyn_dev.ov_gdom,
                                self._dyn_dev.ov_old,
                            )
                        K_rel = int(rc[0].shape[0])
                        rel_fn = self._release_fn(K_rel)
                        rel_buckets.add(K_rel)
                        _reg(rel_fn, args)
                        with span("boundary_fold"):
                            states, rounds_d = rel_fn(*args)
                # Dispatch phase (the chunk-fn if/elif chain below runs exactly
                # one branch), with the chunk's marker inside it.
                with span("dispatch"), span.mark(f"chunk:{ci}"):
                    if dev_rel and self.retry_buffer:
                        # Two programs, the second dispatched on the
                        # first's arrays: nothing comes to the host between.
                        args = (
                            dc, states, stg["rows"], tbt_d, tb_c[ci], b_c[ci],
                            rq_d,
                        )
                        if evicting:
                            # the eviction program's node planes stay out of
                            # the pass program
                            planes = {"until": ev_d.until, "out_at": ev_d.out_at}
                            args += (ev_d._replace(until=None, out_at=None),)
                        _reg(self._retry_fn, args, "retry")
                        got = self._retry_fn(*args)
                        if self.retry_groups:
                            # the pass's row into the record's log
                            states, rq_d, retry_placed, row_d = got
                            args = (rq_d, row_d, stg["rows"], b_c[ci])
                            _reg(self._record_fn, args, "record")
                            rq_d = self._record_fn(*args)
                        elif evicting:
                            states, rq_d, ev_d, retry_placed = got
                            ev_d = ev_d._replace(**planes)
                        else:
                            states, rq_d, retry_placed = got
                        args = (
                            dc, states, srcs[0], srcs[1], durt_d, priot_d,
                            idx_chunks[ci], b_c[ci], vassign_d, rq_d,
                        )
                        if evicting:
                            args += (ev_d.down,)
                        if self.retry_groups:
                            args += (None, stg["jobt"], stg["cin"][ci])
                        _reg(self._chunk_fn, args)
                        states, vassign_d, rq_d, counts = self._chunk_fn(*args)
                        out = (counts, retry_placed)
                    elif dev_rel:
                        args = (
                            dc, states, srcs[0], srcs[1], idx_chunks[ci],
                            b_c[ci], vassign_d,
                        )
                        if dyn_sharded is not None:
                            args = args + (dyn_sharded,)
                        elif pol_d is not None:
                            args = args + (None,)  # dyn slot
                        if pol_d is not None:
                            args = args + (pol_d,)
                        _reg(self._chunk_fn, args)
                        states, vassign_d, out = self._chunk_fn(*args)
                    else:
                        # Fused device-side gather + wave scan: one dispatch per
                        # chunk, indices pre-staged (ops.tpu.SlotSource). Under a
                        # mesh the sources are replicated once per engine and
                        # every device gathers its chunk rows locally.
                        args = (dc, states, srcs[0], srcs[1], idx_chunks[ci])
                        if dyn_sharded is not None:
                            args = args + (dyn_sharded,)
                        elif pol_d is not None:
                            args = args + (None,)  # dyn slot
                        if pol_d is not None:
                            args = args + (pol_d,)
                        _reg(self._chunk_fn, args)
                        states, out = self._chunk_fn(*args)
                if pre_comp:
                    # Deferred eviction-aware fold (round 6): fetch only the
                    # [S] eviction summary now; the previous chunk resolves
                    # here — its D2H copies were launched an iteration ago
                    # and this chunk is already in flight, so the host work
                    # overlaps device compute. Evicting scenarios take the
                    # per-scenario walk; the (common) no-eviction scenarios
                    # get one vectorized fold.
                    ev_d = self._evany_jit(out[1])
                    for a in (out[0], out[1], out[2]):
                        if hasattr(a, "copy_to_host_async"):
                            a.copy_to_host_async()
                    _pre_finish()
                    ppending = {
                        "rows": idx[c0 : c0 + C], "out": out, "ev_d": ev_d,
                        "ev": None, "ch": None,
                    }
                    continue  # host_assign is the result carrier — outs unused
                if kbops is not None:
                    # Deferred fold into the scenario host mirrors (round 6):
                    # only the [S] failure count is fetched per chunk; the
                    # full choices land after the next dispatch (or eagerly
                    # at the next boundary if any retry pass needs them).
                    nf_d = self._kfail_jit(out, idx_chunks[ci], kube_ng)
                    if hasattr(out, "copy_to_host_async"):
                        out.copy_to_host_async()
                    _kfold_pending()
                    kpending = (ci, idx[c0 : c0 + C], out, nf_d)
                    continue  # the mirrors carry the result — outs unused
                outs.append(out)
                if comp_on:
                    # Fold the PREVIOUS chunk's choices AFTER dispatching this
                    # one: the blocking fetch overlaps the in-flight chunk and
                    # boundary b only ever sees chunks ≤ b−2 (one-chunk slack,
                    # shared with JaxReplayEngine and the greedy anchor).
                    if pending_fold is not None:
                        with span("host_mirror"):
                            self._fold(host_assign, *pending_fold)
                    if hasattr(out, "copy_to_host_async"):
                        out.copy_to_host_async()  # overlap D2H with the chunk
                    pending_fold = (idx[c0 : c0 + C], out)
            if pre_comp:
                _pre_finish()  # the last chunk's deferred walk/fold
            if kbops is not None:
                # Trailing boundary (the single-replay/greedy twin): last-
                # chunk failures still get their PostFilter attempt. The
                # final chunk's fold must land first (bookkeeping parity).
                _kfold_pending()
                subs = []
                adds = []
                any_bdelta = False
                for b in kbops:
                    rel, binds, evicts = b.boundary(idx.shape[0] // C, np.inf)
                    sub = (
                        np.concatenate([rel[0], evicts[0]]),
                        np.concatenate([rel[1], evicts[1]]),
                    )
                    if sub[0].size or binds[0].size:
                        any_bdelta = True
                    subs.append(sub)
                    adds.append(binds)
                if any_bdelta:
                    with span("boundary_fold"):
                        states = self._apply_stacked_boundary_delta(
                            states, subs, adds
                        )
                if khas_events:
                    # The stack rows were mutated in lockstep with the
                    # mirrors — restore the t=0 view so the engine (and its
                    # ScenarioSet) stays reusable.
                    hs["alloc"][...] = ksaved_alloc
            with span("device_wait"):
                jax.block_until_ready(states)
            self._last_states = states  # probe: the batch's final carry (tests)
            if ck_every:
                # Round-19 durable-cursor boundary: every queued background
                # publication must be on the KV plane before this process
                # beacons "gather" / completes its work-queue block — a
                # sibling recovering after that point may only be offered
                # cursors that are actually complete. Drain wall is exposed
                # loop wall, attributed to the checkpoint phase.
                with span("checkpoint"):
                    dcn.drain_publisher()
            wall = time.perf_counter() - t0

            # ``gather``: counts, utilization and what the host-side paths
            # assemble, up to the placements' hand-back.
            with span("gather"):
                to_schedule = int((idx >= 0).sum())
                chunk_handback = False  # placements from the chunks' choices
                kube_preempt = kube_dropped = None
                kube_evict = kube_resched = kube_stranded = kube_lat = None
                sc_lat_p50 = sc_lat_p90 = sc_lat_p99 = sc_telemetry = None
                frag_stranded = frag_index = frag_pack = None
                stel = None
                if kbops is not None:
                    host_k = np.stack([b.assignments for b in kbops])
                    assignments = host_k if self.collect_assignments else None
                    scheduled = self.pods.bound_node == PAD
                    placed = (
                        (host_k[:, scheduled] >= 0).sum(axis=1).astype(np.int32)
                    )
                    # One counter tuple per mirror (BoundaryOps.counters owns the
                    # field list — result assembly and the DCN gather can't drift).
                    cnt = np.asarray([b.counters() for b in kbops], np.float64)
                    kube_preempt = cnt[:, 0].astype(np.int32)
                    kube_dropped = cnt[:, 1].astype(np.int32)
                    kube_evict = cnt[:, 2].astype(np.int32)
                    kube_resched = cnt[:, 3].astype(np.int32)
                    kube_stranded = cnt[:, 4].astype(np.int32)
                    kube_lat = cnt[:, 5]
                    # Fragmentation economics (round 13): each mirror holds the
                    # scenario's committed state, its restored allocatable view
                    # (hs["alloc"][s] — put back above when events ran), and the
                    # still-pending set — exactly the inputs the single-replay
                    # engines hand to the same helper, so the [S] gauges
                    # bit-match the per-scenario kube replays.
                    from ..utils.metrics import fragmentation_gauges

                    frag_stranded = np.zeros(self.S, np.float64)
                    frag_index = np.zeros(self.S, np.float64)
                    frag_pack = np.zeros(self.S, np.float64)
                    for s, b in enumerate(kbops):
                        b.flush_planes()
                        pend = scheduled & (host_k[s] == PAD)
                        fr = fragmentation_gauges(
                            b.ec.allocatable, b.st.used,
                            self.pods.requests[pend], b.ec.vocab._r,
                        )
                        frag_stranded[s] = fr["stranded"].get("cpu", 0.0)
                        frag_index[s] = fr["frag_index"].get("cpu", 0.0)
                        frag_pack[s] = fr["packing_efficiency"]
                    if self.telemetry_cfg.enabled:
                        stel = [t.result() for t in ktel]
                        lat_q = np.full((3, self.S), np.nan, np.float64)
                        for s, t in enumerate(stel):
                            if t is not None and t.latency is not None:
                                lat_q[:, s] = (
                                    t.latency["p50"],
                                    t.latency["p90"],
                                    t.latency["p99"],
                                )
                        sc_lat_p50, sc_lat_p90, sc_lat_p99 = lat_q
                        sc_telemetry = (
                            stel if self.telemetry_cfg.want_series else None
                        )
                elif comp_on and self.preemption:
                    # The eager eviction-aware folds ARE the walk (see the chunk
                    # loop); host_assign is the result carrier. Counting device
                    # finals would overcount later-evicted pods.
                    assignments = host_assign if self.collect_assignments else None
                    scheduled = self.pods.bound_node == PAD
                    placed = (
                        (host_assign[:, scheduled] >= 0).sum(axis=1).astype(np.int32)
                    )
                elif self.collect_assignments and self.preemption:
                    choices = np.concatenate([self._fetch(o[0]) for o in outs], axis=1)
                    ev_node = np.concatenate([self._fetch(o[1]) for o in outs], axis=1)
                    ev_tier = np.concatenate([self._fetch(o[2]) for o in outs], axis=1)
                    from .jax_runtime import preemption_walk

                    assignments = np.full((self.S, self.pods.num_pods), PAD, np.int32)
                    prebound = self.pods.bound_node >= 0
                    assignments[:, prebound] = self.pods.bound_node[prebound]
                    for s in range(self.S):
                        preemption_walk(
                            assignments[s], idx, choices[s], ev_node[s], ev_tier[s],
                            self.static3.pod_tier, self.pods.group_id == PAD,
                        )
                    scheduled = ~prebound
                    placed = (assignments[:, scheduled] >= 0).sum(axis=1).astype(np.int32)
                elif self.collect_assignments and not dev_rel:
                    # Every chunk's choices are still on the device: the placements
                    # come to the host in the ``handback`` phase below, and
                    # ``placed`` is counted from them there.
                    assignments = placed = None
                    chunk_handback = True
                else:
                    assignments = None
                    if self._need_choices:
                        # Completions forced per-pod choices; count from them.
                        choices = np.concatenate([self._fetch(o) for o in outs], axis=1)
                        flat_idx = idx.reshape(-1)
                        valid = flat_idx >= 0
                        placed = (
                            (choices.reshape(self.S, -1)[:, valid] >= 0)
                            .sum(axis=1)
                            .astype(np.int32)
                        )
                    elif self.retry_buffer:
                        # (counts [S, C], retry_placed [S]) per chunk: placements
                        # from arrival waves plus boundary retry passes.
                        placed = self._fetch(
                            self._jit_once("placed_retry", lambda: jax.jit(
                                lambda o: (
                                    jnp.concatenate(
                                        [c for c, _ in o], axis=1
                                    ).sum(axis=1, dtype=jnp.int32)
                                    + jnp.stack([r for _, r in o], axis=1).sum(
                                        axis=1, dtype=jnp.int32
                                    )
                                )
                            ))(outs)
                        ).astype(np.int32)
                        if self._wide_gangs:
                            # a wave counted its tentative binds
                            placed -= self._fetch(states.txn.undone)
                        if evicting:
                            # An eviction takes an arriving task's bind out
                            # of ``placed`` again; a resident's re-bind was
                            # never in it (the single replay's count).
                            ev_n = self._fetch(ev_d.n).astype(np.int64)
                            if ev_n[:, _EV["lost"]].any():
                                # The log was reckoned too small for what
                                # these timelines evict: twice the room
                                # (another shape: the programs compile
                                # anew), and the batch is made again.
                                self._evict_scale *= 2
                                self._evict_stage = None
                                for k in ("evict", "evict_state"):
                                    self._run_jits.pop(k, None)
                                return self.run()
                            placed = (
                                placed - ev_n[:, _EV["evict_arriving"]]
                                - ev_n[:, _EV["rebound_resident"]]
                            ).astype(np.int32)
                            kube_evict = ev_n[:, _EV["evictions"]].astype(np.int32)
                            kube_resched = ev_n[:, _EV["rebound"]].astype(np.int32)
                            kube_stranded = kube_evict - kube_resched
                            kube_lat = self._fetch(ev_d.wait_s).astype(
                                np.float64
                            ) / np.maximum(kube_resched, 1)
                    else:
                        # Device-side reduce, ONE small D2H instead of one
                        # np.asarray round-trip per array.
                        placed = self._fetch(
                            self._jit_once("placed", lambda: jax.jit(
                                lambda o: jnp.concatenate(o, axis=1).sum(
                                    axis=1, dtype=jnp.int32
                                )
                            ))(outs)
                        ).astype(np.int32)
                        if self._wide_gangs:
                            # a wave counted its tentative binds
                            placed -= self._fetch(states.txn.undone)

                util = None
                ri = self.ec.vocab._r.get("cpu")
                if ri is not None:
                    def _util(used, alloc):
                        a = alloc[:, :, ri]  # [S, N]
                        u_row = used[:, ri, :]
                        u = jnp.where(a > 0, u_row / jnp.where(a > 0, a, 1.0), 0.0)
                        return u.mean(axis=1)

                    # [S] floats instead of the full [S, R, N] used plane D2H.
                    util = self._fetch(
                        self._jit_once("util", lambda: jax.jit(_util))(
                            states.used, dc.allocatable
                        )
                    )
                dropped = kube_dropped
                if dropped is None and dev_rel and self.retry_buffer:
                    # The device retry path counts overflow drops in-scan now
                    # (round 6): every drop-capable engine reports them.
                    dropped = np.asarray(self._fetch(rq_d.dropped)).astype(np.int32)
                release_rounds = (
                    int(np.max(self._fetch(rounds_d))) if dev_rel else None
                )
                retry_per = group_counts = None
                if dev_rel and self.retry_buffer and not self.kube:
                    retry_per = self._retry_counts(rq_d, outs, dropped)
                    if self.retry_groups:
                        # the wave steps the batch's passes EXECUTED, where a
                        # trace's reader finds them (no phase: a counter)
                        with span.mark(
                            "retry_pass_waves", passes=len(outs),
                            waves=int(retry_per["pass_waves"].max()),
                        ):
                            pass
                        gn = self._fetch(rq_d.gn).astype(np.int32)
                        group_counts = {
                            **{k: gn[:, i] for k, i in _GN.items()},
                            "dropped": dropped,
                            "depth_max": retry_per["depth_max"],
                        }
                    if evicting:
                        retry_per.update(self._evict_counts(ev_n))
                    if evicting and self._budget_on:
                        bn = self._fetch(ev_d.bn).astype(np.int64)
                        retry_per.update(
                            {k: bn[:, i] for k, i in _BN.items()})
            handback_bytes = 0
            bind_boundary = eviction_log = node_out_at = None
            if self.collect_assignments and dev_rel and self.retry_buffer:
                # Two arrays, final as they land: the arrival binds from the
                # wave-order buffer, the re-tried ones and the tasks still
                # queued merged in from the queue's record on the device;
                # where timelines evict, the log beside them.
                with span("handback"):
                    standing = retry_per["retry_placed"]
                    if evicting:  # an evicted re-tried bind left the record
                        standing = standing - retry_per["evict_retried"]
                    assignments, bind_boundary, merged, handback_bytes = (
                        self._handback_retry(span, vassign_d, rq_d, standing)
                    )
                    retry_per["handback_merged"] = merged
                    if evicting:
                        eviction_log = self._handback_log(
                            span, ev_d, retry_per["evictions"])
                        handback_bytes += int(eviction_log.nbytes)
                    if evicting and self._budget_on:
                        node_out_at = self._fetch_answer(
                            span, "node_out_at", ev_d.out_at)
                        handback_bytes += int(node_out_at.nbytes)
            elif self.collect_assignments and dev_rel:
                # The device-release path's placements: the wave-order buffer
                # comes to the host once, after the last chunk.
                with span("handback"):
                    assignments, handback_bytes, _ = self._handback(
                        span, vassign_d, lambda: self._dev_rel_stage["pos"])
            elif chunk_handback:
                # The chunks' choices, put into task order on the device and
                # copied once.
                with span("handback"):
                    assignments, handback_bytes, placed = self._handback(
                        span, outs, lambda: self._chunks_pos(idx), count=True,
                        txn=states.txn if self._wide_gangs else None)
                    prebound = self.pods.bound_node >= 0
                    if prebound.any() or self._fork_choices is not None:
                        assignments = np.array(assignments)  # the copy is read-only
                        assignments[:, prebound] = self.pods.bound_node[prebound]
                    if self._fork_choices is not None:
                        # Pre-fork placements are common to every scenario.
                        pidx = self.waves.idx[: self._fork_waves_done].reshape(-1)
                        pch = self._fork_choices.reshape(-1)
                        pv = pidx >= 0
                        assignments[:, pidx[pv]] = pch[pv][None, :]
            retry_block = (
                None if retry_per is None
                else self._retry_summary(retry_per, len(outs))
            )
            waits = None
            if group_counts is not None:
                # over the batch's scenarios, the largest, scenario 0's own
                retry_block["groups"] = {
                    k: {"sum": int(v.sum()), "max": int(v.max()),
                        "scenario0": int(v[0])}
                    for k, v in group_counts.items()
                }
                if bind_boundary is not None:
                    # how long the jobs of each size waited: what the
                    # answers imply, no counter of the run
                    waits = job_waits(
                        bind_boundary, self._dev_rel_stage["job_host"])
                    retry_block["groups"]["waits_by_job_size"] = {
                        int(k): {
                            "bound_pass": int(waits["bound_pass"][:, i].sum()),
                            "wait_sum": int(waits["wait_sum"][:, i].sum()),
                            "wait_max": int(waits["wait_max"][:, i].max()),
                        } for i, k in enumerate(waits["size"])
                    }
            # This process's partial fleet telemetry (round 12): per-scenario
            # collectors merged same-process (phases key-wise summed would be
            # wrong here — the fleet view wants the ENGINE's wall clocks, so
            # they are overwritten below), shipped through the one gather.
            fleet_local = None
            if self.telemetry_cfg.enabled:
                fleet_local = (
                    ReplayTelemetry.merge(stel) if stel is not None else None
                )
                if fleet_local is None:
                    fleet_local = ReplayTelemetry(
                        granularity=self.telemetry_cfg.granularity
                    )
                fleet_local.phases = run_phases.summary()
                fleet_local.chunk_waves = int(C)
                fleet_local.scenarios = int(self.S)
                from ..ops import tpu3 as V3

                fleet_local.select_form = V3.select_form(
                    self.static3, self.spec, self.ec.num_nodes,
                    traced_weights=self._policies is not None,
                    dyn_labels=self._dyn_dev is not None,
                )
                fleet_local.inwave_corrections = V3.inwave_corrections(
                    self.static3, scenario_axis=True
                )
                fleet_local.count_planes = V3.count_planes(
                    self.static3, scenario_axis=True
                )
                reads = {"arrival": V3.class_row_reads(self.static3, True)}
                if retry_block is not None:  # the batch ran the retry pass
                    reads["retry"] = V3.class_row_reads(
                        self.static3, True, slots_by_scenario=True
                    )
                fleet_local.class_row_reads = {
                    **reads, **V3.class_planes(self.static3, self.spec)
                }
                if dev_rel:
                    fleet_local.release_buckets = sorted(rel_buckets)
                    fleet_local.release_rounds = release_rounds
                if dev_rel or self.collect_assignments:
                    fleet_local.handback_bytes = handback_bytes
                if dev_rel and self.retry_buffer and not self.kube:
                    fleet_local.retry = retry_block
                if self._wide_gangs:
                    fleet_local.gangs = self._gangs_summary(states.txn)
                if self.mesh is not None:
                    fleet_local.mesh = self._mesh_summary()
                # DCN checkpoint-publication attribution (round 16): the
                # cumulative encode+push wall, publication count and encoded
                # MiB ride the fleet phase map (merged under this pid's
                # namespace). Only present when this process actually
                # published — single-process runs keep the pinned phase set.
                _ps = dcn.publish_stats()
                if _ps["count"] > _ps_start["count"]:
                    fleet_local.phases["ckpt_publish"] = round(
                        _ps["wall_s"] - _ps_start["wall_s"], 6
                    )
                    fleet_local.phases["ckpt_publish_count"] = float(
                        _ps["count"] - _ps_start["count"]
                    )
                    fleet_local.phases["ckpt_publish_mib"] = round(
                        (_ps["bytes"] - _ps_start["bytes"]) / 2**20, 3
                    )
                # Background-publisher attribution (round 19): submissions,
                # newest-wins coalesces and drain wall — with the publisher
                # on, ``ckpt_publish`` above is HIDDEN (worker-thread) wall
                # and the drain wait is the only exposed remainder. Only
                # present when the publisher actually ran, so overlap-off
                # and single-process runs keep the pinned phase set.
                _bg = dcn.bg_publish_stats()
                if _bg["submitted"] > _bg_start["submitted"]:
                    fleet_local.phases["ckpt_publish_bg_submitted"] = float(
                        _bg["submitted"] - _bg_start["submitted"]
                    )
                    fleet_local.phases["ckpt_publish_bg_coalesced"] = float(
                        _bg["coalesced"] - _bg_start["coalesced"]
                    )
                    fleet_local.phases["ckpt_publish_drain_s"] = round(
                        _bg["drain_wait_s"] - _bg_start["drain_wait_s"], 6
                    )
                # Faultline attribution (round 17): KV retries burned and CRC
                # fallbacks taken during THIS run ride the same phase map,
                # again only when nonzero — clean runs keep the pinned phase
                # set byte-identical to pre-round-17.
                _rs = dcn.retry_stats()
                if (
                    _rs["retries"] > _rs_start["retries"]
                    or _rs["giveups"] > _rs_start["giveups"]
                ):
                    fleet_local.phases["kv_retry"] = round(
                        _rs["backoff_s"] - _rs_start["backoff_s"], 6
                    )
                    fleet_local.phases["kv_retry_count"] = float(
                        _rs["retries"] - _rs_start["retries"]
                    )
                    fleet_local.phases["kv_retry_giveups"] = float(
                        _rs["giveups"] - _rs_start["giveups"]
                    )
                _cs = dcn.crc_stats()
                if _cs["fallbacks"] > _cs_start["fallbacks"]:
                    fleet_local.phases["ckpt_crc_fallback_count"] = float(
                        _cs["fallbacks"] - _cs_start["fallbacks"]
                    )
                if self._dcn_wq_info is not None:
                    # Work-queue provenance (round 18): which block this
                    # engine executed, at which lease generation, and whether
                    # it was a speculative re-execution — the telemetry trail
                    # the straggler tests pin.
                    fleet_local.phases["wq_block"] = float(
                        self._dcn_wq_info.get("block", -1)
                    )
                    fleet_local.phases["wq_gen"] = float(
                        self._dcn_recovery.get("gen", 0)
                    )
                    if self._dcn_wq_info.get("speculative"):
                        fleet_local.phases["wq_spec"] = 1.0
                elif self._dcn_recovery is not None:
                    # Claim-generation fencing (round 17): which claim
                    # attempt produced this block, and for whom. gen > 0
                    # marks a hand-off after a claimant death mid-recovery.
                    fleet_local.phases["recovery_gen"] = float(
                        self._dcn_recovery.get("gen", 0)
                    )
                    fleet_local.phases["recovery_for"] = float(
                        self._dcn_recovery.get("for_pid", -1)
                    )
            fleet_tel = None
            # ---- THE end-of-replay gather (round 11, parallel.dcn) ----
            # The one point per replay where processes exchange data: every
            # per-scenario result array is concatenated across the contiguous
            # per-process blocks, in process order — bit-identical to what the
            # single-process mesh run assembles. Everything above this line
            # (the whole chunk loop, the boundary passes, the result fetches)
            # was process-local.
            process_count = 1
            if self._dcn_sliced:
                if hb_on:
                    # Final beacon before blocking in the gather: siblings'
                    # attributed-timeout diagnostics see "state=gather" rather
                    # than a stale mid-replay chunk.
                    dcn.heartbeat(
                        n_chunks - 1,
                        total=n_chunks,
                        block=hb_block,
                        wall_s=wall,
                        phases=run_phases.acc,
                        state="gather",
                        # Fleet utilization gauge (round 13): this process's
                        # mean CPU utilization over its local scenario block —
                        # already computed above, so the beacon stays free of
                        # extra D2H. dcn_launch --watch renders it next to
                        # the live-buffer gauge.
                        extra=(
                            {"util_cpu": round(float(np.mean(util)), 4)}
                            if util is not None and len(util)
                            else None
                        ),
                    )
                parts = dcn.gather(
                    "whatif",
                    dict(
                        placed=placed,
                        assignments=assignments,
                        bind_boundary=bind_boundary,
                        util=util,
                        preemptions=kube_preempt,
                        dropped=dropped,
                        evictions=kube_evict,
                        resched=kube_resched,
                        stranded=kube_stranded,
                        evict_lat=kube_lat,
                        lat50=sc_lat_p50,
                        lat90=sc_lat_p90,
                        lat99=sc_lat_p99,
                        frag_stranded=frag_stranded,
                        frag_index=frag_index,
                        frag_pack=frag_pack,
                        telemetry=sc_telemetry,
                        fleet=fleet_local,
                    ),
                    # Survivor rebalance (round 15): with KSIM_DCN_RECOVER on,
                    # a stale sibling's block is claimed and re-executed
                    # through this callback instead of failing the fleet.
                    recover=(
                        self._dcn_recover_block
                        if self._dcn_rebuild is not None
                        else None
                    ),
                )
                # Spare processes contribute liveness, not scenarios — their
                # sentinel parts are dropped before concatenation (worker
                # parts are the contiguous pids 0..workers-1, still in global
                # scenario order).
                parts = [
                    p for p in parts
                    if not (isinstance(p, dict) and p.get("spare"))
                ]

                def _cat(k):
                    if parts[0][k] is None:
                        return None
                    return np.concatenate([p[k] for p in parts], axis=0)

                placed = _cat("placed")
                assignments = _cat("assignments")
                bind_boundary = _cat("bind_boundary")
                util = _cat("util")
                kube_preempt = _cat("preemptions")
                dropped = _cat("dropped")
                kube_evict = _cat("evictions")
                kube_resched = _cat("resched")
                kube_stranded = _cat("stranded")
                kube_lat = _cat("evict_lat")
                sc_lat_p50 = _cat("lat50")
                sc_lat_p90 = _cat("lat90")
                sc_lat_p99 = _cat("lat99")
                frag_stranded = _cat("frag_stranded")
                frag_index = _cat("frag_index")
                frag_pack = _cat("frag_pack")
                sc_telemetry = (
                    None
                    if parts[0]["telemetry"] is None
                    else [t for p in parts for t in p["telemetry"]]
                )
                if parts[0].get("fleet") is not None:
                    # Fleet merge: phases land under "p<pid>/<phase>", the
                    # aggregates are exact merges over the global scenario
                    # order — bit-matching the single-process oracle. A part
                    # recovered by a claimant arrives with its phases ALREADY
                    # scoped "p<claimant>/..." (see _dcn_recover_block) —
                    # merge passes "/"-scoped keys through unprefixed, so
                    # recovered wall clock lands under the pid that spent it.
                    fleet_tel = ReplayTelemetry.merge(
                        [p["fleet"] for p in parts],
                        process_ids=list(range(len(parts))),
                    )
                process_count = jax.process_count()
                # Device-footprint provenance counts block-owning workers
                # only: spares ran no scenario over their devices.
                dev_scale = len(parts)
            elif fleet_local is not None:
                # Single-process runs get the SAME shape ("p0/..." phase keys)
                # so consumers never branch on process_count. A recovery
                # engine (round 15) scopes its phases under the CLAIMANT's
                # pid, keeping per-process attribution honest after a merge.
                fleet_tel = ReplayTelemetry.merge(
                    [fleet_local],
                    process_ids=[
                        jax.process_index()
                        if self._dcn_recovery is not None
                        else 0
                    ],
                )
                dev_scale = process_count
            else:
                dev_scale = process_count
            total = int(placed.sum())
            ndev_local = int(self.mesh.devices.size) if self.mesh is not None else 1
            return WhatIfResult(
                placed=placed,
                unschedulable=(to_schedule - placed).astype(np.int32),
                total_placed=total,
                wall_clock_s=wall,
                placements_per_sec=total / wall if wall > 0 else 0.0,
                assignments=assignments,
                bind_boundary=bind_boundary,
                eviction_log=eviction_log,
                node_out_at=node_out_at,
                group_counts=group_counts,
                job_waits=waits,
                utilization_cpu=util,
                completions_on=self.completions_on,
                engine=self.engine,
                preemptions=kube_preempt,
                retry_dropped=dropped,
                evictions=kube_evict,
                evict_rescheduled=kube_resched,
                evict_stranded=kube_stranded,
                evict_latency_mean=kube_lat,
                latency_p50=sc_lat_p50,
                latency_p90=sc_lat_p90,
                latency_p99=sc_lat_p99,
                stranded_cpu=frag_stranded,
                frag_index_cpu=frag_index,
                packing_efficiency=frag_pack,
                scenario_telemetry=sc_telemetry,
                fleet_telemetry=fleet_tel,
                # Global footprint: worker count × local devices when the
                # scenario axis was DCN-sliced (the local mesh is one worker's
                # share of the fleet that produced the gathered result; spare
                # processes contribute no compute).
                n_devices=ndev_local * dev_scale,
                mesh_shape=(
                    dict(zip(
                        self.mesh.axis_names,
                        (
                            int(d) * dev_scale
                            for d in self.mesh.devices.shape
                        ),
                    ))
                    if self.mesh is not None
                    else None
                ),
                process_count=process_count,
            )


def uniform_scenarios(
    ec: EncodedCluster,
    num_scenarios: int,
    seed: int = 0,
    p_node_down: float = 0.02,
    p_capacity: float = 0.3,
    p_taint: float = 0.1,
) -> List[Scenario]:
    """Random cluster-state perturbation sampler (the [BASELINE] eval shape:
    vmap over cluster-state perturbations). Scenario 0 is always the
    unperturbed base for reference."""
    rng = np.random.default_rng(seed)
    out = [Scenario()]
    N = ec.num_nodes
    for _ in range(num_scenarios - 1):
        pts: List[Perturbation] = []
        if rng.random() < p_node_down:
            k = int(rng.integers(1, max(2, N // 50)))
            pts.append(Perturbation("node_down", nodes=rng.choice(N, size=k, replace=False)))
        if rng.random() < p_capacity:
            k = int(rng.integers(1, max(2, N // 10)))
            pts.append(
                Perturbation(
                    "scale_capacity",
                    nodes=rng.choice(N, size=k, replace=False),
                    resource="cpu",
                    factor=float(rng.choice([0.5, 0.75, 1.25, 1.5])),
                )
            )
        if rng.random() < p_taint:
            k = int(rng.integers(1, max(2, N // 20)))
            pts.append(
                Perturbation(
                    "add_taint",
                    nodes=rng.choice(N, size=k, replace=False),
                    key="whatif/injected",
                    value="true",
                    effect="NoSchedule",
                )
            )
        out.append(Scenario(pts))
    return out
