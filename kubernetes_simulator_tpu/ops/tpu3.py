"""Scheduling kernels v3 — domain-space state + wave-deferred commits.

Why: profiling the v2 node-space design showed the replay is HBM-bound at
scale: every pod step streamed the ``[S, G, N]`` count planes ~10× (reads
+ functional rewrites), saturating ~270k placements/s regardless of
scenario count. v3 restructures the STATE, not the semantics:

- **Domain-space planes** ``[G, Dcap]`` for groups whose topology has few
  domains (zone/rack): tiny (KBs), so reads are micro-matmuls and commits
  are dense one-hot adds — no [N]-wide traffic at all.
- **Host planes** ``[Gh, N]`` only for groups keyed by hostname-scale
  topologies (domain ≈ node), kept per *referenced plane section* so a
  trace with no such terms (Borg shape) carries none.
- **Wave-deferred commits**: within a wave the carried tensors are never
  rewritten; each pod's evaluation adds exact in-wave corrections for the
  pods before it, and the wave commits once — with the gang all-or-nothing
  mask folded in, so rollback is free. ``used`` is read once per pod (the
  unavoidable fit stream) but written once per wave. The COUNT corrections
  are rank-1 terms in the bound domain (domain space, a few KB). The USAGE
  correction is one running ``[R, N]`` plane a wave: zero at wave start,
  and each slot, once it has chosen, adds its request at its one chosen
  node (read, add and write back the node's 128-lane block in place).
  Slot k reads ``used + plane + req``: the f32 association of summing the
  k earlier slots' one-hot terms from zero, at a cost that does not grow
  with k. (The k-term form re-evaluates those k terms over all N inside
  both [N]-wide reduces of slot k: on a v5e at N=10,000 the spread reduce
  took 1.5 µs in slot 0 and 9.2 in slot 7, PERF.md §5.) The tier-
  preemption program keeps the k-term form; the vmapped what-if program
  keeps the k compares but picks sums resolved among the scenarios'
  scalars — see :func:`inwave_corrections`.
- **One node-wide reduce a slot** where every score row but the fit score
  is constant inside a zone (the Borg shape): the best packed node of each
  zone gives the spread's zone feasibility and, once the zone scores are
  added to its seg_D results, the chosen node — see :func:`select_form`.
  Other profiles make two (zone feasibility, then the select).
- **Node-value expansion** of domain-space rows rides a fused masked-sum
  over the ≤Dcap domains (``val[n] = rows[dom(n)]`` without gathers, which
  serialize on TPU — measured 100× slower than the arithmetic forms).

Semantics match the v2 chain (ops.tpu.eval_pod_fused) and the CPU oracle:
same greedy arrival order, same speculative in-wave visibility, same
normalization arithmetic (shared helpers), same tie-breaks. Pinned by
tests/test_jax_parity.py (which drives this path) and test_tpu3_equiv.

Exactness caveat: the wave-deferred ``used`` commit sums a wave's requests
in one reduction instead of v2's per-pod sequential adds. Both are f32
sums of the same multiset, so results are bit-identical whenever the
per-node accumulations are exactly representable (bucketed k8s quantities
— powers-of-two multiples — at realistic magnitudes are); a pathological
trace mixing ~2^24-ulp-apart magnitudes on one node could flip a
floor-quantized score by one. The parity suites pin equality on realistic
traces; whatif batches pick v2/v3 per batch (labels_dirty), so keep that
caveat in mind when comparing across batches at extreme magnitudes.

Scenario batches whose label perturbations change topology domains
(whatif ``labels_dirty``) stay on v3 via per-scenario DynTables (round
3): append-style domain ids plus K sparse node→domain overrides applied
as a correction matmul on top of the scenario-SHARED base expansion
tables — see ``DynTables``/``make_wave_step3(dyn=...)`` below. Callers
fall back to v2 only outside the DynTables envelope (host-scale topology
changes, >32 perturbed nodes/scenario, pre-bound pods, preemption,
forks — sim/whatif.py gates and reports via ``WhatIfEngine.engine``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.encode import PAD, EncodedCluster, EncodedPods
from ..utils.profiling import stage
from . import tpu as T2
from .tpu import (
    DevCluster,
    Derived,
    PodSlot,
    _HI,
    _normalize_row,
    _term_onehot,
    select_node,
)

# Round 10 (fused tier-preemption, PR 2's measured 4.1× standalone cost):
# when on, the preemption wave program (a) packs the three prefix-over-
# tiers stacks into ONE [Tt+1, R+2, N] tensor so each slot pays a single
# dynamic gather instead of three, (b) takes the victim-node rank through
# one variadic (value, index) reduce (tpu.masked_argmin) instead of
# argmax + any, and (c) commits all Tt tier planes in one batched
# einsum pass instead of a per-tier Python loop. Same summands in the
# same w-order per output element — bit-identical to the pre-fusion
# program (tests/test_preemption_device.py pins fused≡prefusion≡oracle).
# Read at TRACE time: monkeypatch ops.tpu3.FUSED_PREEMPT (or set
# KSIM_FUSED_PREEMPT=0) before building an engine to get the old program.
FUSED_PREEMPT = os.environ.get("KSIM_FUSED_PREEMPT", "1") not in ("", "0")

# ---------------------------------------------------------------------------
# Static (per-trace) structure
# ---------------------------------------------------------------------------

# Topologies with more domains than this live in node-space host planes
# instead of [G, Dcap] domain planes. ONE shared constant: V3Static.build's
# default and whatif.ScenarioSet's DynTables eligibility must agree on it.
DMAX_COARSE = 128



@dataclass(frozen=True)
class V3Static:
    """Host-side, numpy. Row layout over the unified term axis KT:
    [A aff | B anti | SP spread | PA pref | MA sym-anti | MP sym-pref];
    every row is one (group, plane) read. Sections read planes:
    aff/anti/spread/pref → match-count; sym-anti → anti; sym-pref → pref."""

    A: int
    B: int
    SP: int
    PA: int
    MA: int
    MP: int
    # Static maintenance gates: a plane is carried only if some row can
    # ever read it (match counts also need A>0 for bootstrap totals).
    maintain_mc: bool
    maintain_anti: bool
    maintain_pref: bool
    Dcap: int  # max #domains over coarse groups (≥1)
    G: int
    is_host: np.ndarray  # [G] bool — hostname-scale topology
    nd_g: np.ndarray  # [G] i32 — #domains of each group's topology
    single_g: np.ndarray  # [G] bool — every domain holds exactly one node
    # (hostname). Host commits then collapse to bound-node one-hots; host
    # groups over multi-node domains need the dom-equality commit path.
    # Host-plane group lists per plane kind (global group ids).
    mc_h_ids: np.ndarray  # [Hmc]
    anti_h_ids: np.ndarray  # [Ha]
    pref_h_ids: np.ndarray  # [Hp]
    g2mc_h: np.ndarray  # [G] local id or -1
    g2anti_h: np.ndarray
    g2pref_h: np.ndarray
    # Per-pod matched-group index lists for the symmetric checks,
    # restricted to groups actually referenced by anti/pref terms.
    anti_midx: np.ndarray  # [P, MA]
    pref_midx: np.ndarray  # [P, MP]
    has_gangs: bool
    # Any DoNotSchedule spread constraint in the trace: when False the
    # node-space spread FILTER block is statically absent (sp_dns is traced
    # data, so XLA cannot DCE it; ScheduleAnyway-only traces — the Borg
    # shape — would otherwise pay the [S, KT, N] count expansion for a
    # filter that never fires). Profile round 3: _expand_rows was ~10% of
    # device time on the north-star shape purely from this.
    has_dns: bool
    # [KT] bool: some pod's term at this position of the row axis names a
    # host-scale group. Only such a position reads a host-plane row where
    # the step reads rows by index (host_rows_at); the others emit no read.
    # Like ``has_dns`` a fact of the pod multiset, not of the arrival order:
    # every deal of the same pods finds the same program.
    host_pos: np.ndarray
    # [KT] bool, the mirror fact: some pod's term at this position names a
    # group that is NOT host-scale. Only such a position can hold a non-zero
    # domain row, so only there does a slot expand one to node space
    # (value_positions); a position may set both flags (a zone group for one
    # pod, a hostname group for another) or neither.
    coarse_pos: np.ndarray
    # All domain-bearing groups share one topology key (the Borg shape:
    # zone-only): bound-node domain lookups collapse to one shared [N] map.
    # ``topo0`` is that topology's id (PAD when no group carries domains);
    # Shared3.build consumes it — ONE detection site.
    single_topo: bool
    topo0: int
    # Structured shared-topology layout: "stride" (dom = n % D) or "block"
    # (dom = n // (N/D)) — per-domain feasibility then reduces over a plain
    # reshape instead of the [S, N]×[N, D] one-hot matmul. "" = no pattern.
    seg_mode: str
    seg_D: int
    # Toleration / node-affinity equivalence classes: pods sharing identical
    # term rows share one per-chunk [N] mask+raw (C ≪ P in real traces, e.g.
    # one class per workload template). class id PAD → fall back row 0 is a
    # never-used zero row only when C == 0.
    tol_class: np.ndarray  # [P] i32
    tol_rep: np.ndarray  # [Ct] i32 representative pod index per class
    na_class: np.ndarray  # [P] i32
    na_rep: np.ndarray  # [Cn] i32
    # Tier preemption (opt-in; see sim.greedy docstring for the semantics).
    preemption: bool = False
    Tt: int = 0  # number of priority tiers (0 = feature off)
    pod_tier: np.ndarray = None  # [P] i32
    # bf16 host planes: exact when every plane value is an integer ≤ 256,
    # i.e. singleton (hostname) domains with bounded pods-per-node. Halves
    # the dominant host-read/commit traffic. pref stays f32 (fractional).
    mc_h_bf16: bool = False
    anti_h_bf16: bool = False
    # No pod names more than ONE row of the plane (matches more than one
    # host-scale group a term reads; holds anti-affinity terms of more than
    # one): its bind then touches one row, which the step mapped over a
    # scenario axis commits alone (host_commit_form "rows"). Like
    # ``host_pos`` a fact of the pod multiset.
    mc_h_one_row: bool = False
    anti_h_one_row: bool = False
    # Pod groups WIDER than the wave (sim.waves.wide_gang_table: [P, 3] i32
    # ``(pos, size, ordinal)`` per pod), or None where the trace has none at
    # this wave width: the step then carries no transaction and is the
    # wave-local program to the letter. Like ``has_gangs`` a static fact.
    txn_tab: Optional[np.ndarray] = None
    wave_width: int = 0  # the width ``txn_tab`` was laid out for
    # Members of the largest pod group, whatever the width: a step built at
    # a narrower wave without ``txn_tab`` would judge the group wave by wave,
    # so ``make_wave_step3`` refuses to build it.
    max_gang: int = 0

    @property
    def has_wide_gangs(self) -> bool:
        return self.txn_tab is not None

    @property
    def wide_groups(self) -> int:
        """How many pod groups are wider than the wave."""
        return int(self.txn_tab[:, 2].max()) + 1 if self.has_wide_gangs else 0

    @property
    def max_wide(self) -> int:
        """Members of the widest such group (0 without one)."""
        return int(self.txn_tab[:, 1].max()) if self.has_wide_gangs else 0

    @property
    def max_waves_spanned(self) -> int:
        """Waves the widest such group fills."""
        return -(-self.max_wide // max(self.wave_width, 1))

    @property
    def KT(self) -> int:
        return self.A + self.B + self.SP + self.PA + self.MA + self.MP

    @property
    def has_host_rows(self) -> bool:
        """Any term row can hit a host plane (else the host-value paths
        compile away entirely)."""
        return bool(len(self.mc_h_ids) or len(self.anti_h_ids) or len(self.pref_h_ids))

    # Class-mask fallback guard: degenerate traces (every pod distinct)
    # would make the per-chunk class tensors [C, N] bigger than the work
    # they save; fall back to per-wave vmap evaluation there.
    MAX_CLASSES = 256

    @property
    def use_tol_classes(self) -> bool:
        return 0 < len(self.tol_rep) <= self.MAX_CLASSES

    @property
    def use_na_classes(self) -> bool:
        return 0 < len(self.na_rep) <= self.MAX_CLASSES

    @property
    def sections(self) -> Tuple[int, ...]:
        """Start offsets of (aff, anti, spread, pref, symanti, sympref, end)."""
        a = self.A
        b = a + self.B
        s = b + self.SP
        p = s + self.PA
        ma = p + self.MA
        return (0, a, b, s, p, ma, ma + self.MP)

    MAX_TIERS = 8

    @classmethod
    def build(
        cls,
        ec: EncodedCluster,
        ep: EncodedPods,
        spec,
        dmax_coarse: int = DMAX_COARSE,
        preemption: bool = False,
        allow_bf16_host: bool = True,
        dcap_min: int = 0,
        wave_width: Optional[int] = None,
    ) -> "V3Static":
        """``dcap_min``: widen the domain axis past the base cluster's
        count — labels_dirty what-if batches append per-scenario domain
        ids for new label values (whatif.ScenarioDyn). ``wave_width``: the
        width the caller packs waves at; a pod group wider than it gets the
        carried transaction (``txn_tab``)."""
        G = max(ec.num_groups, 1)
        gt = ec.group_topo[:G] if ec.group_topo.shape[0] >= G else np.full(G, PAD, np.int32)
        nd_g = np.where(gt >= 0, ec.num_domains[np.clip(gt, 0, None)], 0).astype(np.int32)
        is_host = nd_g > dmax_coarse
        Dcap = int(
            max(nd_g[~is_host].max() if (~is_host).any() else 1, 1, dcap_min)
        )
        # Per topology: does every domain hold exactly one node?
        Tn = ec.node_domain.shape[0]
        topo_single = np.zeros(Tn, bool)
        for ti in range(Tn):
            dom = ec.node_domain[ti]
            labeled = dom[dom >= 0]
            topo_single[ti] = labeled.size == 0 or (
                np.bincount(labeled).max() == 1
            )
        single_g = np.where(gt >= 0, topo_single[np.clip(gt, 0, None)], True)

        interpod = spec.interpod
        spread = spec.spread
        A = ec_width(ep.aff_req) if interpod else 0
        B = ec_width(ep.anti_req) if interpod else 0
        SP = ec_width(ep.spread_g) if spread else 0
        PA = ec_width(ep.pref_aff) if interpod else 0

        pmg = ep.pod_matches_group  # [P, G']
        Pg = pmg.shape[1]
        anti_ref = np.zeros(G, bool)
        pref_ref = np.zeros(G, bool)
        if interpod:
            for g in np.unique(ep.anti_req[ep.anti_req >= 0]):
                anti_ref[g] = True
            for g in np.unique(ep.pref_aff[ep.pref_aff >= 0]):
                pref_ref[g] = True
        anti_midx = _matched_idx(pmg, anti_ref[:Pg]) if interpod else np.zeros((ep.num_pods, 0), np.int32)
        pref_midx = (
            _matched_idx(pmg, pref_ref[:Pg])
            if (interpod and spec.has_symmetric_pref)
            else np.zeros((ep.num_pods, 0), np.int32)
        )

        mc_ref = np.zeros(G, bool)  # groups whose match-count a row can read
        for arr, on in ((ep.aff_req, interpod), (ep.anti_req, interpod),
                        (ep.spread_g, spread), (ep.pref_aff, interpod)):
            if on and arr.size:
                for g in np.unique(arr[arr >= 0]):
                    mc_ref[g] = True
        mc_h_ids = np.nonzero(mc_ref & is_host)[0].astype(np.int32)
        anti_h_ids = np.nonzero(anti_ref & is_host)[0].astype(np.int32)
        pref_h_ids = np.nonzero(pref_ref & is_host)[0].astype(np.int32)

        def inv(ids):
            m = np.full(G, -1, np.int32)
            m[ids] = np.arange(len(ids), dtype=np.int32)
            return m

        tol_class, tol_rep = _row_classes(
            np.concatenate([ep.tol_key, ep.tol_kv, ep.tol_effect], axis=1)
        )
        na_class, na_rep = _row_classes(
            np.concatenate(
                [
                    ep.na_req.reshape(ep.num_pods, -1),
                    ep.na_has_req[:, None].astype(np.int32),
                    ep.na_pref.reshape(ep.num_pods, -1),
                    ep.na_pref_w.view(np.int32).reshape(ep.num_pods, -1),
                ],
                axis=1,
            )
        )
        Tt = 0
        pod_tier = np.zeros(ep.num_pods, np.int32)
        if preemption:
            from ..sim.greedy import priority_tiers

            tiers, pod_tier = priority_tiers(ep)
            Tt = len(tiers)
            if Tt > cls.MAX_TIERS:
                raise ValueError(
                    f"device preemption supports <= {cls.MAX_TIERS} priority "
                    f"tiers; trace has {Tt}"
                )
        # bf16 exactness bound: integers ≤ 256. Counts at singleton
        # (hostname) domains are bounded by pods-per-node; anti activations
        # additionally by the per-pod anti-term width. Callers that mutate
        # capacity at runtime (node events / what-if perturbations scaling
        # the "pods" resource) must pass allow_bf16_host=False — the bound
        # is baked into the jitted kernel.
        pods_ri = ec.vocab._r.get("pods")
        # The per-node count bound only holds if NodeResourcesFit actually
        # enforces the "pods" resource (spec.fit); otherwise counts are
        # unbounded and bf16 would round silently past 256.
        max_pods = (
            float(ec.allocatable[:, pods_ri].max())
            if (spec.fit and pods_ri is not None and ec.num_nodes)
            else np.inf
        )
        mc_h_bf16 = bool(
            allow_bf16_host
            and len(mc_h_ids) and single_g[mc_h_ids].all() and max_pods <= 256
        )
        anti_h_bf16 = bool(
            allow_bf16_host
            and len(anti_h_ids)
            and single_g[anti_h_ids].all()
            and max_pods * max(B, 1) <= 256
        )
        # How many rows of its plane a pod's bind adds to, at most: the
        # host-scale groups it matches (mc_host), its own anti-affinity terms
        # on host-scale groups (anti_host, `_pod_group_vectors`; two terms on
        # one group count twice, which only forgoes the row form).
        mc_named = np.count_nonzero(pmg[:, mc_h_ids[mc_h_ids < Pg]], axis=1)
        anti_h = np.zeros(G, bool)
        anti_h[anti_h_ids] = True
        anti_terms = ep.anti_req[:, :B]
        anti_named = (
            (anti_terms >= 0) & anti_h[np.clip(anti_terms, 0, G - 1)]
        ).sum(axis=1)
        term_g = np.concatenate(
            [ep.aff_req[:, :A], ep.anti_req[:, :B], ep.spread_g[:, :SP],
             ep.pref_aff[:, :PA], anti_midx, pref_midx],
            axis=1,
        )  # [P, KT] the group each pod names at each row position (PAD none)
        named_host = is_host[np.clip(term_g, 0, G - 1)]
        host_pos = ((term_g >= 0) & named_host).any(axis=0)
        coarse_pos = ((term_g >= 0) & ~named_host).any(axis=0)
        topo_groups = (gt >= 0) & (nd_g > 0)
        single_topo = bool(len(set(gt[topo_groups].tolist())) <= 1)
        topo0 = int(gt[topo_groups][0]) if topo_groups.any() else PAD
        seg_mode, seg_D = "", 0
        if single_topo and topo0 != PAD:
            dom = ec.node_domain[topo0]
            D0 = int(ec.num_domains[topo0])
            N = ec.num_nodes
            if 0 < D0 <= Dcap and N % D0 == 0:
                if (dom == np.arange(N) % D0).all():
                    seg_mode, seg_D = "stride", D0
                elif (dom == np.arange(N) // (N // D0)).all():
                    seg_mode, seg_D = "block", D0
        out = cls(
            seg_mode=seg_mode, seg_D=seg_D, topo0=topo0,
            tol_class=tol_class, tol_rep=tol_rep,
            na_class=na_class, na_rep=na_rep,
            preemption=preemption, Tt=Tt, pod_tier=pod_tier,
            mc_h_bf16=mc_h_bf16, anti_h_bf16=anti_h_bf16,
            mc_h_one_row=bool(mc_named.max(initial=0) <= 1),
            anti_h_one_row=bool(anti_named.max(initial=0) <= 1),
            A=A, B=B, SP=SP, PA=PA,
            MA=anti_midx.shape[1], MP=pref_midx.shape[1],
            maintain_mc=bool(mc_ref.any()),
            maintain_anti=bool(anti_midx.shape[1]),
            maintain_pref=bool(pref_midx.shape[1]),
            Dcap=Dcap, G=G, is_host=is_host, nd_g=nd_g, single_g=single_g,
            mc_h_ids=mc_h_ids, anti_h_ids=anti_h_ids, pref_h_ids=pref_h_ids,
            g2mc_h=inv(mc_h_ids), g2anti_h=inv(anti_h_ids), g2pref_h=inv(pref_h_ids),
            anti_midx=anti_midx, pref_midx=pref_midx,
            has_gangs=spec.has_gangs,
            has_dns=bool(
                SP and (ep.spread_dns[:, :SP] & (ep.spread_g[:, :SP] >= 0)).any()
            ),
            host_pos=host_pos, coarse_pos=coarse_pos,
            single_topo=single_topo,
        )
        if spec.has_gangs:
            from ..sim.waves import refuse_wide_gangs, wide_gang_table, widest_gang

            out = replace(out, max_gang=widest_gang(ep))
            tab = None if wave_width is None else wide_gang_table(ep, wave_width)
            if tab is not None:
                out = replace(out, txn_tab=tab, wave_width=int(wave_width))
                refuse_wide_gangs(
                    wave_width, out.max_gang, tier_preemption=preemption,
                    count_planes=(out.maintain_mc or out.maintain_anti
                                  or out.maintain_pref),
                )
        if preemption and out.has_host_rows:
            raise ValueError(
                "device preemption is not supported together with "
                "hostname-scale topology terms (host planes); use the CPU "
                "event engine for full kube PostFilter semantics"
            )
        return out


def ec_width(arr: np.ndarray) -> int:
    """Static term width, treating the all-PAD placeholder column as 0."""
    return arr.shape[1] if arr.size and (arr >= 0).any() else 0


def _row_classes(rows: np.ndarray):
    """(class_of [P] i32, rep [C] i32): group identical rows; rep[c] is the
    first pod index exhibiting class c. Classes are numbered in the order
    of their rows, not of their first pods: the representatives' rows are
    constants of the chunk program, and the same pods in another arrival
    order have to find the same program."""
    if rows.shape[0] == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    _, first, inv = np.unique(
        np.ascontiguousarray(rows), axis=0, return_index=True, return_inverse=True
    )
    return inv.reshape(-1).astype(np.int32), first.astype(np.int32)


def _matched_idx(pmg: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """[P, M] group ids each pod matches, restricted to ``ref`` groups."""
    sel = pmg & ref[None, :]
    counts = sel.sum(axis=1)
    M = int(counts.max()) if counts.size else 0
    out = np.full((pmg.shape[0], M), PAD, np.int32)
    for p in np.nonzero(counts)[0]:
        ids = np.nonzero(sel[p])[0]
        out[p, : len(ids)] = ids
    return out


def _gdom_table(ec: EncodedCluster, G: int) -> np.ndarray:
    """[G, N] i32 — domain of node n under group g's topology (PAD=-1).
    One derivation, shared with the CPU kernels."""
    from .cpu import _group_dom_per_node

    return _group_dom_per_node(ec)[:G]


class Shared3(NamedTuple):
    """Scenario-shared device tensors (v3 requires shared topology)."""

    gdom_f: jax.Array  # [G, N] f32 domain of node n under group g (PAD=-1)
    coarse_f: jax.Array  # [G] f32 1.0 where coarse
    mt_mask: jax.Array  # [G] f32 1.0 where group has domains (for totals)
    # single_topo fast path: the one shared node→domain map and the groups
    # it applies to (all-PAD rows stay PAD through has_dom_g masking).
    topo1_f: jax.Array  # [N] f32 (all-PAD when single_topo is False/vacuous)
    has_dom_g: jax.Array  # [G] f32 1.0 where the group carries domains

    @classmethod
    def build(cls, ec: EncodedCluster, st: V3Static) -> "Shared3":
        gdom = _gdom_table(ec, st.G)
        gt = (
            ec.group_topo[: st.G]
            if ec.group_topo.shape[0] >= st.G
            else np.full(st.G, PAD, np.int32)
        )
        # Single source of truth: V3Static.build already certified topo0 /
        # single_topo; this only materializes the corresponding tensors.
        if st.topo0 != PAD:
            topo1 = ec.node_domain[st.topo0].astype(np.float32)
        else:
            topo1 = np.full(ec.num_nodes, float(PAD), np.float32)
        return cls(
            gdom_f=jnp.asarray(gdom.astype(np.float32)),
            coarse_f=jnp.asarray((~st.is_host).astype(np.float32)),
            mt_mask=jnp.asarray((st.nd_g > 0).astype(np.float32)),
            topo1_f=jnp.asarray(topo1),
            has_dom_g=jnp.asarray(((gt >= 0) & (st.nd_g > 0)).astype(np.float32)),
        )


class GangTxn(NamedTuple):
    """The open transaction of a pod group WIDER than the wave, carried by
    the scan beside the planes (``DevState3.txn``; one a scenario under
    ``vmap``), and what it has settled so far. Between two wide groups
    ``plane`` is all zero, ``bound`` 0 and ``failed`` False."""

    plane: jax.Array  # [R, N] f32 what the open group's tentative binds took
    bound: jax.Array  # [] i32 how many they are
    failed: jax.Array  # [] bool: a member so far fitted nowhere
    log: jax.Array  # [Gw] bool verdict per wide group, by ordinal: rolled back
    undone: jax.Array  # [] i32 binds given back so far

    @classmethod
    def init(cls, st: "V3Static", R: int, N: int) -> "GangTxn":
        return cls(
            plane=jnp.zeros((R, N), jnp.float32),
            bound=jnp.zeros((), jnp.int32),
            failed=jnp.zeros((), bool),
            log=jnp.zeros((st.wide_groups,), bool),
            undone=jnp.zeros((), jnp.int32),
        )


class DevState3(NamedTuple):
    """Carried state. Domain planes are [G, Dcap] (host-group rows stay
    zero); host planes are [H*, N] per plane kind.

    ``used`` is stored TRANSPOSED [R, N]: with R tiny (3-5), [N, R] minor-R
    tensors force every fit/score op to carry a dead minor axis; [R, N]
    planes keep all hot elementwise work at [S, N] shape and let the R loop
    unroll statically."""

    used: jax.Array  # [R, N] f32
    mc_dom: jax.Array  # [G, Dcap] f32
    anti_dom: jax.Array  # [G, Dcap] f32
    pref_dom: jax.Array  # [G, Dcap] f32
    mc_host: jax.Array  # [Hmc, N] f32
    anti_host: jax.Array  # [Ha, N] f32
    pref_host: jax.Array  # [Hp, N] f32
    match_total: jax.Array  # [G] f32
    # Preemption-only planes ([0, ...] when off): non-gang usage / pod
    # counts by priority tier.
    used_tier: jax.Array  # [Tt, R, N] f32
    npods_tier: jax.Array  # [Tt, N] f32
    # Only where the trace has a pod group wider than the wave
    # (``V3Static.has_wide_gangs``); None is no leaf, so every other
    # program's carry, and its text, is what it was.
    txn: Optional[GangTxn] = None

    @classmethod
    def from_host(
        cls, used: np.ndarray, mc: np.ndarray, aa: np.ndarray, pw: np.ndarray,
        ec: EncodedCluster, st: V3Static, ep: Optional[EncodedPods] = None,
    ) -> "DevState3":
        """Domain-space host arrays [G, D] (models.state layout) → v3.
        ``ep`` is required when preemption is on (tier planes rebuild from
        the pre-bound pods)."""
        G, Dcap = st.G, st.Dcap

        def dom_part(arr):
            out = np.zeros((G, Dcap), np.float32)
            w = min(arr.shape[1], Dcap)
            out[: arr.shape[0], :w] = np.where(st.is_host[: arr.shape[0], None], 0.0, arr[:, :w])
            return out

        gdom = _gdom_table(ec, G)

        def host_part(arr, ids):
            out = np.zeros((len(ids), ec.num_nodes), np.float32)
            for li, g in enumerate(ids):
                if g < arr.shape[0]:
                    out[li] = T2.domain_to_node_space(arr[g : g + 1], gdom[g : g + 1])[0]
            return out

        mt = np.zeros(G, np.float32)
        mt[: mc.shape[0]] = mc.sum(axis=1)
        N, R = ec.num_nodes, ec.num_resources
        used_tier = np.zeros((st.Tt, R, N), np.float32)
        npods_tier = np.zeros((st.Tt, N), np.float32)
        if st.Tt and ep is not None:
            pre = np.nonzero((ep.bound_node >= 0) & (ep.group_id == PAD))[0]
            for p in pre:
                t, n = int(st.pod_tier[p]), int(ep.bound_node[p])
                used_tier[t, :, n] += ep.requests[p]
                npods_tier[t, n] += 1.0
        return cls(
            used=jnp.asarray(np.ascontiguousarray(used.T).astype(np.float32)),
            mc_dom=jnp.asarray(dom_part(mc)),
            anti_dom=jnp.asarray(dom_part(aa)),
            pref_dom=jnp.asarray(dom_part(pw)),
            mc_host=_host_plane(host_part(mc, st.mc_h_ids), st.mc_h_bf16),
            anti_host=_host_plane(host_part(aa, st.anti_h_ids), st.anti_h_bf16),
            pref_host=jnp.asarray(host_part(pw, st.pref_h_ids)),
            match_total=jnp.asarray(mt),
            used_tier=jnp.asarray(used_tier),
            npods_tier=jnp.asarray(npods_tier),
            txn=GangTxn.init(st, R, N) if st.has_wide_gangs else None,
        )

    def to_host(self, ec: EncodedCluster, st: V3Static, D: int):
        """v3 → domain-space [G, D] host arrays (checkpoint/result layout)."""
        gdom = _gdom_table(ec, st.G)

        def back(dom_arr, host_arr, ids):
            out = np.zeros((st.G, D), np.float32)
            w = min(st.Dcap, D)
            out[:, :w] = np.asarray(dom_arr)[:, :w]
            host_np = np.asarray(host_arr)  # one device→host transfer
            for li, g in enumerate(ids):
                out[g] = T2.node_space_to_domain(
                    host_np[li : li + 1], gdom[g : g + 1], D
                )[0]
            return out

        return (
            np.ascontiguousarray(np.asarray(self.used).T),  # back to [N, R]
            back(self.mc_dom, self.mc_host, st.mc_h_ids),
            back(self.anti_dom, self.anti_host, st.anti_h_ids),
            back(self.pref_dom, self.pref_host, st.pref_h_ids),
        )


def _host_plane(vals: np.ndarray, bf16: bool) -> jax.Array:
    """Host plane → device, validating the bf16 exactness bound before a
    lossy cast (resumed/trace-provided state could exceed it)."""
    if bf16:
        if vals.size and not (
            (vals <= 256).all() and (vals == np.round(vals)).all()
        ):
            raise ValueError(
                "host-plane values exceed the bf16 exactness bound "
                "(integers <= 256); rebuild with allow_bf16_host=False"
            )
        return jnp.asarray(vals, dtype=jnp.bfloat16)
    return jnp.asarray(vals)


class SlotExtra(NamedTuple):
    """v3-only per-slot rows gathered alongside PodSlot."""

    anti_midx: jax.Array  # [MA] i32
    pref_midx: jax.Array  # [MP] i32
    tol_class: jax.Array  # i32 scalar
    na_class: jax.Array  # i32 scalar
    tier: jax.Array  # i32 scalar (0 when preemption off)
    # [3] i32 (pos, size, ordinal) in a group wider than the wave, (-1, 0, 0)
    # outside one; None (no leaf) where the trace has no such group.
    txn: Optional[jax.Array] = None


_NO_TXN = (-1, 0, 0)


class ExtraSource(NamedTuple):
    """Device-resident twins of the V3Static per-pod rows (see
    ops.tpu.SlotSource — same once-per-engine upload pattern)."""

    anti_midx: jax.Array  # [P, MA]
    pref_midx: jax.Array  # [P, MP]
    tol_class: jax.Array  # [P]
    na_class: jax.Array  # [P]
    tier: jax.Array  # [P]
    txn: Optional[jax.Array] = None  # [P, 3]

    @classmethod
    def build(cls, st: V3Static, num_pods: int) -> "ExtraSource":
        z = np.zeros(num_pods, np.int32)
        return cls(
            anti_midx=jnp.asarray(st.anti_midx.astype(np.int32)),
            pref_midx=jnp.asarray(st.pref_midx.astype(np.int32)),
            tol_class=jnp.asarray(
                st.tol_class.astype(np.int32) if st.tol_class.size else z
            ),
            na_class=jnp.asarray(
                st.na_class.astype(np.int32) if st.na_class.size else z
            ),
            tier=jnp.asarray(st.pod_tier.astype(np.int32) if st.Tt else z),
            txn=jnp.asarray(st.txn_tab) if st.has_wide_gangs else None,
        )

    @classmethod
    def page(cls, st: V3Static, flat: np.ndarray) -> "ExtraSource":
        """One PAGE of the extra source (round 14 paged pod waves — the
        v3 twin of ops.tpu.SlotSource.page): rows at flat pod ids
        ``flat``, PAD ids mapped to neutral zero rows. Keeps the pod
        axis streamable — only chunk_waves × wave_width rows are
        device-resident at once instead of all P."""
        safe = np.clip(flat, 0, None)
        n = safe.shape[0]
        z = np.zeros(n, np.int32)
        return cls(
            anti_midx=jnp.asarray(st.anti_midx[safe].astype(np.int32)),
            pref_midx=jnp.asarray(st.pref_midx[safe].astype(np.int32)),
            tol_class=jnp.asarray(
                st.tol_class[safe].astype(np.int32) if st.tol_class.size else z
            ),
            na_class=jnp.asarray(
                st.na_class[safe].astype(np.int32) if st.na_class.size else z
            ),
            tier=jnp.asarray(st.pod_tier[safe].astype(np.int32) if st.Tt else z),
            txn=(
                jnp.asarray(
                    np.where((flat >= 0)[:, None], st.txn_tab[safe], _NO_TXN)
                    .astype(np.int32)
                )
                if st.has_wide_gangs
                else None
            ),
        )


@jax.jit
def gather_extra_device(src: ExtraSource, idx: jax.Array) -> SlotExtra:
    """jnp twin of gather_extra (value-identical)."""
    safe = jnp.clip(idx, 0, None)
    ok = (idx >= 0)[..., None]
    return SlotExtra(
        anti_midx=jnp.where(ok, src.anti_midx[safe], PAD).astype(jnp.int32),
        pref_midx=jnp.where(ok, src.pref_midx[safe], PAD).astype(jnp.int32),
        tol_class=src.tol_class[safe],
        na_class=src.na_class[safe],
        tier=src.tier[safe],
        txn=(
            None
            if src.txn is None
            else jnp.where(ok, src.txn[safe], jnp.asarray(_NO_TXN, jnp.int32))
        ),
    )


def extra_of_rows(rows: ExtraSource, idx: jax.Array) -> SlotExtra:
    """What :func:`gather_extra_device` gives at ``idx``, from ``rows``: the
    source's rows already read at ``clip(idx, 0)`` (``PackedRows.take``)."""
    ok = (idx >= 0)[..., None]
    return SlotExtra(
        anti_midx=jnp.where(ok, rows.anti_midx, PAD).astype(jnp.int32),
        pref_midx=jnp.where(ok, rows.pref_midx, PAD).astype(jnp.int32),
        tol_class=rows.tol_class,
        na_class=rows.na_class,
        tier=rows.tier,
        txn=(
            None
            if rows.txn is None
            else jnp.where(ok, rows.txn, jnp.asarray(_NO_TXN, jnp.int32))
        ),
    )


def gather_extra(st: V3Static, idx: np.ndarray) -> SlotExtra:
    safe = np.clip(idx, 0, None)
    ok = (idx >= 0)[..., None]
    tol_c = st.tol_class[safe] if st.tol_class.size else np.zeros_like(safe)
    na_c = st.na_class[safe] if st.na_class.size else np.zeros_like(safe)
    tier = st.pod_tier[safe] if st.Tt else np.zeros_like(safe)
    return SlotExtra(
        anti_midx=jnp.asarray(np.where(ok, st.anti_midx[safe], PAD).astype(np.int32)),
        pref_midx=jnp.asarray(np.where(ok, st.pref_midx[safe], PAD).astype(np.int32)),
        tol_class=jnp.asarray(tol_c.astype(np.int32)),
        na_class=jnp.asarray(na_c.astype(np.int32)),
        tier=jnp.asarray(tier.astype(np.int32)),
        txn=(
            jnp.asarray(np.where(ok, st.txn_tab[safe], _NO_TXN).astype(np.int32))
            if st.has_wide_gangs
            else None
        ),
    )


# ---------------------------------------------------------------------------
# Wave machinery
# ---------------------------------------------------------------------------


class DynTables(NamedTuple):
    """Per-scenario domain tables for labels_dirty what-if batches (one
    scenario's slice under vmap; built by whatif.ScenarioDyn). The base
    (scenario-shared) expansion tables stay untouched — these carry only
    the per-scenario corrections: K label-perturbed nodes with their
    old/new domains per group, the domain-existence mask, and the
    per-scenario spread weights. All tiny next to the [S, N] planes."""

    ov_nodes: jax.Array  # [K] i32 (PAD-padded)
    ov_gdom: jax.Array  # [G, K] f32 new domain (PAD where inapplicable)
    ov_old: jax.Array  # [G, K] f32 base domain (PAD likewise)
    dexist: jax.Array  # [G, Dcap] f32 1.0 where the domain has ≥1 node
    sp_w_g: jax.Array  # [G] f32 log(size+2), size = #existing domains


class WavePre3(NamedTuple):
    """Per-wave precompute. Scenario-independent unless noted."""

    row_g: jax.Array  # [W, KT] i32 global group id (PAD invalid)
    oh_row: jax.Array  # [W, KT, G] f32 one-hot
    coarse_row: jax.Array  # [W, KT] f32 row's group is coarse
    dmap: jax.Array  # [W, KT, N] f32 node→domain per row (PAD=-1)
    ov: jax.Array  # [W(j), W(k), KT] f32 bind-of-j → read-of-(k,row) coupling
    # Host-plane reads in the form host_row_reads() names; the other form's
    # fields are empty.
    oh_mc_h: jax.Array  # [W, KT, Hmc] f32 host-plane one-hots ("contraction")
    oh_anti_h: jax.Array  # [W, KT, Ha] f32
    oh_pref_h: jax.Array  # [W, KT, Hp] f32
    row_h: jax.Array  # [W, KT] i32 row of its kind's host plane, PAD none ("rows")
    row_w: jax.Array  # [W, KT] f32 per-row weight (pref rows; 1/0 elsewhere)
    aff_selfm: jax.Array  # [W, A] bool
    sp_selfm: jax.Array  # [W, SP] f32
    sp_skew: jax.Array  # [W, SP] f32
    sp_dns: jax.Array  # [W, SP] bool
    sp_scored: jax.Array  # [W, SP] bool (valid & ScheduleAnyway)
    sp_w: jax.Array  # [W, SP] f32 (upstream log(size+2) weights)
    pmg_f: jax.Array  # [W, G] f32
    anti_g: jax.Array  # [W, G] f32 (required-anti term one-hot sums)
    pref_g: jax.Array  # [W, G] f32 (preferred term weight sums)
    taint_ok: jax.Array  # [W, N] bool (PER-SCENARIO under vmap)
    taint_raw: jax.Array  # [W, N] f32 (per-scenario)
    na_ok: jax.Array  # [W, N] bool (per-scenario)
    na_raw: jax.Array  # [W, N] f32 (per-scenario)
    # labels_dirty (DynTables) rows — zero-width when dyn is None.
    ov_new_row: jax.Array  # [W, KT, K] f32 new dom per row at override j
    ov_old_row: jax.Array  # [W, KT, K] f32 base dom likewise
    dex_row: jax.Array  # [W, SP, Dcap] bool domain-exists per spread row


def build_wave_pre3(
    dc: DevCluster, d: Derived, sh: Shared3, st: V3Static,
    sb: PodSlot, sx: SlotExtra, spec, dyn: Optional[DynTables] = None,
    host_rows_read: bool = False,
) -> WavePre3:
    W = sb.pod_id.shape[0]
    G = st.G
    N = sh.gdom_f.shape[1]
    pmg_f = sb.pmg.astype(jnp.float32)[:, :G] if sb.pmg.shape[1] >= G else jnp.pad(
        sb.pmg.astype(jnp.float32), ((0, 0), (0, G - sb.pmg.shape[1]))
    )

    secs = []
    if st.A:
        secs.append(sb.aff_req[:, : st.A])
    if st.B:
        secs.append(sb.anti_req[:, : st.B])
    if st.SP:
        secs.append(sb.spread_g[:, : st.SP])
    if st.PA:
        secs.append(sb.pref_aff[:, : st.PA])
    if st.MA:
        secs.append(sx.anti_midx)
    if st.MP:
        secs.append(sx.pref_midx)
    row_g = (
        jnp.concatenate(secs, axis=1) if secs else jnp.zeros((W, 0), jnp.int32)
    )
    oh_row = _term_onehot(row_g, G)  # [W, KT, G]
    coarse_row = jnp.einsum("wkg,g->wk", oh_row, sh.coarse_f, precision=_HI)
    dmap = jnp.einsum("wkg,gn->wkn", oh_row, sh.gdom_f, precision=_HI)
    # Rows of PAD groups must read nothing and match no node.
    dmap = jnp.where((row_g >= 0)[:, :, None], dmap, float(PAD))

    anti_g, pref_g = T2._pod_group_vectors(sb, G)

    # Coupling: how much does pod j's bind add to row (k, r)'s count when
    # the bound node shares the row-group's domain — per plane kind.
    kmask = kind_masks(st)
    ov = (
        (
            jnp.einsum("jg,wkg->jwk", pmg_f, oh_row, precision=_HI)
            * kmask["mc"][None, None, :]
            + jnp.einsum("jg,wkg->jwk", anti_g, oh_row, precision=_HI)
            * kmask["anti"][None, None, :]
            + jnp.einsum("jg,wkg->jwk", pref_g, oh_row, precision=_HI)
            * kmask["pref"][None, None, :]
        )
        if st.KT
        else jnp.zeros((W, W, 0), jnp.float32)
    )

    def hostoh(g2local, H):
        if H == 0 or host_rows_read:
            return jnp.zeros((W, st.KT, 0), jnp.float32)
        loc = jnp.asarray(g2local)  # [G] static table
        # one-hot over local host ids; zero for coarse/PAD rows
        lrow = jnp.einsum("wkg,g->wk", oh_row, loc.astype(jnp.float32), precision=_HI)
        valid = (1.0 - coarse_row) * (row_g >= 0)
        return (
            (lrow[:, :, None] == jnp.arange(H)[None, None, :])
            & (valid > 0.5)[:, :, None]
        ).astype(jnp.float32)

    # Host reads per plane kind: mask rows to the right sections.
    oh_mc_h = hostoh(st.g2mc_h, len(st.mc_h_ids)) * kmask["mc"][None, :, None]
    oh_anti_h = hostoh(st.g2anti_h, len(st.anti_h_ids)) * kmask["anti"][None, :, None]
    oh_pref_h = hostoh(st.g2pref_h, len(st.pref_h_ids)) * kmask["pref"][None, :, None]

    # The "rows" form: the row of its plane kind's [H, N] host plane that
    # each position names, from the static [G] tables g2*_h through the term
    # one-hot (small whole numbers, exact); PAD for a PAD, coarse or unread
    # group.
    if st.has_host_rows and host_rows_read:
        row_h = jnp.where(
            row_g >= 0,
            jnp.einsum(
                "wkg,kg->wk", oh_row, jnp.asarray(host_row_table(st)),
                precision=_HI,
            ),
            float(PAD),
        ).astype(jnp.int32)
    else:
        row_h = jnp.full((W, st.KT), PAD, jnp.int32)

    o0, o1, o2, o3, o4, o5, o6 = st.sections
    row_w = jnp.ones((W, st.KT), jnp.float32)
    if st.PA:
        w = jnp.where(sb.pref_aff[:, : st.PA] >= 0, sb.pref_aff_w[:, : st.PA], 0.0)
        row_w = row_w.at[:, o3:o4].set(w)
    row_w = row_w * (row_g >= 0)

    if st.A:
        ohA = oh_row[:, :o1]
        aff_selfm = jnp.einsum("wag,wg->wa", ohA, pmg_f, precision=_HI) > 0.5
    else:
        aff_selfm = jnp.zeros((W, 0), bool)
    if st.SP:
        ohS = oh_row[:, o2:o3]
        sp_selfm = jnp.einsum("wag,wg->wa", ohS, pmg_f, precision=_HI)
        sp_skew = sb.spread_skew[:, : st.SP].astype(jnp.float32)
        sp_dns = (sb.spread_g[:, : st.SP] >= 0) & sb.spread_dns[:, : st.SP]
        sp_scored = (sb.spread_g[:, : st.SP] >= 0) & ~sb.spread_dns[:, : st.SP]
        if dyn is not None:
            # Per-scenario weights: domain sizes change under set_label.
            sp_w = jnp.einsum("wag,g->wa", ohS, dyn.sp_w_g, precision=_HI)
        else:
            # One source of truth for the upstream topologyNormalizingWeight
            # table: spec.sp_w_g (jax_runtime._spread_w_table).
            w_tab = T2._padded_w_table(spec.sp_w_g, G)
            sp_w = jnp.einsum(
                "wag,g->wa", ohS, jnp.asarray(w_tab), precision=_HI
            )
    else:
        sp_selfm = jnp.zeros((W, 0), jnp.float32)
        sp_skew = jnp.zeros((W, 0), jnp.float32)
        sp_dns = jnp.zeros((W, 0), bool)
        sp_scored = jnp.zeros((W, 0), bool)
        sp_w = jnp.zeros((W, 0), jnp.float32)

    # Taint/NA per-wave tensors only exist on the non-class fallback path;
    # with classes the per-chunk [C, N] masks are read via tiny one-hots.
    if spec.taints and not st.use_tol_classes:
        taint_ok = jax.vmap(lambda s: T2.taint_mask(dc, s))(sb)
        taint_raw = jax.vmap(lambda s: T2.taint_prefer_count(dc, s))(sb)
    else:
        taint_ok = jnp.ones((W, 1), bool)
        taint_raw = jnp.zeros((W, 1), jnp.float32)
    if spec.node_affinity and not st.use_na_classes:
        na_ok = jax.vmap(lambda s: T2.node_affinity_mask(d, s))(sb)
        na_raw = jax.vmap(lambda s: T2.node_affinity_score(d, s))(sb)
    else:
        na_ok = jnp.ones((W, 1), bool)
        na_raw = jnp.zeros((W, 1), jnp.float32)

    if dyn is not None:
        K = dyn.ov_nodes.shape[0]
        valid_row = (row_g >= 0)[:, :, None]
        ov_new_row = jnp.where(
            valid_row,
            jnp.einsum("wkg,gj->wkj", oh_row, dyn.ov_gdom, precision=_HI),
            float(PAD),
        )
        ov_old_row = jnp.where(
            valid_row,
            jnp.einsum("wkg,gj->wkj", oh_row, dyn.ov_old, precision=_HI),
            float(PAD),
        )
        if st.SP:
            dex_row = (
                jnp.einsum(
                    "wag,gd->wad", oh_row[:, o2:o3], dyn.dexist, precision=_HI
                )
                > 0.5
            )
        else:
            dex_row = jnp.zeros((W, 0, st.Dcap), bool)
    else:
        ov_new_row = jnp.zeros((W, st.KT, 0), jnp.float32)
        ov_old_row = jnp.zeros((W, st.KT, 0), jnp.float32)
        dex_row = jnp.zeros((W, st.SP, st.Dcap), bool)

    return WavePre3(
        row_g=row_g, oh_row=oh_row, coarse_row=coarse_row, dmap=dmap, ov=ov,
        oh_mc_h=oh_mc_h, oh_anti_h=oh_anti_h, oh_pref_h=oh_pref_h,
        row_h=row_h, row_w=row_w, aff_selfm=aff_selfm,
        sp_selfm=sp_selfm, sp_skew=sp_skew, sp_dns=sp_dns,
        sp_scored=sp_scored, sp_w=sp_w,
        pmg_f=pmg_f, anti_g=anti_g, pref_g=pref_g,
        taint_ok=taint_ok, taint_raw=taint_raw, na_ok=na_ok, na_raw=na_raw,
        ov_new_row=ov_new_row, ov_old_row=ov_old_row, dex_row=dex_row,
    )


def _fit_score_r(used1_r, alloc_r, weights, strategy, shape_x, shape_y) -> jax.Array:
    """NodeResourcesFit scoring over per-resource [N] planes, statically
    unrolled over R. Arithmetic mirrors ops.tpu._int_resource_score /
    piecewise_interp_int bit-for-bit (same floor chain, same r order)."""
    N = used1_r[0].shape[0]
    acc = jnp.zeros(N, jnp.float32)
    wsum = 0.0
    for r in range(len(used1_r)):
        w = float(weights[r])
        if w == 0:
            continue
        alloc = alloc_r[r]
        denom = jnp.where(alloc > 0, alloc, 1.0)
        if strategy == "LeastAllocated":
            frac = jnp.where(alloc > 0, (alloc - used1_r[r]) / denom, 0.0)
        else:
            frac = jnp.where(alloc > 0, used1_r[r] / denom, 0.0)
        frac = jnp.clip(frac, 0.0, 1.0)
        if strategy in ("LeastAllocated", "MostAllocated"):
            s = jnp.floor(frac * np.float32(T2.MAX_NODE_SCORE))
        else:
            util = jnp.floor(frac * np.float32(100.0))
            s = T2.piecewise_interp_int(util, list(shape_x), list(shape_y))
        acc = acc + s * np.float32(w)
        wsum += w
    if wsum == 0:
        return acc
    return jnp.floor(acc / np.float32(wsum))


def _hi_lo_premasked(hi_in: jax.Array, lo_in: jax.Array):
    """(hi, lo) per row from caller-masked inputs (−inf/+inf at excluded
    nodes) — ONE variadic reduce kernel instead of two passes."""

    def comb(a, b):
        return jnp.maximum(a[0], b[0]), jnp.minimum(a[1], b[1])

    return jax.lax.reduce(
        (hi_in, lo_in),
        (np.float32(-np.inf), np.float32(np.inf)),
        comb,
        dimensions=(1,),
    )




def _expand_rows(rows: jax.Array, dom_oh_k: jax.Array) -> jax.Array:
    """[Kc, Dcap] domain rows → [Kc, N] node values: one-hot matmul against
    the per-wave node→domain one-hot (exact selection; rides the MXU —
    gathers serialize on TPU). PAD map entries have all-zero one-hots → 0."""
    return jnp.einsum("kd,knd->kn", rows, dom_oh_k, precision=_HI)


def count_rows(vals, lo: int, hi: int) -> jax.Array:
    """[hi - lo, N]: a section of a slot's node-space count values, held as
    one ``[KT, N]`` array or as a list of ``[N]`` rows, one a position."""
    return jnp.stack(vals[lo:hi]) if isinstance(vals, list) else vals[lo:hi]


def count_rows_add(vals, delta: jax.Array):
    """``vals`` (either form of :func:`count_rows`) plus ``delta`` [KT, N]."""
    if isinstance(vals, list):
        return [v + delta[r] for r, v in enumerate(vals)]
    return vals + delta


def _at_positions(x: jax.Array, pos, axis: int) -> jax.Array:
    """``x`` kept along ``axis`` at the static positions ``pos`` alone, by
    static slices (no gather); ``x`` itself where that is every position."""
    if list(pos) == list(range(x.shape[axis])):
        return x
    return jnp.stack(
        [jax.lax.index_in_dim(x, r, axis, keepdims=False) for r in pos], axis
    )


def class_masks(dc: DevCluster, d: Derived, st: V3Static, spec, rep_slots):
    """Per-chunk [C, N] taint/NA masks+raws for the toleration / NA
    equivalence classes (rep_slots: PodSlot of class representatives,
    gathered host-side at engine build). Computed ONCE per chunk."""
    tol_reps, na_reps = rep_slots
    out = {}
    # 0/1 masks are bf16-exact; the per-pod row reads in the wave step
    # (class_row: a dynamic index by the slot's class id, or, where the
    # slots differ by scenario, a select among the few rows: class_row_reads)
    # then cost half the bytes. Raw score planes stay f32.
    if spec.taints and st.use_tol_classes:
        out["tol_ok"] = jax.vmap(lambda s: T2.taint_mask(dc, s))(tol_reps).astype(
            jnp.bfloat16
        )
        out["tol_raw"] = jax.vmap(lambda s: T2.taint_prefer_count(dc, s))(tol_reps)
    if spec.node_affinity and st.use_na_classes:
        out["na_ok"] = jax.vmap(lambda s: T2.node_affinity_mask(d, s))(na_reps).astype(
            jnp.bfloat16
        )
        out["na_raw"] = jax.vmap(lambda s: T2.node_affinity_score(d, s))(na_reps)
    return out


def make_wave_step3(
    dc: DevCluster, d: Derived, sh: Shared3, st: V3Static,
    wave_width: int, spec, cmasks=None, dyn: Optional[DynTables] = None,
    dyn_flip: bool = True, wvec=None, scenario_axis: bool = False,
    slots_by_scenario: bool = False,
):
    """Scan body over (PodSlot, SlotExtra) wave batches. Bit-identical to
    the v2 step; see module docstring for the traffic model. ``cmasks``:
    per-chunk class masks from :func:`class_masks`. ``dyn``: per-scenario
    DynTables for labels_dirty batches — base expansion tables stay
    shared; corrections apply as K-term fused elementwise updates.
    ``wvec``: optional traced policy vector (T2.POLICY_COLS) replacing the
    static score weights — the round 9 tuner's population axis; disables
    the packed select (its integer-weight bound needs static weights).
    ``scenario_axis``: the caller maps the step over a scenario axis
    (``vmap``) — a static fact of how the program is built, which picks the
    form of the in-wave usage corrections (:func:`inwave_corrections`) and
    of the host-scale count row reads (:func:`host_row_reads`).
    ``slots_by_scenario``: under that axis the step's slots are mapped too
    (the what-if retry pass, whose queue is the scenario's own), so a
    slot's class id is a scalar of the scenario and not of the batch, also
    static, which picks the form of a slot's toleration and node-affinity
    class row read (:func:`class_row_reads`).

    Where a slot reads its host rows one by one (``host_row_reads`` "rows":
    the step under a scenario axis) its node-space count values are built
    per position of the term axis and only where that position can hold a
    non-zero (:func:`value_positions`): the domain-row expansion at the
    ``st.coarse_pos`` positions, the host rows and their in-wave terms at
    the ``st.host_pos`` positions, handed on as ``[N]`` rows; no ``[KT, N]``
    value over all positions is assembled. Under ``dyn`` every position
    stays in both lists: the label corrections add to every position's
    expansion. The single replay (the wave-start "contraction") keeps one
    ``[KT, N]`` array over every position: at op latency the per-position
    form loses a third (PERF.md §6 PR 38)."""
    from ..sim.waves import refuse_wide_gangs

    refuse_wide_gangs(
        wave_width, st.max_gang,
        no_transaction=not st.has_wide_gangs or st.wave_width != wave_width,
    )
    cmasks = cmasks or {}
    G = st.G
    Dcap = st.Dcap
    o0, o1, o2, o3, o4, o5, o6 = st.sections
    w_cfg = dict(spec.weights)
    _w, _on = T2.policy_weight_fns(spec, wvec)
    kmask = kind_masks(st)
    # Bound-node domain vectors are only needed when some plane is carried.
    maintain_dom = st.maintain_mc or st.maintain_anti or st.maintain_pref
    # Single coarse spread constraint: its raw score takes one value per
    # domain (+ one for label-less nodes), so the normalize extrema reduce
    # over [Dcap+1] buckets instead of [N] nodes — with the taint row
    # statically gone (no PreferNoSchedule), the whole [S, K, N] hi/lo
    # pass disappears from Borg-shaped traces.
    spread_dom_hilo = bool(
        spec.spread and st.SP == 1 and not st.has_host_rows and dyn is None
    )
    Kdyn = dyn.ov_nodes.shape[0] if dyn is not None else 0
    # Node-space expansion of the domain rows ([S, KT, N] via the dom_oh
    # one-hot matmul) is only needed when some section actually consumes
    # node values: interpod sections, host planes, a real DoNotSchedule
    # spread filter, or the node-space spread scoring path. The Borg shape
    # (ScheduleAnyway-only spread, no interpod) statically skips it.
    need_vals = bool(
        st.A or st.B or st.MA or st.PA or st.MP
        or st.has_host_rows
        or (st.SP and (st.has_dns or not spread_dom_hilo))
    )
    pack_select = wvec is None and pack_select_ok(spec, w_cfg, dc.allocatable.shape[0])
    corr_form = inwave_corrections(st, scenario_axis)
    corr_plane = corr_form == "plane"
    corr_resolved = corr_form == "resolved_terms"
    host_rows_read = host_row_reads(scenario_axis) == "rows"
    row_form = class_row_reads(st, scenario_axis, slots_by_scenario)
    # A slot's count values: per-position rows where it reads its host rows
    # one by one (the step mapped over a scenario axis); one [KT, N] array
    # over every position where they come from the wave-start contraction.
    cpos, hpos = value_positions(
        st, all_positions=dyn is not None or not host_rows_read
    )
    # The InterPodAffinity verdict gathered apart, behind a barrier (below).
    verdict_apart = host_rows_read and bool(hpos)
    zone_select = (
        select_form(
            st, spec, dc.allocatable.shape[0],
            traced_weights=wvec is not None, dyn_labels=dyn is not None,
        )
        == "zone_packed"
    )

    def wave_step(carry: DevState3, batch):
        sb, sx = batch
        N = dc.allocatable.shape[0]
        with stage("ksim.reads"):
            pre = build_wave_pre3(dc, d, sh, st, sb, sx, spec, dyn, host_rows_read)

            # Wave-start reads (identical for every pod in the wave).
            if st.KT:
                lhs_c = pre.oh_row * pre.coarse_row[:, :, None]  # [W, KT, G]
                rows0 = (
                    jnp.einsum("wkg,gd->wkd", lhs_c * kmask["mc"][None, :, None],
                               carry.mc_dom, precision=_HI)
                    + jnp.einsum("wkg,gd->wkd", lhs_c * kmask["anti"][None, :, None],
                                 carry.anti_dom, precision=_HI)
                    + jnp.einsum("wkg,gd->wkd", lhs_c * kmask["pref"][None, :, None],
                                 carry.pref_dom, precision=_HI)
                )  # [W, KT, Dcap]
                if st.has_host_rows and not host_rows_read:
                    # One-hot LHS cast to the plane dtype: bf16×bf16 einsums
                    # with f32 accumulation stay exact (0/1 × small ints).
                    vals_h0 = jnp.zeros((wave_width, st.KT, N), jnp.float32)
                    if len(st.mc_h_ids):
                        vals_h0 = vals_h0 + jnp.einsum(
                            "wkh,hn->wkn", pre.oh_mc_h.astype(carry.mc_host.dtype),
                            carry.mc_host, precision=_HI,
                            preferred_element_type=jnp.float32,
                        )
                    if len(st.anti_h_ids):
                        vals_h0 = vals_h0 + jnp.einsum(
                            "wkh,hn->wkn", pre.oh_anti_h.astype(carry.anti_host.dtype),
                            carry.anti_host, precision=_HI,
                            preferred_element_type=jnp.float32,
                        )
                    if len(st.pref_h_ids):
                        vals_h0 = vals_h0 + jnp.einsum(
                            "wkh,hn->wkn", pre.oh_pref_h, carry.pref_host, precision=_HI
                        )
                totals0 = jnp.einsum("wkg,g->wk", pre.oh_row, carry.match_total, precision=_HI)
                if need_vals and cpos:
                    # Per-wave node→domain one-hot (scenario-shared) for the
                    # expansion, at the positions that can hold a domain row.
                    dom_oh = (
                        _at_positions(pre.dmap, cpos, 1)[..., None]
                        == jnp.arange(Dcap, dtype=jnp.float32)
                    ).astype(jnp.float32)  # [W, Kc, N, Dcap]
                if spread_dom_hilo and not st.seg_mode:
                    # [W, N, Dcap+1]: spread-row domain one-hot + no-domain col
                    # (built from dmap directly — dom_oh may be skipped).
                    # seg_mode needs neither: domfeas rides the bit-OR reduce
                    # and the score expansion is a tile/repeat.
                    # bf16: 0/1 one-hots and the integer score values they meet
                    # (≤ MAX_NODE_SCORE) are bf16-exact; accumulation stays f32
                    # via preferred_element_type. Halves the dominant operand
                    # traffic of both domain einsums.
                    domoh2 = jnp.concatenate(
                        [
                            (
                                pre.dmap[:, o2][..., None]
                                == jnp.arange(Dcap, dtype=jnp.float32)
                            ).astype(jnp.bfloat16),
                            (pre.dmap[:, o2] < 0)[..., None].astype(jnp.bfloat16),
                        ],
                        axis=-1,
                    )
                # #domains per row (for the domain-space spread min).
                nd_row = jnp.einsum(
                    "wkg,g->wk", pre.oh_row, jnp.asarray(st.nd_g, jnp.float32),
                    precision=_HI,
                )  # [W, KT]
            iota_n = jnp.arange(N)
            if Kdyn:
                # [K, N] override-node one-hots, built once per wave (f32: the
                # count deltas they meet are unbounded integers — bf16 would
                # round past 256).
                at_ov = (
                    dyn.ov_nodes[:, None] == iota_n[None, :]
                ).astype(jnp.float32)
            R = carry.used.shape[0]
            if st.preemption:
                # Prefix-over-tiers stacks: [Tt+1, ...]; row t = aggregate over
                # tiers < t (wave-start values; in-wave corrections per pod).
                pfx_u = [jnp.zeros((R, N), jnp.float32)]
                pfx_n = [jnp.zeros((N,), jnp.float32)]
                mts = [jnp.full((N,), -1.0, jnp.float32)]
                for t in range(st.Tt):
                    pfx_u.append(pfx_u[-1] + carry.used_tier[t])
                    pfx_n.append(pfx_n[-1] + carry.npods_tier[t])
                    mts.append(
                        jnp.maximum(mts[-1], jnp.where(carry.npods_tier[t] > 0, float(t), -1.0))
                    )
                pfx_u = jnp.stack(pfx_u)  # [Tt+1, R, N]
                pfx_n = jnp.stack(pfx_n)  # [Tt+1, N]
                mts = jnp.stack(mts)  # [Tt+1, N]
                if FUSED_PREEMPT:
                    # One packed [Tt+1, R+2, N] stack: each slot's tier gather
                    # becomes a single dynamic read (rows [:R] usage, row R
                    # pod counts, row R+1 max tier) instead of three. Pure
                    # layout — every element is the same f32 value the
                    # separate stacks hold.
                    pfx_pack = jnp.concatenate(
                        [pfx_u, pfx_n[:, None, :], mts[:, None, :]], axis=1
                    )
                preempted = jnp.zeros((), bool)
                ev_node = jnp.asarray(PAD, jnp.int32)
                ev_tier = jnp.zeros((), jnp.int32)
                ev_prior = jnp.zeros((), jnp.float32)
                ev_total = jnp.zeros((), jnp.float32)
                eu_acc = [jnp.zeros((), jnp.float32) for _ in range(R)]
                evicted = []  # per-slot "evicted mid-wave" flags
            if corr_plane:
                # The wave's usage corrections, one running [R, N] plane
                # (N padded to whole 128-lane blocks): zero here, and each
                # slot adds its request at its ONE chosen node once it has
                # chosen (below). Slot k reads it as it stands, whatever k:
                # the [N]-wide reduces of a slot cost the same in slot 7 as
                # in slot 0.
                used_corr = jnp.zeros((R, -(-N // 128) * 128), jnp.float32)
                lane = jnp.arange(128, dtype=jnp.int32)
            # "resolved_terms": per earlier slot, the R sums its node had
            # taken in this wave once that slot was bound (resolve_usage).
            node_sums = []
        choices, placeds, dom_ats = [], [], []
        for k in range(wave_width):
            with stage("ksim.reads"):
                s = jax.tree.map(lambda a: a[k], sb)
                if hpos and host_rows_read:
                    vals_h = host_rows_at(st, carry, pre.row_h[k], hpos)

            # --- exact in-wave corrections from pods j<k -----------------
            # Usage: the running plane, or k terms rebuilt from the chosen-
            # node indices inside the consuming fusions: summed one-hot terms
            # (preemption) or a chain of selects among sums resolved ahead
            # of the pass (scenario batch) — see inwave_corrections().
            # Counts: domain-space or host-row terms, never materialized as
            # carried values.
            with stage("ksim.corrections"):
                rows_corr = jnp.zeros((st.KT, Dcap), jnp.float32) if st.KT else None
                if host_rows_read:
                    valh_corr = {r: jnp.zeros((N,), jnp.float32) for r in hpos}
                else:
                    valh_corr = (
                        jnp.zeros((st.KT, N), jnp.float32)
                        if (st.KT and st.has_host_rows)
                        else None
                    )
                tot_corr = jnp.zeros((st.KT,), jnp.float32) if st.KT else None
                if corr_plane:
                    used_corr_r = [used_corr[r, :N] for r in range(R)]
                else:
                    used_corr_r = [jnp.zeros((N,), jnp.float32) for _ in range(R)]
                if corr_resolved:
                    # One compare and R selects a node a term, no add: the
                    # outermost select is the latest slot's, whose sums
                    # hold everything bound to its node so far.
                    for j in range(k):
                        at_j = iota_n == choices[j]
                        used_corr_r = [
                            jnp.where(at_j, node_sums[j][r], used_corr_r[r])
                            for r in range(R)
                        ]
                if st.preemption and k > 0:
                    # An earlier in-wave eviction frees wave-start usage at the
                    # evicted node (evicted slots are excluded below).
                    oh_e = (
                        preempted.astype(jnp.float32)
                        * (iota_n == ev_node).astype(jnp.float32)
                    )
                    for r in range(R):
                        used_corr_r[r] = used_corr_r[r] - eu_acc[r] * oh_e
                for j in range(k):
                    wj = placeds[j].astype(jnp.float32)
                    if st.preemption:
                        wj_used = wj * (1.0 - evicted[j].astype(jnp.float32))
                    else:
                        wj_used = wj
                    if corr_form == "terms":
                        oh_j = (iota_n == choices[j]).astype(jnp.float32)
                        for r in range(R):
                            used_corr_r[r] = (
                                used_corr_r[r] + wj_used * oh_j * sb.req[j, r]
                            )
                    # Count corrections below keep evicted slots (phantom rule).
                    if st.KT:
                        # domain of j's bound node under row (k, r)'s group
                        domat_r = jnp.einsum(
                            "g,rg->r", dom_ats[j], pre.oh_row[k], precision=_HI
                        )  # [KT]
                        ovr = pre.ov[j, k] * pre.coarse_row[k]  # [KT]
                        oh_d = (
                            domat_r[:, None] == jnp.arange(Dcap, dtype=jnp.float32)
                        ).astype(jnp.float32)
                        rows_corr = rows_corr + (wj * ovr)[:, None] * oh_d
                        # Domain-equality form: credits every node sharing
                        # the bound node's domain (== the bound node alone
                        # for singleton/hostname topologies).
                        if host_rows_read:
                            # One [N] row a position: its two scalars are made
                            # for it alone, by a [G] dot; cut out of the [KT]
                            # vectors above they cost a fusion each under a
                            # scenario axis (PERF.md §6 PR 38).
                            for r in hpos:
                                domat_h = jnp.dot(
                                    dom_ats[j], pre.oh_row[k, r], precision=_HI
                                )
                                ovh = (
                                    wj
                                    * pre.ov[j, k, r]
                                    * (1.0 - pre.coarse_row[k, r])
                                    * (pre.row_g[k, r] >= 0)
                                    * (domat_h >= 0)
                                )
                                valh_corr[r] = valh_corr[r] + ovh * (
                                    pre.dmap[k, r] == domat_h
                                )
                        elif st.has_host_rows:
                            ovh = (
                                wj
                                * pre.ov[j, k]
                                * (1.0 - pre.coarse_row[k])
                                * (pre.row_g[k] >= 0)
                                * (domat_r >= 0)
                            )
                            valh_corr = valh_corr + ovh[:, None] * (
                                pre.dmap[k] == domat_r[:, None]
                            )
                        tot_corr = tot_corr + wj * pre.ov[j, k] * kmask["mc"] * (
                            domat_r >= 0
                        )

            # --- fused Filter + Score (bit-identical to v2) --------------
            # used1_r = per-resource used-after-this-pod planes, shared by
            # the fit mask and every fit scoring strategy.
            with stage("ksim.filter_score"):
                with jax.named_scope("NodeResourcesFit"):
                    used1_r = [
                        carry.used[r] + used_corr_r[r] + s.req[r] for r in range(R)
                    ]
                    alloc_r = [dc.allocatable[:, r] for r in range(R)]
                    # Non-fit filters tracked separately: preemption candidacy
                    # reuses them with the fit check replaced by fit-after-evict.
                    feasible = jnp.ones(N, bool)
                    if spec.fit:
                        for r in range(R):
                            feasible = feasible & (used1_r[r] <= alloc_r[r] + 1e-6)
                    fit_ok = feasible
                nonfit = jnp.ones(N, bool)
                if spec.taints:
                    with jax.named_scope("TaintToleration"):
                        if st.use_tol_classes:
                            # Row select by class id — a dynamic slice reads ONE
                            # [N] row. (The old one-hot einsum contracted the whole
                            # [C, N] plane per pod: 40% of device time on the
                            # north-star profile.) Values identical: one-hot × f32
                            # picked the same row exactly.
                            tok_k = (
                                class_row(cmasks["tol_ok"], sx.tol_class[k], row_form)
                                > 0.5
                            )
                            traw_k = class_row(
                                cmasks["tol_raw"], sx.tol_class[k], row_form
                            )
                        else:
                            tok_k, traw_k = pre.taint_ok[k], pre.taint_raw[k]
                        nonfit = nonfit & tok_k
                if spec.node_affinity:
                    with jax.named_scope("NodeAffinity"):
                        if st.use_na_classes:
                            naok_k = (
                                class_row(cmasks["na_ok"], sx.na_class[k], row_form)
                                > 0.5
                            )
                            naraw_k = class_row(
                                cmasks["na_raw"], sx.na_class[k], row_form
                            )
                        else:
                            naok_k, naraw_k = pre.na_ok[k], pre.na_raw[k]
                        nonfit = nonfit & naok_k

                # Nothing of a slot is materialized by hand. used1_r and
                # `feasible` are re-derived from carry.used inside every
                # [N]-wide reduce that reads them, the k usage terms of
                # the "terms" and "resolved_terms" forms included (of the
                # latter only the selects: the sums they pick are scalars
                # kept out of the fusion, resolve_usage): under select_form() ==
                # "zone_packed" that is ONE reduce a slot (the best packed
                # node per zone, below), otherwise two (the spread's zone
                # feasibility and the select). A barrier on used1_r cost
                # an R×[S, N] write per pod (round 3); one on `feasible`
                # stood here until PR 30 and was dead: its result was
                # overwritten at `feasible = fit_ok & nonfit`, so jax
                # dropped it at lowering. In the single replay XLA writes
                # `used + plane + req` out once a slot by itself (PR 26).
                # Preemption still materializes (prefit re-reads used1_r).
                if st.preemption:
                    used1_r = list(jax.lax.optimization_barrier(tuple(used1_r)))
                if st.KT:
                    rows_k = rows0[k] + rows_corr  # [KT, Dcap]
                    totals = totals0[k] + tot_corr
                    if need_vals:
                        if host_rows_read:
                            # One [N] row a position: its expansion row, its
                            # host row plus its terms, their sum where the
                            # position can name either scale, zeros where
                            # none.
                            vals = [None] * st.KT
                            if cpos:
                                expanded = _expand_rows(
                                    _at_positions(rows_k, cpos, 0), dom_oh[k]
                                )  # [Kc, N]
                                for i, r in enumerate(cpos):
                                    vals[r] = expanded[i]
                            for r in hpos:
                                vals[r] = (
                                    vals_h[r] if vals[r] is None else vals[r] + vals_h[r]
                                ) + valh_corr[r]
                            vals = [
                                jnp.zeros((N,), jnp.float32) if v is None else v
                                for v in vals
                            ]
                        else:
                            vals = _expand_rows(rows_k, dom_oh[k])
                            if st.has_host_rows:
                                vals = vals + vals_h0[k] + valh_corr
                        gvalid = pre.dmap[k] >= 0  # [KT, N]
                        if Kdyn:
                            # labels_dirty: corrections on top of the BASE
                            # expansion — for each perturbed node, swap in
                            # rows_k at its new domain and its new validity.
                            # PAD ids give all-zero one-hots. ONE [2KT, K] ×
                            # [K, N] matmul carries both the value deltas and
                            # the validity flips (a per-j Python loop fused
                            # badly: 1.8× on the config-3 dirty batch).
                            arange_d = jnp.arange(Dcap, dtype=jnp.float32)
                            ohn = (
                                pre.ov_new_row[k][..., None] == arange_d
                            ).astype(jnp.float32)  # [KT, K, Dcap]
                            oho = (
                                pre.ov_old_row[k][..., None] == arange_d
                            ).astype(jnp.float32)
                            newv = jnp.einsum("rjd,rd->rj", ohn, rows_k, precision=_HI)
                            oldv = jnp.einsum("rjd,rd->rj", oho, rows_k, precision=_HI)
                            delta = newv - oldv  # [KT, K]
                            if dyn_flip:
                                flip = (
                                    (pre.ov_new_row[k] >= 0)
                                    != (pre.ov_old_row[k] >= 0)
                                ).astype(jnp.float32)  # [KT, K]
                                corr = jnp.einsum(
                                    "rj,jn->rn",
                                    jnp.concatenate([delta, flip], axis=0),
                                    at_ov,
                                    precision=_HI,
                                )  # [2·KT, N]
                                vals = count_rows_add(vals, corr[: st.KT])
                                gvalid = gvalid != (corr[st.KT :] > 0.5)
                            else:
                                # No key-presence changes in the whole batch:
                                # validity is untouched, only values shift.
                                corr = jnp.einsum(
                                    "rj,jn->rn", delta, at_ov, precision=_HI
                                )
                                vals = count_rows_add(vals, corr)

                with jax.named_scope("InterPodAffinity"):
                    # Where the host rows are per-position rows the plugin's
                    # verdict is gathered apart and written out once a slot
                    # (one bool a node): the host rows and their k terms are
                    # then built in ONE fusion, its only reader, and not
                    # again inside each of the slot's node-wide reduces,
                    # whose operand lists they would swell (PERF.md §6 PR 38).
                    ip_ok = jnp.ones(N, bool) if verdict_apart else nonfit
                    if spec.interpod and st.A:
                        cnt = count_rows(vals, o0, o1)
                        term_ok = (cnt >= 1) & gvalid[o0:o1]
                        boot = (totals[o0:o1] == 0) & pre.aff_selfm[k]
                        valid = (pre.row_g[k, o0:o1] >= 0)[:, None]
                        ip_ok = ip_ok & jnp.all(
                            jnp.where(valid, term_ok | boot[:, None], True), axis=0
                        )
                    if spec.interpod and st.B:
                        viol = (count_rows(vals, o1, o2) >= 1) & gvalid[o1:o2]
                        valid = (pre.row_g[k, o1:o2] >= 0)[:, None]
                        ip_ok = ip_ok & jnp.all(jnp.where(valid, ~viol, True), axis=0)
                    if spec.interpod and st.MA:
                        blocked = jnp.sum(count_rows(vals, o4, o5), axis=0) > 0.5
                        ip_ok = ip_ok & ~blocked
                    nonfit = (
                        nonfit & jax.lax.optimization_barrier(ip_ok)
                        if verdict_apart
                        else ip_ok
                    )
                if spec.spread and st.SP and st.has_dns:
                    with jax.named_scope("PodTopologySpread"):
                        cnts = count_rows(vals, o2, o3)
                        gval = gvalid[o2:o3]
                        # Min over domains — every existing domain has ≥1 node, so
                        # min over valid domains == min over gvalid nodes. Coarse
                        # rows reduce over [Dcap] (tiny); host rows (domain≈node)
                        # need the node-space min.
                        dval = (
                            pre.dex_row[k]
                            if dyn is not None
                            else (
                                jnp.arange(Dcap, dtype=jnp.float32)[None, :]
                                < nd_row[k, o2:o3][:, None]
                            )
                        )  # [SP, Dcap]
                        minv_dom = jnp.min(
                            jnp.where(dval, rows_k[o2:o3], jnp.inf), axis=1
                        )
                        if st.has_host_rows:
                            minv_node = jnp.min(jnp.where(gval, cnts, jnp.inf), axis=1)
                            minv = jnp.where(
                                pre.coarse_row[k, o2:o3] > 0.5, minv_dom, minv_node
                            )
                        else:
                            minv = minv_dom
                        has = jnp.isfinite(minv)
                        c_ok = (
                            gval
                            & has[:, None]
                            & (cnts + pre.sp_selfm[k][:, None]
                               - jnp.where(has, minv, 0.0)[:, None]
                               <= pre.sp_skew[k][:, None])
                        )
                        nonfit = nonfit & jnp.all(
                            jnp.where(pre.sp_dns[k][:, None], c_ok, True), axis=0
                        )

                feasible = fit_ok & nonfit
                any_f = None  # derived from the hi reduce when rows exist
                total = jnp.zeros(N, jnp.float32)
                if spec.fit and _on("NodeResourcesFit"):
                    with jax.named_scope("NodeResourcesFit"):
                        rw = np.asarray(spec.resource_weights, dtype=np.float32)
                        if wvec is not None and spec.fit_strategy in (
                            "LeastAllocated", "MostAllocated"
                        ):
                            raw = jnp.where(
                                wvec[T2.IDX_FIT_LEAST] > 0.5,
                                _fit_score_r(used1_r, alloc_r, rw, "LeastAllocated",
                                             spec.shape_x, spec.shape_y),
                                _fit_score_r(used1_r, alloc_r, rw, "MostAllocated",
                                             spec.shape_x, spec.shape_y),
                            )
                        else:
                            raw = _fit_score_r(
                                used1_r, alloc_r, rw, spec.fit_strategy,
                                spec.shape_x, spec.shape_y,
                            )
                        total = total + _w("NodeResourcesFit") * raw
                rows_n = []
                if spec.taints and spec.taint_score and _on("TaintToleration"):
                    rows_n.append((traw_k, _w("TaintToleration"), False, True))
                if spec.node_affinity and _on("NodeAffinity"):
                    rows_n.append((naraw_k, _w("NodeAffinity"), False, False))
                if spec.interpod and _on("InterPodAffinity"):
                    with jax.named_scope("InterPodAffinity"):
                        raw = jnp.zeros(dc.allocatable.shape[0], jnp.float32)
                        if st.PA:
                            raw = raw + jnp.einsum(
                                "p,pn->n", pre.row_w[k, o3:o4], count_rows(vals, o3, o4),
                                precision=_HI,
                            )
                        if st.MP:
                            raw = raw + jnp.sum(count_rows(vals, o5, o6), axis=0)
                        rows_n.append((raw, _w("InterPodAffinity"), True, False))
                sp_pack = None
                if (
                    spec.spread
                    and _on("PodTopologySpread")
                    and st.SP
                    and not spread_dom_hilo
                ):
                    with jax.named_scope("PodTopologySpread"):
                        # Upstream scoring raw + ignored mask; extrema ride the
                        # shared stacked reduce as an extra ±inf-pre-masked row.
                        cnts = count_rows(vals, o2, o3)
                        gval = gvalid[o2:o3]
                        raw_sp = jnp.zeros(N, jnp.float32)
                        sp_ign = jnp.zeros(N, bool)
                        for i in range(st.SP):
                            contrib = cnts[i] * pre.sp_w[k, i] + (
                                pre.sp_skew[k, i] - 1.0
                            )
                            raw_sp = raw_sp + jnp.where(
                                pre.sp_scored[k, i], contrib, 0.0
                            )
                            sp_ign = sp_ign | (pre.sp_scored[k, i] & ~gval[i])
                        sp_pack = (jnp.floor(raw_sp + 0.5), sp_ign)
                if rows_n or sp_pack is not None:
                    hi_rows = [jnp.where(feasible, r[0], -jnp.inf) for r in rows_n]
                    lo_rows = [jnp.where(feasible, r[0], jnp.inf) for r in rows_n]
                    if sp_pack is not None:
                        # Spread extrema run over feasible & ~ignored: its row
                        # is pre-masked with its own validity, then rides the
                        # same variadic reduce as the other score rows.
                        okn = feasible & ~sp_pack[1]
                        hi_rows.append(jnp.where(okn, sp_pack[0], -jnp.inf))
                        lo_rows.append(jnp.where(okn, sp_pack[0], jnp.inf))
                    hi, lo = _hi_lo_premasked(
                        jnp.stack(hi_rows), jnp.stack(lo_rows)
                    )
                    # hi > -inf ⟺ some node is feasible: any() comes free.
                    any_f = (
                        hi[0] > -jnp.inf if rows_n else jnp.any(feasible)
                    )
                    for i, (raw, wt, minmax, reverse) in enumerate(rows_n):
                        total = total + wt * _normalize_row(
                            raw, lo[i], hi[i], any_f, minmax, reverse
                        )
                    if sp_pack is not None:
                        total = total + _w(
                            "PodTopologySpread"
                        ) * T2.spread_norm_from_extrema(
                            sp_pack[0], sp_pack[1], hi[-1], lo[-1],
                            jnp.any(pre.sp_scored[k]),
                            getattr(spec, "sp_norm_f32", False),
                        )
                else:
                    any_f = None
                if (
                    spec.spread
                    and _on("PodTopologySpread")
                    and st.SP
                    and spread_dom_hilo
                ):
                    with jax.named_scope("PodTopologySpread"):
                        # Upstream scoring ([K8S] scoring.go): cnt·log(size+2) +
                        # (maxSkew−1), rounded, two-pass integer normalize.
                        wt = _w("PodTopologySpread")
                        # Domain-space form (SP == 1, coarse row): raw takes one
                        # value per existing domain; label-less nodes are the
                        # ignored set (the extra bucket), excluded from extrema
                        # and normalized to 0.
                        scored0 = pre.sp_scored[k, 0]
                        raw_d = jnp.floor(
                            rows_k[o2] * pre.sp_w[k, 0] + (pre.sp_skew[k, 0] - 1.0) + 0.5
                        )  # [Dcap] — floor(x+0.5) = upstream math.Round, x ≥ 0
                        dval = (
                            jnp.arange(Dcap, dtype=jnp.float32) < nd_row[k, o2]
                        )  # existing domains
                        if zone_select:
                            # Every score row but the fit score is constant
                            # inside a zone here (no node-space row: the
                            # gate of select_form), so ONE node-wide reduce,
                            # the best packed node of each zone over the
                            # fit-only total, gives this normalize its zone
                            # feasibility and the select below its node.
                            # Under the select's scope: the fit arithmetic
                            # and the usage terms fuse into this reduce.
                            assert not rows_n
                            with stage("ksim.select"):
                                zone_best = T2.zone_packed_max(
                                    total, feasible, st.seg_mode, st.seg_D,
                                    scenario_axis,
                                )
                            core = zone_best > -jnp.inf  # [D]
                        elif st.seg_mode:
                            # Structured layout: per-domain feasibility via ONE
                            # full-width bitwise-OR reduce of (1 << dom(n)) — a
                            # lane-efficient [N]→scalar reduce (the reshape-any
                            # form reduced over the 8-wide minor axis at ~6% lane
                            # utilization; the one-hot matmul before it was ~12%
                            # of device time). Exact: for a PAD spread row the
                            # downstream out_d is masked to 0 by sp_scored either
                            # way, and any(domfeas) still equals any(feasible) —
                            # every node carries a domain under the pattern.
                            if st.seg_D <= 31:
                                # Bit-pack: per-domain feasibility in int32 bits.
                                if st.seg_mode == "stride":
                                    dom_i = iota_n % st.seg_D
                                else:
                                    dom_i = iota_n // (N // st.seg_D)
                                word = jax.lax.reduce(
                                    jnp.where(
                                        feasible,
                                        jnp.left_shift(np.int32(1), dom_i),
                                        np.int32(0),
                                    ),
                                    np.int32(0),
                                    jax.lax.bitwise_or,
                                    (0,),
                                )
                                core = (
                                    jnp.right_shift(word, jnp.arange(st.seg_D)) & 1
                                ) > 0  # [D]
                            elif st.seg_mode == "stride":
                                # 32..Dcap domains: reshape-any (still cheaper
                                # than the [N, Dcap+1] one-hot einsum).
                                core = jnp.any(feasible.reshape(-1, st.seg_D), axis=0)
                            else:
                                core = jnp.any(feasible.reshape(st.seg_D, -1), axis=1)
                        else:
                            domfeas = (
                                jnp.einsum(
                                    "n,nd->d", feasible.astype(jnp.bfloat16), domoh2[k],
                                    precision=_HI, preferred_element_type=jnp.float32,
                                )
                                > 0.5
                            )  # [Dcap+1]
                        if st.seg_mode:
                            domfeas = jnp.concatenate(
                                [core, jnp.zeros(Dcap + 1 - st.seg_D, bool)]
                            )
                        okd = dval & domfeas[:Dcap]
                        hi_sp = jnp.max(jnp.where(okd, raw_d, -jnp.inf))
                        lo_sp = jnp.min(jnp.where(okd, raw_d, jnp.inf))
                        has = hi_sp > -jnp.inf
                        hi_i = jnp.where(has, hi_sp, 0.0).astype(jnp.int32)
                        lo_i = jnp.where(has, lo_sp, 0.0).astype(jnp.int32)
                        vals_d = (
                            np.int32(T2.MAX_NODE_SCORE)
                            * (hi_i + lo_i - raw_d.astype(jnp.int32))
                        ) // jnp.where(hi_i > 0, hi_i, 1)
                        out_d = jnp.where(
                            hi_i > 0,
                            vals_d.astype(jnp.float32),
                            np.float32(T2.MAX_NODE_SCORE),
                        )
                        out_d = jnp.where(dval & has & scored0, out_d, 0.0)
                        if zone_select:
                            # No node-space expansion: the zone's score joins
                            # its best packed node in the select.
                            zone_scores = wt * out_d[: st.seg_D]
                        elif st.seg_mode == "stride":
                            # dom(n) = n % D: the expansion out_d[dom(n)] is a pure
                            # tile — no [N, D] one-hot read at all (the expansion
                            # dot was the single largest op after round-3's other
                            # cuts). PAD spread rows have out_d ≡ 0 → tile of 0.
                            out = jnp.tile(out_d[: st.seg_D], N // st.seg_D)
                        elif st.seg_mode == "block":
                            out = jnp.repeat(out_d[: st.seg_D], N // st.seg_D)
                        else:
                            # out_d holds integer scores in [0, 100] — bf16-exact.
                            out = jnp.einsum(
                                "nd,d->n",
                                domoh2[k][:, :Dcap],
                                out_d.astype(jnp.bfloat16),
                                precision=_HI, preferred_element_type=jnp.float32,
                            )
                        if any_f is None:
                            any_f = jnp.any(domfeas)
                        if not zone_select:
                            total = total + wt * out
                if any_f is None:
                    any_f = jnp.any(feasible)

            with stage("ksim.select"):
                if zone_select:
                    node, _ = T2.select_node_zone_packed(zone_best, zone_scores)
                elif pack_select:
                    node, _ = T2.select_node_packed(total, feasible)
                else:
                    node, _ = select_node(total, feasible)
                placed = any_f & s.valid
            if corr_plane and k + 1 < wave_width:
                with stage("ksim.corrections"):
                    # Point update: R values at the chosen node, as the
                    # node's 128-lane block read, added to and written back
                    # in place. (The slice starts on a lane-block boundary:
                    # sliced at the node itself, an [R, 1] column, XLA lays
                    # the whole plane out node-major and copies around
                    # every consumer.) `where(here, req, 0)` is the k-term
                    # form's `placed * one_hot * req` to the bit; the
                    # block's other nodes add 0.0, as every node did there.
                    # An unplaced slot's node is PAD (a dynamic slice would
                    # wrap -1 to the last node): it lands on node 0 with
                    # nothing to add. Two slots on one node add in slot
                    # order.
                    at = jnp.clip(node, 0)
                    lo = jax.lax.bitwise_and(at, ~127)
                    blk = jax.lax.dynamic_slice(used_corr, (0, lo), (R, 128))
                    here = placed & (lane == at - lo)
                    add = jnp.where(here[None, :], s.req[:, None], 0.0)
                    used_corr = jax.lax.dynamic_update_slice(
                        used_corr, blk + add, (0, lo)
                    )
            if st.preemption:
                with stage("ksim.preempt"):
                    tier_k = sx.tier[k]  # shared scalar
                    if FUSED_PREEMPT:
                        pk = jax.lax.dynamic_index_in_dim(
                            pfx_pack, tier_k, axis=0, keepdims=False
                        )  # [R+2, N] packed lower-tier aggregates (wave start)
                        lt_u = pk[:R]  # [R, N] usage of tiers < tier_k
                        lt_np = pk[R]
                        mt0 = pk[R + 1]
                    else:
                        lt_u = jax.lax.dynamic_index_in_dim(
                            pfx_u, tier_k, axis=0, keepdims=False
                        )  # [R, N] usage of tiers < tier_k (wave start)
                        lt_np = jax.lax.dynamic_index_in_dim(
                            pfx_n, tier_k, 0, False
                        )
                        mt0 = jax.lax.dynamic_index_in_dim(mts, tier_k, 0, False)
                    lt_u_eff = [lt_u[r] for r in range(R)]
                    lt_np_eff = lt_np
                    mt_eff = mt0
                    for j in range(k):
                        lowmask = (
                            placeds[j].astype(jnp.float32)
                            * (sx.tier[j] < tier_k).astype(jnp.float32)
                            * (sb.group[j] == PAD).astype(jnp.float32)
                        )
                        oh_j = lowmask * (iota_n == choices[j]).astype(jnp.float32)
                        for r in range(R):
                            lt_u_eff[r] = lt_u_eff[r] + oh_j * sb.req[j, r]
                        lt_np_eff = lt_np_eff + oh_j
                        mt_eff = jnp.maximum(
                            mt_eff, jnp.where(oh_j > 0, sx.tier[j].astype(jnp.float32), -1.0)
                        )
                    prefit = jnp.ones(N, bool)
                    for r in range(R):
                        prefit = prefit & (
                            used1_r[r] - lt_u_eff[r] <= alloc_r[r] + 1e-6
                        )
                    cand = (
                        prefit
                        & nonfit
                        & (lt_np_eff >= 1)
                        & ~preempted
                        & ~any_f
                        & s.valid
                        & (s.group == PAD)
                        & (tier_k > 0)
                    )
                    # Rank (fewest victims, lowest max victim tier, lowest
                    # index) — exact small ints in f32; mirrors sim.greedy.
                    score = lt_np_eff * np.float32(1024.0) + mt_eff
                    if FUSED_PREEMPT:
                        # One variadic reduce for (victim node, any candidate)
                        # — selection identical to the argmax + any pair.
                        pnode, p_ok = T2.masked_argmin(score, cand)
                    else:
                        pnode = jnp.argmax(
                            jnp.where(cand, -score, -jnp.inf)
                        ).astype(jnp.int32)
                        p_ok = jnp.any(cand)
                    evict_k = p_ok & ~any_f & s.valid
                    node = jnp.where(evict_k, pnode, node)
                    placed = placed | evict_k
                    oh_p = evict_k.astype(jnp.float32) * (iota_n == node).astype(jnp.float32)
                    for r in range(R):
                        eu_acc[r] = jnp.where(
                            evict_k, jnp.sum(lt_u[r] * oh_p), eu_acc[r]
                        )
                    ev_prior = jnp.where(evict_k, jnp.sum(lt_np * oh_p), ev_prior)
                    ev_total = jnp.where(evict_k, jnp.sum(lt_np_eff * oh_p), ev_total)
                    ev_node = jnp.where(evict_k, node, ev_node)
                    ev_tier = jnp.where(evict_k, tier_k, ev_tier)
                    preempted = preempted | evict_k
                    # Mark lower-tier non-gang slots already bound there evicted.
                    for j in range(k):
                        evicted[j] = evicted[j] | (
                            evict_k
                            & (choices[j] == node)
                            & placeds[j]
                            & (sx.tier[j] < tier_k)
                            & (sb.group[j] == PAD)
                        )
                    evicted.append(jnp.zeros((), bool))
            with stage("ksim.commit"):
                if maintain_dom:
                    if st.single_topo and dyn is None:
                        # Every domain-bearing group shares ONE topology: the
                        # bound node's domain is a single dynamic read of the
                        # shared [N] map, broadcast over groups — instead of an
                        # einsum streaming the whole [G, N] table per pod.
                        dom1 = jax.lax.dynamic_index_in_dim(
                            sh.topo1_f, jnp.clip(node, 0), 0, keepdims=False
                        )
                        dom_at = jnp.where(
                            placed & (sh.has_dom_g > 0.5), dom1, float(PAD)
                        )
                    else:
                        oh_n = ((iota_n == node) & (node >= 0)).astype(jnp.float32)
                        dom_at = jnp.einsum("gn,n->g", sh.gdom_f, oh_n, precision=_HI)
                        for j in range(Kdyn):
                            # Perturbed node bound: its per-group domain is the
                            # override (== base where that topology unchanged).
                            dom_at = jnp.where(
                                node == dyn.ov_nodes[j], dyn.ov_gdom[:, j], dom_at
                            )
                        # A miss (or padded slot) must not look like domain 0.
                        dom_at = jnp.where(placed, dom_at, float(PAD))
                    dom_ats.append(dom_at)
            if corr_resolved and k + 1 < wave_width:
                with stage("ksim.corrections"):
                    node_sums.append(
                        resolve_usage(node_sums, choices, node, placed, s.req)
                    )
            choices.append(node)
            placeds.append(placed)

        with stage("ksim.commit"):
            choice = jnp.stack(choices)  # [W]
            placed = jnp.stack(placeds)  # [W]
            if st.has_gangs:
                groups = sb.group
                same = (groups[:, None] == groups[None, :]) & (groups[:, None] >= 0)
                fail = jnp.any(same & ~placed[None, :], axis=1)
                if st.has_wide_gangs:
                    with stage("ksim.gang_txn"):
                        # A wave's rows say where it stands in a group wider
                        # than the wave: the same in every scenario. Such a
                        # group's members bind tentatively whatever this
                        # wave's other members did; the verdict falls where
                        # the group closes (below).
                        t_member = sx.txn[:, 0] >= 0  # [W]
                        fail = fail & ~t_member
                commit = placed & ~fail
            else:
                commit = placed
            if st.preemption:
                evicted_w = jnp.stack(evicted)  # [W]
                # Phantom rule: counts commit for evicted slots too; usage and
                # the reported placement do not.
                commit_used = commit & ~evicted_w
            else:
                commit_used = commit
            final = jnp.where(commit_used, choice, PAD).astype(jnp.int32)

            # --- wave-end commit (gang rollback folded into the mask) --------
            wv = commit.astype(jnp.float32)  # [W]
            wv_used = commit_used.astype(jnp.float32)  # [W]
            # One-hots rebuilt from chosen-node indices, bf16 operands: exact
            # (0/1 values), half the einsum traffic of stacked f32 planes. Only
            # the tier commits and a `pref_host` plane still consume them — the
            # `used` update itself is an unrolled elementwise add since round 3
            # (the [W, N]×[W, R] dot emitted layout copies around the carry
            # that cost more than the dot; same f32 sum of the same multiset),
            # and so are the whole-number host planes (host_commit_form).
            need_oh_all = (
                st.preemption or host_commit_form(st, scenario_axis)["dot"] > 0
            )
            if need_oh_all:
                oh_all = (
                    (iota_n[None, :] == choice[:, None]) & (choice[:, None] >= 0)
                ).astype(jnp.bfloat16)  # [W, N]
            if st.preemption:
                used = carry.used + jnp.einsum(
                    "w,wn,wr->rn", wv_used, oh_all, sb.req,
                    precision=_HI, preferred_element_type=jnp.float32,
                )
            else:
                coefs = wv_used[:, None] * sb.req  # [W, R] tiny
                rows_u = []
                for r in range(R):
                    acc = carry.used[r]
                    for w in range(wave_width):
                        acc = acc + jnp.where(
                            iota_n == choice[w], coefs[w, r], 0.0
                        )
                    rows_u.append(acc)
                used = jnp.stack(rows_u)
            txn = carry.txn
            if st.has_wide_gangs:
                used, txn = _gang_txn_close(
                    used, txn, sb, sx.txn, choice, placed, commit_used, iota_n
                )
            used_tier, npods_tier = carry.used_tier, carry.npods_tier
            if st.preemption:
                # Eviction: free the wave-start lower-tier usage at the node.
                oh_e = (
                    preempted.astype(jnp.float32)
                    * (iota_n == ev_node).astype(jnp.float32)
                )  # [N]
                used = used - jnp.stack([eu_acc[r] * oh_e for r in range(R)])
                nong = (sb.group == PAD).astype(jnp.float32)  # [W]
                tiers_w = sx.tier  # [W] shared
                if st.Tt and FUSED_PREEMPT:
                    # Batched tier commit: one [Tt, W] slot-weight one-hot and
                    # two einsums replace the per-tier Python loop (Tt× fewer
                    # passes over the [W, N] placement one-hot). Each
                    # (t, ·, n) output still reduces the SAME summands over w
                    # — bit-parity with the loop form.
                    wt_all = (
                        wv_used[None, :]
                        * nong[None, :]
                        * (
                            tiers_w[None, :] == jnp.arange(st.Tt)[:, None]
                        ).astype(jnp.float32)
                    )  # [Tt, W]
                    du_all = jnp.einsum(
                        "tw,wn,wr->trn", wt_all, oh_all, sb.req,
                        precision=_HI, preferred_element_type=jnp.float32,
                    )
                    dn_all = jnp.einsum(
                        "tw,wn->tn", wt_all, oh_all,
                        precision=_HI, preferred_element_type=jnp.float32,
                    )
                    zmask_all = (
                        preempted & (jnp.arange(st.Tt) < ev_tier)
                    ).astype(jnp.float32)[:, None] * (
                        iota_n == ev_node
                    ).astype(jnp.float32)[None, :]  # [Tt, N]
                    used_tier = (
                        carry.used_tier * (1.0 - zmask_all)[:, None, :] + du_all
                    )
                    npods_tier = carry.npods_tier * (1.0 - zmask_all) + dn_all
                elif st.Tt:
                    new_ut, new_np = [], []
                    for t in range(st.Tt):
                        zmask = (
                            preempted & (jnp.asarray(t) < ev_tier)
                        ).astype(jnp.float32) * (
                            iota_n == ev_node
                        ).astype(jnp.float32)
                        w_t = wv_used * nong * (tiers_w == t).astype(jnp.float32)
                        du = jnp.einsum(
                            "w,wn,wr->rn", w_t, oh_all, sb.req,
                            precision=_HI, preferred_element_type=jnp.float32,
                        )
                        dn = jnp.einsum(
                            "w,wn->n", w_t, oh_all,
                            precision=_HI, preferred_element_type=jnp.float32,
                        )
                        new_ut.append(
                            carry.used_tier[t] * (1.0 - zmask)[None, :] + du
                        )
                        new_np.append(carry.npods_tier[t] * (1.0 - zmask) + dn)
                    used_tier = jnp.stack(new_ut)
                    npods_tier = jnp.stack(new_np)
            mc_dom, anti_dom, pref_dom = carry.mc_dom, carry.anti_dom, carry.pref_dom
            mc_host, anti_host, pref_host = carry.mc_host, carry.anti_host, carry.pref_host
            match_total = carry.match_total
            if maintain_dom:
                dom_all = jnp.stack(dom_ats)  # [W, G]
                oh_dom = (
                    dom_all[:, :, None] == jnp.arange(Dcap, dtype=jnp.float32)
                ).astype(jnp.float32)  # [W, G, Dcap]
                cf = sh.coarse_f[None, :]

                def dom_commit(plane, vec):
                    return plane + jnp.einsum(
                        "w,wg,wgd->gd", wv, vec * cf, oh_dom, precision=_HI
                    )

                if st.maintain_mc:
                    mc_dom = dom_commit(carry.mc_dom, pre.pmg_f)
                if st.maintain_anti:
                    anti_dom = dom_commit(carry.anti_dom, pre.anti_g)
                if st.maintain_pref:
                    pref_dom = dom_commit(carry.pref_dom, pre.pref_g)
                if st.A:
                    has_dom = (dom_all >= 0).astype(jnp.float32)  # [W, G]
                    match_total = carry.match_total + jnp.einsum(
                        "w,wg->g", wv, pre.pmg_f * has_dom, precision=_HI
                    )

            def host_commit(plane, vec, ids, kind):
                vh = vec[:, jnp.asarray(ids)]  # [W, H]
                if st.single_g[ids].all():
                    # Singleton domains (hostname): the bound node IS the domain
                    # — but only when it actually carries the topology label
                    # (v2's node_has_dom gate; a partially-labeled topology must
                    # not credit label-less nodes).
                    has_dom_h = (
                        jnp.stack(dom_ats)[:, jnp.asarray(ids)] >= 0
                    ).astype(jnp.float32)  # [W, H]
                    form = host_commit_plane_form(st, kind, scenario_axis)
                    if form == "dot":
                        # `pref_host` (fractional weights): the sum depends on
                        # the order of its additions, so it keeps the dot.
                        delta = jnp.einsum(
                            "w,wh,wn->hn", wv, vh * has_dom_h, oh_all,
                            precision=_HI, preferred_element_type=jnp.float32,
                        )
                        return (plane.astype(jnp.float32) + delta).astype(plane.dtype)
                    # Whole-number planes (match counts, anti-affinity
                    # holders): the wave's binds added at the chosen nodes in
                    # slot order, the `used` update's form above and for its
                    # reason: the [W, H]x[W, N] dot wrote the plane in another
                    # layout than the row reads want and XLA turned each plane
                    # before it and back after it, every wave (42% of a
                    # k8s5k-whatif256 wave, PERF.md §6 PR 34). The dot's values
                    # to the bit: every summand is a whole number and an f32
                    # sum of whole numbers under 2^24 is exact in any order. A
                    # slot with choice < 0 matches no node (iota_n >= 0).
                    coef = wv[:, None] * vh * has_dom_h  # [W, H] tiny
                    if form == "rows":
                        return host_named_row_add(plane, coef, choice, vh != 0)
                    return host_rows_add(plane, coef, choice)
                # General path: credit every node in the bound node's domain.
                gdom_h = sh.gdom_f[jnp.asarray(ids)]  # [H, N] (static row select)
                dom_at_h = jnp.stack(dom_ats)[:, jnp.asarray(ids)]  # [W, H]
                for w in range(wave_width):
                    sel = (
                        (gdom_h == dom_at_h[w][:, None]) & (dom_at_h[w] >= 0)[:, None]
                    ).astype(jnp.float32)
                    plane = plane + (wv[w] * vh[w])[:, None] * sel
                return plane

            if len(st.mc_h_ids):
                mc_host = host_commit(carry.mc_host, pre.pmg_f, st.mc_h_ids, "mc")
            if len(st.anti_h_ids):
                anti_host = host_commit(
                    carry.anti_host, pre.anti_g, st.anti_h_ids, "anti"
                )
            if len(st.pref_h_ids):
                pref_host = host_commit(
                    carry.pref_host, pre.pref_g, st.pref_h_ids, "pref"
                )
            new_state = DevState3(
                used=used, mc_dom=mc_dom, anti_dom=anti_dom, pref_dom=pref_dom,
                mc_host=mc_host, anti_host=anti_host, pref_host=pref_host,
                match_total=match_total, used_tier=used_tier, npods_tier=npods_tier,
                txn=txn,
            )
        if st.preemption:
            # Eviction event for the host fix-up walk: victims from PRIOR
            # waves (ev_prior) are reconstructed deterministically from the
            # choice log; in-wave victims are already PAD in `final`.
            return new_state, (
                final, ev_node, ev_tier,
                ev_prior.astype(jnp.int32), ev_total.astype(jnp.int32),
            )
        return new_state, final

    return wave_step


def _gang_txn_close(
    used, txn: GangTxn, sb, rows, choice, placed, commit_used, iota_n
):
    """The transaction of a pod group wider than the wave, at a wave's end:
    ``(used, txn)`` after it. ``used`` holds the wave's binds already, the
    group's tentative ones among them, so that its later waves and the pods
    behind it in its closing wave are scheduled on them.

    Every wave (``ksim.gang_txn``): what the wave's members took is added to
    the group's own carried ``[R, N]`` plane, in the wave-end commit's form
    (W compares and W x R select-adds over the nodes, in slot order), and a
    member that fitted nowhere fails the group for this scenario. Where the
    group closes (``ksim.gang_rollback``), a scenario in which it failed
    takes the whole plane out of ``used`` again, and the plane starts from
    zero for the next group: one masked pass, no list of binds, no loop and
    no branch (``rollback_form`` ``"txn_plane"``). Whether a wave closes a
    group is in its rows, the same in every scenario; whether the group
    failed is the scenario's own. The verdict goes into the log at the
    group's ordinal, for the hand-back.

    On a v5e at 256 x 1,800 (PERF.md §6, PR 37) a loop that undid the
    group's listed binds, a trip a wave of the group, ran the batch 35%
    slower, and the same unrolled under a ``lax.cond`` 43%: the carried
    state lives in VMEM through the scan and an inner loop or a conditional
    took ``used`` out of it. ``used - plane`` is the sum given back in one
    subtraction where the list gave it back bind by bind: the same float32
    wherever a node's sums are exactly representable (the module's standing
    caveat; bucketed requests are)."""
    R = used.shape[0]
    with stage("ksim.gang_txn"):
        # the wave's rows (pos, size, ordinal), the same in every scenario
        pos, size, ordinal = rows[:, 0], rows[:, 1], rows[0, 2]
        member = pos >= 0
        closes = jnp.any(member & (pos == size - 1))
        tent = member & commit_used
        coefs = tent.astype(jnp.float32)[:, None] * sb.req  # [W, R] tiny
        plane_r = []
        for r in range(R):
            acc = txn.plane[r]
            for w in range(choice.shape[0]):
                acc = acc + jnp.where(iota_n == choice[w], coefs[w, r], 0.0)
            plane_r.append(acc)
        plane = jnp.stack(plane_r)
        failed = txn.failed | jnp.any(member & sb.valid & ~placed)
        bound = txn.bound + jnp.sum(tent, dtype=jnp.int32)
        rolled = closes & failed
    with stage("ksim.gang_rollback"):
        used = jnp.where(rolled, used - plane, used)
    with stage("ksim.gang_txn"):
        was = jax.lax.dynamic_slice(txn.log, (ordinal,), (1,))
        log = jax.lax.dynamic_update_slice(
            txn.log, jnp.where(closes, failed, was[0])[None], (ordinal,)
        )
        txn = GangTxn(
            plane=jnp.where(closes, 0.0, plane),
            bound=jnp.where(closes, 0, bound),
            failed=failed & ~closes,
            log=log,
            undone=txn.undone + jnp.where(rolled, bound, 0),
        )
    return used, txn


def gangs_summary(st: V3Static, rolled_back: int, undone: int) -> dict:
    """``telemetry.summary()["gangs"]`` of a replay or a what-if batch whose
    trace has a pod group wider than the wave: the static layout and form,
    and the verdicts counted on the device (``GangTxn.log`` / ``undone``,
    summed over a batch's scenarios)."""
    return {
        "wide_groups": st.wide_groups,
        "max_group": st.max_wide,
        "max_waves_spanned": st.max_waves_spanned,
        "rollback_form": rollback_form(st),
        "wide_rolled_back": int(rolled_back),
        "pods_rolled_back": int(undone),
    }


def rollback_form(st: V3Static) -> Optional[str]:
    """How a step built from ``st`` gives a failed wide pod group's binds
    back (:func:`_gang_txn_close`), or None where the trace has no group
    wider than the wave — static per compiled program; a replay reports it
    in ``telemetry.summary()["gangs"]``."""
    return "txn_plane" if st.has_wide_gangs else None


def resolve_usage(node_sums, choices, node, placed, req):
    """The ``"resolved_terms"`` form's upkeep once a slot has chosen:
    same-node collisions of a wave are resolved among scalars (``[S]``
    vectors under a scenario axis), never on the node axis.

    ``node_sums[j]`` holds, for every earlier slot j, the R float32 sums of
    what the slots up to and including j bound to j's node asked for, added
    from zero in slot order: the value the k-term form's sum of one-hot
    terms takes at that node once slot j's term is in, to the bit (a term
    adds +0.0 everywhere else, which changes no float32). The new slot
    starts from the sums of the LATEST earlier slot on its node, else from
    zero, and adds its own request if it was placed. Nothing is written
    back to the earlier slots: the node-wide select takes a node's value
    from the latest slot bound to it. Returns the new slot's R scalars
    (scalars, not one vector: a vector is sliced apart again, in a fusion
    of its own, before every node-wide pass that reads it).

    The barrier hands the ``[N]``-wide fusions that select among the sums
    R finished values a slot. Without it XLA spreads the chain over more
    small fusions (237 against 232 a wave in the Borg what-if program) and
    a v5e runs the batch 6% slower (PERF.md §6 PR 36); fused into a
    node-wide pass the chain would be computed again for every register of
    nodes."""
    w = placed.astype(jnp.float32)
    same = [choice_j == node for choice_j in choices]
    sums = []
    for r in range(req.shape[0]):
        prior = jnp.zeros((), jnp.float32)
        for same_j, sums_j in zip(same, node_sums):
            prior = jnp.where(same_j, sums_j[r], prior)
        sums.append(prior + w * req[r])
    return jax.lax.optimization_barrier(sums)


def inwave_corrections(st: V3Static, scenario_axis: bool = False) -> str:
    """Which form of the in-wave USAGE corrections a step built from ``st``
    carries — static per compiled program; a replay reports it as
    ``telemetry.summary()["inwave_corrections"]``.

    ``"plane"``: one running ``[R, N]`` plane a wave, updated at each slot's
    chosen node (module docstring). ``"terms"``: slot k rebuilds k one-hot
    terms from the chosen-node indices and sums them, fused into every
    ``[N]``-wide consumer: a compare, a convert and per resource two
    multiplies and an add a node a term. ``"resolved_terms"``: the same k
    compares, but each picks R scalars that already hold the node's sum
    (:func:`resolve_usage`), so a term is a compare and R selects. Which
    one is a fact of how the step is built, never a switch:

    - a step mapped over a scenario axis (the what-if batch) cannot keep
      the plane: there the point update is a gather and a scatter of one
      block per scenario, which a v5e runs 17x slower than the whole
      fused-terms program (128 scenarios x 10,000 nodes: 148 s a batch
      against 8.7, PERF.md §6 PR 26), and a plane written out whole is
      R x [S, N] of HBM traffic per slot. Its correction starts from zero,
      so the sums can be resolved per node ahead of the node-wide pass;
    - tier preemption keeps the summed terms, mapped or not: its correction
      starts from the eviction term and drops evicted slots retroactively,
      an order of f32 additions that neither a running plane nor a
      resolved sum reproduces bit for bit."""
    if st.preemption:
        return "terms"
    return "resolved_terms" if scenario_axis else "plane"


def host_row_reads(scenario_axis: bool = False) -> str:
    """How a slot of the step obtains the wave-start values of its
    host-scale count rows — static per compiled program, and like
    :func:`inwave_corrections` a fact of how the program is built, not a
    switch. Both forms give the same values to the bit.

    ``"rows"``: each slot reads the ONE row a term names, by its index, at
    the positions of the term axis that can name a host-scale group and at
    no other (:func:`host_rows_at`). The step mapped over a scenario axis
    (the what-if batch): the index comes from the pods' terms, which every
    scenario shares, so the read is a dynamic slice of the ``[S, H, N]``
    plane, where the contraction below wrote and read back an
    ``[S, W, KT, N]`` float32 tensor every wave: 42% of a
    ``k8s5k-whatif256`` wave, 687,457 -> 1,047,151 placements/s on a v5e
    (PERF.md §6 PR 32).

    ``"contraction"``: at wave start the ``[W, KT, H]`` one-hots of all
    positions against the whole ``[H, N]`` planes, two small dots and an add
    (``[8, 4, 5000]`` float32, 640 KB), each slot a static slice of the
    result. The single replay, which runs at op latency: 16 row reads a
    wave in place of those three operations lose 8.6% there (50,000 pods on
    5,000 nodes: 1.1272 s a replay against 1.2239; read once at wave start
    for all slots, 11%; same PR)."""
    return "rows" if scenario_axis else "contraction"


# Most class rows a plane may have for a slot to pick its row by a chain of
# selects (class_row_reads "select"): a row more is a compare and a select
# more a node in the slot's reduce, and another [N] row read.
CLASS_SELECT_MAX = 4


def class_row_reads(
    st: V3Static, scenario_axis: bool = False, slots_by_scenario: bool = False
) -> str:
    """How a slot of the step reads the row of its toleration / node-affinity
    class from the per-chunk class planes (:func:`class_masks`: ``[C, N]`` a
    scenario) — static per compiled program and, like
    :func:`inwave_corrections` and :func:`host_row_reads`, a fact of how the
    program is built, never a switch; a what-if batch reports it as
    ``summary()["class_row_reads"]``. Both forms pick a row and sum nothing:
    the same values to the bit (:func:`class_row`).

    ``"slice"``: ``dynamic_index_in_dim(plane, c)``. Every step whose slots
    are scenario-shared, and the single replay: the class id is one scalar
    for the whole batch, so the read is a true dynamic slice of the planes
    and fuses into the slot's node-wide reduce (0.001 ms a wave in
    ``borg10k-backlog128``'s arrival waves).

    ``"select"``: the row built elementwise from the ``C`` static rows and
    ``c == i``. The step whose slots differ by scenario
    (``slots_by_scenario``: the what-if retry pass walks each scenario's own
    queue): there the class id is batched, ``vmap`` turns the dynamic index
    into a gather with a batch dimension, one a slot a plane, materialised
    outside the reduce (0.154 ms a pass wave for the two rows of that cell,
    PERF.md §5, PR 41), where a compare and a select fuse as the slice does.
    Only while every class plane has at most ``CLASS_SELECT_MAX`` rows (read
    off ``st.tol_rep`` / ``st.na_rep``): a longer chain of selects reads
    every row a node, and the gather stays."""
    if not (scenario_axis and slots_by_scenario):
        return "slice"
    rows = max(
        len(st.tol_rep) if st.use_tol_classes else 0,
        len(st.na_rep) if st.use_na_classes else 0,
    )
    return "select" if rows <= CLASS_SELECT_MAX else "slice"


def class_row(plane: jax.Array, c: jax.Array, form: str) -> jax.Array:
    """Row ``c`` of the ``[C, N]`` class plane in the form
    :func:`class_row_reads` names; ``c`` an int32 scalar in ``[0, C)``, as
    every slot's class id is (an empty slot carries task 0's:
    :func:`gather_extra_device` clips the id it gathers by)."""
    if form == "slice":
        return jax.lax.dynamic_index_in_dim(plane, c, 0, keepdims=False)
    # static slices (``plane[i]`` would lower to a dynamic one by a constant)
    rows = [
        jax.lax.index_in_dim(plane, i, 0, keepdims=False)
        for i in range(plane.shape[0])
    ]
    row = rows[0]
    for i, row_i in enumerate(rows[1:], 1):
        row = jnp.where(c == i, row_i, row)
    return row


def class_planes(st: V3Static, spec) -> dict:
    """Rows of the class planes :func:`class_masks` builds for a step of
    these static facts under this profile (0: no such plane), as
    ``summary()["class_row_reads"]`` carries them."""
    return {
        "tol_classes": len(st.tol_rep) if spec.taints and st.use_tol_classes else 0,
        "na_classes": (
            len(st.na_rep) if spec.node_affinity and st.use_na_classes else 0
        ),
    }


def pack_select_ok(spec, w_cfg, n_nodes: int) -> bool:
    """Static gate for ops.tpu.select_node_packed (see its exactness
    bounds): integer non-negative weights on every ACTIVE score row keep
    the total an integer ≤ 100·Σw, so (total, node) packs exactly into
    f32 when 100·Σw ≤ PACK_MAX_TOTAL and N ≤ PACK_MAX_NODES."""
    w_active = [
        w_cfg.get(name, 1.0)
        for name, on in (
            ("NodeResourcesFit", spec.fit),
            ("TaintToleration", spec.taints and spec.taint_score),
            ("NodeAffinity", spec.node_affinity),
            ("InterPodAffinity", spec.interpod),
            ("PodTopologySpread", spec.spread),
        )
        if on and w_cfg.get(name, 1.0) != 0
    ]
    return (
        n_nodes <= T2.PACK_MAX_NODES
        and all(float(w).is_integer() and w >= 0 for w in w_active)
        and 100.0 * sum(w_active) <= T2.PACK_MAX_TOTAL
    )


def select_form(
    st: V3Static, spec, n_nodes: int,
    traced_weights: bool = False, dyn_labels: bool = False,
) -> str:
    """How many node-wide reduces a slot of a step built from these static
    facts makes for the spread's zone feasibility and the select — static
    per compiled program; a replay reports it as
    ``telemetry.summary()["select_form"]``, a what-if batch in its
    ``fleet_telemetry``.

    ``"zone_packed"``: ONE, the best packed node of each zone
    (``ops.tpu.zone_packed_max`` over the fit-only total); zone
    feasibility is ``best > -inf`` and the node is the best zone's once the
    zone scores are added (``select_node_zone_packed``). Exact where every
    score row but the fit score is constant inside a zone and the packed
    select holds: one coarse spread row scored over a structured layout
    (``seg_mode``), no TaintToleration / NodeAffinity / InterPodAffinity
    score row, static integer weights (``pack_select_ok``), no label
    perturbation. ``"two_pass"``: the bit-OR word (or the domain one-hot
    contraction) and then the select, each over all nodes. Kept, by a fact
    of how the program is built and not by a switch, by every profile
    outside that gate, by a stride layout whose zones do not tile the 128
    lanes or more than 31 zones, and by tier preemption, whose candidate
    ranking reads ``feasible`` again after the select."""
    _, scored = T2.policy_weight_fns(spec, None)
    ok = bool(
        not traced_weights and not dyn_labels and not st.preemption
        and pack_select_ok(spec, dict(spec.weights), n_nodes)
        # one coarse spread row, scored, over a structured layout ...
        and spec.spread and scored("PodTopologySpread")
        and st.SP == 1 and not st.has_host_rows
        and st.seg_mode and st.seg_D <= 31
        and (st.seg_mode == "block" or 128 % st.seg_D == 0)
        # ... and no score row that varies inside a zone but the fit score
        and not (spec.taints and spec.taint_score and scored("TaintToleration"))
        and not (spec.node_affinity and scored("NodeAffinity"))
        and not (spec.interpod and scored("InterPodAffinity"))
    )
    return "zone_packed" if ok else "two_pass"


def host_commit_plane_form(
    st: V3Static, kind: str, scenario_axis: bool = False
) -> str:
    """The form (:func:`host_commit_form`) in which the rows of ONE host
    plane, ``"mc"``, ``"anti"`` or ``"pref"``, are committed."""
    ids, whole, one_row = {
        "mc": (st.mc_h_ids, True, st.mc_h_one_row),
        "anti": (st.anti_h_ids, True, st.anti_h_one_row),
        "pref": (st.pref_h_ids, False, False),
    }[kind]
    if not st.single_g[ids].all():
        return "elementwise"
    if not whole:
        return "dot"
    return "rows" if scenario_axis and one_row else "elementwise"


def host_commit_form(st: V3Static, scenario_axis: bool = False) -> dict:
    """How the wave-end commit of a step built from ``st`` adds a wave's
    binds to its host-scale count rows: the rows by form, static per
    compiled program and like :func:`host_row_reads` a fact of how it is
    built, not a switch. Every form gives the same values to the bit.

    ``"rows"``: each slot adds its bind to the ONE row its pod names, read,
    added to at the chosen node and written back in place by the row's index
    (:func:`host_named_row_add`). The step mapped over a scenario axis, on a
    singleton-domain (hostname) plane of whole numbers (``mc_host``,
    ``anti_host``) of which no pod names more than one row
    (``V3Static.mc_h_one_row`` / ``anti_h_one_row``): the index comes from
    the pods' labels and terms, which every scenario shares, so 8 slots
    rewrite 16 rows of ``[S, N]`` where the form below rewrites both planes
    whole: ``k8s5k-whatif256`` 9.81 -> 8.21 s a batch on a v5e (PERF.md §6
    PR 34). The single replay, at op latency, loses 24% to it (1.393 s a
    replay against 1.134) and keeps the form below.

    ``"elementwise"``: an unrolled add over the whole plane in slot order,
    no matrix product: at the chosen node for the other singleton-domain
    rows of whole numbers (the float32 sum of whole numbers is exact in any
    order, so the values are the dot's), over the bound node's whole domain
    for a row of a wider topology.

    ``"dot"``: the ``[W, H] x [W, N]`` product with the wave's node
    one-hots, kept by the singleton-domain rows of ``pref_host`` alone: its
    fractional preference weights sum to another float32 in another order.
    Until PR 34 the whole-number planes went through it too: it wrote the
    plane in a layout of its own and XLA turned the carried plane before it
    and back after it every wave, 0.82 ms of a 1.95 ms ``k8s5k-whatif256``
    wave (12.22 -> 9.81 s a batch without it)."""
    form = {"rows": 0, "elementwise": 0, "dot": 0}
    for kind, ids in (
        ("mc", st.mc_h_ids), ("anti", st.anti_h_ids), ("pref", st.pref_h_ids)
    ):
        if len(ids):
            form[host_commit_plane_form(st, kind, scenario_axis)] += len(ids)
    return form


def count_planes(st: V3Static, scenario_axis: bool = False) -> dict:
    """The count planes a step built from these static facts carries,
    static per compiled program; a what-if batch reports it in its
    ``fleet_telemetry`` as ``summary()["count_planes"]``. ``domain_rows``:
    count groups kept at domain scale, rows of the ``[G, Dcap]`` planes a
    term can read; ``host_rows``: rows of the ``[H, N]`` host-scale planes
    (match counts, anti-affinity holders, preference weights: a topology
    of more than ``DMAX_COARSE`` domains, hostname in practice); ``dcap``:
    the domain planes' width; ``spread_rows`` and ``term_rows``: ``SP`` and
    ``KT`` of the unified term axis; ``host_read_positions``: how many of
    the ``KT`` positions read a host row in every slot
    (:func:`host_rows_at`; the others can never name a host-scale group);
    ``expand_positions``: how many can name a domain-scale group
    (``st.coarse_pos``), the rows of a slot's domain-row expansion where the
    step expands at all (:func:`value_positions`; the Borg traces' one
    position is such a one and their step, a single ``ScheduleAnyway`` zone
    spread scored in domain space, builds no node-space count value);
    ``host_commit``: host rows by the form of their wave-end commit
    (:func:`host_commit_form`; ``scenario_axis`` as the step was built)."""
    return {
        "domain_rows": int((~st.is_host).sum()),
        "host_rows": len(st.mc_h_ids) + len(st.anti_h_ids) + len(st.pref_h_ids),
        "dcap": int(st.Dcap),
        "spread_rows": int(st.SP),
        "term_rows": int(st.KT),
        "host_read_positions": int(st.host_pos.sum()),
        "expand_positions": int(st.coarse_pos.sum()),
        "host_commit": host_commit_form(st, scenario_axis),
    }


def host_row_table(st: V3Static) -> np.ndarray:
    """[KT, G] f32: the row of its plane kind's host plane that group g
    holds, for each position of the term axis (PAD where the group is
    coarse or no term reads it): ``g2mc_h`` under the match-count sections,
    ``g2anti_h`` under sym-anti, ``g2pref_h`` under sym-pref."""
    o0, o1, o2, o3, o4, o5, o6 = st.sections
    return np.stack(
        [st.g2mc_h] * (o4 - o0) + [st.g2anti_h] * (o5 - o4)
        + [st.g2pref_h] * (o6 - o5)
    ).astype(np.float32)


def host_rows_add(plane: jax.Array, coef: jax.Array, choice: jax.Array) -> jax.Array:
    """The ``[H, N]`` host plane with a wave's binds added: slot ``w`` adds
    ``coef[w]`` (``[W, H]`` f32, whole numbers) to every row at its node
    ``choice[w]`` (``[W]``; negative: no node). Elementwise in slot order on
    the plane in float32, cast back to the plane's dtype: a bf16 plane holds
    small whole numbers, exact through the add."""
    iota_n = jnp.arange(plane.shape[1])
    acc = plane.astype(jnp.float32)
    for w in range(choice.shape[0]):
        acc = acc + jnp.where(iota_n[None, :] == choice[w], coef[w][:, None], 0.0)
    return acc.astype(plane.dtype)


def host_named_row_add(
    plane: jax.Array, coef: jax.Array, choice: jax.Array, named: jax.Array
) -> jax.Array:
    """:func:`host_rows_add` where slot ``w`` names at most ONE row of the
    plane (``named`` ``[W, H]`` bool, from the pod's labels and terms alone):
    that row takes ``coef[w]``'s entry at the node ``choice[w]``, by a
    scatter-add at the row's index, and no other row is touched. A slot that
    names none adds zero to row 0. Where the step is mapped over a scenario
    axis the index is shared, and XLA reads the ``[S, 1, N]`` row, adds and
    writes it back in place in ONE fusion a slot."""
    iota_n = jnp.arange(plane.shape[1])
    row = jnp.argmax(named, axis=1)  # [W]
    for w in range(choice.shape[0]):
        c = jax.lax.dynamic_index_in_dim(coef[w], row[w], 0, keepdims=False)
        delta = jnp.where(iota_n == choice[w], c, 0.0).astype(plane.dtype)
        plane = plane.at[row[w]].add(delta)
    return plane


def value_positions(st: V3Static, all_positions: bool = False):
    """``(expansion positions, host positions)``: the positions of the term
    axis at which a slot of the step builds node-space count values, as two
    static lists. The first (``st.coarse_pos``) can name a domain-scale
    group: only there is a domain row non-zero, so only those rows are
    expanded to node space. The second (``st.host_pos``) can name a
    host-scale group: only there is a host row read and are its in-wave
    terms built. At every other position each addend is ``+0.0`` and the
    values it met are counts ``>= 0``, so leaving it out changes no bit.
    ``all_positions`` keeps every position in both lists, the form of
    before PR 38: label perturbations, whose corrections land on every
    position's expansion, and the single replay, whose one ``[KT, N]`` array
    from the wave-start contraction is cheaper at op latency than rows (a
    third of a replay, PERF.md §6 PR 38). The second list is empty where
    the trace has no host row."""
    every = list(range(st.KT))
    if all_positions:
        return every, every if st.has_host_rows else []
    return (
        [r for r in every if st.coarse_pos[r]],
        [r for r in every if st.host_pos[r]] if st.has_host_rows else [],
    )


def host_rows_at(st: V3Static, carry: DevState3, row_h_k: jax.Array, positions) -> dict:
    """{position: [N] f32}: the wave-start values of the host-scale count
    rows one slot's terms name (``row_h_k``: the slot's ``WavePre3.row_h``)
    at the ``positions`` of the term axis that can name one
    (:func:`value_positions`), zero where the slot's term names none. Each
    is read from the carried plane of its kind by row index: what a one-hot
    over the plane's rows selects, to the bit (one non-zero term; a bf16
    plane holds small whole numbers). Every other position reads nothing and
    is no key. The rows pass an ``optimization_barrier``: written out once,
    in float32, for the one fusion a slot that reads them; the read fused
    into that fusion loses 14% of a ``k8s5k-whatif256`` batch (PR 32 met the
    same; PERF.md §6 PR 38)."""
    o0, o1, o2, o3, o4, o5, o6 = st.sections
    rows = {}
    for r in positions:
        plane = (
            carry.mc_host if r < o4 else carry.anti_host if r < o5
            else carry.pref_host
        )
        if plane.shape[0]:
            row = jax.lax.dynamic_index_in_dim(
                plane, jnp.clip(row_h_k[r], 0), 0, keepdims=False
            )
            rows[r] = jnp.where(row_h_k[r] >= 0, row.astype(jnp.float32), 0.0)
        else:
            rows[r] = jnp.zeros(carry.used.shape[1:], jnp.float32)
    return jax.lax.optimization_barrier(rows)


def kind_masks(st: V3Static):
    """[KT] static 0/1 row masks by plane kind (mc/anti/pref sections)."""
    o0, o1, o2, o3, o4, o5, o6 = st.sections
    mc = np.zeros(st.KT, np.float32)
    mc[:o4] = 1.0
    anti = np.zeros(st.KT, np.float32)
    anti[o4:o5] = 1.0
    pref = np.zeros(st.KT, np.float32)
    pref[o5:o6] = 1.0
    return {
        "mc": jnp.asarray(mc),
        "anti": jnp.asarray(anti),
        "pref": jnp.asarray(pref),
    }
