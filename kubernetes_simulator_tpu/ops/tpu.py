"""Scheduling kernels — JAX device edition (SURVEY.md §3.5).

Same math as :mod:`.cpu`, re-expressed for XLA: everything is static-shape
jnp over ``[N]``/``[G, N]`` tensors, composable under ``jit``/``vmap``/
``lax.scan``. One pending pod (a "slot" row pytree) is evaluated against
all nodes at once; the mutable scheduling state is a small pytree updated
by masked elementwise adds so the whole replay runs as one compiled scan
on device.

Design notes (TPU-first):
- **No gathers or scatters anywhere in the hot loop.** Batched
  gather/scatter with per-scenario dynamic indices lowers to a serialized
  per-batch loop on TPU (~135 µs per op measured on v5e — 100× the cost of
  the math). Every dynamic-index access is instead expressed as a one-hot
  contraction (MXU matvec) or a masked elementwise update (VPU), which are
  effectively free at these shapes.
- Count-group state lives in **node space** ``[G, N]`` (the value each node
  *sees*: ``count[g, domain_of(g, n)]``), not domain space ``[G, D]``.
  Reads become row contractions; a bind updates every node in the bound
  node's domain via an equality mask — one fused elementwise op.
- masks stay bool, scores f32; per-pod term loops (tolerations, affinity
  terms, spread constraints) are python-unrolled over SMALL static widths.
- no data-dependent shapes: padded slots are neutralized with `where`, a
  `valid` flag multiplies every state update.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.encode import PAD, TOL_PAD, TOL_WILDCARD, EncodedCluster, EncodedPods
from ..models.core import Effect, Operator

MAX_NODE_SCORE = 100.0
NEG_INF = -jnp.inf

#: Policy-vector column order (round 9 policy tuner). A wave step built
#: with ``wvec`` (a traced f32 [len(POLICY_COLS)] vector) reads Score
#: weights from these columns instead of the static spec, so one compiled
#: program serves a whole population of scheduler policies — the vector
#: rides the scenario (vmap/mesh) axis and only its VALUES change between
#: candidates. The first five columns are plugin weights; ``fit_least`` is
#: the NodeResourcesFit scoring-strategy selector (> 0.5 → LeastAllocated,
#: else MostAllocated; ignored when the static base strategy is
#: RequestedToCapacityRatio, whose shape table has no cheap traced form).
POLICY_WEIGHT_COLS = (
    "NodeResourcesFit",
    "TaintToleration",
    "NodeAffinity",
    "InterPodAffinity",
    "PodTopologySpread",
)
IDX_FIT_LEAST = len(POLICY_WEIGHT_COLS)
POLICY_COLS = POLICY_WEIGHT_COLS + ("fit_least",)


def policy_weight_fns(spec, wvec):
    """(_w, _on) weight accessors for the score fold.

    Static mode (wvec is None): ``_w`` returns the np.float32 config weight
    and ``_on`` gates zero-weight rows OUT of the program (the historical
    behaviour). Traced mode: ``_w`` indexes wvec and ``_on`` keeps every
    spec-enabled row IN the program — a zero weight then contributes an
    exact ``0.0 * normalized`` term, and because each row's hi/lo extrema
    never depend on the weights, totals bit-match the static program at
    equal weight values."""
    if wvec is None:
        w = dict(spec.weights)

        def _w(name):
            return np.float32(w.get(name, 1.0))

        def _on(name):
            return w.get(name, 1.0) != 0

    else:

        def _w(name):
            return wvec[POLICY_WEIGHT_COLS.index(name)]

        def _on(name):
            return True

    return _w, _on
# One-hot contractions must accumulate exactly (integer-valued f32 counts).
_HI = jax.lax.Precision.HIGHEST


class DevCluster(NamedTuple):
    """Static per-scenario node-side tensors (device copies of
    EncodedCluster). Leading axes may gain a scenario dimension under vmap."""

    allocatable: jax.Array  # [N, R] f32
    node_label_key: jax.Array  # [N, L] i32
    node_label_kv: jax.Array  # [N, L] i32
    node_label_num: jax.Array  # [N, L] f32
    taint_key: jax.Array  # [N, TT] i32
    taint_kv: jax.Array  # [N, TT] i32
    taint_effect: jax.Array  # [N, TT] i32
    node_domain: jax.Array  # [T, N] i32
    num_domains: jax.Array  # [T] i32
    expr_key: jax.Array  # [E] i32
    expr_op: jax.Array  # [E] i32
    expr_vals: jax.Array  # [E, V] i32
    expr_num: jax.Array  # [E] f32
    group_topo: jax.Array  # [G] i32

    @classmethod
    def from_encoded(cls, ec: EncodedCluster) -> "DevCluster":
        return cls(
            allocatable=jnp.asarray(ec.allocatable),
            node_label_key=jnp.asarray(ec.node_label_key),
            node_label_kv=jnp.asarray(ec.node_label_kv),
            node_label_num=jnp.asarray(ec.node_label_num),
            taint_key=jnp.asarray(ec.taint_key),
            taint_kv=jnp.asarray(ec.taint_kv),
            taint_effect=jnp.asarray(ec.taint_effect),
            node_domain=jnp.asarray(ec.node_domain),
            num_domains=jnp.asarray(ec.num_domains),
            expr_key=jnp.asarray(ec.expr_key),
            expr_op=jnp.asarray(ec.expr_op),
            expr_vals=jnp.asarray(ec.expr_vals),
            expr_num=jnp.asarray(ec.expr_num),
            group_topo=jnp.asarray(ec.group_topo),
        )


class DevState(NamedTuple):
    """Mutable scheduling state carried through lax.scan (device twin of
    models.state.SchedState, **node space**): ``match_count[g, n]`` is the
    number of placed pods matching group g in node n's domain under g's
    topology key (0 where the node has no domain). ``match_total[g]`` is the
    cluster-wide count (needed for the bootstrap self-match rule — a plain
    sum over node space would overcount domains with many nodes)."""

    used: jax.Array  # [N, R] f32
    match_count: jax.Array  # [G, N] f32
    anti_active: jax.Array  # [G, N] f32
    pref_wsum: jax.Array  # [G, N] f32
    match_total: jax.Array  # [G] f32

    @classmethod
    def init(cls, ec: EncodedCluster) -> "DevState":
        G = max(ec.num_groups, 1)
        N = ec.num_nodes
        return cls(
            used=jnp.zeros((N, ec.num_resources), jnp.float32),
            match_count=jnp.zeros((G, N), jnp.float32),
            anti_active=jnp.zeros((G, N), jnp.float32),
            pref_wsum=jnp.zeros((G, N), jnp.float32),
            match_total=jnp.zeros((G,), jnp.float32),
        )


def domain_to_node_space(arr_gd: np.ndarray, gdom: np.ndarray) -> np.ndarray:
    """Host: [G, D] domain-space counts → [G, N] node-space (0 where the
    node has no domain under that group's topology key)."""
    safe = np.clip(gdom, 0, None)
    out = np.take_along_axis(arr_gd, safe, axis=1).astype(np.float32)
    return np.where(gdom >= 0, out, 0.0)


def node_space_to_domain(arr_gn: np.ndarray, gdom: np.ndarray, D: int) -> np.ndarray:
    """Host: inverse of :func:`domain_to_node_space` (every domain has ≥1
    node by construction; values agree across a domain's nodes)."""
    G, N = arr_gn.shape
    out = np.zeros((G, D), np.float32)
    valid = gdom >= 0
    gi = np.broadcast_to(np.arange(G)[:, None], (G, N))
    out[gi[valid], gdom[valid]] = arr_gn[valid]
    return out


class PodSlot(NamedTuple):
    """One pending pod's row pytree (scan element)."""

    pod_id: jax.Array  # i32 scalar (PAD = padding slot)
    valid: jax.Array  # bool scalar
    req: jax.Array  # [R] f32
    tol_key: jax.Array  # [TO] i32
    tol_kv: jax.Array  # [TO] i32
    tol_effect: jax.Array  # [TO] i32
    na_req: jax.Array  # [TR, TE] i32
    na_has_req: jax.Array  # bool
    na_pref: jax.Array  # [TP, TE] i32
    na_pref_w: jax.Array  # [TP] f32
    aff_req: jax.Array  # [AR] i32
    anti_req: jax.Array  # [AA] i32
    pref_aff: jax.Array  # [PA] i32
    pref_aff_w: jax.Array  # [PA] f32
    spread_g: jax.Array  # [SP] i32
    spread_skew: jax.Array  # [SP] i32
    spread_dns: jax.Array  # [SP] bool
    pmg: jax.Array  # [G] bool
    group: jax.Array  # i32 scalar (wave-local gang handling)


class SlotSource(NamedTuple):
    """All per-pod slot arrays resident ON DEVICE, uploaded once per
    engine. Per-chunk slot batches are then gathered inside jit from these
    (gather_slots_device) — only the [C, W] index array crosses the host
    boundary per chunk, instead of a host-side numpy gather plus an H2D
    of ~18 arrays."""

    requests: jax.Array
    tol_key: jax.Array
    tol_kv: jax.Array
    tol_effect: jax.Array
    na_req: jax.Array
    na_has_req: jax.Array
    na_pref: jax.Array
    na_pref_w: jax.Array
    aff_req: jax.Array
    anti_req: jax.Array
    pref_aff: jax.Array
    pref_aff_w: jax.Array
    spread_g: jax.Array
    spread_skew: jax.Array
    spread_dns: jax.Array
    pmg: jax.Array
    group_id: jax.Array

    @classmethod
    def build(cls, ep: EncodedPods) -> "SlotSource":
        return cls(
            requests=jnp.asarray(ep.requests),
            tol_key=jnp.asarray(ep.tol_key),
            tol_kv=jnp.asarray(ep.tol_kv),
            tol_effect=jnp.asarray(ep.tol_effect),
            na_req=jnp.asarray(ep.na_req),
            na_has_req=jnp.asarray(ep.na_has_req),
            na_pref=jnp.asarray(ep.na_pref),
            na_pref_w=jnp.asarray(ep.na_pref_w),
            aff_req=jnp.asarray(ep.aff_req),
            anti_req=jnp.asarray(ep.anti_req),
            pref_aff=jnp.asarray(ep.pref_aff),
            pref_aff_w=jnp.asarray(ep.pref_aff_w),
            spread_g=jnp.asarray(ep.spread_g),
            spread_skew=jnp.asarray(ep.spread_skew),
            spread_dns=jnp.asarray(ep.spread_dns),
            pmg=jnp.asarray(ep.pod_matches_group),
            group_id=jnp.asarray(ep.group_id.astype(np.int32)),
        )

    @classmethod
    def page(cls, ep: EncodedPods, flat: np.ndarray) -> "SlotSource":
        """One PAGE of the slot source (round 14 paged pod waves): the
        rows at flat pod ids ``flat`` (PAD → neutral row-0 copy; the
        page-local index array keeps those slots invalid), host-gathered
        and uploaded as a fixed-shape SlotSource so the compiled chunk
        program is reused page after page. The full ``build`` keeps every
        pod resident; a page holds chunk_waves × wave_width rows."""
        safe = np.clip(flat, 0, None)
        take = lambda a: jnp.asarray(a[safe])
        return cls(
            requests=take(ep.requests),
            tol_key=take(ep.tol_key),
            tol_kv=take(ep.tol_kv),
            tol_effect=take(ep.tol_effect),
            na_req=take(ep.na_req),
            na_has_req=take(ep.na_has_req),
            na_pref=take(ep.na_pref),
            na_pref_w=take(ep.na_pref_w),
            aff_req=take(ep.aff_req),
            anti_req=take(ep.anti_req),
            pref_aff=take(ep.pref_aff),
            pref_aff_w=take(ep.pref_aff_w),
            spread_g=take(ep.spread_g),
            spread_skew=take(ep.spread_skew),
            spread_dns=take(ep.spread_dns),
            pmg=take(ep.pod_matches_group),
            group_id=jnp.asarray(
                np.where(flat >= 0, ep.group_id[safe], PAD).astype(np.int32)
            ),
        )


@jax.jit
def gather_slots_device(src: SlotSource, idx: jax.Array) -> PodSlot:
    """jnp twin of gather_slots: row-gather on device (value-identical)."""
    safe = jnp.clip(idx, 0, None)
    take = lambda a: a[safe]
    return PodSlot(
        pod_id=idx.astype(jnp.int32),
        valid=idx >= 0,
        req=take(src.requests),
        tol_key=take(src.tol_key),
        tol_kv=take(src.tol_kv),
        tol_effect=take(src.tol_effect),
        na_req=take(src.na_req),
        na_has_req=take(src.na_has_req),
        na_pref=take(src.na_pref),
        na_pref_w=take(src.na_pref_w),
        aff_req=take(src.aff_req),
        anti_req=take(src.anti_req),
        pref_aff=take(src.pref_aff),
        pref_aff_w=take(src.pref_aff_w),
        spread_g=take(src.spread_g),
        spread_skew=take(src.spread_skew),
        spread_dns=take(src.spread_dns),
        pmg=take(src.pmg),
        group=jnp.where(idx >= 0, src.group_id[safe], PAD).astype(jnp.int32),
    )


def gather_slots(ep: EncodedPods, idx: np.ndarray) -> PodSlot:
    """Host-side gather of pod rows at ``idx`` (any leading shape); PAD ids
    become invalid slots."""
    safe = np.clip(idx, 0, None)
    take = lambda a: jnp.asarray(a[safe])
    return PodSlot(
        pod_id=jnp.asarray(idx.astype(np.int32)),
        valid=jnp.asarray(idx >= 0),
        req=take(ep.requests),
        tol_key=take(ep.tol_key),
        tol_kv=take(ep.tol_kv),
        tol_effect=take(ep.tol_effect),
        na_req=take(ep.na_req),
        na_has_req=take(ep.na_has_req),
        na_pref=take(ep.na_pref),
        na_pref_w=take(ep.na_pref_w),
        aff_req=take(ep.aff_req),
        anti_req=take(ep.anti_req),
        pref_aff=take(ep.pref_aff),
        pref_aff_w=take(ep.pref_aff_w),
        spread_g=take(ep.spread_g),
        spread_skew=take(ep.spread_skew),
        spread_dns=take(ep.spread_dns),
        pmg=take(ep.pod_matches_group),
        group=jnp.asarray(np.where(idx >= 0, ep.group_id[safe], PAD).astype(np.int32)),
    )


# ---------------------------------------------------------------------------
# Per-replay derived tensors (computed INSIDE jit so scenario perturbations
# to labels/taints/capacity flow through without host re-encode)
# ---------------------------------------------------------------------------

def expr_match_matrix(dc: DevCluster) -> jax.Array:
    """[N, E] bool — jnp twin of ops.cpu.expr_match_matrix."""
    nk = dc.node_label_key[:, :, None]  # [N, L, 1]
    nv = dc.node_label_kv[:, :, None]
    ek = dc.expr_key[None, None, :]
    key_present = jnp.any((nk == ek) & (nk != PAD), axis=1)  # [N, E]
    in_set = jnp.any(
        (nv[:, :, :, None] == dc.expr_vals[None, None, :, :]) & (nv[:, :, :, None] != PAD),
        axis=(1, 3),
    )
    num = dc.node_label_num[:, :, None]
    gt = jnp.any((nk == ek) & (num > dc.expr_num[None, None, :]), axis=1)
    lt = jnp.any((nk == ek) & (num < dc.expr_num[None, None, :]), axis=1)
    op = dc.expr_op[None, :]
    return (
        ((op == Operator.IN) & key_present & in_set)
        | ((op == Operator.NOT_IN) & ~(key_present & in_set))
        | ((op == Operator.EXISTS) & key_present)
        | ((op == Operator.DOES_NOT_EXIST) & ~key_present)
        | ((op == Operator.GT) & gt)
        | ((op == Operator.LT) & lt)
    )


def group_dom_per_node(dc: DevCluster) -> jax.Array:
    """[G, N] f32 — domain of each node under each count-group's topology
    key (PAD = -1 where none). f32 so node one-hots can contract with it on
    the MXU; domain ids ≤ N are exact in f32."""
    gt = jnp.clip(dc.group_topo, 0, None)
    dom = dc.node_domain[gt]  # [G, N] (static indices — fine)
    return jnp.where(dc.group_topo[:, None] >= 0, dom, PAD).astype(jnp.float32)


class Derived(NamedTuple):
    M: jax.Array  # [N, E] expr match
    gdom_f: jax.Array  # [G, N] f32 (PAD = -1)

    @classmethod
    def build(cls, dc: DevCluster) -> "Derived":
        return cls(expr_match_matrix(dc), group_dom_per_node(dc))


def _term_onehot(gs: jax.Array, G: int) -> jax.Array:
    """[..., A, G] f32 — one-hot rows for term group ids (zero row for
    PAD). Broadcasts over any leading axes (e.g. a wave axis)."""
    return ((gs[..., None] == jnp.arange(G)) & (gs[..., None] >= 0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Filters
# ---------------------------------------------------------------------------

def fit_mask(dc: DevCluster, st: DevState, s: PodSlot) -> jax.Array:
    return jnp.all(st.used + s.req[None, :] <= dc.allocatable + 1e-6, axis=1)


def taint_untolerated(dc: DevCluster, s: PodSlot, effects) -> jax.Array:
    t_eff = dc.taint_effect  # [N, TT]
    active = (dc.taint_key != PAD)
    eff_match = jnp.zeros_like(active)
    for e in effects:
        eff_match = eff_match | (t_eff == e)
    active = active & eff_match
    tk = s.tol_key  # [TO]
    valid_tol = tk != TOL_PAD
    key_ok = (tk[None, None, :] == TOL_WILDCARD) | (tk[None, None, :] == dc.taint_key[:, :, None])
    val_ok = (s.tol_kv[None, None, :] == PAD) | (s.tol_kv[None, None, :] == dc.taint_kv[:, :, None])
    eff_ok = (s.tol_effect[None, None, :] == 0) | (s.tol_effect[None, None, :] == t_eff[:, :, None])
    tolerated = jnp.any(key_ok & val_ok & eff_ok & valid_tol[None, None, :], axis=2)
    return active & ~tolerated


def taint_mask(dc: DevCluster, s: PodSlot) -> jax.Array:
    bad = taint_untolerated(dc, s, (int(Effect.NO_SCHEDULE), int(Effect.NO_EXECUTE)))
    return ~jnp.any(bad, axis=1)


def taint_prefer_count(dc: DevCluster, s: PodSlot) -> jax.Array:
    bad = taint_untolerated(dc, s, (int(Effect.PREFER_NO_SCHEDULE),))
    return jnp.sum(bad, axis=1).astype(jnp.float32)


def _terms_match(M: jax.Array, terms: jax.Array) -> jax.Array:
    """[N] — OR over terms of AND over exprs (PAD exprs auto-true; a term is
    valid iff slot 0 is a real expr)."""
    valid_term = terms[:, 0] >= 0  # [T]
    safe = jnp.clip(terms, 0, None)
    per_expr = M[:, safe] | (terms[None, :, :] < 0)  # [N, T, E]
    per_term = jnp.all(per_expr, axis=2) & valid_term[None, :]
    return jnp.any(per_term, axis=1)


def node_affinity_mask(d: Derived, s: PodSlot) -> jax.Array:
    return jnp.where(s.na_has_req, _terms_match(d.M, s.na_req), True)


def node_affinity_score(d: Derived, s: PodSlot) -> jax.Array:
    terms = s.na_pref  # [TP, TE]
    valid_term = terms[:, 0] >= 0
    safe = jnp.clip(terms, 0, None)
    per_expr = d.M[:, safe] | (terms[None, :, :] < 0)
    per_term = jnp.all(per_expr, axis=2) & valid_term[None, :]
    return jnp.sum(per_term * s.na_pref_w[None, :], axis=1).astype(jnp.float32)


def _term_rows(st_counts: jax.Array, oh: jax.Array) -> jax.Array:
    """[A, N] — node-space count rows for A term groups (one-hot matmul —
    exact: each output is a single selected element)."""
    return jnp.einsum("ag,gn->an", oh, st_counts, precision=_HI)


def interpod_filter_mask(d: Derived, st: DevState, s: PodSlot) -> jax.Array:
    """Required (anti-)affinity + the SYMMETRIC existing-pods'-anti check,
    all as one-hot contractions over node-space counts — no gathers."""
    G = st.match_count.shape[0]
    N = d.gdom_f.shape[1]
    pmg_f = s.pmg.astype(jnp.float32)
    ok = jnp.ones(N, dtype=bool)
    gvalid_all = d.gdom_f >= 0  # [G, N]

    ohA = _term_onehot(s.aff_req, G)  # [A, G]
    if ohA.shape[0]:
        cnt = _term_rows(st.match_count, ohA)  # [A, N]
        gvalid = jnp.einsum("ag,gn->an", ohA, gvalid_all.astype(jnp.float32), precision=_HI) > 0.5
        total = jnp.einsum("ag,g->a", ohA, st.match_total, precision=_HI)  # [A]
        selfm = jnp.einsum("ag,g->a", ohA, pmg_f, precision=_HI) > 0.5  # [A]
        boot = (total == 0) & selfm
        term_ok = (cnt >= 1) & gvalid
        ok = ok & jnp.all(
            jnp.where((s.aff_req >= 0)[:, None], term_ok | boot[:, None], True), axis=0
        )

    ohB = _term_onehot(s.anti_req, G)
    if ohB.shape[0]:
        cntb = _term_rows(st.match_count, ohB)
        gvalidb = jnp.einsum("ag,gn->an", ohB, gvalid_all.astype(jnp.float32), precision=_HI) > 0.5
        viol = (cntb >= 1) & gvalidb
        ok = ok & jnp.all(jnp.where((s.anti_req >= 0)[:, None], ~viol, True), axis=0)

    # Symmetric: a node is blocked if any placed pod with a required anti
    # term g sits in its domain and this pod matches g.
    blocked = (
        jnp.einsum("g,gn->n", pmg_f, (st.anti_active > 0).astype(jnp.float32), precision=_HI)
        > 0.5
    )
    return ok & ~blocked


def interpod_score(d: Derived, st: DevState, s: PodSlot, has_symmetric_pref: bool = True) -> jax.Array:
    G = st.match_count.shape[0]
    N = d.gdom_f.shape[1]
    raw = jnp.zeros(N, dtype=jnp.float32)
    ohP = _term_onehot(s.pref_aff, G)
    if ohP.shape[0]:
        cnt = _term_rows(st.match_count, ohP)  # [P, N]
        w = jnp.where(s.pref_aff >= 0, s.pref_aff_w, 0.0)
        raw = raw + jnp.einsum("p,pn->n", w, cnt, precision=_HI)
    if has_symmetric_pref:
        # pref_wsum is already node-space — the old [G, N] sweep is now a
        # single matvec.
        raw = raw + jnp.einsum(
            "g,gn->n", s.pmg.astype(jnp.float32), st.pref_wsum, precision=_HI
        )
    return raw


def spread_filter_mask(d: Derived, st: DevState, s: PodSlot) -> jax.Array:
    G = st.match_count.shape[0]
    N = d.gdom_f.shape[1]
    ohS = _term_onehot(s.spread_g, G)  # [A, G]
    if not ohS.shape[0]:
        return jnp.ones(N, dtype=bool)
    cnt = _term_rows(st.match_count, ohS)  # [A, N]
    gvalid = jnp.einsum("ag,gn->an", ohS, (d.gdom_f >= 0).astype(jnp.float32), precision=_HI) > 0.5
    # min over valid domains == min over nodes that have a domain (every
    # domain has ≥1 node by construction).
    minv = jnp.min(jnp.where(gvalid, cnt, jnp.inf), axis=1)  # [A]
    has_domains = jnp.isfinite(minv)
    selfm = jnp.einsum("ag,g->a", ohS, s.pmg.astype(jnp.float32), precision=_HI)
    c_ok = (
        gvalid
        & has_domains[:, None]
        & (cnt + selfm[:, None] - jnp.where(has_domains, minv, 0.0)[:, None]
           <= s.spread_skew[:, None])
    )
    return jnp.all(jnp.where(((s.spread_g >= 0) & s.spread_dns)[:, None], c_ok, True), axis=0)


def spread_score_upstream(d: Derived, st: DevState, s: PodSlot, w_g) -> tuple:
    """Upstream podtopologyspread raw score (mirrors ops.cpu.spread_score):
    ``floor(Σ_scored cnt·log(size+2) + (maxSkew−1))`` per node over the
    ScheduleAnyway constraints, plus the ignored mask (node missing a
    scored key) and the dynamic any-scored flag (PreScore Skip). ``w_g`` is
    the static [G] weight table."""
    G = st.match_count.shape[0]
    N = d.gdom_f.shape[1]
    ohS = _term_onehot(s.spread_g, G)
    if not ohS.shape[0]:
        return (
            jnp.zeros(N, jnp.float32),
            jnp.zeros(N, bool),
            jnp.zeros((), bool),
        )
    cnt = _term_rows(st.match_count, ohS)  # [A, N]
    gvalid = (
        jnp.einsum("ag,gn->an", ohS, (d.gdom_f >= 0).astype(jnp.float32), precision=_HI)
        > 0.5
    )
    scored = (s.spread_g >= 0) & ~s.spread_dns  # [A]
    wrow = jnp.einsum("ag,g->a", ohS, jnp.asarray(w_g, jnp.float32), precision=_HI)
    raw = jnp.zeros(N, jnp.float32)
    ignored = jnp.zeros(N, bool)
    for i in range(ohS.shape[0]):
        contrib = cnt[i] * wrow[i] + (s.spread_skew[i].astype(jnp.float32) - 1.0)
        raw = raw + jnp.where(scored[i], contrib, 0.0)
        ignored = ignored | (scored[i] & ~gvalid[i])
    # Upstream int64(math.Round(score)): floor(x+0.5), non-negative x.
    return jnp.floor(raw + 0.5), ignored, jnp.any(scored)


def floor_div_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """``floor(a / b)`` for integer-valued f32 ``a ≥ 0`` and ``b > 0`` with
    ``a + b < 2²⁴``, EXACT on a backend whose f32 division is not correctly
    rounded. A TPU's is not: ``floor(6100 / 61)`` reads 99 there, and
    ``floor(100·57 / 95)`` 59 (chip run, PR 31: 5 of the 99 node-affinity
    weights and 6,411 of 7.4M spread triples part from the integer
    division), which moved a ScheduleAnyway pod's zone scores by a point
    and 0.6% of config 2's picks. The quotient is corrected by its
    remainder, exact under the bound (``q·b ≤ a + b``); where the division
    is correctly rounded (the CPU backend, numpy) no correction ever
    fires, so the host and device paths stay bit-identical."""
    return _fix_quotient(jnp.floor(a / b), a, b)


def _fix_quotient(q: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """``q`` moved by one where the remainder ``a − q·b`` says it is off by
    one (the reach of a division wrong in its last bits)."""
    r = a - q * b
    return q + (r >= b).astype(q.dtype) - (r < 0).astype(q.dtype)


def spread_norm_from_extrema(raw, ignored, hi, lo, any_scored, f32ok=False) -> jax.Array:
    """The normalize half of :func:`spread_upstream_normalize`, with the
    extrema (over feasible & ~ignored nodes, ±inf-masked reductions)
    supplied by the caller — so they can ride a shared stacked reduce.

    ``f32ok`` (static): when the trace bound guarantees raw ≤ 83886,
    ``floor((100·(hi+lo−s)) / hi)`` runs in f32 (numerator ≤ 200·83886 <
    2²⁴ is exactly representable; :func:`floor_div_f32` makes the quotient
    the integer division's on any backend), so the slow int32 floordiv (no
    hardware int div on TPU) is skipped."""
    has = hi > -jnp.inf
    if f32ok:
        hi_f = jnp.where(has, hi, 0.0)
        lo_f = jnp.where(has, lo, 0.0)
        pos = hi_f > 0
        vals = floor_div_f32(
            np.float32(MAX_NODE_SCORE) * (hi_f + lo_f - raw),
            jnp.where(pos, hi_f, 1.0),
        )
        out = jnp.where(pos, vals, np.float32(MAX_NODE_SCORE))
        return jnp.where(ignored | ~has | ~any_scored, 0.0, out)
    hi_i = jnp.where(has, hi, 0.0).astype(jnp.int32)
    lo_i = jnp.where(has, lo, 0.0).astype(jnp.int32)
    vals = (np.int32(MAX_NODE_SCORE) * (hi_i + lo_i - raw.astype(jnp.int32))) // jnp.where(
        hi_i > 0, hi_i, 1
    )
    out = jnp.where(hi_i > 0, vals.astype(jnp.float32), np.float32(MAX_NODE_SCORE))
    return jnp.where(ignored | ~has | ~any_scored, 0.0, out)


def spread_upstream_normalize(raw, ignored, feasible, any_scored, f32ok=False) -> jax.Array:
    """Upstream two-pass NormalizeScore (mirrors ops.cpu.spread_normalize
    bit-for-bit): int32-exact ``100·(max+min−s) // max`` with extrema over
    non-ignored feasible nodes; ignored → 0; max == 0 → 100; no scored
    constraints → all 0."""
    okn = feasible & ~ignored
    hi = jnp.max(jnp.where(okn, raw, -jnp.inf))
    lo = jnp.min(jnp.where(okn, raw, jnp.inf))
    return spread_norm_from_extrema(raw, ignored, hi, lo, any_scored, f32ok)


# ---------------------------------------------------------------------------
# Resource scores
# ---------------------------------------------------------------------------

# Scores are INTEGER-valued f32, floored through single-op chains — nothing
# XLA can FMA-fuse — so device scores are bit-identical to ops.cpu and
# argmax ties break the same way (SURVEY.md §7 hard part #6). Mirrors
# upstream's int64 node scores.


def _int_resource_score(frac: jax.Array, weights) -> jax.Array:
    s = jnp.floor(frac * np.float32(MAX_NODE_SCORE))  # [N, R], integral
    acc = jnp.zeros(frac.shape[0], dtype=jnp.float32)
    wsum = 0.0
    for r in range(frac.shape[1]):
        w = float(weights[r])
        if w != 0:
            acc = acc + s[:, r] * np.float32(w)  # exact: small ints
            wsum += w
    if wsum == 0:
        return acc
    return jnp.floor(acc / np.float32(wsum))


def least_allocated_score_from_used(dc: DevCluster, used: jax.Array, s: PodSlot, weights) -> jax.Array:
    alloc = dc.allocatable
    denom = jnp.where(alloc > 0, alloc, 1.0)
    frac = jnp.where(alloc > 0, (alloc - used - s.req[None, :]) / denom, 0.0)
    frac = jnp.clip(frac, 0.0, 1.0)
    return _int_resource_score(frac, weights)


def least_allocated_score(dc: DevCluster, st: DevState, s: PodSlot, weights) -> jax.Array:
    return least_allocated_score_from_used(dc, st.used, s, weights)


def most_allocated_score_from_used(dc: DevCluster, used: jax.Array, s: PodSlot, weights) -> jax.Array:
    alloc = dc.allocatable
    denom = jnp.where(alloc > 0, alloc, 1.0)
    frac = jnp.where(alloc > 0, (used + s.req[None, :]) / denom, 0.0)
    frac = jnp.clip(frac, 0.0, 1.0)
    return _int_resource_score(frac, weights)


def most_allocated_score(dc: DevCluster, st: DevState, s: PodSlot, weights) -> jax.Array:
    return most_allocated_score_from_used(dc, st.used, s, weights)


def piecewise_interp_int(util: jax.Array, xs, ys) -> jax.Array:
    """Mirror of ops.cpu.piecewise_interp_int (seg = y0 + floor(t·Δy))."""
    out = jnp.full(util.shape, np.float32(ys[-1]), dtype=jnp.float32)
    for i in range(len(xs) - 2, -1, -1):
        x0, x1 = np.float32(xs[i]), np.float32(xs[i + 1])
        y0, y1 = np.float32(ys[i]), np.float32(ys[i + 1])
        t = (util.astype(jnp.float32) - x0) * (np.float32(1.0) / (x1 - x0))
        seg = y0 + jnp.floor(t * (y1 - y0))
        out = jnp.where(util <= x1, seg, out)
    return jnp.where(util <= np.float32(xs[0]), np.float32(ys[0]), out).astype(jnp.float32)


def requested_to_capacity_ratio_score(
    dc: DevCluster, st: DevState, s: PodSlot, weights, shape_x, shape_y
) -> jax.Array:
    return requested_to_capacity_ratio_score_from_used(
        dc, st.used, s, weights, shape_x, shape_y
    )


def requested_to_capacity_ratio_score_from_used(
    dc: DevCluster, used: jax.Array, s: PodSlot, weights, shape_x, shape_y
) -> jax.Array:
    alloc = dc.allocatable
    denom = jnp.where(alloc > 0, alloc, 1.0)
    frac = jnp.where(alloc > 0, (used + s.req[None, :]) / denom, 0.0)
    util = jnp.floor(jnp.clip(frac, 0.0, 1.0) * np.float32(100.0))
    score_r = piecewise_interp_int(util, list(shape_x), list(shape_y))
    acc = jnp.zeros(alloc.shape[0], dtype=jnp.float32)
    wsum = 0.0
    for r in range(score_r.shape[1]):
        w = float(weights[r])
        if w != 0:
            acc = acc + score_r[:, r] * np.float32(w)
            wsum += w
    if wsum == 0:
        return acc
    return jnp.floor(acc / np.float32(wsum))


# ---------------------------------------------------------------------------
# Normalization + selection + state update
# ---------------------------------------------------------------------------

def _normalize_row(raw, lo, hi, any_f, minmax: bool, reverse: bool) -> jax.Array:
    """The one copy of the normalize arithmetic (mirrors ops.cpu). Callers
    supply the masked extrema; ``minmax`` picks min-max vs max-only form.
    For the max-only form, a −inf-filled ``hi`` is equivalent to the CPU
    path's 0-filled max because raws are non-negative."""
    if minmax:
        span = hi - lo
        ok = any_f & (span > 0)
        out = jnp.floor(
            (raw - jnp.where(ok, lo, 0.0))
            * (np.float32(MAX_NODE_SCORE) / jnp.where(ok, span, 1.0))
        )
        out = jnp.where(ok, out, 0.0)
        if reverse:
            out = jnp.where(ok, np.float32(MAX_NODE_SCORE) - out, 0.0)
    else:
        # Raws are small non-negative integers (counts, summed int weights):
        # the exact floordiv, not the backend's division alone.
        pos = hi > 0
        out = floor_div_f32(
            raw * np.float32(MAX_NODE_SCORE), jnp.where(pos, hi, 1.0)
        )
        out = jnp.where(pos, out, 0.0)
        if reverse:
            out = jnp.where(
                pos, np.float32(MAX_NODE_SCORE) - out, np.float32(MAX_NODE_SCORE)
            )
    return out.astype(jnp.float32)


def normalize_max(raw: jax.Array, feasible: jax.Array, reverse: bool = False) -> jax.Array:
    """Mirror of ops.cpu.normalize_max: floor(raw·100/max), integer scores."""
    mx = jnp.max(jnp.where(feasible, raw, 0.0))
    return _normalize_row(raw, None, mx, None, False, reverse)


def normalize_min_max(raw: jax.Array, feasible: jax.Array, reverse: bool = False) -> jax.Array:
    """Mirror of ops.cpu.normalize_min_max: floor((raw−lo)·(100/span))."""
    any_f = jnp.any(feasible)
    lo = jnp.min(jnp.where(feasible, raw, jnp.inf)).astype(jnp.float32)
    hi = jnp.max(jnp.where(feasible, raw, -jnp.inf)).astype(jnp.float32)
    return _normalize_row(raw, lo, hi, any_f, True, reverse)


def select_node(scores: jax.Array, feasible: jax.Array):
    """(choice i32, placed bool) — lowest-index argmax tie-break, matching
    numpy argmax (SURVEY.md §7 hard part #6).

    ONE variadic reduce computes (max, argmax-with-min-index-ties) — and
    ``placed`` falls out as max > −inf (a node is feasible iff its masked
    score is finite), instead of a second full reduce_or pass over
    ``feasible`` (profile round 3: the separate any() was 19% of north-star
    device time)."""
    masked = jnp.where(feasible, scores, NEG_INF)
    iota = jax.lax.broadcasted_iota(jnp.int32, masked.shape, masked.ndim - 1)

    def comb(a, b):
        av, ai = a
        bv, bi = b
        better = (bv > av) | ((bv == av) & (bi < ai))
        return jnp.where(better, bv, av), jnp.where(better, bi, ai)

    mx, choice = jax.lax.reduce(
        (masked, iota),
        (np.float32(-np.inf), np.int32(np.iinfo(np.int32).max)),
        comb,
        dimensions=(masked.ndim - 1,),
    )
    placed = mx > NEG_INF
    return jnp.where(placed, choice.astype(jnp.int32), PAD), placed


def masked_argmin(scores: jax.Array, mask: jax.Array):
    """(choice i32, any bool) — lowest-index argmin over the masked
    entries, in ONE variadic reduce (the ``select_node`` comparator with
    the sign flipped). Selection is identical to
    ``argmax(where(mask, -scores, -inf))`` + a separate ``any(mask)``
    (numpy first-occurrence tie-break) but pays one pass instead of two —
    the preempt-select's victim-node rank is the hot consumer (round 10
    fused tier-preemption). ``choice`` is PAD when nothing is masked
    in."""
    masked = jnp.where(mask, -scores, NEG_INF)
    iota = jax.lax.broadcasted_iota(jnp.int32, masked.shape, masked.ndim - 1)

    def comb(a, b):
        av, ai = a
        bv, bi = b
        better = (bv > av) | ((bv == av) & (bi < ai))
        return jnp.where(better, bv, av), jnp.where(better, bi, ai)

    mx, choice = jax.lax.reduce(
        (masked, iota),
        (np.float32(-np.inf), np.int32(np.iinfo(np.int32).max)),
        comb,
        dimensions=(masked.ndim - 1,),
    )
    ok = mx > NEG_INF
    return jnp.where(ok, choice.astype(jnp.int32), PAD), ok


def first_reject_counts(masks, failed) -> jax.Array:
    """[K] i32 — per-plugin first-reject node counts for one slot, the
    device form of the kube "0/N nodes available" attribution
    (ops.cpu.first_reject_update is the host edition). ``masks`` is the
    ordered list of per-plugin [N] bool masks from the fused eval;
    ``failed`` gates the whole vector (a placed or PAD slot charges
    nothing). Only fully-failed attempts are ever counted, so the K
    entries always sum to N per counted slot — matching the event
    engine's episode semantics at W=1/C=1."""
    so_far = jnp.ones_like(masks[0])
    outs = []
    for m in masks:
        outs.append(jnp.sum(so_far & ~m).astype(jnp.int32))
        so_far = so_far & m
    return jnp.where(failed, jnp.stack(outs), 0)


# Packed-select bounds: scores are packed as total·2^14 + (2^14−1−n), which
# is exact in f32 iff every packed value is an integer < 2^24.
PACK_SHIFT = 16384.0  # 2^14
PACK_MAX_TOTAL = 1023  # (1023·2^14 + 16383) < 2^24
PACK_MAX_NODES = 16384


def _pack(scores: jax.Array, feasible: jax.Array) -> jax.Array:
    """``total·2^14 + (2^14−1−n)`` at feasible nodes, −inf elsewhere."""
    N = scores.shape[-1]
    iota_f = jnp.arange(N, dtype=jnp.float32)
    return jnp.where(
        feasible,
        scores * np.float32(PACK_SHIFT)
        + (np.float32(PACK_SHIFT - 1.0) - iota_f),
        NEG_INF,
    )


def _unpack(mx: jax.Array):
    """(choice i32, placed bool) from the max of packed values."""
    placed = mx > NEG_INF
    safe = jnp.where(placed, mx, 0.0)
    t = jnp.floor(safe / np.float32(PACK_SHIFT))  # power-of-2 divide: exact
    idx = np.float32(PACK_SHIFT - 1.0) - (safe - t * np.float32(PACK_SHIFT))
    return jnp.where(placed, idx.astype(jnp.int32), PAD), placed


def select_node_packed(scores: jax.Array, feasible: jax.Array):
    """select_node via a single native max reduce: pack (total, node) into
    one f32 so argmax-with-min-index-ties becomes max over
    ``total·2^14 + (2^14−1−n)``, decoded from the scalar afterwards.

    EXACT only under the caller-checked static gate: integer non-negative
    plugin weights with Σw·100 ≤ PACK_MAX_TOTAL (every normalized plugin
    score is an integer in [0, 100], so total is an integer), and
    N ≤ PACK_MAX_NODES — then every packed value is an integer < 2^24,
    exactly representable in f32, and max/decode are bit-exact. A native
    single-output max reduce is ~2× the throughput of the variadic
    (value, index) comparator reduce on TPU."""
    return _unpack(jnp.max(_pack(scores, feasible), axis=-1))


def zone_packed_max(
    scores: jax.Array, feasible: jax.Array, seg_mode: str, seg_D: int,
    scenario_axis: bool = False,
) -> jax.Array:
    """[seg_D] — the best packed node of each zone (−inf: the zone has no
    feasible node), in ONE pass over the node axis. ``scores`` holds the
    part of the total that varies inside a zone; what is constant in a
    zone is added to these seg_D values afterwards
    (:func:`select_node_zone_packed`), so one reduce tells which zones are
    feasible AND, once the zone scores are known, which node wins.

    Zones follow V3Static's structured layouts: ``"block"`` (zone =
    n // (N / seg_D)) reduces the minor axis of the ``[seg_D, N / seg_D]``
    view; ``"stride"`` (zone = n % seg_D, the benchmark's clusters) the
    major axis of a view chosen for where the TPU compiler puts the nodes
    (``scenario_axis``: the caller maps this over a scenario axis):

    - one scenario's ``[N]`` operands lie along the lanes, 1,024 nodes a
      register. N is padded to whole 128-lane rows; the max over the rows
      is elementwise into 128 lanes, and the lanes fold 128 → seg_D on 128
      values (128 % seg_D == 0: a lane's zone is lane % seg_D);
    - mapped over scenarios, ``[S, N]`` operands lie with the scenarios on
      the lanes and the nodes on the sublanes, so a stride zone IS a
      sublane: the ``[N / seg_D, seg_D]`` view is a bitcast of the fused
      producer (seg_D a multiple of 8) and the reduce an elementwise max
      of whole registers, with no padding pass.

    Measured on a v5e at N = 10,000, seg_D 8 (PERF.md §6, PR 30): the
    lane fold under the scenario map costs the 128-scenario batch 2.8%
    (the padded copy is written out), the sublane view without it costs
    the single replay 12%, and one variadic reduce of seg_D rows masked
    to their zones 9.5%."""
    N = scores.shape[-1]
    packed = _pack(scores, feasible)
    if seg_mode == "block":
        return jnp.max(packed.reshape(seg_D, N // seg_D), axis=1)
    if scenario_axis:
        return jnp.max(packed.reshape(N // seg_D, seg_D), axis=0)
    rows = -(-N // 128)
    lanes = jnp.max(
        jnp.pad(packed, (0, rows * 128 - N), constant_values=NEG_INF).reshape(
            rows, 128
        ),
        axis=0,
    )
    return jnp.max(lanes.reshape(128 // seg_D, seg_D), axis=0)


def select_node_zone_packed(zone_best: jax.Array, zone_scores: jax.Array):
    """(choice i32, placed bool) from :func:`zone_packed_max`'s per-zone
    bests and each zone's constant score: the max of
    ``zone_best + zone_scores·2^14``. Under :func:`select_node_packed`'s
    gate (integer totals ≤ PACK_MAX_TOTAL with the zone score included)
    every value is an integer < 2^24, adding a zone's constant to all
    packed values of the zone keeps their order and float32 adds of such
    integers are exact: the max is the number ``select_node_packed`` finds
    over ``scores + zone_scores[zone(n)]``, bit for bit, the lowest index
    among equal totals included."""
    return _unpack(jnp.max(zone_best + zone_scores * np.float32(PACK_SHIFT)))


def _bind_deltas(d: Derived, node: jax.Array):
    """Shared pieces of a masked bind: the node one-hot, the [G, N]
    domain-equality mask (node n is in the same domain as `node` under
    group g's topology key), and the [G] has-domain flags for the bound
    node."""
    N = d.gdom_f.shape[1]
    oh_n = ((jnp.arange(N) == node) & (node >= 0)).astype(jnp.float32)  # [N]
    # Domain id of the bound node per group (one selected element — exact).
    gdom_at = jnp.einsum("gn,n->g", d.gdom_f, oh_n, precision=_HI)  # [G]
    node_has_dom = (
        jnp.einsum("gn,n->g", (d.gdom_f >= 0).astype(jnp.float32), oh_n, precision=_HI) > 0.5
    )
    dom_sel = (
        (d.gdom_f == gdom_at[:, None]) & node_has_dom[:, None] & (d.gdom_f >= 0)
    ).astype(jnp.float32)  # [G, N]
    return oh_n, dom_sel, node_has_dom.astype(jnp.float32)


def _pod_group_vectors(s: PodSlot, G: int):
    """([..., G] anti-term one-hot sum, [..., G] pref weight sum); term axes
    may carry a leading wave axis."""
    ohB = _term_onehot(s.anti_req, G)
    anti_g = jnp.sum(ohB, axis=-2)
    ohP = _term_onehot(s.pref_aff, G)
    w = jnp.where(s.pref_aff >= 0, s.pref_aff_w, 0.0)
    pref_g = jnp.einsum("...a,...ag->...g", w, ohP, precision=_HI)
    return anti_g, pref_g


def apply_binding(
    d: Derived, st: DevState, s: PodSlot, node: jax.Array, on: jax.Array
) -> DevState:
    """Masked bind. ``on`` is a bool scalar; when False the update is a
    no-op — keeps the scan branch-free. All updates are elementwise (no
    scatters). Gang rollback goes through :func:`apply_unbind_wave`."""
    G = st.match_count.shape[0]
    w = jnp.where(on & s.valid, 1.0, 0.0).astype(jnp.float32)
    oh_n, dom_sel, has_dom = _bind_deltas(d, node)
    used = st.used + (w * oh_n)[:, None] * s.req[None, :]
    pmg_f = s.pmg.astype(jnp.float32)
    match_count = st.match_count + (w * pmg_f)[:, None] * dom_sel
    # Total counts only domain-carrying binds — it must stay exactly
    # sum-over-domains of match_count (ops.cpu's bootstrap total).
    match_total = st.match_total + w * pmg_f * has_dom
    anti_g, pref_g = _pod_group_vectors(s, G)
    anti = st.anti_active + (w * anti_g)[:, None] * dom_sel
    pref = st.pref_wsum + (w * pref_g)[:, None] * dom_sel
    return DevState(
        used=used, match_count=match_count, anti_active=anti, pref_wsum=pref,
        match_total=match_total,
    )


# ---------------------------------------------------------------------------
# Fused wave evaluation (the hot path)
#
# The naive per-pod chain (eval_pod in sim.jax_runtime) issues ~30
# non-fusable ops per pod (einsums + reductions); at ~1-3 µs fixed cost per
# op inside a TPU scan, the replay is dispatch-latency-bound, not
# FLOP-bound.  Two fixes, both exact (bit-identical results):
#
# 1. Everything state-INDEPENDENT (taint matrices, node-affinity expression
#    matching, term one-hots, bind vectors) is precomputed for the whole
#    wave in one batched shot (WavePre) — W pods' worth of the biggest
#    tensors leave the sequential chain.
# 2. The per-pod state reads collapse into ONE stacked one-hot matmul
#    against match_count (+3 small matvecs), and the per-plugin score
#    normalizations collapse into one stacked masked min+max pair.
# ---------------------------------------------------------------------------


class WavePre(NamedTuple):
    """Per-wave precomputed tensors (leading axis W). Static widths:
    A = #required-affinity terms, B = #required-anti terms, SP = #spread
    constraints; lhs row layout is [A aff | B anti | SP spread | 1 pref]."""

    lhs: jax.Array  # [W, K, G] f32 stacked one-hot rows (K = A+B+SP+1 or 0)
    gvalid: jax.Array  # [W, KT, N] bool (KT = A+B+SP) domain-valid per term row
    taint_ok: jax.Array  # [W, N] bool
    taint_raw: jax.Array  # [W, N] f32 (PreferNoSchedule counts)
    na_ok: jax.Array  # [W, N] bool
    na_raw: jax.Array  # [W, N] f32
    aff_valid: jax.Array  # [W, A] bool
    aff_selfm: jax.Array  # [W, A] bool (pod matches its own aff term)
    anti_valid: jax.Array  # [W, B] bool
    sp_valid: jax.Array  # [W, SP] bool
    sp_dns: jax.Array  # [W, SP] bool (valid & DoNotSchedule)
    sp_scored: jax.Array  # [W, SP] bool (valid & ScheduleAnyway — scoring rows)
    sp_selfm: jax.Array  # [W, SP] f32
    sp_skew: jax.Array  # [W, SP] f32
    sp_w: jax.Array  # [W, SP] f32 (upstream log(size+2) topology weights)
    pmg_f: jax.Array  # [W, G] f32


def _padded_w_table(sp_w_g, G: int) -> np.ndarray:
    """Static [G] spread-weight table from spec.sp_w_g, padded/clipped to
    the one-hot group axis width."""
    tab = np.zeros(G, np.float32)
    arr = np.asarray(sp_w_g, np.float32)
    n = min(G, arr.shape[0])
    tab[:n] = arr[:n]
    return tab


def wave_widths(s: "PodSlot", spec) -> tuple:
    """(A, B, SP) static term widths after spec gating."""
    A = s.aff_req.shape[-1] if spec.interpod else 0
    B = s.anti_req.shape[-1] if spec.interpod else 0
    SP = s.spread_g.shape[-1] if spec.spread else 0
    return A, B, SP


def build_wave_pre(dc: DevCluster, d: Derived, sb: PodSlot, spec) -> WavePre:
    """Batched (over the wave axis) precompute of every state-independent
    piece of eval. ``sb`` fields carry a leading W axis."""
    W = sb.pod_id.shape[0]
    G = d.gdom_f.shape[0]
    N = d.gdom_f.shape[1]
    A, B, SP = wave_widths(sb, spec)
    pmg_f = sb.pmg.astype(jnp.float32)  # [W, G]

    pieces = []
    if spec.interpod:
        ohA = _term_onehot(sb.aff_req, G)  # [W, A, G]
        ohB = _term_onehot(sb.anti_req, G)
        pieces += [ohA, ohB]
    else:
        ohA = jnp.zeros((W, 0, G), jnp.float32)
        ohB = ohA
    if spec.spread:
        ohS = _term_onehot(sb.spread_g, G)
        pieces.append(ohS)
    else:
        ohS = jnp.zeros((W, 0, G), jnp.float32)
    if spec.interpod:
        ohP = _term_onehot(sb.pref_aff, G)  # [W, PA, G]
        wp = jnp.where(sb.pref_aff >= 0, sb.pref_aff_w, 0.0)
        pref_row = jnp.einsum("wp,wpg->wg", wp, ohP, precision=_HI)[:, None, :]
        pieces.append(pref_row)
    lhs = (
        jnp.concatenate(pieces, axis=1)
        if pieces
        else jnp.zeros((W, 0, G), jnp.float32)
    )
    terms = lhs[:, : A + B + SP]
    gvalid = (
        jnp.einsum(
            "wkg,gn->wkn", terms, (d.gdom_f >= 0).astype(jnp.float32), precision=_HI
        )
        > 0.5
        if A + B + SP
        else jnp.zeros((W, 0, N), bool)
    )

    if spec.taints:
        taint_ok = jax.vmap(lambda s: taint_mask(dc, s))(sb)
        taint_raw = jax.vmap(lambda s: taint_prefer_count(dc, s))(sb)
    else:
        taint_ok = jnp.ones((W, N), bool)
        taint_raw = jnp.zeros((W, N), jnp.float32)
    if spec.node_affinity:
        na_ok = jax.vmap(lambda s: node_affinity_mask(d, s))(sb)
        na_raw = jax.vmap(lambda s: node_affinity_score(d, s))(sb)
    else:
        na_ok = jnp.ones((W, N), bool)
        na_raw = jnp.zeros((W, N), jnp.float32)

    return WavePre(
        lhs=lhs,
        gvalid=gvalid,
        taint_ok=taint_ok,
        taint_raw=taint_raw,
        na_ok=na_ok,
        na_raw=na_raw,
        aff_valid=sb.aff_req[:, :A] >= 0,
        aff_selfm=jnp.einsum("wag,wg->wa", ohA, pmg_f, precision=_HI) > 0.5,
        anti_valid=sb.anti_req[:, :B] >= 0,
        sp_valid=sb.spread_g[:, :SP] >= 0,
        sp_dns=(sb.spread_g[:, :SP] >= 0) & sb.spread_dns[:, :SP],
        sp_scored=(sb.spread_g[:, :SP] >= 0) & ~sb.spread_dns[:, :SP],
        sp_selfm=jnp.einsum("wag,wg->wa", ohS, pmg_f, precision=_HI),
        sp_skew=sb.spread_skew[:, :SP].astype(jnp.float32),
        sp_w=jnp.einsum(
            "wag,g->wa", ohS, _padded_w_table(spec.sp_w_g, G), precision=_HI
        )
        if SP
        else jnp.zeros((W, 0), jnp.float32),
        pmg_f=pmg_f,
    )


def eval_pod_fused(
    dc: DevCluster,
    d: Derived,
    st: DevState,
    s: PodSlot,
    p: WavePre,
    spec,
    widths: tuple,
    wvec=None,
):
    """Fused Filter+Score for one slot using wave-precomputed tensors.
    Bit-identical to the reference chain (sim.jax_runtime.eval_pod) — the
    parity suites pin this. Returns (feasible [N], scores [N], any_f).

    ``wvec`` (optional [len(POLICY_COLS)] traced f32) swaps the static
    config weights for per-scenario policy-vector columns (round 9 tuner);
    filtering is weight-independent and unchanged."""
    N = dc.allocatable.shape[0]
    A, B, SP = widths
    K = p.lhs.shape[0]

    used1 = st.used + s.req[None, :]  # shared by fit mask + fit score
    feasible = jnp.ones(N, dtype=bool)
    if spec.fit:
        feasible = jnp.all(used1 <= dc.allocatable + 1e-6, axis=1)
    if spec.taints:
        feasible = feasible & p.taint_ok
    if spec.node_affinity:
        feasible = feasible & p.na_ok

    reads = (
        jnp.einsum("kg,gn->kn", p.lhs, st.match_count, precision=_HI)
        if K
        else jnp.zeros((0, N), jnp.float32)
    )
    if spec.interpod:
        if A:
            totals = jnp.einsum("ag,g->a", p.lhs[:A], st.match_total, precision=_HI)
            boot = (totals == 0) & p.aff_selfm  # bootstrap self-match
            term_ok = (reads[:A] >= 1) & p.gvalid[:A]
            feasible = feasible & jnp.all(
                jnp.where(p.aff_valid[:, None], term_ok | boot[:, None], True), axis=0
            )
        if B:
            viol = (reads[A : A + B] >= 1) & p.gvalid[A : A + B]
            feasible = feasible & jnp.all(
                jnp.where(p.anti_valid[:, None], ~viol, True), axis=0
            )
        blocked = (
            jnp.einsum("g,gn->n", p.pmg_f, st.anti_active, precision=_HI) > 0.5
        )  # symmetric: anti_active entries are non-negative counts
        feasible = feasible & ~blocked
    if spec.spread and SP:
        cnts = reads[A + B : A + B + SP]  # [SP, N]
        gval = p.gvalid[A + B : A + B + SP]
        minv = jnp.min(jnp.where(gval, cnts, jnp.inf), axis=1)
        has = jnp.isfinite(minv)
        c_ok = (
            gval
            & has[:, None]
            & (cnts + p.sp_selfm[:, None] - jnp.where(has, minv, 0.0)[:, None]
               <= p.sp_skew[:, None])
        )
        feasible = feasible & jnp.all(
            jnp.where(p.sp_dns[:, None], c_ok, True), axis=0
        )

    any_f = jnp.any(feasible)

    # ---- scores: stack raw rows, one masked min+max, per-row normalize ----
    _w, _on = policy_weight_fns(spec, wvec)
    total = jnp.zeros(N, dtype=jnp.float32)
    if spec.fit and _on("NodeResourcesFit"):
        rw = np.asarray(spec.resource_weights, dtype=np.float32)
        if spec.fit_strategy not in ("LeastAllocated", "MostAllocated"):
            raw = requested_to_capacity_ratio_score(
                dc, st, s, rw, spec.shape_x, spec.shape_y
            )
        elif wvec is None:
            raw = (
                least_allocated_score(dc, st, s, rw)
                if spec.fit_strategy == "LeastAllocated"
                else most_allocated_score(dc, st, s, rw)
            )
        else:
            raw = jnp.where(
                wvec[IDX_FIT_LEAST] > 0.5,
                least_allocated_score(dc, st, s, rw),
                most_allocated_score(dc, st, s, rw),
            )
        total = total + _w("NodeResourcesFit") * raw

    # (raw, weight, minmax?, reverse?) rows, in the reference accumulation
    # order: taint, node-affinity, interpod, spread.
    rows = []
    if spec.taints and spec.taint_score and _on("TaintToleration"):
        rows.append((p.taint_raw, _w("TaintToleration"), False, True))
    if spec.node_affinity and _on("NodeAffinity"):
        rows.append((p.na_raw, _w("NodeAffinity"), False, False))
    if spec.interpod and _on("InterPodAffinity"):
        raw = reads[A + B + SP]
        if spec.has_symmetric_pref:
            raw = raw + jnp.einsum("g,gn->n", p.pmg_f, st.pref_wsum, precision=_HI)
        rows.append((raw, _w("InterPodAffinity"), True, False))
    sp_pack = None
    if spec.spread and _on("PodTopologySpread") and SP:
        # Upstream scoring: raw + ignored mask computed here; the extrema
        # (over feasible & ~ignored) ride the shared stacked reduce below
        # as an extra row with the ignored nodes pre-masked to ±inf.
        cnts = reads[A + B : A + B + SP]
        gval = p.gvalid[A + B : A + B + SP]
        raw_sp = jnp.zeros(N, jnp.float32)
        ignored = jnp.zeros(N, bool)
        for i in range(SP):
            contrib = cnts[i] * p.sp_w[i] + (p.sp_skew[i] - 1.0)
            raw_sp = raw_sp + jnp.where(p.sp_scored[i], contrib, 0.0)
            ignored = ignored | (p.sp_scored[i] & ~gval[i])
        sp_pack = (jnp.floor(raw_sp + 0.5), ignored)
    if rows or sp_pack is not None:
        hi_rows = [r[0] for r in rows]
        lo_rows = list(hi_rows)
        if sp_pack is not None:
            raw_sp, ignored = sp_pack
            hi_rows.append(jnp.where(ignored, -jnp.inf, raw_sp))
            lo_rows.append(jnp.where(ignored, jnp.inf, raw_sp))
        hi_stack = jnp.where(feasible[None, :], jnp.stack(hi_rows), -jnp.inf)
        lo_stack = jnp.where(feasible[None, :], jnp.stack(lo_rows), jnp.inf)
        hi = jnp.max(hi_stack, axis=1)
        lo = jnp.min(lo_stack, axis=1)
        for i, (raw, wt, minmax, reverse) in enumerate(rows):
            out = _normalize_row(raw, lo[i], hi[i], any_f, minmax, reverse)
            total = total + wt * out
        if sp_pack is not None:
            raw_sp, ignored = sp_pack
            out = spread_norm_from_extrema(
                raw_sp, ignored, hi[-1], lo[-1], jnp.any(p.sp_scored),
                getattr(spec, "sp_norm_f32", False),
            )
            total = total + _w("PodTopologySpread") * out
    return feasible, total, any_f


def apply_unbind_wave(
    d: Derived, st: DevState, sb: PodSlot, choice: jax.Array, revert: jax.Array
) -> DevState:
    """Batched gang rollback: subtract every reverted slot's bind in ONE
    set of elementwise updates (sb fields have leading wave axis W)."""
    G = st.match_count.shape[0]
    N = d.gdom_f.shape[1]
    w = jnp.where(revert & sb.valid, 1.0, 0.0).astype(jnp.float32)  # [W]
    oh = ((jnp.arange(N)[None, :] == choice[:, None]) & (choice[:, None] >= 0)).astype(
        jnp.float32
    )  # [W, N]
    used = st.used - jnp.einsum("w,wn,wr->nr", w, oh, sb.req, precision=_HI)
    gdom_at = jnp.einsum("gn,wn->wg", d.gdom_f, oh, precision=_HI)  # [W, G]
    has_dom = jnp.einsum("gn,wn->wg", (d.gdom_f >= 0).astype(jnp.float32), oh, precision=_HI) > 0.5
    dom_sel = (
        (d.gdom_f[None] == gdom_at[:, :, None]) & has_dom[:, :, None] & (d.gdom_f >= 0)[None]
    ).astype(jnp.float32)  # [W, G, N]
    pmg_f = sb.pmg.astype(jnp.float32)  # [W, G]
    match_count = st.match_count - jnp.einsum("w,wg,wgn->gn", w, pmg_f, dom_sel, precision=_HI)
    match_total = st.match_total - jnp.einsum(
        "w,wg->g", w, pmg_f * has_dom.astype(jnp.float32), precision=_HI
    )
    anti_wg, pref_wg = _pod_group_vectors(sb, G)  # [W, G] each
    anti = st.anti_active - jnp.einsum("w,wg,wgn->gn", w, anti_wg, dom_sel, precision=_HI)
    pref = st.pref_wsum - jnp.einsum("w,wg,wgn->gn", w, pref_wg, dom_sel, precision=_HI)
    return DevState(
        used=used, match_count=match_count, anti_active=anti, pref_wsum=pref,
        match_total=match_total,
    )
