"""Containers and helpers of the device wave step (SURVEY.md §3.5).

What :mod:`.tpu3` (``make_wave_step3``, the one device program) imports:
the cluster and slot containers (``DevCluster``, ``PodSlot``,
``SlotSource`` with the in-jit and the host slot gathers, ``Derived``), the
state-independent Filter and Score rows (taints, node affinity), the
normalize arithmetic (``_normalize_row``, ``spread_norm_from_extrema``,
``floor_div_f32``), the selects (``select_node`` and its packed forms,
``masked_argmin``), the policy-vector columns, and the two host converters
between the domain-space ``[G, D]`` and node-space ``[G, N]`` count
layouts. Same math as :mod:`.cpu`, static-shape jnp, composable under
``jit``/``vmap``/``lax.scan``.

Design notes (TPU-first):
- **No gathers or scatters anywhere in the hot loop.** Batched
  gather/scatter with per-scenario dynamic indices lowers to a serialized
  per-batch loop on TPU (~135 µs per op measured on v5e — 100× the cost of
  the math). Every dynamic-index access is instead expressed as a one-hot
  contraction (MXU matvec) or a masked elementwise update (VPU), which are
  effectively free at these shapes.
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


@jax.tree_util.register_pytree_node_class
class PackedRows:
    """A pytree of per-task columns (``[P, ...]`` each: ``int32``,
    ``float32`` or ``bool``) laid side by side as ONE ``int32 [P, C]``
    table, so that a read of many columns BY TASK ID is one gather of one
    row an id: on the chip a gather costs by the index, not by the byte
    (PERF.md §5, the retry pass). ``float32`` goes in and out through a
    bitcast and ``bool`` as 0 / 1, both exact; trailing axes lie flattened
    and a zero-wide column takes no room. The layout (``cols``: shape
    behind the task axis and dtype a leaf) is static and rides the pytree
    as aux data: the table is the one leaf."""

    def __init__(self, table: jax.Array, treedef, cols: tuple):
        self.table, self.treedef, self.cols = table, treedef, cols

    def tree_flatten(self):
        return (self.table,), (self.treedef, self.cols)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @classmethod
    def pack(cls, tree) -> "PackedRows":
        leaves, treedef = jax.tree.flatten(tree)
        flat = []
        for a in leaves:
            if a.dtype == jnp.float32:
                a = jax.lax.bitcast_convert_type(a, jnp.int32)
            elif a.dtype == jnp.bool_:
                a = a.astype(jnp.int32)
            elif a.dtype != jnp.int32:
                raise TypeError(f"no packed form for a {a.dtype} column")
            flat.append(a.reshape(a.shape[0], -1))
        return cls(
            jnp.concatenate(flat, axis=1), treedef,
            tuple((a.shape[1:], a.dtype.name) for a in leaves),
        )

    def take(self, ids: jax.Array):
        """The packed tree's rows at ``ids`` (any shape, every id in
        range): each leaf what ``leaf[ids]`` reads, dtype, shape and
        bits."""
        return self.unpack(self.table[ids])

    def unpack(self, rows: jax.Array):
        """The tree of ``rows`` (``[..., C]``, rows of the table already
        read): :meth:`take` without its gather."""
        cols = jnp.moveaxis(rows, -1, 0)
        leaves, at = [], 0
        for tail, dtype in self.cols:
            n = int(np.prod(tail, dtype=np.int64))
            a = jnp.moveaxis(cols[at:at + n], 0, -1)
            at += n
            if dtype == "float32":
                a = jax.lax.bitcast_convert_type(a, jnp.float32)
            elif dtype == "bool":
                a = a != 0
            leaves.append(a.reshape(rows.shape[:-1] + tail))
        return jax.tree.unflatten(self.treedef, leaves)


def slots_of_rows(rows: SlotSource, idx: jax.Array) -> PodSlot:
    """What :func:`gather_slots_device` gives at ``idx``, from ``rows``: the
    source's rows already read at ``clip(idx, 0)`` (``PackedRows.take``)."""
    return PodSlot(
        pod_id=idx.astype(jnp.int32),
        valid=idx >= 0,
        req=rows.requests,
        tol_key=rows.tol_key,
        tol_kv=rows.tol_kv,
        tol_effect=rows.tol_effect,
        na_req=rows.na_req,
        na_has_req=rows.na_has_req,
        na_pref=rows.na_pref,
        na_pref_w=rows.na_pref_w,
        aff_req=rows.aff_req,
        anti_req=rows.anti_req,
        pref_aff=rows.pref_aff,
        pref_aff_w=rows.pref_aff_w,
        spread_g=rows.spread_g,
        spread_skew=rows.spread_skew,
        spread_dns=rows.spread_dns,
        pmg=rows.pmg,
        group=jnp.where(idx >= 0, rows.group_id, PAD).astype(jnp.int32),
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


# ---------------------------------------------------------------------------
# Scores
# ---------------------------------------------------------------------------

# Scores are INTEGER-valued f32, floored through single-op chains — nothing
# XLA can FMA-fuse — so device scores are bit-identical to ops.cpu and
# argmax ties break the same way (SURVEY.md §7 hard part #6). Mirrors
# upstream's int64 node scores.


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


# ---------------------------------------------------------------------------
# Normalization + selection
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


def _pod_group_vectors(s: PodSlot, G: int):
    """([..., G] anti-term one-hot sum, [..., G] pref weight sum); term axes
    may carry a leading wave axis."""
    ohB = _term_onehot(s.anti_req, G)
    anti_g = jnp.sum(ohB, axis=-2)
    ohP = _term_onehot(s.pref_aff, G)
    w = jnp.where(s.pref_aff >= 0, s.pref_aff_w, 0.0)
    pref_g = jnp.einsum("...a,...ag->...g", w, ohP, precision=_HI)
    return anti_g, pref_g


def _padded_w_table(sp_w_g, G: int) -> np.ndarray:
    """Static [G] spread-weight table from spec.sp_w_g, padded/clipped to
    the one-hot group axis width."""
    tab = np.zeros(G, np.float32)
    arr = np.asarray(sp_w_g, np.float32)
    n = min(G, arr.shape[0])
    tab[:n] = arr[:n]
    return tab


