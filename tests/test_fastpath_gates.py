"""Gate boundaries and exactness of the round-3 fast paths:

- ops.tpu.select_node_packed vs select_node (ties, boundary totals,
  all-infeasible) — the packed form must be bit-identical within its gate.
- tpu3.pack_select_ok gate edges (Σw·100 bound, node-count bound,
  fractional / negative / zero weights).
- V3Static seg_mode detection (stride / block / none) and the segmented
  domfeas path vs the one-hot matmul path on the same trace.
- single_topo dom_at fast path vs the [G, N] einsum (multi-topology traces
  must NOT take it).
"""

import dataclasses
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import PAD, encode
from kubernetes_simulator_tpu.ops import tpu as T
from kubernetes_simulator_tpu.ops import tpu3 as V3
from kubernetes_simulator_tpu.sim.greedy import greedy_replay
from kubernetes_simulator_tpu.sim.jax_runtime import JaxReplayEngine, StepSpec
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload


# ---------------------------------------------------------------------------
# select_node_packed vs select_node
# ---------------------------------------------------------------------------


def _both(scores, feasible):
    n1, p1 = jax.jit(T.select_node)(scores, feasible)
    n2, p2 = jax.jit(T.select_node_packed)(scores, feasible)
    return (int(n1), bool(p1)), (int(n2), bool(p2))


def test_packed_matches_plain_on_ties_and_boundaries():
    rng = np.random.default_rng(0)
    N = 257
    for trial in range(50):
        # Integer totals up to the packing bound, dense ties.
        scores = rng.integers(0, T.PACK_MAX_TOTAL + 1, size=N).astype(np.float32)
        scores[rng.integers(0, N, size=N // 3)] = float(T.PACK_MAX_TOTAL)
        feasible = rng.random(N) < rng.choice([0.02, 0.5, 0.98])
        a, b = _both(jnp.asarray(scores), jnp.asarray(feasible))
        assert a == b, (trial, a, b)


def test_packed_all_infeasible_returns_pad():
    scores = jnp.zeros(64, jnp.float32)
    feasible = jnp.zeros(64, bool)
    a, b = _both(scores, feasible)
    assert a == (PAD, False) and b == (PAD, False)


def test_packed_max_total_exact_at_bound():
    # Max packed value must round-trip exactly at the documented bound.
    N = T.PACK_MAX_NODES
    v = float(T.PACK_MAX_TOTAL) * T.PACK_SHIFT + (T.PACK_SHIFT - 1.0)
    assert v < 2**24
    assert np.float32(v) == v  # integer < 2^24 is f32-exact


def test_pack_gate_edges():
    spec = StepSpec(
        fit=True, taints=False, node_affinity=False, interpod=False,
        spread=False,
    )
    ok = V3.pack_select_ok
    assert ok(spec, {"NodeResourcesFit": 1.0}, 16384)
    assert not ok(spec, {"NodeResourcesFit": 1.0}, 16385)  # node bound
    assert ok(spec, {"NodeResourcesFit": 10.0}, 100)  # 1000 <= 1023
    assert not ok(spec, {"NodeResourcesFit": 11.0}, 100)  # 1100 > 1023
    assert not ok(spec, {"NodeResourcesFit": 1.5}, 100)  # fractional
    assert not ok(spec, {"NodeResourcesFit": -1.0}, 100)  # negative
    # Zero-weight rows do not count toward the bound.
    assert ok(spec, {"NodeResourcesFit": 1.0, "PodTopologySpread": 0.0}, 100)
    # Inactive plugins do not count either.
    spec5 = StepSpec()
    w5 = {n: 3.0 for n in (
        "NodeResourcesFit", "TaintToleration", "NodeAffinity",
        "InterPodAffinity", "PodTopologySpread",
    )}
    assert not ok(spec5, w5, 100)  # 5*3*100 = 1500 > 1023
    spec2 = StepSpec(taints=False, node_affinity=False, interpod=False)
    assert ok(spec2, w5, 100)  # only fit+spread active: 600


# ---------------------------------------------------------------------------
# zone-packed select (one reduce: best packed node per zone) vs
# select_node_packed over the same (total, feasible)
# ---------------------------------------------------------------------------


def _zone_of(N, D, mode):
    n = np.arange(N)
    return n % D if mode == "stride" else n // (N // D)


def _zone_inputs(case, N, D, mode):
    """(fit [N], zone scores [D], feasible [N]): integer scores whose sum
    stays within the packed select's bound."""
    rng = np.random.default_rng(zlib.crc32(repr((case, N, D, mode)).encode()))
    zone = _zone_of(N, D, mode)
    fit = rng.integers(0, 101, size=N).astype(np.float32)
    zs = (2.0 * rng.integers(0, 101, size=D)).astype(np.float32)
    feasible = rng.random(N) < 0.4
    if case == "empty_zone":
        feasible &= zone != 3 % D
        zs[3 % D] = 200.0  # the best zone score has nobody to give it to
    elif case == "none_feasible":
        feasible[:] = False
    elif case == "ties_across_zones":
        # every feasible node has the same total: the lowest index wins,
        # and it is not in zone 0
        fit[:] = 40.0
        zs[:] = 60.0
        feasible[: N // 2] = False
    elif case == "ties_inside_a_zone":
        fit[:] = 7.0
        zs[:] = 0.0
        zs[D - 1] = 100.0
    elif case == "max_total":
        zs = np.full(D, T.PACK_MAX_TOTAL - 100.0, np.float32)
        fit[rng.integers(0, N, size=N // 4)] = 100.0
    elif case == "last_node_only":
        feasible[:] = False
        feasible[N - 1] = True
    else:
        assert case == "random"
    return fit, zs, feasible, zone


@pytest.mark.parametrize("scenario_axis", [False, True], ids=["one", "vmap"])
@pytest.mark.parametrize(
    "N, D, mode",
    [(272, 8, "stride"), (272, 8, "block"), (240, 16, "stride"),
     (96, 4, "stride"), (90, 6, "block")],
    ids=["stride8-tail16", "block8", "stride16", "stride4", "block6"],
)
@pytest.mark.parametrize(
    "case",
    ["random", "empty_zone", "none_feasible", "ties_across_zones",
     "ties_inside_a_zone", "max_total", "last_node_only"],
)
def test_zone_packed_select_equals_packed_select(case, N, D, mode, scenario_axis):
    """The node, ``placed`` and each zone's feasibility from the ONE
    per-zone reduce equal what select_node_packed and a per-zone any() give
    on the summed total — N off a 128 multiple, an empty zone, nothing
    feasible (PAD), equal totals across and inside zones (lowest index),
    totals at PACK_MAX_TOTAL; one scenario and a scenario-mapped batch."""
    fit, zs, feasible, zone = _zone_inputs(case, N, D, mode)

    def zone_form(fit, zs, feasible):
        best = T.zone_packed_max(fit, feasible, mode, D, scenario_axis)
        node, placed = T.select_node_zone_packed(best, zs)
        return node, placed, best > -jnp.inf

    def plain(fit, zs, feasible):
        node, placed = T.select_node_packed(fit + zs[jnp.asarray(zone)], feasible)
        return node, placed

    args = (jnp.asarray(fit), jnp.asarray(zs), jnp.asarray(feasible))
    if scenario_axis:
        # three scenarios: the case, its mirror image, nothing feasible
        args = (
            jnp.stack([args[0], args[0][::-1], args[0]]),
            jnp.stack([args[1], args[1], args[1]]),
            jnp.stack([args[2], args[2][::-1], jnp.zeros(N, bool)]),
        )
        zone_form, plain = jax.vmap(zone_form), jax.vmap(plain)
    node, placed, zfeas = jax.jit(zone_form)(*args)
    want_node, want_placed = jax.jit(plain)(*args)
    np.testing.assert_array_equal(node, want_node)
    np.testing.assert_array_equal(placed, want_placed)
    feas = np.asarray(args[2]).reshape(-1, N)
    want_zfeas = np.stack(
        [[f[zone == d].any() for d in range(D)] for f in feas]
    )
    np.testing.assert_array_equal(np.asarray(zfeas).reshape(-1, D), want_zfeas)
    if case == "none_feasible":
        assert (np.asarray(node) == PAD).all() and not np.asarray(placed).any()
    if case == "ties_across_zones" and not scenario_axis:
        assert int(node) == int(np.flatnonzero(feasible)[0])


def _borg_like_static(nodes=80, D=None, mode="stride", **spec_kw):
    ec, ep = _spread_case(nodes=nodes, pods=40, seed=9)
    spec = StepSpec.from_config(ec, None, ep)
    if spec_kw:
        spec = dataclasses.replace(spec, **spec_kw)
    t0 = V3.V3Static.build(ec, ep, spec).topo0
    if D is not None:
        n = np.arange(ec.num_nodes)
        ec.node_domain[t0] = (
            n % D if mode == "stride" else n // (ec.num_nodes // D)
        ).astype(np.int32)
        ec.num_domains[t0] = D
        ec.max_domains = max(ec.max_domains, D)
    return V3.V3Static.build(ec, ep, spec), spec, ec.num_nodes


@pytest.mark.parametrize(
    "kw, form",
    [
        (dict(), "zone_packed"),
        (dict(D=16), "zone_packed"),
        (dict(D=8, mode="block"), "zone_packed"),
        (dict(D=10, mode="block"), "zone_packed"),
        (dict(D=40), "two_pass"),          # more zones than the word holds
        (dict(D=10), "two_pass"),          # stride zones do not tile 128 lanes
        (dict(D=5), "two_pass"),
        (dict(taint_score=True, taints=True), "two_pass"),   # node-space row
        (dict(node_affinity=True), "two_pass"),
        (dict(weights=(("PodTopologySpread", 1.5),)), "two_pass"),  # no packing
        (dict(weights=(("PodTopologySpread", 0.0),)), "two_pass"),  # no zone row
    ],
    ids=["borg", "stride16", "block8", "block10", "stride40", "stride10",
         "stride5", "taint-score-row", "node-affinity-row", "fractional-weight",
         "spread-unscored"],
)
def test_select_form_gate(kw, form):
    kw = dict(kw)
    D, mode = kw.pop("D", None), kw.pop("mode", "stride")
    st, spec, N = _borg_like_static(D=D, mode=mode, **kw)
    assert st.seg_mode == mode and (D is None or st.seg_D == D)
    assert V3.select_form(st, spec, N) == form


@pytest.mark.parametrize(
    "kw",
    [dict(traced_weights=True), dict(dyn_labels=True), dict(preemption=True)],
    ids=["traced-weights", "label-perturbation", "tier-preemption"],
)
def test_select_form_follows_how_the_step_is_built(kw):
    """Facts of the program, not of the profile, that keep two reduces: a
    traced policy vector (no packed select), per-scenario label tables (no
    domain-space spread), tier preemption (reads `feasible` again)."""
    st, spec, N = _borg_like_static()
    assert V3.select_form(st, spec, N) == "zone_packed"
    if kw.pop("preemption", False):
        st = dataclasses.replace(st, preemption=True)
    assert V3.select_form(st, spec, N, **kw) == "two_pass"


# ---------------------------------------------------------------------------
# seg_mode detection + parity of the segmented domfeas path
# ---------------------------------------------------------------------------


def _spread_case(nodes=64, pods=160, seed=0):
    cluster = make_cluster(nodes, seed=seed, taint_fraction=0.0)
    pod_list, _ = make_workload(
        pods, seed=seed, with_affinity=False, with_spread=True,
        with_tolerations=False, gang_fraction=0.0,
    )
    return encode(cluster, pod_list)


def test_seg_mode_detected_stride():
    ec, ep = _spread_case()
    spec = StepSpec.from_config(ec, None, ep)
    st = V3.V3Static.build(ec, ep, spec)
    # make_cluster assigns zone = i % num_zones → stride pattern.
    assert st.single_topo
    assert st.seg_mode == "stride" and st.seg_D > 0


def test_seg_mode_block_and_none_detection():
    ec, ep = _spread_case()
    spec = StepSpec.from_config(ec, None, ep)
    st = V3.V3Static.build(ec, ep, spec)
    t0 = st.topo0
    N = ec.num_nodes
    D = int(ec.num_domains[t0])
    saved = ec.node_domain
    try:
        # Rewrite the node→domain map to a block layout.
        nd = saved.copy()
        nd[t0] = np.arange(N) // (N // D)
        ec.node_domain = nd
        assert V3.V3Static.build(ec, ep, spec).seg_mode == "block"
        # Scrambled layout → no pattern (keep it genuinely unstructured).
        nd2 = nd.copy()
        nd2[t0] = np.random.default_rng(0).permutation(nd[t0])
        ec.node_domain = nd2
        if (nd2[t0] == np.arange(N) % D).all() or (
            nd2[t0] == np.arange(N) // (N // D)
        ).all():  # pragma: no cover - astronomically unlikely
            pytest.skip("permutation landed on a structured layout")
        assert V3.V3Static.build(ec, ep, spec).seg_mode == ""
    finally:
        ec.node_domain = saved


def test_segmented_domfeas_matches_einsum_path():
    """Same trace through the seg path and the forced-einsum path must give
    identical assignments (greedy anchor pins both)."""
    ec, ep = _spread_case(nodes=48, pods=120, seed=3)
    cfg = FrameworkConfig()
    eng = JaxReplayEngine(ec, ep, cfg, chunk_waves=8)
    assert eng.static3.seg_mode == "stride"
    res_seg = eng.replay()

    eng2 = JaxReplayEngine(ec, ep, cfg, chunk_waves=8)
    eng2.static3 = dataclasses.replace(eng2.static3, seg_mode="", seg_D=0)
    from kubernetes_simulator_tpu.sim.jax_runtime import (
        make_chunk_fn3_src, rep_slots_for,
    )

    eng2.chunk_fn = make_chunk_fn3_src(
        eng2.static3, eng2.shared3, rep_slots_for(eng2.static3, ep),
        eng2.wave_width, eng2.spec,
    )
    res_ein = eng2.replay()
    np.testing.assert_array_equal(res_seg.assignments, res_ein.assignments)

    anchor = greedy_replay(ec, ep, cfg)
    np.testing.assert_array_equal(res_seg.assignments, anchor.assignments)


def test_packed_select_off_matches_on():
    """Fractional weight disables packing; assignments must still match the
    anchor (plain select path)."""
    ec, ep = _spread_case(nodes=48, pods=120, seed=4)
    cfg = FrameworkConfig(weights={"PodTopologySpread": 1.5})
    from kubernetes_simulator_tpu.sim.jax_runtime import StepSpec as SS

    eng = JaxReplayEngine(ec, ep, cfg, chunk_waves=8)
    assert not V3.pack_select_ok(
        eng.spec, dict(eng.spec.weights), ec.num_nodes
    )
    res = eng.replay()
    anchor = greedy_replay(ec, ep, cfg)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)


# ---------------------------------------------------------------------------
# single_topo dom_at fast path
# ---------------------------------------------------------------------------


def test_multi_topology_disables_single_topo():
    cluster = make_cluster(32, seed=1, taint_fraction=0.0)
    pods, _ = make_workload(
        96, seed=1, with_affinity=True, with_spread=True,
        with_tolerations=False, gang_fraction=0.0,
    )
    ec, ep = encode(cluster, pods)
    spec = StepSpec.from_config(ec, None, ep)
    st = V3.V3Static.build(ec, ep, spec)
    n_topos = len({
        int(t) for t, nd in zip(
            ec.group_topo[: st.G], st.nd_g
        ) if t >= 0 and nd > 0
    })
    assert st.single_topo == (n_topos <= 1)
    # Either way the engine must match the host anchor.
    cfg = FrameworkConfig()
    res = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay()
    anchor = greedy_replay(ec, ep, cfg)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)


def test_seg_mode_wide_domain_fallback_parity():
    """32..Dcap domains: seg_mode stays on (reshape-any domfeas, tile
    expansion) — the bit-pack int32 bound must not silently drop the
    structured fast path for wide stride layouts. Generator zone names
    sort lexicographically past 9 domains, so the 40-domain stride map is
    installed directly (every consumer downstream of encode reads
    node_domain/num_domains, not the raw labels)."""
    ec, ep = _spread_case(nodes=80, pods=200, seed=9)
    spec = StepSpec.from_config(ec, None, ep)
    t0 = V3.V3Static.build(ec, ep, spec).topo0
    ec.node_domain[t0] = (np.arange(ec.num_nodes) % 40).astype(np.int32)
    ec.num_domains[t0] = 40
    ec.max_domains = max(ec.max_domains, 40)
    st = V3.V3Static.build(ec, ep, spec)
    assert st.seg_mode == "stride" and st.seg_D == 40
    cfg = FrameworkConfig()
    res = JaxReplayEngine(ec, ep, cfg, chunk_waves=8).replay()
    anchor = greedy_replay(ec, ep, cfg)
    np.testing.assert_array_equal(res.assignments, anchor.assignments)
