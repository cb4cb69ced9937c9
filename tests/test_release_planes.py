"""The what-if release program's mechanism (``ops.release_planes``): a release
list's node-space accumulators through node-factored one-hot contractions,
colliding rows taken in rank order. Held here to the host arithmetic
(``np.add.at`` in list order, then one subtraction) bit for bit; the CPU
backend's dot is exact and sequential, so what only an MXU can get wrong is
held by ``chip_smoke.py``'s parity phase on the chip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.models.encode import encode
from kubernetes_simulator_tpu.ops.release_planes import (
    BLOCK, bf16_parts, collision_rank, release_planes)
from kubernetes_simulator_tpu.sim.synthetic import make_cluster, make_workload
from kubernetes_simulator_tpu.sim.whatif import WhatIfEngine, uniform_scenarios

N = 2500  # 19 * 128 + 68: no multiple of the lane width
R, C = 3, 5


def built_list(K, depth, block, seed=0):
    """K rows on pairwise different nodes, then: node 7 released ``depth``
    times inside block 0, node 11 ``depth`` times across the first block
    edge, node N - 1 once, every 13th row not placed. Requests are the
    non-dyadic 0.1 / 0.3 / 0.7 cores. The deepest block needs ``depth``
    rounds."""
    rng = np.random.default_rng(seed)
    nd = (20 + rng.permutation(N - 21)[:K]).astype(np.int32)
    nd[1::13] = -1
    nd[0] = N - 1
    nd[3 : 3 + depth] = 7
    edge = np.arange(block - depth // 2, block - depth // 2 + depth)
    nd[edge[edge < K]] = 11
    req = rng.choice(np.array([0.1, 0.3, 0.7], np.float32), (K, R))
    req[:, 1] *= np.float32(2**30)
    counts = rng.integers(0, 4, (K, C)).astype(np.float32)
    return nd, req, counts


def host_planes(nd, req, counts):
    ok = nd >= 0
    rel = np.zeros((N, R), np.float32)
    np.add.at(rel, nd[ok], req[ok])
    rc = np.zeros((N, C), np.float32)
    np.add.at(rc, nd[ok], counts[ok])
    return rel.T, rc.T


def host_depth(nd, width):
    """The most releases one node has inside one block of the list (1 if none)."""
    return max([1] + [int(np.bincount(b[b >= 0]).max())
                      for b in nd.reshape(-1, width) if (b >= 0).any()])


def same_bits(a, b):
    return (np.asarray(a).view(np.uint32) == np.asarray(b).view(np.uint32)).all()


@pytest.mark.parametrize("block", [None, 64, 256])
@pytest.mark.parametrize("depth", [1, 2, 5, 40])
@pytest.mark.parametrize("K", [256, 2048])
def test_planes_equal_host_arithmetic_bit_for_bit(K, depth, block):
    width = min(block or BLOCK, K)
    nd, req, counts = built_list(K, depth, width)
    assert host_depth(nd, width) == depth  # node 7, inside block 0
    # scenarios: the list, the list a row later, the list without node 7
    nd3 = np.stack([nd, np.roll(nd, 1), np.where(nd == 7, -1, nd)])
    used0 = np.random.default_rng(1).random((3, R, N)).astype(np.float32) * 64

    def one(u, n):
        rel, rc, rounds = release_planes(
            n, req, counts, N, block=block, axis_name="s")
        return u - rel, rc, rounds

    used, rc, rounds = jax.jit(jax.vmap(one, axis_name="s"))(used0, nd3)
    for s in range(3):
        rel_h, rc_h = host_planes(nd3[s], req, counts)
        assert same_bits(used[s], used0[s] - rel_h), (s, K, depth, block)
        assert (np.asarray(rc[s]) == rc_h).all()
    # per scenario its own depth, whatever the shared trip count was
    assert [int(r) for r in rounds] == [host_depth(n, width) for n in nd3]
    # unnamed (the retry path's form): each scenario loops to its own rank
    alone = jax.jit(lambda n: release_planes(n, req, counts, N, block=block))
    rel1, rc1, rounds1 = alone(nd)
    rel_h, rc_h = host_planes(nd, req, counts)
    assert same_bits(rel1, rel_h) and (np.asarray(rc1) == rc_h).all()
    assert int(rounds1) == depth


def test_a_list_of_padding_needs_one_round_and_releases_nothing():
    nd = np.full(256, -1, np.int32)
    _, req, counts = built_list(256, 1, BLOCK)
    rel, rc, rounds = release_planes(nd, req, counts, N)
    assert not np.asarray(rel).any() and not np.asarray(rc).any()
    assert int(rounds) == 1


@pytest.mark.parametrize("seed", range(4))
def test_collision_rank_is_the_count_of_earlier_rows_on_the_node(seed):
    rng = np.random.default_rng(seed)
    nd = rng.integers(-1, 9, 200).astype(np.int32)
    want = [sum(nd[j] == nd[k] and nd[j] >= 0 for j in range(k))
            for k in range(len(nd))]
    np.testing.assert_array_equal(np.asarray(collision_rank(jnp.asarray(nd))), want)


def test_bf16_parts_are_exact_disjoint_and_sum_back_in_any_order():
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 1 << 32, 20000, dtype=np.uint64).astype(np.uint32)
    x = bits.view(np.float32)
    x = np.concatenate([x[np.isfinite(x) & (np.abs(x) > 1e-30)],
                        np.array([0.1, 0.3, 0.7, 0.0, 1.0, -2.5], np.float32)])
    p = [np.asarray(a) for a in bf16_parts(jnp.asarray(x))]
    for a in p:  # each survives the trip through bfloat16
        assert same_bits(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32), a)
    for i, j, k in ((0, 1, 2), (2, 1, 0), (0, 2, 1)):
        # no partial sum rounds: float32 and float64 agree on it
        pair = p[i] + p[j]
        assert (pair.astype(np.float64)
                == p[i].astype(np.float64) + p[j].astype(np.float64)).all()
        assert same_bits(pair + p[k], x)


# -- the lowered program ---------------------------------------------------------


@pytest.fixture(scope="module")
def release_engine():
    """A handful of nodes, many short tasks: every release block collides."""
    cluster = make_cluster(12, seed=21, taint_fraction=0.2)
    pods, _ = make_workload(
        384, seed=21, with_affinity=True, with_spread=True,
        with_tolerations=True, duration_mean=10.0)
    ec, ep = encode(cluster, pods)
    scen = uniform_scenarios(ec, 4, seed=21, p_capacity=0.5, p_taint=0.3)
    eng = WhatIfEngine(ec, ep, scen, FrameworkConfig(), chunk_waves=16,
                       completions=True, collect_assignments=True)
    assert eng.release_path == "device"
    return eng, eng.run(), (ec, ep, scen)


def test_release_program_keeps_its_name_and_holds_no_scatter(release_engine):
    eng, _, _ = release_engine
    call = next(c for c in eng._dev_rel_stage["rel_calls"] if c is not None)
    K = int(call[0].shape[0])
    va = eng._dev_rel_stage["va"]
    args = (eng._init_states(),
            jnp.broadcast_to(va[None], (eng.S,) + va.shape),
            jnp.zeros(eng.S, jnp.int32)) + call
    lowered = eng._release_fn(K).lower(*args)
    text = lowered.as_text()
    assert re.search(r"module @(\S+)", text).group(1) == f"jit_whatif_release_k{K}"
    assert "scatter" not in text
    compiled = lowered.compile().as_text()
    assert compiled.startswith(f"HloModule jit_whatif_release_k{K}")
    assert not re.search(r"\bscatter\(", compiled)


def test_release_rounds_is_reported_and_counts_a_real_collision(release_engine):
    eng, res, (ec, ep, scen) = release_engine
    got = res.fleet_telemetry.summary()
    # 12 nodes and blocks of 128 rows: some node is released many times a block
    assert got["release_rounds"] >= 3
    assert got["release_buckets"]
    off = WhatIfEngine(ec, ep, scen, FrameworkConfig(), chunk_waves=16,
                       completions=False).run()
    assert "release_rounds" not in off.fleet_telemetry.summary()


def test_core_subtracts_the_list_order_sum_from_used(release_engine):
    """``core`` itself, on a built list of the engine's own width."""
    eng, _, _ = release_engine
    core = eng._release_core()
    n_nodes, K = eng.ec.num_nodes, 256
    rng = np.random.default_rng(3)
    nd = rng.integers(0, n_nodes, K).astype(np.int32)
    nd[::9] = -1
    nd[5] = n_nodes - 1
    req = rng.choice(np.array([0.1, 0.3, 0.7], np.float32),
                     (K, eng.ec.num_resources))
    call = next(c for c in eng._dev_rel_stage["rel_calls"] if c is not None)
    tables = [np.asarray(a)[:K] for a in call[2:]]
    state = jax.tree_util.tree_map(lambda a: a[0], eng._init_states())
    used0 = np.asarray(state.used) + np.float32(50.0)
    state = state._replace(used=jnp.asarray(used0))
    out, raw, rounds = jax.jit(
        lambda st: core(st, jnp.asarray(nd), jnp.asarray(req), *tables))(state)
    ok = nd >= 0
    rel = np.zeros((n_nodes, req.shape[1]), np.float32)
    np.add.at(rel, nd[ok], req[ok])
    assert same_bits(out.used, used0 - rel.T)
    G = eng.static3.G
    mm = (tables[0][:, :, None] == np.arange(G)[None, None, :]).sum(1)
    rc = np.zeros((n_nodes, G), np.float32)
    np.add.at(rc, nd[ok], mm[ok].astype(np.float32))
    assert (np.asarray(raw)[:G] == rc.T).all()
    assert int(rounds) == host_depth(nd, BLOCK)
