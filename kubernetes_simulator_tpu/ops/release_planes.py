"""Node-space accumulators of a release list, through node-factored one-hot
contractions (the what-if release program's one mechanism).

A release list is K rows ``(node, request[R], counts[C])``; ``node == -1`` is a
row that releases nothing. Wanted, per scenario: ``rel[r, n]``, the float32 sum
of the requests released on node ``n`` taken IN LIST ORDER (the arithmetic of
``models.state.release_delta``: ``np.add.at``, bit for bit), and ``rc[c, n]``,
the integer sums of the count channels.

Placing K values at K of N positions costs K x N somewhere. Here the node is
factored, ``n = 128 * hi + lo``, and
``out[c, hi, lo] = sum_k val[k, c] * (hi_k == hi) * (lo_k == lo)`` is a
``[C * NH, K] x [K, 128]`` product on the MXU whose two one-hots cost
K x (NH + 128) to build, not K x N. Operands are bfloat16 (one MXU pass),
accumulation is float32:

* a count channel is a small integer: exact in bfloat16, and integer sums are
  exact in any order. One product over the whole list.
* a request is any float32 and resource sums are NOT order-free (0.1 core is no
  dyadic rational). Two things make the product exact all the same. (1) Rows of
  one product are on pairwise different nodes: each row's collision rank (how
  many earlier rows of its block hit its node) is computed first, and round
  ``r`` contracts the rows of rank ``r`` only, ``rel += P_r`` for r = 0, 1, ...:
  a node's releases are added in rank order, which is list order, and every
  other element of ``P_r`` is 0.0. (2) A float32 is cut into three bfloat16
  parts on DISJOINT bits (truncation, not rounding) that form one more
  contracted axis: an output element is a sum of zeros and those three parts, every
  partial sum of which is a float32 on a subset of the value's own bits, so no
  order of accumulation rounds. Both forms of a single-term product (this one
  and ``precision=HIGHEST``) returned the value itself on the v5e for every
  Borg request and 92,160 random float32s (PERF.md, PR 28); this one is half
  the passes and a third of the output.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

LANES = 128  # the node's low factor: one MXU tile's width
BLOCK = 128  # rows of one rank block, where it divides the list

_F32, _BF16 = jnp.float32, jnp.bfloat16


def collision_rank(nd):
    """``rank[k] = #{j < k : nd[j] == nd[k], nd[j] >= 0}`` for a block ``nd [W]``."""
    W = nd.shape[0]
    ar = jnp.arange(W, dtype=jnp.int32)
    earlier_same = (
        (nd[:, None] == nd[None, :])
        & (nd[None, :] >= 0)
        & (ar[None, :] < ar[:, None])
    )
    return jnp.sum(earlier_same, axis=1, dtype=jnp.int32)


def bf16_parts(x):
    """Three float32 arrays, each exact in bfloat16, on disjoint bits of ``x``:
    ``x == p0 + p1 + p2`` summed in any order. Bit masks, not ``astype``
    round trips, which XLA may elide (``xla_allow_excess_precision``)."""
    top = jnp.uint32(0xFFFF0000)

    def cut(v):
        bits = lax.bitcast_convert_type(v, jnp.uint32)
        return lax.bitcast_convert_type(bits & top, _F32)

    p0 = cut(x)
    r = x - p0
    p1 = cut(r)
    return p0, p1, r - p1


def _node_onehots(nd, NH):
    """``A [W, NH]`` bool (no row of it set for ``nd == -1``), ``B [W, 128]`` bf16."""
    hi, lo = nd >> 7, nd & (LANES - 1)
    A = (hi[:, None] == jnp.arange(NH, dtype=jnp.int32)[None, :]) & (
        nd[:, None] >= 0
    )
    B = (lo[:, None] == jnp.arange(LANES, dtype=jnp.int32)[None, :]).astype(_BF16)
    return A, B


def _place(vals, A, B):
    """``vals [..., W, C]`` (bf16-exact float32) at the rows' nodes, summed over
    every leading axis too: ``[C, NH, 128]``."""
    lead = vals.ndim - 2
    X = jnp.where(A[:, None, :], vals[..., None], 0.0).astype(_BF16)
    B = jnp.broadcast_to(B, vals.shape[:-1] + B.shape[-1:])
    over = tuple(range(lead + 1))
    return lax.dot_general(X, B, ((over, over), ((), ())),
                           preferred_element_type=_F32)


def release_planes(nd, req, counts, num_nodes, *, block=None, axis_name=None):
    """``(rel [R, N], rc [C, N], rounds)`` of one scenario's release list.

    ``nd [K]`` int32, ``req [K, R]`` any float32, ``counts [K, C]`` integers that
    are exact in bfloat16 (cut a wider one with ``bf16_parts`` and add its three
    planes), their sums per node below 2**24. ``block`` divides K (default
    ``BLOCK`` where that does, else K). ``rounds`` is the largest number of
    rank rounds a block needed, at least 1. Under ``vmap`` give the mapped
    axis's name: every scenario then loops to the largest rank among them
    (adding 0.0 is exact), the loop has ONE trip count, and XLA fuses the
    accumulation into the product instead of selecting per scenario over the
    whole plane.
    """
    K, R = req.shape
    NH = -(-num_nodes // LANES)
    Wr = block or (BLOCK if K % BLOCK == 0 else K)
    nb = K // Wr

    A, B = _node_onehots(nd, NH)
    rc = _place(counts, A, B)

    def body(carry, xs):
        rel, rounds = carry
        nd_b, req_b = xs
        rank = collision_rank(nd_b)
        top = jnp.max(jnp.where(nd_b >= 0, rank, 0))
        last = top if axis_name is None else lax.pmax(top, axis_name)
        A_b, B_b = _node_onehots(nd_b, NH)
        # the three parts are one more contracted axis: the MXU recombines them
        parts = jnp.stack(bf16_parts(req_b))  # [3, Wr, R]

        def one_round(r, rel):
            vals = jnp.where((rank == r)[:, None], parts, 0.0)
            return rel + _place(vals, A_b, B_b)

        rel = lax.fori_loop(0, last + 1, one_round, rel)
        return (rel, jnp.maximum(rounds, top + 1)), None

    (rel, rounds), _ = lax.scan(
        body,
        (jnp.zeros((R, NH, LANES), _F32), jnp.int32(0)),
        (nd.reshape(nb, Wr), req.reshape(nb, Wr, R)),
    )
    flat = lambda a: a.reshape(a.shape[0], NH * LANES)[:, :num_nodes]
    return flat(rel), flat(rc), rounds
