"""The retry pass reads a queued task's rows ONCE (PR 53): one gather of one
packed ``int32`` row a slot (``ops.tpu.PackedRows``, ``sim.whatif.queued_rows``)
has to hand the pass loop and the record the arrays the column-at-a-time read
gave them, ``gather_slots_device`` / ``gather_extra_device`` /
``table[clip(q, 0)]``: leaf for leaf, dtype, shape and bits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_simulator_tpu.models.encode import PAD
from kubernetes_simulator_tpu.ops import tpu as T
from kubernetes_simulator_tpu.ops import tpu3 as V3
from kubernetes_simulator_tpu.sim.whatif import queued_rows

P, RB, W, S = 257, 64, 8, 3

# Widths behind the task axis: the slot source's term columns, the groups,
# the extra source's, the record's (None: the column is not read there).
SHAPES = {
    # the full Borg cell under budgets (ISSUE 53: 36 values a task)
    "borg_budget": dict(R=3, TO=1, TR=1, TE=1, TP=1, AR=1, AA=1, PA=1, SP=1,
                        G=12, MA=0, MP=0, txn=False, mg=1, an=None, pf=None,
                        resd=True, app=True),
    # the default plugin set: term columns wider than one, the release
    # rewinds anti and preferred terms (want_an / want_pf)
    "k8s_default": dict(R=4, TO=3, TR=2, TE=3, TP=2, AR=2, AA=2, PA=3, SP=2,
                        G=40, MA=2, MP=3, txn=False, mg=5, an=2, pf=3,
                        resd=False, app=False),
    # pod groups wider than the wave: the extra source carries ``txn``
    "wide_gangs": dict(R=2, TO=1, TR=1, TE=1, TP=1, AR=1, AA=1, PA=1, SP=1,
                       G=3, MA=1, MP=1, txn=True, mg=1, an=None, pf=None,
                       resd=True, app=False),
    # every optional column zero wide
    "bare": dict(R=1, TO=0, TR=0, TE=0, TP=0, AR=0, AA=0, PA=0, SP=0, G=0,
                 MA=0, MP=0, txn=False, mg=0, an=None, pf=None, resd=False,
                 app=False),
}


def tables(shape, seed):
    """(SlotSource, ExtraSource, the record's rows) of ``P`` tasks at the
    widths ``shape`` names: ids with ``PAD`` among them, floats with every
    kind of bit pattern (-0.0, inf, NaN payloads, denormals)."""
    rng = np.random.default_rng(seed)
    ints = lambda *tail: jnp.asarray(
        rng.integers(PAD, 1 << 20, size=(P,) + tail, dtype=np.int32))
    bools = lambda *tail: jnp.asarray(rng.random((P,) + tail) < 0.5)

    def floats(*tail):
        bits = rng.integers(-(1 << 31), 1 << 31, size=(P,) + tail,
                            dtype=np.int64).astype(np.int32)
        odd = np.array([0.0, -0.0, np.inf, -np.inf, 1e-42, 0.1],
                       np.float32).view(np.int32)
        pick = rng.random(bits.shape) < 0.3
        bits = np.where(pick, rng.choice(odd, size=bits.shape), bits)
        return jnp.asarray(bits.astype(np.int32).view(np.float32))

    k = shape
    src = T.SlotSource(
        requests=floats(k["R"]), tol_key=ints(k["TO"]), tol_kv=ints(k["TO"]),
        tol_effect=ints(k["TO"]), na_req=ints(k["TR"], k["TE"]),
        na_has_req=bools(), na_pref=ints(k["TP"], k["TE"]),
        na_pref_w=floats(k["TP"]), aff_req=ints(k["AR"]),
        anti_req=ints(k["AA"]), pref_aff=ints(k["PA"]),
        pref_aff_w=floats(k["PA"]), spread_g=ints(k["SP"]),
        spread_skew=ints(k["SP"]), spread_dns=bools(k["SP"]),
        pmg=bools(k["G"]), group_id=ints(),
    )
    xsrc = V3.ExtraSource(
        anti_midx=ints(k["MA"]), pref_midx=ints(k["MP"]), tol_class=ints(),
        na_class=ints(), tier=ints(), txn=ints(3) if k["txn"] else None,
    )
    rec = {"mg": ints(k["mg"])}
    if k["an"] is not None:
        rec["an"] = ints(k["an"])
    if k["pf"] is not None:
        rec["pf"], rec["pw"] = ints(k["pf"]), floats(k["pf"])
    if k["resd"]:
        rec["resd"] = bools()
    if k["app"]:
        rec["app"] = ints()
    return src, xsrc, rec


def by_column(src, xsrc, rec, q):
    """The read as it stood: one gather a column."""
    waves = q.reshape(RB // W, W)
    return (T.gather_slots_device(src, waves),
            V3.gather_extra_device(xsrc, waves),
            jax.tree.map(lambda t: t[jnp.clip(q, 0)], rec))


def queues(seed):
    """``[S, RB]`` task ids, the first and the last task among them, ``PAD``
    holes anywhere (a pass leaves them where it bound)."""
    rng = np.random.default_rng(seed)
    q = rng.integers(0, P, size=(S, RB), dtype=np.int32)
    q[:, 0], q[:, 1] = 0, P - 1
    q[rng.random((S, RB)) < 0.3] = PAD
    q[S - 1] = PAD  # an empty queue
    return jnp.asarray(q)


def assert_same_leaves(got, want):
    got_l, got_def = jax.tree.flatten(got)
    want_l, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and g.shape == w.shape, (g.dtype, g.shape,
                                                           w.dtype, w.shape)
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype == np.float32:
            g, w = g.view(np.int32), w.view(np.int32)
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("mapped", (False, True), ids=("one_queue", "vmapped"))
@pytest.mark.parametrize("name", SHAPES)
def test_the_packed_read_gives_the_columns_leaf_for_leaf(name, mapped):
    src, xsrc, rec = tables(SHAPES[name], seed=len(name))
    rows = T.PackedRows.pack((src, xsrc, rec))
    width = sum(int(np.prod(t.shape[1:])) for t in jax.tree.leaves(
        (src, xsrc, rec)))
    assert rows.table.shape == (P, width) and rows.table.dtype == jnp.int32
    if name == "borg_budget":
        assert width == 36
    q = queues(seed=7)
    if mapped:  # as the program reads: the tables shared, a queue a scenario
        packed = jax.jit(jax.vmap(
            lambda r, q: queued_rows(r, q, W), in_axes=(None, 0)))(rows, q)
        column = jax.vmap(by_column, in_axes=(None, None, None, 0))(
            src, xsrc, rec, q)
    else:
        packed = queued_rows(rows, q[0], W)
        column = by_column(src, xsrc, rec, q[0])
    assert_same_leaves(packed, column)
    slots, extra, _ = packed
    if SHAPES[name]["txn"]:
        assert extra.txn is not None
        holes = np.asarray(q if mapped else q[0]).reshape(extra.txn.shape[:-1]) < 0
        assert (np.asarray(extra.txn)[holes] == V3._NO_TXN).all()
    else:
        assert extra.txn is None


@pytest.mark.parametrize("name", SHAPES)
def test_a_packed_table_unpacks_to_its_columns(name):
    """Every row read back in order is the tree that was packed, under ``jit``
    too (the layout rides the pytree as aux data; the table is its one
    leaf)."""
    tree = tables(SHAPES[name], seed=3)
    rows = jax.jit(T.PackedRows.pack)(tree)
    assert len(jax.tree.leaves(rows)) == 1
    back = jax.jit(lambda r: r.take(jnp.arange(P)))(rows)
    assert_same_leaves(back, tree)


def test_a_column_with_no_packed_form_is_refused():
    with pytest.raises(TypeError, match="int8"):
        T.PackedRows.pack({"a": jnp.zeros((4, 2), jnp.int8)})
