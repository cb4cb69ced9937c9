"""The candidate search both eviction programs call (``sim.whatif.evict_search``,
PR 50), alone against a NumPy reference (``np.nonzero``'s order), at one and
at two block-finding levels; and the structure of the two programs that call
it, read from their lowered text: one compare pass over the places (no plane
of list indices), and a slot's gathers at the number the PR ships."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_simulator_tpu.framework.framework import FrameworkConfig
from kubernetes_simulator_tpu.sim import whatif
from kubernetes_simulator_tpu.sim.whatif import Scenario, WhatIfEngine
from kubernetes_simulator_tpu.utils import profiling

N, V, REC, L, E = 40, 3000, (3, 400), 8, 256


def reference(vassign, live_v, t_node, live_r, task_v, t_id, nodes, on, E):
    node = np.concatenate([vassign, t_node.reshape(-1)])
    live = np.concatenate([live_v, live_r.reshape(-1)])
    task = np.concatenate([task_v, t_id.reshape(-1)])
    entry = np.full(node.shape, len(nodes), np.int64)
    for i in np.nonzero(on)[0]:
        entry[node == nodes[i]] = i
    hit = live & (entry < len(nodes))
    at = np.nonzero(hit)[0]
    hits, at = len(at), at[:E]
    pad = lambda a, fill: np.concatenate([a, np.full(E - len(a), fill, a.dtype)])
    return (hit[:len(vassign)], hit[len(vassign):].reshape(t_node.shape), hits,
            np.arange(E) < hits, pad(at, 0), pad(task[at], 0),
            pad(entry[at], len(nodes)))


def places(seed, listed=(3, 17, 29)):
    """Binds drawn over all nodes, every place live; the list names three
    nodes and pads with -1, every real entry on."""
    rng = np.random.default_rng(seed)
    nodes = np.array(list(listed) + [-1] * (L - len(listed)), np.int32)
    return dict(
        vassign=rng.integers(0, N, size=V).astype(np.int32),
        live_v=np.ones(V, bool),
        t_node=rng.integers(0, N, size=REC).astype(np.int32),
        live_r=np.ones(REC, bool),
        task_v=rng.permutation(V).astype(np.int32),
        t_id=rng.integers(0, V, size=REC).astype(np.int32),
        nodes=nodes, on=nodes >= 0)


def no_hit():
    p = places(0)
    p["vassign"][np.isin(p["vassign"], p["nodes"])] = 0
    p["t_node"][np.isin(p["t_node"], p["nodes"])] = 0
    return p


def across_the_seam():
    """Hits only in the last places of ``vassign`` and the first of the
    record: one block holds both."""
    p = no_hit()
    p["vassign"][-5:] = [3, 0, 17, 17, 29]
    p["t_node"][0, :4] = [29, 0, 3, 3]
    return p


def more_hits_than_slots():
    p = places(1, listed=range(12, 20))
    assert np.isin(p["vassign"], p["nodes"]).sum() > E
    return p


def pad_and_gang_codes():
    """PAD (-1, also the list's own filler) and -2 in ``vassign``, -1 rows
    in the record: none is a bind."""
    p = places(2)
    p["vassign"][::3] = -1
    p["vassign"][1::7] = -2
    p["t_node"][:, ::2] = -1
    return p


def a_dead_place():
    """Binds on a listed node whose release is past (``relb < b``)."""
    p = places(3)
    p["live_v"] = np.random.default_rng(3).random(V) < 0.5
    p["live_r"] = np.random.default_rng(4).random(REC) < 0.5
    assert (np.isin(p["vassign"], p["nodes"]) & ~p["live_v"]).any()
    return p


def an_entry_that_is_off():
    p = places(4)
    p["on"] = p["on"] & (np.arange(L) != 1)
    assert (p["vassign"] == p["nodes"][1]).any()
    return p


def every_entry_off():
    p = places(5)
    p["on"] = np.zeros(L, bool)
    return p


CASES = (no_hit, across_the_seam, more_hits_than_slots, pad_and_gang_codes,
         a_dead_place, an_entry_that_is_off, every_entry_off)


@pytest.mark.parametrize("levels", (1, 2))
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_the_search_against_numpy(case, levels, monkeypatch):
    # 33 blocks here: one level while they are few, two past that
    monkeypatch.setattr(whatif, "_SEARCH_BLOCKS", 4096 if levels == 1 else 8)
    p = case()
    got = jax.jit(whatif.evict_search, static_argnums=8)(*p.values(), E)
    want = reference(*p.values(), E)
    for name, g, w in zip(("hv", "hr", "hits", "ok", "at", "task", "walk"),
                          got, want):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)
    if case is more_hits_than_slots:
        assert int(got[2]) > E and bool(got[3].all())
    if case in (no_hit, every_entry_off):
        assert int(got[2]) == 0


def test_two_levels_past_so_many_blocks(monkeypatch):
    """The number of levels follows from the number of blocks: the text of
    the search holds the second step's gather, a row of offsets a slot,
    exactly where the blocks pass ``_SEARCH_BLOCKS``."""
    monkeypatch.setattr(whatif, "_SEARCH_BLOCKS", 32)
    p = places(0)
    text = lambda V: jax.jit(whatif.evict_search, static_argnums=8).lower(
        *(dict(p, vassign=np.zeros(V, np.int32), live_v=np.ones(V, bool),
               task_v=np.zeros(V, np.int32)).values()), E).as_text()
    gathers = lambda t: sorted(re.findall(
        r'"stablehlo\.gather"\(.*\) -> (tensor<[\dx]+x\w+>)', t))
    few = 32 * 128 - REC[0] * REC[1]
    hit_row, by_place = f"tensor<{E}x128xi1>", f"tensor<2x{E}xi32>"
    assert gathers(text(few)) == sorted([hit_row, by_place])
    assert gathers(text(few + 1)) == sorted(
        [hit_row, by_place, f"tensor<{E}x128xi32>"])


# -- the two programs that call it


class _Captured(Exception):
    pass


def evict_program(eng):
    """(the jitted eviction program, its first call's arguments as shapes):
    the batch is started and ends at the first call of the program, which is
    made at its first boundary whatever the timelines say."""
    staged, build, got = eng._stage_events, eng._evict_fn, {}

    def stage_events():
        evs = staged()
        evs["calls"][0] = next(c for c in evs["calls"] if c is not None)
        return evs

    def evict_fn():
        fn = build()

        def spy(*args):
            got["call"] = (fn, profiling.shape_structs(args))
            raise _Captured

        spy.__name__ = fn.__name__
        return spy

    eng._stage_events, eng._evict_fn = stage_events, evict_fn
    try:
        with pytest.raises(_Captured):
            eng.run()
    finally:
        del eng._stage_events, eng._evict_fn
    return got["call"]


def whatif_budget(app, most, grace, out_for):
    from kubernetes_simulator_tpu.sim.runtime import DisruptionBudget

    return DisruptionBudget(
        app, np.full(int(app.max()) + 1, most, np.int32), grace, out_for)


def small_engine(kind):
    if kind == "drain":
        import test_whatif_events_device as cells

        ec, ep, tb = cells.cell()
        scenarios = [Scenario(events=tl) for tl in (
            [], cells.timeline(cells.plan(tb, 2, range(6))),
            cells.timeline(cells.plan(tb, 1, (10, 11, 12), 1)))]
    else:
        import test_whatif_budget_device as cells

        ec, ep, tb = cells.cell()
        app = np.asarray(ep.app_id)
        budget = whatif_budget(app, 1, 8, 1)
        scenarios = [Scenario(events=cells.timeline(tb, *ev), budget=budget)
                     for ev in ([], cells.cordons(1, range(4), 2),
                                cells.cordons(1, (8, 9), 2))]
    return WhatIfEngine(
        ec, ep, scenarios, FrameworkConfig(), wave_width=8, chunk_waves=16,
        completions=True, retry_buffer=64, collect_assignments=True)


PROGRAMS = {}


def program(kind):
    if kind not in PROGRAMS:
        eng = small_engine(kind)
        fn, structs = evict_program(eng)
        PROGRAMS[kind] = (eng, fn, structs)
    return PROGRAMS[kind]


# gathers with E output rows a program holds: the search's two (a slot's hit
# row; its place's task and node) and what its callers read by task or entry.
# Before PR 50: 11 and 7 (the search four and five of them, and the budgeted
# program's ``forced_l[walk]``).
E_ROW_GATHERS = {"drain": 9, "budget": 3}


@pytest.mark.parametrize("kind", ("drain", "budget"))
def test_the_lowered_program_compares_the_places_once_and_gathers_twice(kind):
    eng, fn, structs = program(kind)
    sizes = eng._evict_sizes
    S, Lk, Ek = eng.S, sizes["L"], sizes["E"]
    vassign, t_node = structs[1], structs[2].t_node
    V, (nb, RB) = vassign.shape[1], t_node.shape[1:]
    # the slots' size can be told from the others in the text
    assert Ek not in {S, Lk, RB, nb, 128, sizes.get("Ea")}
    text = fn.lower(*structs).as_text()
    reduced = lambda dtype: [
        bool(re.search(
            rf"stablehlo\.reduce.*tensor<{S}x({dims}x{Lk}|{Lk}x{dims})x{dtype}>"
            rf".* -> tensor<{S}x{dims}x{dtype}>", text))
        for dims in (V, f"{nb}x{RB}")]
    # the compare is there, over the buffer and over the record, reduced to
    # the hit bit, and no reduce over L gives a [places] plane of integers
    # (the list indices, before PR 50)
    assert reduced("i1") == [True, True]
    assert reduced("i32") == [False, False]
    gathers = re.findall(
        r'"stablehlo\.gather"\(.*\) -> tensor<([\dx]+)x\w+>', text)
    e_rows = [g for g in gathers if str(Ek) in g.split("x")[1:]]
    assert len(e_rows) == E_ROW_GATHERS[kind], e_rows
    # the search's own, lowered alone at the program's shapes
    alone = jax.jit(jax.vmap(
        lambda v, n, i, nodes: whatif.evict_search(
            v, v >= 0, n, n >= 0, jnp.arange(V, dtype=jnp.int32), i, nodes,
            nodes >= 0, Ek))).lower(
        vassign, t_node, t_node, jax.ShapeDtypeStruct((S, Lk), jnp.int32)
    ).as_text()
    mine = re.findall(
        r'"stablehlo\.gather"\(.*\) -> tensor<([\dx]+)x\w+>', alone)
    assert sorted(mine) == sorted(
        [f"{S}x{Ek}x128", f"{S}x2x{Ek}"]), mine
