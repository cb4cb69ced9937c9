#!/usr/bin/env python3
"""The forms the eviction program's candidate search can take, timed on one
chip at both cells' shapes (PERF.md §6, PR 50; after
``benchmark/tests/chip_forms_drain.py``, which ranked the search's two parts
against sorts and scatters): not the program, the search alone on made-up
data of the same shapes (128 scenarios; 341,527 places of the placement
buffer and 22 x 8,192 of the record; 10,000 nodes; ``borg10k-budget128``: a
list of 384 entries, 8,192 slots; ``borg10k-drain128``: 64 and 2,048).

``shipped`` is ``sim.whatif.evict_search`` itself; every other form is the
one function below with one thing changed, and has to hand on the values
``shipped`` hands on (asserted):

* ``parent``: the search as it stood before PR 50: the list compared twice
  over the places (the hit bit and the entry's index), a gather for the
  super-row of starts, for the block's own start, for the hit row, for the
  task and for the entry's index.
* the block of a slot: one level (every block's start compared with the
  slot) or two (rows of 128 blocks first); the row of starts by a gather, a
  select over the rows, or a product with the row's one-hot; the block's
  own start by a gather or as the largest start the slot has reached.
* a slot's reads: the hit row as ``pred`` or ``int32``; task and node by two
  gathers, by one gather of a ``[2, places]`` table, or with no second
  gather at all (``rows``: the block's 128 tasks and nodes read with its hit
  row, the lane selected densely).
* the count within the row: ``cumsum`` or a product with a triangle.
* the compare over the places with the list on the minor or the major axis.

One JSON line a shape: ms a call, median of five after a warm-up, and
written to ``chiprun_out/forms_evict_search.jsonl``. On the chip:

    python3 scripts/chip_forms_evict_search.py [budget|drain|wide4|wide16] [form ...]
"""

import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

S, V, REC, N = 128, 341_527, (22, 8_192), 10_000
SHAPES = {"budget": {"L": 384, "E": 8_192, "on": 60},
          "drain": {"L": 64, "E": 2_048, "on": 24},
          # four and sixteen times the places (16,384 and 65,536 blocks), for
          # the count of blocks past which two levels pay: not a cell's shape
          "wide4": {"L": 64, "E": 8_192, "on": 12, "V": 4 * 524_288 - 180_224},
          "wide16": {"L": 16, "E": 8_192, "on": 3, "V": 16 * 524_288 - 180_224,
                     "S": 32}}

BASE = dict(index_planes=False, levels=2, sup_read="select", own="max",
            row="pred", cand="stack", prefix="cumsum", list_axis="minor",
            forced="dense", premask=False)
FORMS = {
    "parent2": dict(index_planes=True, levels=2, sup_read="gather",
                    own="gather", cand="two", forced="gather"),
    "parent1": dict(index_planes=True, levels=1, own="gather", cand="two",
                    forced="gather"),
    # the issue's steps, one at a time
    "hit_only2": dict(levels=2, sup_read="gather", own="gather", cand="two"),
    "hit_only1": dict(levels=1, own="gather", cand="two"),
    "own_max2": dict(levels=2, sup_read="gather", cand="two"),
    "own_max1": dict(levels=1, cand="two"),
    "sup_select": dict(cand="two"),
    "sup_dot": dict(sup_read="dot", cand="two"),
    "stack2": dict(),
    "stack1": dict(levels=1),
    "rows2": dict(cand="rows"),
    "rows1": dict(levels=1, cand="rows"),
    "row_int32": dict(row="int32"),
    "prefix_dot": dict(prefix="dot"),
    "list_major": dict(list_axis="major"),
    # an entry that is off reads a node no place holds: the pass over the
    # places is a compare and an or, no and
    "premask1": dict(levels=1, premask=True),
    "premask1_major": dict(levels=1, premask=True, list_axis="major"),
    "premask1_dot": dict(levels=1, premask=True, prefix="dot"),
    "premask1_major_dot": dict(levels=1, premask=True, list_axis="major",
                               prefix="dot"),
    "stack2_gather": dict(sup_read="gather"),
    "premask2_gather": dict(sup_read="gather", premask=True),
}


def search(vassign, live_v, t_node, live_r, task_v, t_id, nodes, on, flag, *,
           E, index_planes, levels, sup_read, own, row, cand, prefix,
           list_axis, forced, premask):
    """One scenario's search in the form the keywords name:
    ``(hv, hr, hits, at, task, walk, flagged)``, the last three 0 / False
    past ``hits``."""
    L = nodes.shape[0]
    ar_L = jnp.arange(L, dtype=jnp.int32)
    slot = jnp.arange(E, dtype=jnp.int32)

    if premask:
        nodes = jnp.where(on, nodes, jnp.iinfo(jnp.int32).min)
        on = True

    def listed(x):
        if list_axis == "major" and premask:
            return (nodes.reshape((L,) + (1,) * x.ndim) == x[None]).any(0), None
        if premask:
            return (x[..., None] == nodes).any(-1), None
        if list_axis == "major":
            eq = (nodes.reshape((L,) + (1,) * x.ndim) == x[None]) & on.reshape(
                (L,) + (1,) * x.ndim)
            return eq.any(0), (eq * ar_L.reshape(eq.shape[:1] + (1,) * x.ndim)
                               ).sum(0, dtype=jnp.int32)
        eq = (x[..., None] == nodes) & on
        return eq.any(-1), (eq * ar_L).sum(-1, dtype=jnp.int32)

    hv, lv = listed(vassign)
    hr, lr = listed(t_node)
    hv, hr = hv & live_v, hr & live_r
    places = vassign.shape[0] + t_node.size
    pad = -places % (128 * (128 if levels == 2 else 1))
    cat = lambda v, r, fill: jnp.concatenate(
        [v, r.reshape(-1), jnp.full((pad,), fill, v.dtype)])
    hit = cat(hv, hr, False).reshape(-1, 128)
    count = hit.sum(1, dtype=jnp.int32)
    start = jnp.cumsum(count) - count
    hits = count.sum()
    if levels == 1:
        le = start[None, :] <= slot[:, None]
        block = le.sum(1, dtype=jnp.int32) - 1
        reached = jnp.where(le, start[None, :], 0)
    else:
        rows = count.reshape(-1, 128).sum(1, dtype=jnp.int32)
        row_start = jnp.cumsum(rows) - rows
        sup = (row_start[None, :] <= slot[:, None]).sum(1, dtype=jnp.int32) - 1
        starts = start.reshape(-1, 128)
        if sup_read == "gather":
            srow = starts[sup]
        else:
            mine = sup[:, None] == jnp.arange(starts.shape[0], dtype=jnp.int32)
            if sup_read == "select":
                srow = jnp.where(mine[:, :, None], starts[None], 0).sum(
                    1, dtype=jnp.int32)
            else:  # counts up to E: three bf16 digits of 7 bits
                digits = jnp.stack([(starts >> s) & 127 for s in (0, 7, 14)])
                got = jnp.einsum(
                    "er,drl->del", mine.astype(jnp.bfloat16),
                    digits.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
                srow = got[0] + (got[1] << 7) + (got[2] << 14)
        le = srow <= slot[:, None]
        block = sup * 128 + le.sum(1, dtype=jnp.int32) - 1
        reached = jnp.where(le, srow, 0)
    own_start = start[block] if own == "gather" else reached.max(1)
    rank = slot - own_start
    ok = slot < hits
    tasks, nodes_at = cat(task_v, t_id, 0), cat(vassign, t_node, -1)
    if cand == "rows":
        table = jnp.concatenate([
            jnp.where(hit, tasks.reshape(-1, 128), -1),
            nodes_at.reshape(-1, 128)], axis=1)
        got = table[block]
        hrow = got[:, :128] >= 0
    else:
        hrow = (hit.astype(jnp.int32)[block] > 0 if row == "int32"
                else hit[block])
    if prefix == "dot":
        tri = (jnp.arange(128)[:, None] <= jnp.arange(128)[None, :])
        upto = jnp.dot(hrow.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    else:
        upto = jnp.cumsum(hrow.astype(jnp.int32), axis=1)
    pick = (upto == rank[:, None] + 1) & hrow
    lane = jnp.argmax(pick, axis=1).astype(jnp.int32)
    at = jnp.where(ok, block * 128 + lane, 0)
    if index_planes:
        task = tasks[at]
        walk = cat(lv, lr, 0)[at]
    else:
        if cand == "rows":
            task = jnp.where(pick, got[:, :128], 0).sum(1, dtype=jnp.int32)
            node = jnp.where(pick, got[:, 128:], 0).sum(1, dtype=jnp.int32)
        elif cand == "stack":
            task, node = jnp.stack([tasks, nodes_at])[:, at]
        else:
            task, node = tasks[at], nodes_at[at]
        walk = (((node[:, None] == nodes) & on) * ar_L).sum(
            -1, dtype=jnp.int32)
    flagged = (flag[walk] if forced == "gather"
               else ((walk[:, None] == ar_L) & flag).any(-1))
    return (hv, hr, hits, at, jnp.where(ok, task, 0), jnp.where(ok, walk, 0),
            ok & flagged)


def shipped(E):
    from kubernetes_simulator_tpu.sim.whatif import evict_search

    def one(vassign, live_v, t_node, live_r, task_v, t_id, nodes, on, flag):
        hv, hr, hits, ok, at, task, walk = evict_search(
            vassign, live_v, t_node, live_r, task_v, t_id, nodes, on, E)
        L = nodes.shape[0]
        flagged = ((walk[:, None] == jnp.arange(L, dtype=jnp.int32))
                   & flag).any(-1)
        return (hv, hr, hits, at, jnp.where(ok, task, 0),
                jnp.where(ok, walk, 0), ok & flagged)

    return one


def data(L, on, S=S, V=V, seed=0):
    """Places whose node is drawn evenly (PAD and -2 among them), a list of
    ``L`` distinct nodes of which the first ``on`` are on: about
    ``places * on / N`` hits a scenario, under ``E`` at both shapes."""
    rng = np.random.default_rng(seed)
    vassign = rng.integers(-2, N, size=(S, V), dtype=np.int32)
    t_node = rng.integers(-1, N, size=(S,) + REC, dtype=np.int32)
    nodes = np.stack([rng.choice(N, size=L, replace=False) for _ in range(S)])
    return tuple(jnp.asarray(a) for a in (
        vassign, rng.random((S, V)) < 0.9, t_node,
        rng.random((S,) + REC) < 0.9,
        rng.permutation(V).astype(np.int32),
        rng.integers(0, V, size=(S,) + REC, dtype=np.int32),
        nodes.astype(np.int32), np.arange(L)[None, :].repeat(S, 0) < on,
        rng.random((S, L)) < 0.5))


def ms(fn, args):
    f = jax.jit(jax.vmap(fn, in_axes=(0, 0, 0, 0, None, 0, 0, 0, 0)))
    t = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first = time.perf_counter() - t
    took = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        took.append(1e3 * (time.perf_counter() - t))
    return statistics.median(took), first, out


def main(argv) -> int:
    if jax.devices()[0].platform != "tpu" and "--rehearse" not in argv:
        print("no TPU: forms off the chip need --rehearse", file=sys.stderr)
        return 1
    if "--rehearse" in argv:  # the control flow on the CPU: no timing
        global S, V, REC, N
        S, V, REC, N = 4, 20_000, (3, 512), 500
        for shape in SHAPES.values():
            shape.update(E=shape["E"] // 8, on=max(shape["on"] // 4, 1))
            shape.pop("S", None)
            if "V" in shape:
                shape["V"] = 3 * V
    argv = [a for a in argv if a != "--rehearse"]
    shapes = [a for a in argv if a in SHAPES] or ["budget", "drain"]
    forms = [a for a in argv if a not in SHAPES] or ["shipped"] + list(FORMS)
    out = ROOT / "chiprun_out" / "forms_evict_search.jsonl"
    out.parent.mkdir(exist_ok=True)
    for name in shapes:
        L, E, on = (SHAPES[name][k] for k in ("L", "E", "on"))
        args = data(L, on, SHAPES[name].get("S", S), SHAPES[name].get("V", V))
        line = {"platform": jax.devices()[0].platform, "shape": name,
                "S": args[0].shape[0],
                "places": args[0].shape[1] + REC[0] * REC[1], "L": L, "E": E}
        want = None
        for form in forms:
            fn = shipped(E) if form == "shipped" else (
                lambda *a, _kw=dict(BASE, **FORMS[form]): search(
                    *a, E=E, **_kw))
            try:
                line[form + "_ms"], first, got = ms(fn, args)
            except Exception as e:  # a form the compiler refuses is a finding
                line[form + "_ms"] = f"{type(e).__name__}: {str(e)[:200]}"
                continue
            print(f"{name} {form}: {line[form + '_ms']:.3f} ms "
                  f"(first call {first:.1f} s)", file=sys.stderr, flush=True)
            if want is None:
                want = got
                line["hits_mean"] = float(got[2].mean())
                line["hits_max"] = int(got[2].max())
            for a, b in zip(got, want):
                assert bool((a == b).all()), form
        print(json.dumps(line), flush=True)
        if line["platform"] == "tpu":
            with open(out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
