#!/usr/bin/env python3
"""The forms the retry pass's read of its queue BY TASK ID can take, timed on
one chip (PERF.md §5 and §6, PR 53; after ``chip_forms_evict_search.py``): not
the pass program, the read alone, over the tables of a benchmark cell's own
engine (``borg10k-drain128``: 337,430 tasks, 35 ``int32`` values a task) at
``[S, RB]`` = ``[128, 8192]`` and ``[128, 4096]``, with ids drawn as a queue's
are: distinct tasks at the front of the buffer, ``PAD`` behind (``full``: the
whole buffer queued; ``part``: a depth drawn evenly a scenario).

Every form hands on ``(slots, extra, rec)``, ALL their leaves, and has to
hand on ``columns``' values, dtype, shape and bits (asserted; the pass
program keeps only the leaves its wave step reads, about half: the ledger's
86 ms at 8,192 is ``columns`` with the dead ones gone):

* ``columns``: the read as it stood before PR 53: ``gather_slots_device``,
  ``gather_extra_device`` and ``table[clip(q, 0)]`` a row of the record, one
  gather a column.
* ``packed``: ``sim.whatif.queued_rows`` itself, the shipped read: ONE gather
  of one row a slot from the ``int32 [P, C]`` table the engine staged
  (``ops.tpu.PackedRows``), the rows turned column-major once, the leaves
  slices of that.
* ``packed_built``: the same with the table packed INSIDE the program, from
  the columns the parent's program received (no table kept on the device).
* ``packed128``: the table padded to 128 lanes.
* ``packed_split``: two tables, the ``float32`` columns and the rest.
* ``packed_minor``: one gather, the leaves sliced off the rows' minor axis
  with no turn (what ``take`` did first: every one-wide leaf a 128-lane
  array).

One JSON line a size and fill: ms a call, median of five after a warm-up,
and written to ``chiprun_out/forms_retry_gather.jsonl``. On the chip:

    python3 scripts/chip_forms_retry_gather.py [8192|4096] [full|part] [form ...]
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
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]

CELL, S, SIZES, FILLS = "borg10k-drain128", 128, (8192, 4096), ("full", "part")
FORMS = ("columns", "packed", "packed_built", "packed128", "packed_split",
         "packed_minor")


class _Staged(Exception):
    pass


def cell_rows(rehearse: bool):
    """(the cell's engine, the packed table it staged for its pass program):
    the batch is cut at its first boundary's pass."""
    import run as bench

    _, _, config, traffic = bench.load_cell(CELL)
    engine = bench.prepare(config, traffic, 7, rehearse, {})[2].engine
    kept = {}

    def keep(*args):
        kept["rows"] = args[2]
        raise _Staged

    keep.__name__ = engine._retry_fn.__name__
    engine._retry_fn = keep
    try:
        engine.run()
    except _Staged:
        pass
    return engine, kept["rows"]


def queues(P: int, RB: int, fill: str, seed: int = 0) -> jax.Array:
    rng = np.random.default_rng(seed)
    depth = (np.full(S, RB) if fill == "full"
             else rng.integers(0, RB + 1, size=S))
    q = np.stack([rng.choice(P, size=RB, replace=False) for _ in range(S)])
    return jnp.asarray(
        np.where(np.arange(RB)[None, :] < depth[:, None], q, -1), jnp.int32)


def forms(rows, W: int):
    """{form: (one scenario's read ``f(q, *tables)``, the tables)}."""
    from kubernetes_simulator_tpu.ops import tpu as T
    from kubernetes_simulator_tpu.ops import tpu3 as V3
    from kubernetes_simulator_tpu.sim.whatif import queued_rows

    P, C = rows.table.shape
    tree = jax.jit(lambda r: r.take(jnp.arange(P)))(rows)  # the columns back
    waves = lambda a: a.reshape((a.shape[0] // W, W) + a.shape[1:])

    def columns(q, src, xsrc, rec):
        return (T.gather_slots_device(src, waves(q)),
                V3.gather_extra_device(xsrc, waves(q)),
                jax.tree.map(lambda t: t[jnp.clip(q, 0)], rec))

    leaves, treedef = jax.tree.flatten(tree)
    is_f = [a.dtype == jnp.float32 for a in leaves]
    part = lambda keep: T.PackedRows.pack(
        [a for a, f in zip(leaves, is_f) if f == keep])

    def split(q, floats, rest):
        got = {True: iter(floats.take(jnp.clip(q, 0))),
               False: iter(rest.take(jnp.clip(q, 0)))}
        src_r, xsrc_r, rec = jax.tree.unflatten(
            treedef, [next(got[f]) for f in is_f])
        return (jax.tree.map(waves, T.slots_of_rows(src_r, q)),
                jax.tree.map(waves, V3.extra_of_rows(xsrc_r, q)), rec)

    class MinorRows(T.PackedRows):
        def take(self, ids):
            got, out, at = self.table[ids], [], 0
            for tail, dtype in self.cols:
                n = int(np.prod(tail, dtype=np.int64))
                a, at = got[..., at:at + n], at + n
                a = (jax.lax.bitcast_convert_type(a, jnp.float32)
                     if dtype == "float32" else a != 0 if dtype == "bool" else a)
                out.append(a.reshape(ids.shape + tail))
            return jax.tree.unflatten(self.treedef, out)

    jax.tree_util.register_pytree_node_class(MinorRows)
    wide = T.PackedRows(jnp.pad(rows.table, ((0, 0), (0, -C % 128))),
                        rows.treedef, rows.cols)
    packed = lambda q, r: queued_rows(r, q, W)
    return {
        "columns": (columns, tree),
        "packed": (packed, (rows,)),
        "packed_built": (lambda q, *t: queued_rows(T.PackedRows.pack(t), q, W),
                         tree),
        "packed128": (packed, (wide,)),
        "packed_split": (split, (part(True), part(False))),
        "packed_minor": (packed, (MinorRows(
            rows.table, rows.treedef, rows.cols),)),
    }


def ms(fn, q, tables):
    f = jax.jit(jax.vmap(fn, in_axes=(0,) + (None,) * len(tables)))
    t = time.perf_counter()
    out = jax.block_until_ready(f(q, *tables))
    first = time.perf_counter() - t
    took = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(f(q, *tables))
        took.append(1e3 * (time.perf_counter() - t))
    return statistics.median(took), first, out


def same(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    bits = lambda x: np.asarray(x).view(
        np.int32 if x.dtype == jnp.float32 else x.dtype)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and x.shape == y.shape
        and np.array_equal(bits(x), bits(y)) for x, y in zip(la, lb))


def main(argv) -> int:
    global S
    rehearse = "--rehearse" in argv
    if jax.devices()[0].platform != "tpu" and not rehearse:
        print("no TPU: forms off the chip need --rehearse", file=sys.stderr)
        return 1
    argv = [a for a in argv if a != "--rehearse"]
    sizes = [int(a) for a in argv if a.isdigit()] or list(SIZES)
    fills = [a for a in argv if a in FILLS] or list(FILLS)
    names = [a for a in argv if a in FORMS] or list(FORMS)
    if rehearse:  # the control flow on the CPU: no timing
        S, sizes = 4, [s // 64 for s in sizes]
    engine, rows = cell_rows(rehearse)
    P, C = rows.table.shape
    table = forms(rows, engine.wave_width)
    out = ROOT / "chiprun_out" / "forms_retry_gather.jsonl"
    out.parent.mkdir(exist_ok=True)
    for RB in sizes:
        for fill in fills:
            q = queues(P, RB, fill)
            line = {"platform": jax.devices()[0].platform, "cell": CELL,
                    "S": S, "RB": RB, "P": P, "C": C, "fill": fill,
                    "queued_mean": float((q >= 0).sum(1).mean())}
            want = None
            for form in names:
                fn, tables = table[form]
                try:
                    line[form + "_ms"], first, got = ms(fn, q, tables)
                except Exception as e:  # a form the chip refuses is a finding
                    line[form + "_ms"] = f"{type(e).__name__}: {str(e)[:200]}"
                    print(f"{RB} {fill} {form}: {line[form + '_ms']}",
                          file=sys.stderr, flush=True)
                    continue
                want = got if want is None else want
                line[form + "_same"] = same(got, want)
                print(f"{RB} {fill} {form}: {line[form + '_ms']:.3f} ms (first "
                      f"call {first:.1f} s) same={line[form + '_same']}",
                      file=sys.stderr, flush=True)
                assert line[form + "_same"], form
                del got
            print(json.dumps(line), flush=True)
            if line["platform"] == "tpu":
                with open(out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
