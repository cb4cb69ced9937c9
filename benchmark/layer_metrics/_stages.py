"""Not a metric: the one pass the stage metrics share.

Device op events carry the HLO instruction's name (``%fusion.628 = s32[]
fusion(...)``); which stage of the program an instruction belongs to is
in the executable's HLO, and the program hands that out as
``utils.profiling.stage_tables()``: {XLA module name: {instruction name:
stage path}}, a stage path being one of ``profiling.STAGES``
(``ksim.reads`` ... ``ksim.commit``) with the plugin's name beneath
``ksim.filter_score``, or "" under no scope.

``read(ctx)`` goes ONCE over device 0's op events, keeps the leaves (a
``while`` event spans its body's ops: the reducer's rule) that start
inside an execution of the chunk program, looks each up by module and
instruction name, and keeps on ``ctx`` the seconds and the event count of
every stage path. Time whose instruction is in no table, or under no
``ksim.`` scope, is counted under "": ``chunk_unattributed_share`` is the
check on the join itself. Every stage path is printed on stderr with its
ms and ops a wave; the per-plugin lines are no ledger metric.

The op events do not tile a program: scalar arithmetic between two fusions
runs on the scalar core and has no event (266 of the 414 instructions of
this cell's scan body, my chip run, PR 25). So beside each stage's op time
the pass keeps the time between the end of the op event before and the
start of each of its own (``between``): what issuing that op cost, the
scalar work that feeds it included. It is printed, not added to the stage
metrics, which are op time.

Returns None, and never raises, where the program has no
``stage_tables`` (an older tree), the tables are empty, or no chunk
program ran in the window.
"""

import bisect
import re
import sys
import time

from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM

WHILE = re.compile(r"^%?while[.\d]* ")
INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
MODULE = re.compile(r"^([\w.\-]+)")
KEY = "stage_seconds"  # where the result is kept on ctx


def say(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def stage_tables():
    """The program's tables, or None: an older tree has none."""
    try:
        from kubernetes_simulator_tpu.utils import profiling

        t = time.perf_counter()
        tables = profiling.stage_tables()
    except Exception as e:  # a reader never takes the result line down
        say(f"no stage tables ({type(e).__name__}: {e})")
        return None
    if not any(tables.values()):
        return None
    say(f"stage tables of {sorted(tables)} in {time.perf_counter() - t:.1f}s")
    return tables


def read(ctx):
    """{"waves", "seconds": {path: s}, "ops": {path: n}} or None."""
    if KEY not in ctx:
        ctx[KEY] = _read(ctx)
    return ctx[KEY]


def _read(ctx):
    trace = ctx["trace"]
    rx, (w0, w1) = re.compile(CHUNK_PROGRAM), trace.window
    runs = sorted((s, s + d, MODULE.match(n).group(1))
                  for n, s, d in trace.devices[0]["modules"]
                  if rx.search(n) and s >= w0 and s + d <= w1)
    tables = stage_tables() if runs else None
    if not tables:
        return None
    starts = [r[0] for r in runs]
    known = {}  # event name -> (leaf?, instruction name)
    ns, ops, between = {}, {}, {}
    at, done = -1, 0  # the run of the last leaf, and where that leaf ended
    for name, s, d in trace.devices[0]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        if name not in known:
            known[name] = (not WHILE.match(name),
                           INSTRUCTION.match(name).group(1))
        leaf, instruction = known[name]
        if not leaf:
            continue
        path = tables.get(runs[i][2], {}).get(instruction, "")
        ns[path] = ns.get(path, 0) + d
        ops[path] = ops.get(path, 0) + 1
        if i != at:
            at, done = i, runs[i][0]
        between[path] = between.get(path, 0) + max(0, s - done)
        done = max(done, s + d)
    waves = len(runs) * ctx["shape"]["chunk_waves"]
    per_wave = 1e6 * waves  # ns a wave -> ms
    for path in sorted(ns):
        say(f"stage {path or '(none)'} {ns[path] / per_wave:.5f} ms/wave, "
            f"{ops[path] / waves:.2f} ops/wave, "
            f"{between[path] / per_wave:.5f} ms/wave between ops")
    say(f"chunk program {sum(e - s for s, e, _ in runs) / per_wave:.5f} "
        f"ms/wave: {sum(ns.values()) / per_wave:.5f} under op events, "
        f"{sum(between.values()) / per_wave:.5f} between them")
    return {"waves": waves, "ops": ops,
            "seconds": {p: v / 1e9 for p, v in ns.items()}}


def ms_per_wave(ctx, *stages):
    """Device ms a wave under the given stages, everything beneath them
    (``ksim.filter_score/<plugin>``) included."""
    got = read(ctx)
    if not got:
        return None
    return 1e3 * sum(
        s for path, s in got["seconds"].items()
        if any(path == st or path.startswith(st + "/") for st in stages)
    ) / got["waves"]
