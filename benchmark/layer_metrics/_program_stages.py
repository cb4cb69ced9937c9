"""Not a metric: a program's device time by stage path, one program at a
time, for the programs AROUND the wave step: the eviction program
(``jit_whatif_evict``), the retry pass (``jit_per_scenario_retry``) and the
arrival scan with the queue's upkeep (``jit_per_scenario_arrivals``).

``_stages.py`` sums the stage paths over every chunk program and divides by
waves; ``_budget.py`` reads one path of one program. Here ``read(ctx,
module)`` gives, for ONE module by its XLA name, ``{"seconds": {path: s},
"runs", "op_seconds", "paths"}``: the leaf op events (a ``while`` event spans
its body's ops and is not counted) of device 0 that start inside a run of
that module in the window, joined by instruction name to the module's own
stage table (``utils.profiling.stage_tables()``), an instruction the table
does not hold under ``""`` like one under no scope; ``paths`` are the paths
the table files anything under. **A boundary is a run of the module**: each
of the three runs once a boundary it runs at, so nothing here is divided by
chunk calls (two a boundary since PR 47). The op events are walked once, for
every module that has a table, at the first call; the result is kept on
``ctx`` per module, and each program's split is printed on stderr.

``ms_per_run(ctx, module, stage)`` is the device ms a run under ``stage``
(everything beneath it included; ``under`` is the rule that says so), None
where the module's table files no instruction under it: a tree without that
scope.

Returns None, and never raises, where the program has no ``stage_tables``
(an older tree), the module is in no table, or it did not run in the
window."""

import bisect

from layer_metrics import _stages

KEY = "program_stages"  # where the pass is kept on ctx: {module: result}
say = _stages.say


def read(ctx, module):
    if KEY not in ctx:
        ctx[KEY] = _read(ctx)
    return ctx[KEY].get(module)


def _read(ctx):
    trace = ctx["trace"]
    w0, w1 = trace.window
    named = ((_stages.MODULE.match(n), s, d)
             for n, s, d in trace.devices[0]["modules"])
    runs = sorted((s, s + d, m.group(1)) for m, s, d in named
                  if m and s >= w0 and s + d <= w1)
    tables = _stages.stage_tables() if runs else None
    runs = [r for r in runs if (tables or {}).get(r[2])]
    if not runs:
        return {}
    starts = [r[0] for r in runs]
    known = {}  # event name -> instruction name, None for a while
    ns = {}  # module -> {path: ns}
    for name, s, d in trace.devices[0]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        if name not in known:
            m = _stages.INSTRUCTION.match(name)
            known[name] = (None if _stages.WHILE.match(name)
                           else m.group(1) if m else "")
        if known[name] is None:
            continue
        module = runs[i][2]
        path = tables[module].get(known[name], "")
        by_path = ns.setdefault(module, {})
        by_path[path] = by_path.get(path, 0) + d
    out = {}
    for module, by_path in sorted(ns.items()):
        n = sum(r[2] == module for r in runs)
        wall = sum(e - s for s, e, m in runs if m == module)
        total = sum(by_path.values())
        say(f"program {module}: {n} runs, {wall / 1e6 / n:.3f} ms a run, "
            f"{total / 1e6 / n:.3f} under op events")
        for path, v in sorted(by_path.items(), key=lambda kv: -kv[1]):
            say(f"program {module} {path or '(none)'} {v / 1e6 / n:.3f} ms a "
                f"run ({100 * v / max(total, 1):.2f}%)")
        out[module] = {
            "seconds": {p: v / 1e9 for p, v in by_path.items()},
            "runs": n, "op_seconds": total / 1e9,
            "paths": set(tables[module].values())}
    return out


def beneath(path, stage):
    return path == stage or path.startswith(stage + "/")


def ms_per_run(ctx, module, stage, under=beneath):
    got = read(ctx, module)
    if not got or not any(under(p, stage) for p in got["paths"]):
        return None
    return 1e3 * sum(v for p, v in got["seconds"].items()
                     if under(p, stage)) / got["runs"]
