"""Not a metric: the program's own host spans in the traced run, and the
one pass the host-side metrics share.

The program writes a ``jax.profiler.TraceAnnotation`` around every phase of
a ``replay()`` / what-if ``run()`` when ``KSIM_PROFILE_DIR`` is set (the
harness sets it for a traced window), under one root span per call
(``replay:<n>`` / ``whatif_run:<n>``), and exports the names it can write:
``sim.telemetry.HOST_SPAN_NAMES`` (the phases, ``checkpoint``,
``mesh_put``, ``mesh_fetch``), ``CHUNK_SPAN`` (``chunk:<i>``) and
``ROOT_SPANS``. This file holds no list of its own: it imports those.

``read(ctx)`` opens the traced run's ``.xplane.pb`` ONCE
(``trace_reduce.find_xplane`` on the harness's trace directory; kept on
``ctx`` for all the metric files over it), walks the ``/host:`` planes
only, and keeps the events with such a name or a ``bench:batch:<i>``, each
with its thread's line and its stats (``bytes`` on ``mesh_put`` and
``mesh_fetch``), in ns on the trace's one clock, which is the device
planes' too. Per traced batch (a ``bench:batch:<i>`` whole inside
``ctx["trace"].window``): the root inside it and the root's children, the
program spans that lie inside the root on its thread.

``idle(ctx)`` puts every idle gap of EVERY chip of the cell
(``trace.busy[i]``, each chip's own gaps) down to what the host was doing:
``in-program`` where the gap lies inside one program execution on that
chip, else the innermost program span around the gap's middle
(``chunk:<i>`` read as ``chunk:*``), else ``root`` (under a call's root
span and nothing else: host work no span names) or ``none``. The table is
printed on stderr: the all-chip form of ``breakdown.idle_gaps``, which
reads device 0 and knows six names.

Returns None, and never raises, where the tree exports no such names (an
older tree), no trace file is found, or the trace holds no root span.

    python3 benchmark/layer_metrics/_program_spans.py <trace dir> --cut out.json [chips]

writes a recorded cut for ``testdata/``: ``trace_reduce.cut`` of the first
``chips`` device planes (default 1) beside the kept host events.
"""

import bisect
import json
import re
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import trace_reduce  # noqa: E402
from trace_reduce import WINDOW_SPAN  # noqa: E402

KEY = "program_spans"  # where the pass is kept on ctx
TRACE_DIR = BENCH.parent / ".bench_trace"  # run.py's; ctx["trace_dir"] wins


def say(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def span_names():
    """(regex of every name kept, regex of a root) from the program's
    exported tuples, or None: an older tree exports none."""
    try:
        from kubernetes_simulator_tpu.sim import telemetry

        fixed = "|".join(map(re.escape, telemetry.HOST_SPAN_NAMES))
        roots = "|".join(map(re.escape, telemetry.ROOT_SPANS))
        chunk = re.escape(telemetry.CHUNK_SPAN)
    except Exception as e:  # a reader never takes the result line down
        say(f"no program spans ({type(e).__name__}: {e})")
        return None
    root = re.compile(rf"^(?:{roots}):\d+$")
    kept = re.compile(
        rf"^(?:{fixed}|{chunk}:\d+|(?:{roots}):\d+|bench:batch:\d+)$")
    return kept, root


def events_from_xplane(path, kept):
    """[[name, start_ns, duration_ns, line, {stat: value}]] of the host
    planes' events whose name ``kept`` matches, in start order; ``line``
    numbers the thread."""
    from jax.profiler import ProfileData

    out, line_no = [], 0
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            out += [[e.name, int(e.start_ns), int(e.duration_ns), line_no,
                     dict(e.stats)]
                    for e in line.events if kept.match(e.name)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def read(ctx):
    """{"events", "batches": [{"span": (start, end), "root": event,
    "children": [event]}]} or None. ``ctx["program_span_events"]`` (a
    recorded cut) stands in for the trace file."""
    if KEY not in ctx:
        ctx[KEY] = _read(ctx)
    return ctx[KEY]


def _read(ctx):
    names = span_names()
    if names is None:
        return None
    kept, root = names
    events = ctx.get("program_span_events")
    if events is None:
        try:
            path = trace_reduce.find_xplane(ctx.get("trace_dir", TRACE_DIR))
            t = time.perf_counter()
            events = events_from_xplane(path, kept)
            say(f"program spans: {len(events)} host events read in "
                f"{time.perf_counter() - t:.1f}s")
        except Exception as e:
            say(f"no program spans ({type(e).__name__}: {e})")
            return None
    events = [e for e in events if kept.match(e[0])]
    w0, w1 = ctx["trace"].window
    batches = []
    for n, s, d, *_ in events:
        if not WINDOW_SPAN.match(n) or s < w0 or s + d > w1:
            continue
        roots = [e for e in events if root.match(e[0])
                 and s <= e[1] and e[1] + e[2] <= s + d]
        if not roots:
            continue
        r = roots[0]
        children = [e for e in events
                    if e[3] == r[3] and r[1] <= e[1]
                    and e[1] + e[2] <= r[1] + r[2]
                    and not root.match(e[0]) and not WINDOW_SPAN.match(e[0])]
        batches.append({"span": (s, s + d), "root": r, "children": children})
    if not batches:
        return None
    return {"events": events, "batches": batches, "root": root}


def ms_per_batch(ctx, name, inside=None):
    """Median over the traced batches of the ms a batch's root holds under
    spans called ``name`` (those inside a span called ``inside``, if
    given); None where no batch has one."""
    got = read(ctx)
    if not got:
        return None
    per_batch = []
    for b in got["batches"]:
        spans = [e for e in b["children"] if e[0] == name]
        if inside is not None:
            outer = [e for e in b["children"] if e[0] == inside]
            spans = [e for e in spans
                     if any(o[1] <= e[1] and e[1] + e[2] <= o[1] + o[2]
                            for o in outer)]
        if spans:
            per_batch.append(sum(e[2] for e in spans) / 1e6)
    return statistics.median(per_batch) if per_batch else None


def idle(ctx):
    """{label: idle ns of the window over all chips} by the rule in this
    file's head, or None; printed on stderr once."""
    got = read(ctx)
    if not got:
        return None
    if "idle" not in got:
        got["idle"] = _idle(ctx["trace"], got)
    return got["idle"]


def outer_gaps(busy, modules, window):
    """One chip's idle gaps [(start, end)] in the window that do NOT lie
    inside one program execution: those that reach into a stretch between
    executions. Found from the stretches (a few hundred), not by a walk
    over the gaps (one between any two ops: millions)."""
    w0, w1 = window
    starts, ends = [s for s, _ in busy], [e for _, e in busy]
    covered = trace_reduce.merge([(max(s, w0), min(e, w1)) for s, e in modules
                                  if e > w0 and s < w1])
    edges = [w0] + [x for iv in covered for x in iv] + [w1]
    found = set()
    for c0, c1 in zip(edges[0::2], edges[1::2]):
        if c1 > c0:  # gap k lies before busy interval k; the last after all
            found.update(range(bisect.bisect_right(starts, c0),
                               bisect.bisect_left(ends, c1) + 1))
    gaps = [((ends[k - 1] if k else w0), (starts[k] if k < len(busy) else w1))
            for k in sorted(found)]
    return [(g0, g1) for g0, g1 in gaps if g1 > g0]


def _idle(trace, got):
    spans = [e for e in got["events"] if not WINDOW_SPAN.match(e[0])]
    table, t = {}, time.perf_counter()
    for dev, busy in zip(trace.devices, trace.busy):
        mods = [(s, s + d) for _, s, d in dev["modules"]]
        idle_ns = (trace.window[1] - trace.window[0]
                   - sum(e - s for s, e in busy))
        for g0, g1 in outer_gaps(busy, mods, trace.window):
            mid = (g0 + g1) / 2
            around = [(e[2], e[0]) for e in spans
                      if e[1] <= mid <= e[1] + e[2]]
            named = [a for a in around if not got["root"].match(a[1])]
            label = (re.sub(r"\d+", "*", min(named)[1]) if named
                     else "root" if around else "none")
            table[label] = table.get(label, 0) + (g1 - g0)
            idle_ns -= g1 - g0
        table["in-program"] = table.get("in-program", 0) + idle_ns
    total = sum(table.values())
    say(f"idle gaps of {len(trace.busy)} chip(s) put down to spans in "
        f"{time.perf_counter() - t:.1f}s")
    for label, ns in sorted(table.items(), key=lambda kv: -kv[1]):
        say(f"idle over {len(trace.busy)} chip(s) under {label}: "
            f"{ns / 1e9:.6f} s ({100 * ns / max(total, 1):.2f}%)")
    return table


if __name__ == "__main__":
    if len(sys.argv) not in (4, 5) or sys.argv[2] != "--cut":
        sys.exit(__doc__)
    chips = int(sys.argv[4]) if len(sys.argv) == 5 else 1
    xplane = trace_reduce.find_xplane(sys.argv[1])
    names = span_names()
    if names is None:
        sys.exit("this tree exports no span names")
    doc = trace_reduce.cut(
        trace_reduce.events_from_xplane(xplane, chips), keep=80)
    w0, w1 = trace_reduce.Reduced(doc).window
    doc["program_span_events"] = [
        e for e in events_from_xplane(xplane, names[0]) if w0 <= e[1] < w1]
    Path(sys.argv[3]).write_text(json.dumps(doc))
