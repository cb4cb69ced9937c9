"""Not a metric: what the ``drain_*`` metrics share. A boundary's eviction is
a program of its own, ``jit_whatif_evict`` (scope ``ksim.evict``), called
before the boundary's static releases at every boundary at which some plan
has a node leaving or coming back; the host's part of it lies under the
program's ``host_events`` span.

The device's trace buffer overflows inside one batch of this cell (a batch is
about 32,000 wave steps, 6.6M op events against the buffer's 6M, PERF.md §7),
and the reducer ends the window where it did: the device-side metrics read
the window that is there, and the host-side ones read the program's spans
over the WHOLE traced batch (``whole``): the host's planes lose nothing.

Returns None or nothing, and never raises, where the program has no such
program or span (an older tree) or none ran in the window."""

import json
import re
import types
from pathlib import Path

from layer_metrics import _program_spans

ROOT = Path(__file__).resolve().parents[2]
EVICT_PROGRAM = re.compile(r"^jit_whatif_evict\(")


def runs(ctx):
    """[duration in ns] of the eviction program's executions on device 0
    inside the window."""
    trace = ctx["trace"]
    w0, w1 = trace.window
    return [dur for name, start, dur in trace.devices[0]["modules"]
            if EVICT_PROGRAM.match(name) and start >= w0 and start + dur <= w1]


def ms_per_boundary(ctx):
    got = runs(ctx)
    return sum(got) / 1e6 / len(got) if got else None


def whole(ctx):
    """``ctx`` for the readers of the program's spans, its window the whole
    trace: ``_program_spans.read`` keeps a batch that lies inside the window,
    and this cell's ends before its one batch does."""
    if "drain_whole" not in ctx:
        ctx["drain_whole"] = {
            k: v for k, v in ctx.items() if k != _program_spans.KEY}
        ctx["drain_whole"]["trace"] = types.SimpleNamespace(
            window=(float("-inf"), float("inf")))
    return ctx["drain_whole"]


def config_of(ctx, metric):
    """The configuration of the cell ``metric`` lists whose node count the
    run has (looked up through BENCHMARK.json: no fixed path)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = next(m for m in bench["per_layer"] if m["name"] == metric)["workloads"]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        if w["name"] in listed:
            config = json.loads((ROOT / files[w["config"]]).read_text())
            if config["cluster"]["nodes"] == ctx["shape"]["nodes"]:
                return config
    return None
