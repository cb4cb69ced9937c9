"""From a JAX profiler trace to the numbers the per-layer metrics read.

Two stages, so the second can be tested on a small recorded trace
(``testdata/``) without the profiler or a chip:

1. ``events_from_xplane(path)``: the ``.xplane.pb`` the profiler wrote ->
   plain lists. Per TPU device plane the executions of whole XLA programs
   (line "XLA Modules") and of single ops (line "XLA Ops"); from the host
   planes the ``TraceAnnotation`` spans this benchmark and the program
   write (``bench:batch:<i>``, ``chunk:<i>``, the telemetry phase names).
   All times are nanoseconds on the trace's one clock.
2. ``Reduced(events)``: window, busy time, program executions by name,
   idle gaps and the ``breakdown`` the result line carries.

    python3 benchmark/trace_reduce.py <trace dir> --cut out.json   # for testdata/
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

HOST_SPANS = re.compile(
    r"^(bench:\w+(:\d+)?|chunk:\d+|dispatch|device_wait|boundary_fold|"
    r"host_mirror|checkpoint)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
DROPPED = "Trace Buffers Dropped"  # on line "XLA TraceMe": the device's
# trace buffer overflowed (about 6M op events); nothing after it is whole
WINDOW_SPAN = re.compile(r"^bench:batch:\d+$")


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def events_from_xplane(path, n_devices: int = 1) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            dev = {"modules": [], "ops": [], "dropped": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
                dev["dropped"] += [int(e.start_ns) for e in line.events
                                   if e.name == DROPPED]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if HOST_SPANS.match(e.name)]
    return {"devices": [devices[i] for i in sorted(devices)],
            "host": sorted(host, key=lambda e: e[1])}


def merge(intervals):
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_label(name: str) -> str:
    """An HLO op event's name, cut to ``<op> <result type and shape>``."""
    m = re.match(r"^%?([\w.\-]+)(?: = \(?(\w+\[[\d,]*\]))?", name)
    if not m:
        return name[:60]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


class Reduced:
    def __init__(self, events: dict):
        self.devices = events["devices"]
        self.host = events["host"]
        if not self.devices:
            raise ValueError("the trace has no TPU device plane")
        spans = [(s, s + d) for n, s, d in self.host if WINDOW_SPAN.match(n)]
        if not spans:
            raise ValueError("the trace has no bench:batch span")
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
        # Where the device's trace buffer overflowed, the window ends there.
        dropped = [t for dev in self.devices for t in dev.get("dropped", [])
                   if w0 < t < w1]
        self.window = w0, w1 = (w0, min(dropped + [w1]))
        self.busy = []  # per device: merged op intervals clipped to the window
        for dev in self.devices:
            clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in dev["ops"]
                       if s + d > w0 and s < w1]
            self.busy.append(merge(clipped))
        self.window_s = (w1 - w0) / 1e9
        per_dev = [sum(e - s for s, e in b) / 1e9 for b in self.busy]
        self.busy_s = sum(per_dev) / len(per_dev)

    def _gaps(self, covered):
        """[start, end) of the window's stretches outside ``covered``."""
        w0, w1 = self.window
        edges = [w0] + [x for iv in merge(covered) for x in iv] + [w1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def program_runs(self, pattern: str):
        """Per device: [(start, duration)] of the XLA-program executions
        whose module name matches, inside the window, in start order."""
        rx, (w0, w1) = re.compile(pattern), self.window
        return [sorted((s, d) for n, s, d in dev["modules"]
                       if rx.search(n) and s >= w0 and s + d <= w1)
                for dev in self.devices]

    def program_seconds(self, pattern: str) -> float:
        """Device seconds in matching programs, mean over devices."""
        runs = self.program_runs(pattern)
        return sum(sum(d for _, d in r) for r in runs) / 1e9 / len(runs)

    def idle_ns(self, t0: int, t1: int, device: int = 0) -> int:
        """Nanoseconds of [t0, t1) in which no op ran on the device."""
        return (t1 - t0) - sum(max(0, min(e, t1) - max(s, t0))
                               for s, e in self.busy[device])

    def idle_gaps(self):
        """Device 0's idle gaps in the window: [(start, end, label)]. A gap
        inside one program execution is labelled ``in-program``; else by
        the innermost host span around its middle, else ``none``."""
        mods = sorted((s, s + d) for _, s, d in self.devices[0]["modules"])
        out = []
        for g0, g1 in self._gaps(self.busy[0]):
            if any(s <= g0 and g1 <= e for s, e in mods):
                out.append((g0, g1, "in-program"))
                continue
            mid = (g0 + g1) / 2
            around = [(d, n) for n, s, d in self.host if s <= mid <= s + d
                      and not WINDOW_SPAN.match(n)]
            label = re.sub(r"\d+", "*", min(around)[1]) if around else "none"
            out.append((g0, g1, label))
        return out

    def breakdown(self) -> dict:
        (w0, w1), ops, gaps = self.window, {}, {}
        # A while loop's event spans its body's ops, which have events of
        # their own: leaves only, or the loop would hide them.
        for n, s, d in self.devices[0]["ops"]:
            if s >= w0 and s + d <= w1 and not re.match(r"^%?while[.\d]* ", n):
                ops[op_label(n)] = ops.get(op_label(n), 0) + d
        for g0, g1, label in self.idle_gaps():
            gaps[label] = gaps.get(label, 0) + (g1 - g0)

        def top(d):
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:10]]

        return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def reduce_dir(trace_dir, n_devices: int = 1) -> Reduced:
    return Reduced(events_from_xplane(find_xplane(trace_dir), n_devices))


def cut(events: dict, keep: int = 300) -> dict:
    """A recorded trace small enough to commit (testdata/): the window's
    host spans and program executions as recorded, and each device's
    millions of op events merged into at most ``keep`` busy intervals
    (gaps under a tolerance, doubled until they fit, are closed)."""
    red = Reduced(events)
    w0, w1 = red.window
    devs = []
    for dev, busy in zip(events["devices"], red.busy):
        tol = 0
        while len(busy) > keep:
            tol = max(1000, tol * 2)
            busy = merge([(s, e + tol) for s, e in busy])
            busy = [[s, e - tol] for s, e in busy]
        devs.append({
            "modules": [e for e in dev["modules"] if w0 <= e[1] < w1],
            "ops": [["merged-ops", s, e - s] for s, e in busy],
            "dropped": dev.get("dropped", []), "merge_tolerance_ns": tol})
    return {"devices": devs,
            "host": [e for e in events["host"] if w0 <= e[1] < w1]}


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[2] != "--cut":
        sys.exit(__doc__)
    Path(sys.argv[3]).write_text(json.dumps(
        cut(events_from_xplane(find_xplane(sys.argv[1]), 4))))
