"""Not a metric: the executions of the what-if release program inside the
traced window, by its pinned XLA module name ``jit_whatif_release_k<K>``
(one program per pow2 width K of a boundary's release list)."""

import re

RELEASE_PROGRAM = re.compile(r"^jit_whatif_release_k(\d+)\(")


def runs(ctx):
    """[(K, duration in ns)] on device 0; empty where the program (as the
    parent's) gives the release program no such name."""
    trace = ctx["trace"]
    w0, w1 = trace.window
    out = []
    for name, start, dur in trace.devices[0]["modules"]:
        m = RELEASE_PROGRAM.match(name)
        if m and start >= w0 and start + dur <= w1:
            out.append((int(m.group(1)), dur))
    return out
