"""mesh_handback_roofline: the least time ONE chip's share of the hand-back's
bytes needs at the chip's HBM peak (roofline_whatif_arrivals.handback_min_ms
at the scenarios a chip holds) over the time ``jit_whatif_handback`` took on
the chip that took longest, in %. Every chip runs the program over its own
scenarios, none waits for another inside it; the slots read are those of
the chunk programs that ran before it in the window. The count takes a slot
for a pod: this cell's waves close early before a gang that does not fit, so
the bytes written are over-counted by the empty slots (336 of 10,336 slots,
2.2% of the bytes). None where no such program ran."""

import roofline_whatif_arrivals
from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM
from layer_metrics.whatif_arrivals_handback_roofline import HANDBACK_PROGRAM


def read(ctx):
    trace, sh = ctx["trace"], ctx["shape"]
    runs = trace.program_runs(HANDBACK_PROGRAM)
    chunks = trace.program_runs(CHUNK_PROGRAM)
    if not all(runs) or not all(chunks):
        return None
    slots = (len(chunks[0]) / len(runs[0])
             * sh["chunk_waves"] * sh["wave_width"])
    least = roofline_whatif_arrivals.handback_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        slots=slots, nodes=sh["nodes"])
    slowest = max(sum(d for _, d in chip) for chip in runs) / 1e6
    return 100.0 * least * len(runs[0]) / slowest
