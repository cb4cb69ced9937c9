"""whatif_arrivals_handback_roofline: the least time the hand-back program's
bytes need at the chip's HBM peak (roofline_whatif_arrivals.handback_min_ms)
over the time its executions took, in %. The program
(``jit_whatif_handback``) strings the chunks' choices together and puts them
into task order; the slots it reads are those of the chunk programs that ran
before it in the window. None where no such program ran (a tree that puts
the choices into task order on the host)."""

import roofline_whatif_arrivals
from layer_metrics.chunk_ms_per_wave import CHUNK_PROGRAM

HANDBACK_PROGRAM = r"^jit_whatif_handback\("


def read(ctx):
    trace, sh = ctx["trace"], ctx["shape"]
    runs = trace.program_runs(HANDBACK_PROGRAM)[0]
    chunks = trace.program_runs(CHUNK_PROGRAM)[0]
    if not runs or not chunks:
        return None
    slots = len(chunks) / len(runs) * sh["chunk_waves"] * sh["wave_width"]
    least = roofline_whatif_arrivals.handback_min_ms(
        ctx["device_kind"], scenarios=sh["scenarios_per_chip"],
        slots=slots, nodes=sh["nodes"])
    return 100.0 * least * len(runs) / (trace.program_seconds(HANDBACK_PROGRAM) * 1e3)
