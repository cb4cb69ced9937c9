"""retry_steps_ms_per_boundary: device ms a run of the retry pass program
under ``ksim.retry/ksim.<stage>``: the pass's own wave steps (the NORMAL wave
step over the queue's waves, as many as the fullest scenario has queued),
whatever their stage: what the wave step's floor gives this program."""

from layer_metrics import _program_stages
from layer_metrics.retry_gather_ms_per_boundary import MODULE

STEPS = "ksim.retry/ksim."


def read(ctx):
    return _program_stages.ms_per_run(ctx, MODULE, STEPS, under=str.startswith)
