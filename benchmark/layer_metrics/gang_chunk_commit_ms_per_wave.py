"""gang_chunk_commit_ms_per_wave: device ms a wave of the gang-scheduled
training cell's vmapped chunk program under ``ksim.commit``: what
``chunk_commit_ms_per_wave`` reads in the replay cell, under a name of its
own because the accepted metric lists that cell alone (_stages.py)."""

from layer_metrics.chunk_commit_ms_per_wave import read  # noqa: F401
