"""whatif_chunk_reads_ms_per_wave: device ms a wave of the vmapped chunk
program in ``ksim.reads`` (the wave-start reads: here the expansion of the
domain and host count rows of a wave's slots to node space, ``[S, W, KT, N]``),
``ksim.gather`` and ``ksim.derive``: what ``chunk_reads_ms_per_wave`` reads in
the replay cell, under a name of its own because the accepted metric lists
that cell alone and a PR edits no accepted entry (_stages.py)."""

from layer_metrics.chunk_reads_ms_per_wave import read  # noqa: F401
