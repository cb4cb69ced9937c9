"""replay5k_chunk_reads_ms_per_wave: what ``chunk_reads_ms_per_wave`` reads from the
chunk program's stage scopes, in the single replay of the default plugin set
(``k8s5k-replay1``), under a name of its own because the accepted metric
lists its cells and cannot be edited (_stages.py)."""

from layer_metrics.chunk_reads_ms_per_wave import read  # noqa: F401
