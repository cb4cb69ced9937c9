"""gang_chunk_unattributed_share: of the op time inside the gang-scheduled
training cell's chunk program executions, the % whose instruction is in no
stage table or under no ``ksim.`` scope: what ``chunk_unattributed_share``
reads in the replay cell, under a name of its own because the accepted metric
lists that cell alone (_stages.py)."""

from layer_metrics.chunk_unattributed_share import read  # noqa: F401
