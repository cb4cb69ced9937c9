"""chunk_ops_per_wave: leaf device op events inside the chunk program's
executions over the waves they covered: what sets the pace of a program
that runs at op latency (_stages.py makes the pass)."""

from layer_metrics import _stages


def read(ctx):
    got = _stages.read(ctx)
    return sum(got["ops"].values()) / got["waves"] if got else None
